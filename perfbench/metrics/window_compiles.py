"""Backend compiles inside the measured window; it should read 0."""


def read(run):
    return run["compile"]["window_n"]
