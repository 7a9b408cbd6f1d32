"""Input rows of every table the query scans, times queries answered,
over the window's elapsed host-clock time."""


def read(run):
    return run["input_rows"] * run["completed"] / run["elapsed_s"]
