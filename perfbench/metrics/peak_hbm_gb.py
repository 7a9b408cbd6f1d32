"""``peak_bytes_in_use`` of the fullest device after the window, in GB:
the resident tables plus the most the program held beside them."""


def read(run):
    peak = run["peak_bytes"]
    return None if peak is None else peak / 1e9
