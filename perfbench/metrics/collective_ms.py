"""Device time of collective operations per traced query, averaged over
the devices; nothing where the trace has no collective operation."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["collective_ops"]:
        return None
    return 1000.0 * tr["collective_s"] / tr["queries"]
