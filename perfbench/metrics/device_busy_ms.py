"""Device time per traced query: the union of the operations' intervals
on each device, averaged over the devices."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["busy_s"]:
        return None
    return 1000.0 * tr["busy_s"] / tr["queries"]
