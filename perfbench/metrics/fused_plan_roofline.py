"""The fused plan program's share of its HBM roofline: the least time,
the bytes of the columns the query references read once over the chips'
HBM bandwidth, over the device time per query. The bound is bandwidth:
these plans do a few integer operations per byte read."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["busy_s"]:
        return None
    least_s = run["bytes_read"] / (run["chips"]
                                   * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (tr["busy_s"] / tr["queries"])
