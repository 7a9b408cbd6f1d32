"""95th percentile of the window's query times, each from its call to
its result on the host (linear interpolation between order statistics)."""

import numpy as np


def read(run):
    lat = run["latencies_s"]
    return 1000.0 * float(np.percentile(lat, 95)) if lat else None
