"""95th percentile of every completed window's latency (due time to
result on the host), nearest rank, in seconds."""
import math

import numpy as np


def read(run):
    lat = np.sort(np.asarray(run.records, dtype=float))
    lat = lat[np.isfinite(lat)]
    if lat.size == 0:
        return None
    return float(lat[math.ceil(0.95 * lat.size) - 1])
