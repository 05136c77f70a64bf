"""Windows completed over the measured window's seconds (first slide's
due time to the last result)."""
import numpy as np


def read(run):
    done = int(np.isfinite(np.asarray(run.records, dtype=float)).sum())
    if done == 0 or run.window_s <= 0:
        return None
    return done / run.window_s
