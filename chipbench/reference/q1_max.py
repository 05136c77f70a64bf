"""Plain reference for Q1: the max of a thing's last minutes, in float64.

Round ``r`` lands records ``[r·per, (r+1)·per)`` of each thing's live
stream, and Q1's window of that round is the thing's last ``window``
records. Where the stream is still shorter than that, the window
begins in the thing's stored history (the paper's series AND queue):
with ``window`` = 3·``per``, the window of round ``r ≥ 2`` is stream
records ``[(r-2)·per, (r+1)·per)``, and for ``r < 2`` history records
``[n - (2-r)·per, n)`` followed by stream records ``[0, (r+1)·per)``.

The history is made again from the seed by the benchmark's generator,
the stream is the generator's ingest (``[slides, things, records]``,
a thing's stream its slides one after another), and the max is numpy
on the host. The control is the same windows with their records
rounded to bfloat16 first, the precision below the configuration's
float32.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import neubot_data as data


def history_tail(key, thing: int, *, n: int, back: int,
                 scale: float) -> np.ndarray:
    """The last ``back`` records of thing ``thing``'s stored history."""
    row = data.history_row(key, np.int32(thing), n=n, scale=scale)
    return np.asarray(row[n - back:])


def window_maxes(key, new: np.ndarray, rnd, thing, *, n: int, window: int,
                 per: int, scale: float, dtype=np.float64) -> np.ndarray:
    """Max of each ``(rnd[j], thing[j])`` window, its records taken in
    ``dtype`` and the max returned in float64."""
    rnd, thing = np.asarray(rnd, int), np.asarray(thing, int)
    back = window - per
    out = np.empty(len(rnd))
    for i in np.unique(thing):
        stream = new[:, i].reshape(-1)
        tail = history_tail(key, int(i), n=n, back=back, scale=scale)
        for j in np.flatnonzero(thing == i):
            hi = (rnd[j] + 1) * per
            lo = hi - window
            if lo >= 0:
                w = stream[lo:hi]
            else:
                w = np.concatenate([tail[tail.size + lo:], stream[:hi]])
            out[j] = w.astype(dtype).astype(np.float64).max()
    return out


def control_maxes(key, new: np.ndarray, rnd, thing, **kw) -> np.ndarray:
    """The same windows, their records rounded to bfloat16."""
    return window_maxes(key, new, rnd, thing, dtype=jnp.bfloat16, **kw)
