"""Plain reference for Q2: the mean of a thing's ring, in float64.

The ring of thing ``i`` after slide ``k`` is its seeded history with
the first ``(k+1)·per`` records replaced by slides ``0..k``'s ingest.
The histories are made again from the seed by the benchmark's own
generator; the sums are numpy float64 on the host. One pass over a
thing's history gives the mean of every window of that thing: the
history is summed in blocks of one slide's records, and the ring after
slide ``k`` is the blocks from ``k+1`` on plus the first ``k+1``
slides' ingest.

The control is the same ring averaged in bfloat16 on the device, the
precision below the configuration's float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import neubot_data as data


def _row(key, thing: int, n: int, scale: float) -> np.ndarray:
    """Thing ``thing``'s initial history, as an f32 host array."""
    return next(_rows(key, [thing], n, scale))[1]


def _rows(key, things, n: int, scale: float):
    """``(thing, history)`` of each thing, as f32 host arrays; the next
    history is made and copied while the caller sums this one."""
    def start(i):
        row = data.history_row(key, np.int32(i), n=n, scale=scale)
        row.copy_to_host_async()
        return i, row

    things = [int(i) for i in things]
    ahead = start(things[0]) if things else None
    for j in range(len(things)):
        i, row = ahead
        ahead = start(things[j + 1]) if j + 1 < len(things) else None
        out = np.asarray(row)
        row.delete()
        yield i, out


def ring(row0: np.ndarray, new: np.ndarray, k: int, i: int) -> np.ndarray:
    """Thing ``i``'s ring after slide ``k`` (no wrap), as f32."""
    written = new[:k + 1, i].reshape(-1)
    out = row0.copy()
    out[:written.size] = written
    return out


def thing_means(row0: np.ndarray, new_i: np.ndarray) -> np.ndarray:
    """Float64 mean of one thing's ring after each slide ``k`` of
    ``new_i`` (``[slides, per]``): entry ``k`` is the window due at
    slide ``k``."""
    slides, per = new_i.shape
    n = row0.size
    if n % per or slides * per > n:
        raise ValueError("the ring must hold whole slides, unwrapped")
    blocks = row0.reshape(-1, per).sum(axis=1, dtype=np.float64)
    # kept[j]: the sum of blocks j.. of the history (0 past its end)
    kept = np.append(np.cumsum(blocks[::-1])[::-1], 0.0)
    landed = np.cumsum(new_i.sum(axis=1, dtype=np.float64))
    return (kept[1:slides + 1] + landed) / n


def ring_means(key, new: np.ndarray, slide, thing, *, n: int,
               scale: float) -> np.ndarray:
    """Float64 mean of each ``(slide[j], thing[j])`` window; each
    thing's history is made and read once."""
    slide, thing = np.asarray(slide, int), np.asarray(thing, int)
    out = np.empty(len(slide))
    for i, row0 in _rows(key, np.unique(thing), n, scale):
        at = np.flatnonzero(thing == i)
        out[at] = thing_means(row0, new[:, i])[slide[at]]
    return out


@jax.jit
def _mean_bf16(x):
    return jnp.mean(x.astype(jnp.bfloat16), dtype=jnp.bfloat16)


def control_means(key, new: np.ndarray, slide, thing, *, n: int,
                  scale: float) -> np.ndarray:
    """The same windows averaged in bfloat16 on the device."""
    out = np.empty(len(slide))
    for j, (k, i) in enumerate(zip(slide, thing)):
        row0 = _row(key, int(i), n, scale)
        out[j] = float(_mean_bf16(jnp.asarray(ring(row0, new, int(k),
                                                   int(i)))))
    return out
