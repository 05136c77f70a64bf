"""jit'd wrapper: sliding-window aggregation = Pallas segment reduce +
vectorized combine of window//stride consecutive segments."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.window_agg.kernel import INIT, segment_reduce_tc


@functools.partial(jax.jit, static_argnames=("agg", "window", "stride",
                                             "interpret"))
def window_aggregate(x: jax.Array, *, agg: str, window: int, stride: int,
                     interpret: bool = False) -> jax.Array:
    """x: [T, C] → [n_out, C] with out[o] = agg(x[o·stride : o·stride+window]).

    window must be a multiple of stride (the paper's queries are:
    180 s / 60 s, 120 d / 5 min). n_out = (T - window)//stride + 1.
    """
    if window % stride:
        raise ValueError("window must be a multiple of stride")
    T, C = x.shape
    if T < window:
        raise ValueError("series shorter than window")
    m = window // stride
    base = "sum" if agg == "mean" else agg

    # pad T to a block multiple, C to the 128-lane register width; the
    # output block's rows stay a multiple of the 8-row sublane tile even
    # on short series (padded segments hold the neutral fill and are
    # never combined)
    block_o, block_c = 8, 128
    pad_t = (-T) % (block_o * stride)
    pad_c = (-C) % block_c
    fill = INIT[base]
    xp = jnp.pad(x, ((0, pad_t), (0, pad_c)), constant_values=fill)

    seg = segment_reduce_tc(xp, agg=base, stride=stride, block_o=block_o,
                            block_c=block_c, interpret=interpret)
    seg = seg[:, :C]
    n_seg_valid = T // stride

    # combine m consecutive segments per output (cheap: n_seg × C)
    n_out = (T - window) // stride + 1
    parts = jnp.stack([seg[i:i + n_out] for i in range(m)])
    if base == "max":
        out = jnp.max(parts, axis=0)
    elif base == "min":
        out = jnp.min(parts, axis=0)
    else:
        out = jnp.sum(parts, axis=0)
    if agg == "mean":
        out = out / window
    return out
