"""Segment reduction, TPU Pallas — the hot loop of the paper's stream
services ("EVERY 60s compute the max of download_speed over the last 3
minutes", §3).

TPU adaptation (DESIGN §2): a sliding window with stride s and width w=m·s
factors into (1) a dense reduction of the raw stream into s-sized
segments — this kernel, where all the bytes move — and (2) a combine of m
consecutive segment aggregates per output (ops.py, trivially vectorized).
Phase 1 is perfectly Blocked for Pallas: each grid cell owns
(block_o · stride) rows × 128 lanes of VMEM and reduces on the VPU.

Aggregations must be decomposable (max/min/sum/mean — the paper's
services, Fig. 2); mean combines as sum/width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INIT = {"max": -3.4e38, "min": 3.4e38, "sum": 0.0}


def _segment_kernel(x_ref, o_ref, *, agg: str, stride: int, block_o: int):
    """x_ref: [block_o·stride, block_c] → o_ref: [block_o, block_c]."""
    block_c = o_ref.shape[1]
    x = x_ref[...].astype(jnp.float32)
    x = x.reshape(block_o, stride, block_c)
    if agg == "max":
        r = jnp.max(x, axis=1)
    elif agg == "min":
        r = jnp.min(x, axis=1)
    else:
        r = jnp.sum(x, axis=1)
    o_ref[...] = r.astype(o_ref.dtype)


def segment_reduce_tc(x: jax.Array, *, agg: str, stride: int,
                      block_o: int = 8, block_c: int = 128,
                      interpret: bool = False) -> jax.Array:
    """x: [T, C] → [T//stride, C]; T % (block_o·stride) == 0, C % block_c == 0
    (ops.py pads). agg ∈ {max, min, sum}."""
    T, C = x.shape
    n_seg = T // stride
    assert T % (block_o * stride) == 0 and C % block_c == 0, (T, C)

    kernel = functools.partial(_segment_kernel, agg=agg, stride=stride,
                               block_o=block_o)
    return pl.pallas_call(
        kernel,
        grid=(n_seg // block_o, C // block_c),
        in_specs=[pl.BlockSpec((block_o * stride, block_c),
                               lambda o, c: (o, c))],
        out_specs=pl.BlockSpec((block_o, block_c), lambda o, c: (o, c)),
        out_shape=jax.ShapeDtypeStruct((n_seg, C), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x)
