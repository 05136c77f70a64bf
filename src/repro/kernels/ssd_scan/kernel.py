"""Mamba-2 SSD chunk scan, TPU Pallas (arXiv:2405.21060).

The state-space-duality chunking maps onto the MXU as three GEMMs per
chunk — C·Bᵀ (scores), M·X (diagonal term), Xᵀ·B̃ (state update) — with
the O(1)-size recurrent state h [P, N] carried across the sequential
chunk grid dimension in VMEM scratch. Grid: (B·H, n_chunks), chunk dim
"arbitrary".

Layouts (per b·h): x [BH, L, P], B/C [BH, L, N] (groups broadcast to
heads by ops.py; G=1 in all assigned configs). The per-step scalars dt
and cum (the within-chunk cumulative sum of dt·A, computed by ops.py)
arrive chunked, each as a column [BH, nc, Q, 1] and a row
[BH, nc, 1, Q]: a block then spans the array's two minor dimensions
whole, which meets the TPU's (8, 128) tiling for any B·H and chunk, and
the kernel needs no in-register transpose or cumsum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dtc_ref, dtr_ref, cumc_ref, cumr_ref, b_ref, c_ref,
                o_ref, h_ref, *, chunk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)        # [Q, P]
    dt_c = dtc_ref[0, 0]                     # [Q, 1]
    dt_r = dtr_ref[0, 0]                     # [1, Q]
    cum_c = cumc_ref[0, 0]                   # [Q, 1]  Σ_{k≤i} dt_k·A
    cum_r = cumr_ref[0, 0]                   # [1, Q]
    Bm = b_ref[0].astype(jnp.float32)       # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)       # [Q, N]
    Q = x.shape[0]

    seg = cum_c - cum_r                      # [Q, Q]
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    mask = jj <= ii

    # diagonal (within-chunk) term: (C Bᵀ ⊙ decay ⊙ dt_j) X
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    M = jnp.where(mask, cb * jnp.exp(seg) * dt_r, 0.0)
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [Q, P]

    # carry-in term: (C ⊙ e^cum) hᵀ
    h = h_ref[...]                           # [P, N]
    Cin = Cm * jnp.exp(cum_c)
    y = y + jax.lax.dot_general(Cin, h, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update: h' = e^{cum_Q} h + Xᵀ (B ⊙ dt ⊙ e^{cum_Q − cum})
    # cum at the chunk's last step, as a masked lane reduction: a slice
    # at lane offset Q-1 has a layout Mosaic cannot broadcast from
    last = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    total = jnp.sum(jnp.where(last, cum_r, 0.0), axis=1,
                    keepdims=True)          # [1, 1]
    wB = Bm * (dt_c * jnp.exp(total - cum_c))                     # [Q, N]
    h_new = (jnp.exp(total) * h
             + jax.lax.dot_general(x, wB, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32))
    h_ref[...] = h_new
    o_ref[0] = y.astype(o_ref.dtype)


def ssd_scan_bhl(x: jax.Array, dt: jax.Array, cum: jax.Array,
                 B_: jax.Array, C: jax.Array, *, chunk: int = 128,
                 interpret: bool = False) -> jax.Array:
    """x: [BH, L, P]; dt/cum: [BH, L] f32; B_/C: [BH, L, N].
    L % chunk == 0; ``cum`` restarts at every chunk boundary."""
    BH, L, P = x.shape
    N = B_.shape[-1]
    nc = L // chunk
    col = lambda a: a.reshape(BH, nc, chunk, 1)
    row = lambda a: a.reshape(BH, nc, 1, chunk)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bh, j: (bh, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bh, j: (bh, j, 0, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bh, j: (bh, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bh, j: (bh, j, 0, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda bh, j: (bh, j, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, L, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, col(dt), row(dt), col(cum), row(cum), B_, C)
