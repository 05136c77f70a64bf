"""Causal flash attention, TPU Pallas (pl.pallas_call + BlockSpec).

Canonical online-softmax formulation (FlashAttention-2, arXiv:2307.08691)
tiled for the TPU memory hierarchy: q/k/v stream HBM→VMEM in MXU-aligned
(block_q × d) / (block_k × d) tiles; the running (m, l, acc) state lives in
VMEM scratch across the sequential k-block grid dimension. GQA is handled
in the kv index_map (no repeated-KV materialization in HBM).

Grid: (batch·q_heads, n_q_blocks, n_k_blocks), k-dim "arbitrary"
(sequential) so scratch carries across it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  n_k_blocks: int, seq_kv: int, q_offset: int):
    i = pl.program_id(1)   # q block
    j = pl.program_id(2)   # k block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * block_q
    k_start = j * block_k

    def _body():
        q = q_ref[0].astype(jnp.float32)            # [block_q, d]
        k = k_ref[0].astype(jnp.float32)            # [block_k, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        kv_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kv_pos < seq_kv
        if causal:
            # right-aligned causal (query i sees kv ≤ i + q_offset, the
            # continuation/decode convention when Skv > Sq)
            q_pos = (q_start + q_offset
                     + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            valid = valid & (kv_pos <= q_pos)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]                          # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    if causal:
        # skip k blocks strictly after the last query of this q block
        pl.when(k_start <= q_start + q_offset + block_q - 1)(_body)
    else:
        _body()

    @pl.when(j == n_k_blocks - 1)
    def _finalize():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, block_q: int = 128,
                         block_k: int = 128, interpret: bool = False,
                         rep: int = 1, seq_kv_valid: int | None = None,
                         seq_q_valid: int | None = None) -> jax.Array:
    """q: [BH, Sq, d]; k/v: [B·KV, Skv, d]; rep = H // KV (GQA).

    Sq/Skv must be multiples of block_q/block_k (ops.py pads);
    seq_kv_valid masks right-padded kv rows (defaults to Skv).
    """
    BH, Sq, d = q.shape
    _, Skv, _ = k.shape
    nq = Sq // block_q
    nk = Skv // block_k
    scale = 1.0 / math.sqrt(d)

    svalid = Skv if seq_kv_valid is None else seq_kv_valid
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, n_k_blocks=nk, seq_kv=svalid,
        q_offset=svalid - (Sq if seq_q_valid is None else seq_q_valid))

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh // rep, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
