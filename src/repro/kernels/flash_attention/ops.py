"""jit'd public wrapper for the flash attention kernel: layout, GQA,
padding to MXU-aligned blocks, and the interpret/TPU switch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bhsd


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jax.Array:
    """q: [B, Sq, H, d]; k/v: [B, Skv, KV, d] (GQA) → [B, Sq, H, d].

    Pads sequence dims up to block multiples (padded kv masked inside the
    kernel via seq_kv; padded q rows discarded on return).
    """
    B, Sq, H, d = q.shape
    _, Skv, KV, _ = k.shape
    rep = H // KV

    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    qb = q.transpose(0, 2, 1, 3).reshape(B * H, Sq + pad_q, d)
    kb = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv + pad_k, d)
    vb = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv + pad_k, d)

    ob = flash_attention_bhsd(qb, kb, vb, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=interpret, rep=rep,
                              seq_kv_valid=Skv, seq_q_valid=Sq)
    out = ob.reshape(B, H, Sq + pad_q, d).transpose(0, 2, 1, 3)
    return out[:, :Sq]
