"""jit'd wrapper for the SSD scan kernel: model layout → kernel layout."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_scan_bhl


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B_: jax.Array,
             C: jax.Array, *, chunk: int = 128,
             interpret: bool = False) -> jax.Array:
    """Model layout (matches models/ssm.ssd_chunked):
    x [B, L, H, P]; dt [B, L, H] (post-softplus); A [H] (negative);
    B_/C [B, L, G, N] (G groups broadcast over H). Returns y [B, L, H, P]
    (without the D·x skip, which the caller adds)."""
    Bb, L, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    rep = H // G

    pad = (-L) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad

    xb = x.transpose(0, 2, 1, 3).reshape(Bb * H, Lp, P)
    dtb = dt.transpose(0, 2, 1).reshape(Bb * H, Lp).astype(jnp.float32)
    dab = dtb * jnp.tile(A, Bb)[:, None]   # da[b·H+h, l] = dt · A_h
    # within-chunk cumulative decay, restarting at each chunk boundary
    cum = jnp.cumsum(dab.reshape(Bb * H, Lp // chunk, chunk),
                     axis=-1).reshape(Bb * H, Lp)
    Bq = jnp.repeat(B_.transpose(0, 2, 1, 3), rep, axis=1).reshape(
        Bb * H, Lp, N)
    Cq = jnp.repeat(C.transpose(0, 2, 1, 3), rep, axis=1).reshape(
        Bb * H, Lp, N)

    y = ssd_scan_bhl(xb, dtb, cum, Bq, Cq, chunk=chunk, interpret=interpret)
    y = y.reshape(Bb, H, Lp, P).transpose(0, 2, 1, 3)
    return y[:, :L]
