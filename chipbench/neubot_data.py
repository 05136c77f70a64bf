"""Neubot ``download_speed`` records, made on the device from a seed.

Each thing's 120-day history and each slide's ingest come from their
own key, so any part of the data can be made again alone: the
reference remakes the histories it checks after the measured window,
with the same program that made them in set-up.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int) -> jax.Array:
    """A key from a seed of any size (the run's seeds pass 32 bits)."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _speeds(key, shape, scale):
    return jnp.abs(jax.random.normal(key, shape, jnp.float32)) * scale


@functools.partial(jax.jit, static_argnames=("n", "scale"))
def history_row(key, thing, *, n: int, scale: float) -> jax.Array:
    """Thing ``thing``'s 120-day history, ``[n]`` f32: a buffer of its
    own, as a store keeps each thing's series."""
    return _speeds(jax.random.fold_in(jax.random.fold_in(key, 0), thing),
                   (n,), scale)


def history(key, *, things: int, n: int, scale: float):
    """Every thing's history, one device buffer each: one program,
    called once per thing. One jitted call returning every buffer took
    75 s to compile, and a run set up in 21-22 s with it against 15-17 s
    with this, on one TPU v5e."""
    return tuple(history_row(key, np.int32(i), n=n, scale=scale)
                 for i in range(things))


@functools.partial(jax.jit, static_argnames=("slides", "things", "per",
                                             "scale"))
def ingest(key, *, slides: int, things: int, per: int,
           scale: float) -> jax.Array:
    """New records of each slide: ``[slides, things, per]`` f32."""
    return _speeds(jax.random.fold_in(key, 1), (slides, things, per), scale)
