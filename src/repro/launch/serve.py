"""Serving launcher: batched prefill + decode with a KV/state cache.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b \
      --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.data import make_batch
from repro.models import model as M
from repro.train.serve_step import greedy_generate


def demo_inputs(arch: str, *, batch: int = 4, prompt_len: int = 64,
                full: bool = False, seed: int = 0):
    """The demo's config, random parameters and prompt batch (all from
    ``seed``)."""
    cfg = get_arch(arch) if full else get_arch(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    bd = make_batch(cfg, prompt_len, batch, 0, seed)
    bd.pop("labels", None)
    return cfg, params, {k: jnp.asarray(v) for k, v in bd.items()}


def serve_demo(arch: str, *, batch: int = 4, prompt_len: int = 64,
               gen: int = 32, full: bool = False, seed: int = 0):
    cfg, params, bd = demo_inputs(arch, batch=batch, prompt_len=prompt_len,
                                  full=full, seed=seed)

    t0 = time.perf_counter()
    toks, cache = greedy_generate(cfg, params, bd, steps=gen,
                                  cache_len=prompt_len + gen)
    toks = np.asarray(toks)
    dt = time.perf_counter() - t0
    print(f"{arch}: generated {toks.shape} in {dt:.2f}s "
          f"({batch * gen / dt:.1f} tok/s incl. compile)")
    assert np.all((toks >= 0) & (toks < cfg.padded_vocab))
    return toks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    serve_demo(args.arch, batch=args.batch, prompt_len=args.prompt_len,
               gen=args.gen, full=args.full)


if __name__ == "__main__":
    main()
