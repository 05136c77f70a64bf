"""Bring-up smoke test: the system's main path, once, on one TPU chip.

    python chip_smoke.py [--seed N]

Run from the root of the repository on a machine with a TPU. Every phase
goes through the entry points a user calls, at full size, with data and
weights generated from ``--seed``, and checks its results against a
reference:

0. device  — exits non-zero, before anything else, unless JAX's first
             device is a TPU; prints the device and the versions.
1. offload — the paper's Neubot Q2 (a 120-day mean at 1 Hz) through
             ``HybridExecutor.run_window`` for 8 things' histories held
             on the device, ``mean`` and ``max``, against numpy float64;
             every result must be one the program wrote to host memory.
2. kernels — ``ssd_scan`` at mamba2-1.3b widths and ``flash_attention``
             at smollm-135m widths against their ``ref.py`` run in
             float32 on the CPU device.
3. planner — the 500-site / 8-region fleet: one ``region_search`` sweep
             whose exact tier fans out over a 2-worker DES pool and
             whose candidates are ranked by the fluid ensemble on the
             TPU; the fluid VoS against the same program on the CPU
             device; bench_robust's 256 × 32 ensemble likewise; and the
             fluid-vs-DES agreement on the recorded anchor plans.
4. model   — smollm-135m at full width served through
             ``launch/serve.py``; prefill logits against float32 on the
             CPU device.

Everything runs in this one process: a chip belongs to one process, and
the DES pool's workers are spawned and never touch JAX. Any failed
check raises and exits non-zero. The last line of standard output is
one JSON object naming the device. Times printed are smoke timings that
include compilation, not measurements.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# Limits of each check (see the phase that uses each for why).
MEAN_RTOL = 1e-4          # Q2 mean vs float64
SSD_TOL = 2e-2            # ssd_scan max error / max |ref|
FLASH_ATOL = 2e-2         # flash_attention bf16 max abs error
FLUID_RTOL = 1e-3         # fluid VoS, TPU vs CPU
LOGITS_RTOL = 5e-2        # bf16 prefill logits, relative Frobenius error


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class Phase:
    """Prints a phase's smoke timing (compilation included)."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        print(f"[{self.name}] start", flush=True)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *exc):
        if exc_type is None:
            print(f"[{self.name}] passed; smoke time "
                  f"{time.perf_counter() - self.t0:.1f} s incl. compile",
                  flush=True)


# ------------------------------------------------------------- phase 0
def phase_device():
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        sys.exit(1)
    print(f"[device] kind={dev.device_kind!r} count={len(devs)} "
          f"jax={jax.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')}", flush=True)
    return dev, len(devs)


# ------------------------------------------------------------- phase 1
def phase_offload(key):
    """8 things × 120 days × 1 Hz = 8 × 10,368,000 f32 records (332 MB)
    on the device. ``max`` of f32 values is exact; the mean sums 10.4 M
    values in f32 in a tree of segments, well inside ``MEAN_RTOL``."""
    import jax
    import jax.numpy as jnp
    from repro.pipeline.queries import HybridExecutor, offload_aggregate

    things, n = 8, 120 * 86400
    hist = jnp.abs(jax.random.normal(key, (things, n), jnp.float32)) * 20e6
    hlo = offload_aggregate.lower(hist[0], agg="mean").compile().as_text()
    check("tpu_custom_call" in hlo, "offload HLO holds the Pallas kernel")
    print("[offload] compiled offload HLO contains tpu_custom_call")
    hx = HybridExecutor()
    worst = 0.0
    for i in range(things):
        ref = np.asarray(hist[i], dtype=np.float64)
        got_mean = hx.run_window(hist[i], "mean")
        got_max = hx.run_window(hist[i], "max")
        check(got_max == ref.max(), f"thing {i}: max {got_max} != "
              f"{ref.max()}")
        err = abs(got_mean - ref.mean()) / abs(ref.mean())
        check(err <= MEAN_RTOL, f"thing {i}: mean rel err {err:.3e}")
        worst = max(worst, err)
    check(hx.offloads == 2 * things and hx.edge_runs == 0,
          f"every window offloads ({hx.offloads} offloads, "
          f"{hx.edge_runs} edge runs)")
    check(hx.host_results == hx.offloads,
          f"every offloaded result lands in host memory "
          f"({hx.host_results} of {hx.offloads})")
    print(f"[offload] {things} things x {n} records: max exact, mean worst "
          f"rel err {worst:.3e} (limit {MEAN_RTOL:g}); "
          f"{hx.offloads} offloads, {hx.host_results} results in host "
          f"memory")


# ------------------------------------------------------------- phase 2
def phase_kernels(key, cpu):
    """The kernels compute in f32 on the MXU; the references run in f32
    on the CPU. ``SSD_TOL`` leaves room for the MXU's bf16 passes over
    a 256-step chunk; ``FLASH_ATOL`` is the interpret-mode tests' bf16
    tolerance."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import (attention_reference,
                                               flash_attention)
    from repro.kernels.ssd_scan import ssd_scan, ssd_scan_reference

    B, L, H, P, N, chunk = 1, 2048, 64, 64, 128, 256     # mamba2-1.3b
    ks = jax.random.split(key, 8)
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, L, 1, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, L, 1, N)) * 0.3
    y = np.asarray(ssd_scan(x, dt, A, Bm, Cm, chunk=chunk))
    ref = np.asarray(ssd_scan_reference(
        *jax.device_put((x, dt, A, Bm, Cm), cpu)))
    err = np.abs(y - ref).max() / np.abs(ref).max()
    check(np.isfinite(y).all() and err <= SSD_TOL,
          f"ssd_scan error {err:.3e}")
    print(f"[kernels] ssd_scan B{B} L{L} H{H} P{P} N{N} chunk {chunk}: "
          f"max err / max|ref| {err:.3e} (limit {SSD_TOL:g})")

    S, Hq, KV, d = 2048, 9, 3, 64                         # smollm-135m
    q = jax.random.normal(ks[5], (1, S, Hq, d)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[6], (1, S, KV, d)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[7], (1, S, KV, d)).astype(jnp.bfloat16)
    o = np.asarray(flash_attention(q, k, v, causal=True), np.float32)
    ref = np.asarray(attention_reference(
        *jax.device_put(tuple(a.astype(jnp.float32) for a in (q, k, v)),
                        cpu), causal=True))
    err = np.abs(o - ref).max()
    check(np.isfinite(o).all() and err <= FLASH_ATOL,
          f"flash_attention error {err:.3e}")
    print(f"[kernels] flash_attention S{S} H{Hq}/KV{KV} d{d} causal bf16: "
          f"max abs err {err:.3e} (limit {FLASH_ATOL:g})")


# ------------------------------------------------------------- phase 3
def _fluid_agree(name, vos_tpu, vos_cpu):
    """The same jitted fluid program on the TPU and on the CPU device:
    the same feasible set, the same best plan by mean VoS, and every
    VoS within ``FLUID_RTOL``."""
    fin = np.isfinite(vos_cpu)
    check(np.array_equal(fin, np.isfinite(vos_tpu)),
          f"{name}: feasible sets differ")
    rel = (np.abs(vos_tpu[fin] - vos_cpu[fin])
           / np.maximum(np.abs(vos_cpu[fin]), 1e-9))
    best_t = int(np.argmax(vos_tpu.mean(axis=0)))
    best_c = int(np.argmax(vos_cpu.mean(axis=0)))
    check(best_t == best_c, f"{name}: best plan {best_t} on the TPU, "
          f"{best_c} on the CPU")
    check(rel.max() <= FLUID_RTOL, f"{name}: fluid VoS rel diff "
          f"{rel.max():.3e}")
    print(f"[planner] fluid {name} {vos_tpu.shape[0]}x{vos_tpu.shape[1]}: "
          f"TPU vs CPU max rel diff {rel.max():.3e} "
          f"(limit {FLUID_RTOL:g}), best plan {best_t} on both")


def phase_planner(cpu):
    import jax
    from benchmarks.bench_fleet import N_REGIONS, N_SITES, SEED, _home_edge
    from benchmarks.bench_robust import (AGREEMENT_TOL, agreement_block,
                                         heavy_analytics_plans)
    from repro.fluid import FluidEngine, ScenarioEnsemble
    from repro.placement.parallel import ParallelEvaluator
    from repro.placement.plan import PlacementPlan
    from repro.region import FleetGenSpec, generate_fleet, region_search

    spec = generate_fleet(FleetGenSpec(n_sites=N_SITES, n_regions=N_REGIONS,
                                       seed=SEED, epoch_s=300.0,
                                       drift="bursts"))
    cs = spec.compile()
    ens = ScenarioEnsemble.from_spec(spec, n=16, seed=0, engine=cs)
    with ParallelEvaluator(cs, workers=2, spec=spec) as pev:
        sr = region_search(cs, chips_options=(4, 8), seed=0, sweeps=1,
                           evaluator=pev, ensemble=ens, risk="cvar")
        pool = pev.stats()
    check(pool["parallel_batches"] > 0,
          f"the DES pool served no batch: {pool}")
    check(sr.screen["robust"]["candidates"] > 0, "fluid tier ranked nothing")
    print(f"[planner] region_search {N_SITES} sites / {N_REGIONS} regions: "
          f"vos {sr.result.vos:.4f}, {sr.screen['robust']['candidates']} "
          f"candidates ranked by the fluid ensemble, pool "
          f"batches={pool['parallel_batches']} "
          f"jobs={pool['parallel_jobs']} serial={pool['serial_jobs']}")

    names = list(cs.order)
    cands = [sr.plan, _home_edge(spec)] + [
        PlacementPlan.all_dc(names, chips=c, dvfs_f=1.0) for c in (4, 8)]
    fleet_tpu = ens.evaluate(cands).vos
    spec_h, eng_h, plans_h = heavy_analytics_plans(32)
    ens_h = ScenarioEnsemble.from_spec(spec_h, n=256, engine=eng_h)
    heavy_tpu = ens_h.evaluate(plans_h).vos
    with jax.default_device(cpu):
        fleet_cpu = FluidEngine.compile(cs).evaluate(
            cands, realizations=ens.realizations).vos
        heavy_cpu = FluidEngine.compile(eng_h).evaluate(
            plans_h, realizations=ens_h.realizations).vos
    _fluid_agree("fleet", fleet_tpu, fleet_cpu)
    _fluid_agree("heavy_analytics", heavy_tpu, heavy_cpu)

    rows = agreement_block()
    worst = max(r["rel_err"] for r in rows)
    check(worst <= AGREEMENT_TOL, f"fluid vs DES rel err {worst:.3e}")
    print(f"[planner] fluid vs DES on {len(rows)} recorded anchor plans: "
          f"worst rel err {worst:.3e} (limit {AGREEMENT_TOL:g})")


# ------------------------------------------------------------- phase 4
def phase_model(cpu, seed):
    """Serving computes in bf16; the reference is float32 on the CPU
    device. ``LOGITS_RTOL`` is 2.5× the bf16-vs-f32 error of the same
    prefill on a CPU (1.9e-2)."""
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import demo_inputs, serve_demo
    from repro.models import model as M

    arch, batch, prompt, gen = "smollm-135m", 4, 64, 32
    toks = serve_demo(arch, batch=batch, prompt_len=prompt, gen=gen,
                      full=True, seed=seed)
    cfg, params, bd = demo_inputs(arch, batch=batch, prompt_len=prompt,
                                  full=True, seed=seed)
    check(toks.shape == (batch, gen), f"tokens shape {toks.shape}")
    check(bool(((toks >= 0) & (toks < cfg.padded_vocab)).all()),
          "tokens in range")
    prefill = jax.jit(lambda p, b, dtype: M.prefill(
        cfg, p, b, prompt + gen, compute_dtype=dtype)[0],
        static_argnums=2)
    got = np.asarray(prefill(params, bd, jnp.bfloat16), np.float32)
    ref = np.asarray(prefill(*jax.device_put((params, bd), cpu),
                             jnp.float32))
    check(np.isfinite(got).all(), "prefill logits finite")
    err = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    check(err <= LOGITS_RTOL, f"prefill logits rel err {err:.3e}")
    print(f"[model] {arch} full width: batch {batch}, prompt {prompt}, "
          f"{gen} tokens in range; bf16 prefill logits vs f32 CPU rel err "
          f"{err:.3e} (limit {LOGITS_RTOL:g})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev, count = phase_device()

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    from repro.utils.compile_cache import enable_compile_cache
    print(f"[device] compile cache: {enable_compile_cache()}")
    cpu = jax.devices("cpu")[0]
    k_offload, k_kernels = jax.random.split(jax.random.PRNGKey(args.seed))

    with Phase("offload"):
        phase_offload(k_offload)
    with Phase("kernels"):
        phase_kernels(k_kernels, cpu)
    with Phase("planner"):
        phase_planner(cpu)
    with Phase("model"):
        phase_model(cpu, args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
