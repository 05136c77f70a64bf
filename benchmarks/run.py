"""Benchmark harness — one module per paper table/figure.

  Fig. 4  -> bench_value_heuristics   (VPTR vs Simple value gains)
  Fig. 5  -> bench_power_capping      (power caps, sim vs emulation)
  §3 use case -> bench_pipeline       (Neubot queries, edge vs VDC offload)
  placement -> bench_placement        (edge↔DC plans, BENCH_placement.json)
  online  -> bench_online             (fleet controller, BENCH_online.json)
  search  -> bench_search_perf        (exact vs screened, BENCH_search.json)
  robust  -> bench_robust             (fluid ensemble vs DES, CVaR-vs-mean
                                       plan choice, BENCH_robust.json)
  serve   -> bench_serve              (engine vs live runtime sim-to-real
                                       gap, BENCH_serve.json)
  fleet   -> bench_fleet              (500-site hierarchical fleet:
                                       decomposed region search +
                                       warm-started online control,
                                       BENCH_fleet.json)
  chaos   -> bench_chaos              (unplanned mid-epoch faults vs the
                                       chaos-aware controller,
                                       BENCH_chaos.json)
  kernels -> bench_kernels            (Pallas vs jnp-oracle microbench)
  §Roofline -> bench_roofline         (dry-run derived terms per cell)

``--smoke`` is the CI fast path: the stream benches (placement, online)
run 1 scenario each at reduced trace length, writing *_smoke.json so the
committed full reports aren't clobbered. Keeps the benches from rotting
without burning CI minutes.

Prints ``name,us_per_call,derived`` CSV at the end.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

# allow `python benchmarks/run.py` (script dir on sys.path, repo root not)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig4,fig5,pipeline,placement,online,"
                         "search,robust,serve,fleet,chaos,kernels,roofline")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode: 1 scenario per stream bench at "
                         "reduced trace length")
    ap.add_argument("--calibrate", action="store_true",
                    help="placement bench: measure flops_per_record from "
                         "Pallas kernel dry-runs (repro.scenario.calibrate) "
                         "instead of the declared profile values")
    ap.add_argument("--no-emulation", action="store_true")
    args = ap.parse_args()
    want = set(args.only.split(",")) if args.only else None
    if (args.smoke or args.calibrate) and want is None:
        want = {"placement", "online", "search", "robust", "serve",
                "fleet", "chaos"} if args.smoke else {"placement"}

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    csv_rows: list = []
    failures = []

    def run(tag, fn, *a, **kw):
        if want is not None and tag not in want:
            return
        try:
            fn(*a, **kw)
        except Exception as e:  # keep the harness going, report at the end
            failures.append((tag, repr(e)))
            traceback.print_exc()

    from benchmarks import (bench_chaos, bench_fleet, bench_kernels,
                            bench_online, bench_pipeline, bench_placement,
                            bench_robust, bench_roofline, bench_search_perf,
                            bench_serve, bench_value_heuristics,
                            bench_power_capping)
    run("fig4", bench_value_heuristics.main, csv_rows)
    run("fig5", bench_power_capping.main, csv_rows,
        emulate=not args.no_emulation)
    run("pipeline", bench_pipeline.main, csv_rows)
    run("placement", bench_placement.main, csv_rows, smoke=args.smoke,
        calibrate=args.calibrate)
    run("online", bench_online.main, csv_rows, smoke=args.smoke)
    run("search", bench_search_perf.main, csv_rows, smoke=args.smoke)
    run("robust", bench_robust.main, csv_rows, smoke=args.smoke)
    run("serve", bench_serve.main, csv_rows, smoke=args.smoke)
    run("fleet", bench_fleet.main, csv_rows, smoke=args.smoke)
    run("chaos", bench_chaos.main, csv_rows, smoke=args.smoke)
    run("kernels", bench_kernels.main, csv_rows)
    run("roofline", bench_roofline.main, csv_rows)

    print("\nname,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.1f},{derived}")
    if failures:
        print("\nBENCH FAILURES:", failures, file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
