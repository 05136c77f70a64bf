"""Edge↔DC placement engine benchmark: all-edge vs. all-DC vs. searched
placement across three workload scenarios, written to BENCH_placement.json.

Scenarios (each a declarative ScenarioSpec — the co-sim runs through the
unified DES-bridged engine via ``spec.compile()``):

  light_windows    — small sliding windows, gateway-class edge, per-fire
                     energy SLOs that punish composing a VDC for tiny
                     aggregations (edge should win).
  heavy_analytics  — a CNN-scoring service whose window FLOPs exceed the
                     edge device by ~10×: it must offload, but its light
                     siblings should stay on the edge (hybrid wins).
  constrained_edge — a weak, RAM-starved edge where the all-edge plan is
                     infeasible and the stream must move to the DC.

The searched placement must achieve VoS >= both baselines on at least
2 of 3 scenarios (the search runs the two-tier screened path — batch
numpy screening, exact DES on the top-K survivors plus the baseline
anchors — so this holds by construction; the bench verifies it
end-to-end and records the tier stats).
The report embeds each spec (JSON round-trip checked by scripts/ci.sh)
and the searched plan in structured form, pinning the engine against
regressions (tests/test_scenario.py).

``--calibrate`` replaces the declared flops_per_record with values
measured from Pallas kernel dry-runs (repro.scenario.calibrate) and
writes BENCH_placement_calibrated.json so the canonical declared-profile
report is never clobbered.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Dict, Optional, Sequence

from repro.placement import Evaluator, PlacementPlan, search_placement
from repro.placement.edge import EdgeSpec
from repro.placement.network import LinkSpec
from repro.scenario import (KernelCalibrator, RateSpec, ScenarioSpec,
                            scenario)


def _out_path(smoke: bool, calibrate: bool = False) -> str:
    default = ("BENCH_placement_smoke.json" if smoke
               else "BENCH_placement_calibrated.json" if calibrate
               else "BENCH_placement.json")
    return os.environ.get("BENCH_PLACEMENT_OUT", default)


@dataclasses.dataclass
class Scenario:
    name: str
    spec: ScenarioSpec
    chips_options: Sequence[int] = (4, 8)


# ---------------------------------------------------------------------------
def scenario_light_windows() -> Scenario:
    """Tiny windows at modest rate: the edge absorbs everything; a VDC
    burns ~1 kW for milliseconds per fire and loses on the energy curve."""
    spec = (scenario("light_windows")
            .horizon(600.0)
            .farm(n_things=8, seed=11, rate=RateSpec.constant(2.0))
            .service("agg", queue="neubotspeed", column="download_speed",
                     agg="max", width_s=120, slide_s=60)
            .slo(soft_latency_s=2.0, hard_latency_s=10.0,
                 soft_energy_j=1.0, hard_energy_j=60.0)
            .profile(flops_per_record=2e3)
            .service("smooth", queue="agg_out", column="value",
                     agg="mean", width_s=300, slide_s=60)
            .fed_by("agg")
            .slo(soft_latency_s=2.0, hard_latency_s=10.0,
                 soft_energy_j=1.0, hard_energy_j=60.0)
            .profile(flops_per_record=2e3)
            .build())
    return Scenario("light_windows", spec)


def scenario_heavy_analytics() -> Scenario:
    """One CNN-scoring service needs ~10× the edge's FLOP/s: it has to be
    offloaded onto a JIT-composed VDC, while the cheap filter/trend
    services are better left on the edge (network + VDC energy)."""
    spec = (scenario("heavy_analytics")
            .horizon(600.0)
            .site("edge", link=LinkSpec(uplink_bps=40e6, compression=0.5))
            .farm(n_things=8, seed=23, rate=RateSpec.constant(4.0))
            .service("clean", queue="neubotspeed", column="download_speed",
                     agg="max", width_s=60, slide_s=30)
            .slo(soft_latency_s=2.0, hard_latency_s=10.0,
                 soft_energy_j=1.0, hard_energy_j=60.0)
            .profile(flops_per_record=2e3)
            # ~10x over the 20 GFLOP/s edge at 9600-record windows: 96 s
            .service("classify", queue="neubotspeed", column="latency_ms",
                     agg="mean", width_s=300, slide_s=60,
                     buffer_budget=16384)
            .slo(soft_latency_s=5.0, hard_latency_s=15.0,
                 soft_energy_j=80.0, hard_energy_j=400.0, gamma=2.0)
            .profile(flops_per_record=2e8, bytes_per_record=16.0,
                     operator="flash_attention")
            .service("trend", queue="clean_out", column="value",
                     agg="mean", width_s=300, slide_s=60)
            .fed_by("clean")
            .slo(soft_latency_s=2.0, hard_latency_s=10.0,
                 soft_energy_j=1.0, hard_energy_j=60.0)
            .profile(flops_per_record=2e3)
            .build())
    return Scenario("heavy_analytics", spec, chips_options=(4, 8, 16))


def scenario_constrained_edge() -> Scenario:
    """A weak, RAM-starved gateway: hosting every service's buffer budget
    exceeds device RAM (all-edge infeasible) and its record pump is slow
    enough that windows blow their latency SLO on-device."""
    b = (scenario("constrained_edge")
         .horizon(600.0)
         .site("edge", edge=EdgeSpec(throughput_rps=800.0, flops_per_s=2e9,
                                     ram_bytes=4 * 2**20),
               link=LinkSpec(uplink_bps=50e6, compression=0.5))
         .farm(n_things=12, seed=37, rate=RateSpec.constant(2.0)))
    for name, queue, column, agg, width, slide, budget in (
            ("agg", "neubotspeed", "download_speed", "max", 120, 60, 32768),
            ("pctl", "neubotspeed", "latency_ms", "mean", 300, 60, 32768),
            ("trend", "agg_out", "value", "mean", 600, 120, 16384)):
        b.service(name, queue=queue, column=column, agg=agg, width_s=width,
                  slide_s=slide, buffer_budget=budget)
        b.slo(soft_latency_s=3.0, hard_latency_s=12.0,
              soft_energy_j=40.0, hard_energy_j=400.0)
        b.profile(flops_per_record=5e3)
    b.fed_by("agg")   # trend (last declared) consumes agg's agg_out
    return Scenario("constrained_edge", b.build())


SCENARIOS = (scenario_light_windows, scenario_heavy_analytics,
             scenario_constrained_edge)


# ---------------------------------------------------------------------------
def run_scenario(sc: Scenario, calibrate: bool = False) -> Dict:
    # host-CPU benchmark: the calibrator's dry-runs use the interpreter
    cal: Optional[KernelCalibrator] = (KernelCalibrator(interpret=True)
                                       if calibrate else None)
    engine = sc.spec.compile(calibrator=cal)
    names = list(engine.topology)
    t0 = time.perf_counter()
    # one memoized evaluator: the search reuses the baseline co-sim runs
    ev = Evaluator(engine)
    all_edge = ev(PlacementPlan.all_edge(names))
    all_dc = ev(PlacementPlan.all_dc(names, chips=sc.chips_options[0]))
    sr = search_placement(engine, chips_options=sc.chips_options,
                          dvfs_options=(1.0, 0.7), evaluator=ev)
    dt = time.perf_counter() - t0
    searched = sr.result
    base_best = max(
        [r.vos for r in (all_edge, all_dc) if r.feasible] or [float("-inf")])
    out = {
        "spec": sc.spec.to_dict(),
        "all_edge": all_edge.summary(),
        "all_dc": all_dc.summary(),
        "searched": searched.summary(),
        "search": {**sr.stats(), "plan": sr.plan.label,
                   "assignments": sr.plan.to_dict(),
                   "chips_options": list(sc.chips_options)},
        "evaluator": ev.stats(),
        "searched_beats_baselines": bool(searched.feasible
                                         and searched.vos >= base_best),
        "wall_s": round(dt, 2),
    }
    if cal is not None:
        out["calibration"] = cal.report()
    return out


def main(csv_rows, smoke: bool = False, calibrate: bool = False) -> None:
    print("\n== Edge↔DC placement: all-edge vs all-DC vs searched ==")
    report: Dict = {"scenarios": {}, "smoke": smoke, "calibrated": calibrate}
    wins = 0
    for make in (SCENARIOS[:1] if smoke else SCENARIOS):
        sc = make()
        if smoke:
            sc.spec = dataclasses.replace(sc.spec, horizon_s=300.0)
        res = run_scenario(sc, calibrate=calibrate)
        report["scenarios"][sc.name] = res
        wins += res["searched_beats_baselines"]

        def _vos(d):
            return "infeasible" if not d["feasible"] else f"{d['vos']:.2f}"
        print(f"{sc.name:18s} all-edge={_vos(res['all_edge']):>10s} "
              f"all-dc={_vos(res['all_dc']):>10s} "
              f"searched={_vos(res['searched']):>10s}  "
              f"[{res['search']['evaluations']} evals, "
              f"{res['search']['method']}]")
        print(f"{'':18s} plan: {res['search']['plan']}")
        if calibrate:
            for c in res.get("calibration", ()):
                print(f"{'':18s} calibrated {c['operator']}/{c['agg']} "
                      f"m={c['m']}: {c['flops_per_record']:.1f} "
                      f"flops/record ({c['source']})")
        sv = res["searched"]
        csv_rows.append((f"placement_{sc.name}_vos",
                         0.0 if sv["vos"] is None else sv["vos"] * 1e3,
                         res["search"]["plan"]))
    need = 1 if smoke else 2
    report["acceptance"] = {"wins": wins, "of": len(report["scenarios"]),
                            "pass": wins >= need}
    out = _out_path(smoke, calibrate)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    status = "PASS" if wins >= need else "FAIL"
    print(f"searched >= both baselines on {wins}/{len(report['scenarios'])} "
          f"scenarios -> {status}; wrote {out}")


if __name__ == "__main__":
    import sys
    main([], smoke="--smoke" in sys.argv, calibrate="--calibrate" in sys.argv)
