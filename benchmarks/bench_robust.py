"""Distributionally robust planning benchmark → BENCH_robust.json.

Three blocks, each pinning one acceptance gate of the fluid-ensemble
engine (``repro.fluid``):

  agreement   — the fluid engine's nominal-trace VoS vs the exact DES
                on every recorded BENCH_placement scenario's anchor
                plans (gate: ≤ 5% relative error everywhere; in
                practice the per-bin backlog recursion reproduces the
                DES latencies exactly).
  throughput  — one jitted ensemble call (257 realizations × 32 plans)
                vs sequential DES scenario evaluations (gate: ≥ 50×
                scenario-evals/sec; measured in the thousands).
  choice      — CVaR-vs-mean plan choice from ``robust_search()`` on
                the ``correlated_bursts`` / ``ramp_outage`` adversarial
                scenarios (recorded) and on ``burst_tail``, a scenario
                built so the mean-optimal all-edge plan saturates the
                gateway on rate-tail realizations while the DC plan
                pays a flat WAN latency (gate: the CVaR objective
                strictly improves worst-quantile VoS, with exact-DES
                scores on the tail realizations confirming the ranking
                and no screen-tier mis-rank of either final winner).

Every gate asserts in ``--smoke`` (the CI path) as well as in the full
run, so the robust tier cannot rot silently.
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, List, Sequence

import numpy as np

from benchmarks.bench_online import (scenario_correlated_bursts,
                                     scenario_ramp_outage)
from benchmarks.bench_placement import (SCENARIOS as PLACEMENT_SCENARIOS)
from repro.fluid import FluidEngine, RiskSpec, ScenarioEnsemble
from repro.placement import Evaluator, PlacementPlan, robust_search
from repro.placement.edge import EdgeSpec
from repro.placement.network import LinkSpec
from repro.placement.plan import enumerate_plans
from repro.scenario import RateSpec, ScenarioSpec, scenario

AGREEMENT_TOL = 0.05          # fluid vs DES relative VoS error
SPEEDUP_FLOOR = 50.0          # ensemble vs sequential-DES evals/sec


def _out_path(smoke: bool) -> str:
    default = "BENCH_robust_smoke.json" if smoke else "BENCH_robust.json"
    return os.environ.get("BENCH_ROBUST_OUT", default)


# ---------------------------------------------------------------------------
# Block 1: fluid vs exact-DES agreement on the recorded placement scenarios
# ---------------------------------------------------------------------------
def _anchor_plans(eng, chips_options: Sequence[int]) -> List[PlacementPlan]:
    names = list(eng.order)
    sites = list(eng.info().fleet.site_names)
    plans = [PlacementPlan.all_edge(names, site=s) for s in sites]
    plans += [PlacementPlan.all_dc(names, chips=c) for c in chips_options]
    return plans


def agreement_block() -> List[Dict]:
    rows = []
    for builder in PLACEMENT_SCENARIOS:
        sc = builder()
        eng = sc.spec.compile()
        fluid = FluidEngine.compile(eng)
        plans = _anchor_plans(eng, sc.chips_options)
        fr = fluid.evaluate(plans)
        for m, plan in enumerate(plans):
            des = eng.run_plan(plan)
            f_vos = float(fr.vos[0, m])
            d_vos = des.vos if des.feasible else float("-inf")
            if not des.feasible or not np.isfinite(f_vos):
                # both tiers must agree a plan is infeasible
                err = 0.0 if (not des.feasible
                              and not np.isfinite(f_vos)) else float("inf")
            else:
                err = abs(f_vos - d_vos) / max(abs(d_vos), 1e-9)
            rows.append({
                "scenario": sc.name, "plan": plan.label,
                "fluid_vos": (round(f_vos, 4)
                              if np.isfinite(f_vos) else None),
                "des_vos": round(d_vos, 4) if des.feasible else None,
                "rel_err": round(err, 6),
            })
    return rows


# ---------------------------------------------------------------------------
# Block 2: ensemble throughput vs sequential DES
# ---------------------------------------------------------------------------
def heavy_analytics_plans(n_plans: int = 32):
    """The throughput block's workload: the ``heavy_analytics`` spec,
    its compiled engine and its first ``n_plans`` enumerated plans."""
    sc = next(b() for b in PLACEMENT_SCENARIOS
              if b().name == "heavy_analytics")
    eng = sc.spec.compile()
    names = list(eng.order)
    sites = tuple(eng.info().fleet.site_names)
    plans = list(enumerate_plans(names, (4, 8, 16), (1.0,),
                                 edge_sites=sites))[:n_plans]
    return sc.spec, eng, plans


def throughput_block(n_realizations: int = 256, n_plans: int = 32,
                     des_samples: int = 2) -> Dict:
    spec, eng, plans = heavy_analytics_plans(n_plans)

    t0 = time.perf_counter()
    ens = ScenarioEnsemble.from_spec(spec, n=n_realizations, engine=eng)
    setup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ens.evaluate(plans)                      # includes XLA trace
    first_call_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    fr = ens.evaluate(plans)                 # warm jitted call
    warm_s = time.perf_counter() - t0
    evals = fr.n_realizations * fr.n_plans

    # sequential DES baseline: one scenario-eval = compile a realization
    # spec + replay one plan through the event loop
    t0 = time.perf_counter()
    for i in range(1, 1 + des_samples):
        ens.specs[i].compile().run_plan(plans[0])
    des_per_eval_s = (time.perf_counter() - t0) / des_samples

    ens_rate = evals / warm_s
    des_rate = 1.0 / des_per_eval_s
    return {
        "realizations": fr.n_realizations, "plans": fr.n_plans,
        "scenario_evals": evals,
        "ensemble_setup_s": round(setup_s, 3),
        "first_call_s": round(first_call_s, 3),
        "warm_call_s": round(warm_s, 4),
        "ensemble_evals_per_s": round(ens_rate, 1),
        "des_s_per_eval": round(des_per_eval_s, 4),
        "des_evals_per_s": round(des_rate, 3),
        "speedup": round(ens_rate / des_rate, 1),
    }


# ---------------------------------------------------------------------------
# Block 3: CVaR-vs-mean plan choice
# ---------------------------------------------------------------------------
def scenario_burst_tail() -> ScenarioSpec:
    """Adversarial drift scenario for the robust-planning gate: a
    gateway sized so the all-edge plan rides at ~0.8 burst utilization
    on the *nominal* trace (comfortably the mean-VoS winner) but
    saturates — backlog divergence, latency past the hard SLO — on the
    upper rate tail of the drift ensemble, while DC offload pays a flat
    mid-curve WAN latency that barely moves with the rate. Mean ranking
    prefers the edge; any tail-sensitive ranking prefers the DC."""
    b = (scenario("burst_tail")
         .site("gw-a", edge=EdgeSpec(name="gw-a", throughput_rps=180.0,
                                     flops_per_s=20e9, active_power_w=0.2,
                                     energy_per_record_j=100e-6),
               link=LinkSpec(uplink_bps=1e6, downlink_bps=2e6,
                             rtt_s=6.0, record_bytes=1024.0,
                             compression=0.25))
         .horizon(1800.0).epochs(300.0).dc(dc_step_floor_s=2e-3)
         .farm(queue="neubotspeed", n_things=8, seed=7, site="gw-a",
               rate=RateSpec.bursts(2.0, 9.0, [(300.0, 900.0),
                                               (1200.0, 1800.0)])))
    (b.service("agg", queue="neubotspeed", column="download_speed",
               agg="max", width_s=10, slide_s=5, buffer_budget=8192)
     .slo(soft_latency_s=4.0, hard_latency_s=6.5,
          soft_energy_j=5.0, hard_energy_j=50.0)
     .profile(flops_per_record=2e3)
     .service("trend", queue="agg_out", column="value", agg="mean",
              width_s=60, slide_s=30, buffer_budget=8192)
     .fed_by("agg")
     .slo(soft_latency_s=4.0, hard_latency_s=10.0,
          soft_energy_j=5.0, hard_energy_j=60.0)
     .profile(flops_per_record=2e3))
    return b.build()


def _choice_row(name: str, spec: ScenarioSpec,
                chips_options: Sequence[int], n: int = 48, seed: int = 0,
                rate_scale: float = 0.25, onset_scale: float = 0.15,
                des_tail_k: int = 0) -> Dict:
    """Run robust_search twice (mean / CVaR objective) over one shared
    ensemble; report the fluid worst-quantile VoS of both winners and,
    when ``des_tail_k`` > 0 and the winners diverge, the exact-DES
    scores of both plans on the worst tail realizations."""
    eng = spec.compile()
    sites = tuple(eng.info().fleet.site_names)
    ens = ScenarioEnsemble.from_spec(spec, n=n, seed=seed, engine=eng,
                                     rate_scale=rate_scale,
                                     onset_scale=onset_scale)
    ev = Evaluator(eng)
    srs = {m: robust_search(eng, ens, risk=m, chips_options=chips_options,
                            shortlist=16, final_k=6, evaluator=ev,
                            edge_sites=sites)
           for m in ("mean", "cvar")}
    mp, cp = srs["mean"].plan, srs["cvar"].plan
    fr = ens.evaluate([mp, cp])
    mean_v = fr.vos.mean(axis=0)
    q10 = np.quantile(fr.vos, 0.1, axis=0)
    row = {
        "scenario": name,
        "realizations": ens.n_realizations,
        "rate_scale": rate_scale,
        "mean_plan": mp.label, "cvar_plan": cp.label,
        "diverged": bool(mp.key() != cp.key()),
        "fluid": {
            "mean_plan": {"mean": round(float(mean_v[0]), 4),
                          "q10": round(float(q10[0]), 4)},
            "cvar_plan": {"mean": round(float(mean_v[1]), 4),
                          "q10": round(float(q10[1]), 4)},
        },
        "search": {m: {"agreement": sr.screen["agreement"],
                       "robust": sr.screen["robust"]}
                   for m, sr in srs.items()},
    }
    if des_tail_k > 0 and row["diverged"]:
        # exact-DES confirmation on the union of each plan's worst
        # realizations (one compile per member, both plans replayed)
        tail = sorted(int(i) for i in
                      set(np.argsort(fr.vos[:, 0])[:des_tail_k])
                      | set(np.argsort(fr.vos[:, 1])[:des_tail_k]))
        des = {}
        for i in tail:
            cs = ens.specs[int(i)].compile()
            des[int(i)] = (cs.run_plan(mp).vos, cs.run_plan(cp).vos)
        dm = [v[0] for v in des.values()]
        dc = [v[1] for v in des.values()]
        row["des_tail"] = {
            "members": tail,
            "mean_plan": {"min": round(min(dm), 4),
                          "mean": round(float(np.mean(dm)), 4)},
            "cvar_plan": {"min": round(min(dc), 4),
                          "mean": round(float(np.mean(dc)), 4)},
        }
    return row


# ---------------------------------------------------------------------------
def main(csv_rows, smoke: bool = False) -> None:
    report: Dict = {"blocks": {}}

    agreement = agreement_block()
    worst_err = max(r["rel_err"] for r in agreement)
    report["blocks"]["agreement"] = {
        "tolerance": AGREEMENT_TOL, "worst_rel_err": round(worst_err, 6),
        "plans": agreement}
    assert worst_err <= AGREEMENT_TOL, (
        f"fluid-vs-DES agreement gate: worst rel err {worst_err:.4f} "
        f"> {AGREEMENT_TOL}")

    thr = throughput_block()
    report["blocks"]["throughput"] = thr
    assert thr["speedup"] >= SPEEDUP_FLOOR, (
        f"throughput gate: {thr['speedup']}x < {SPEEDUP_FLOOR}x")

    tail_k = 3 if smoke else 5
    choice = [
        _choice_row("correlated_bursts",
                    scenario_correlated_bursts(smoke=smoke).spec,
                    (4, 8), seed=3, des_tail_k=0),
        _choice_row("ramp_outage",
                    scenario_ramp_outage(smoke=smoke).spec,
                    (4, 8), seed=3, des_tail_k=0),
        _choice_row("burst_tail", scenario_burst_tail(), (4, 8),
                    rate_scale=0.45, des_tail_k=tail_k),
    ]
    report["blocks"]["choice"] = choice

    bt = next(r for r in choice if r["scenario"] == "burst_tail")
    q10_gain = (bt["fluid"]["cvar_plan"]["q10"]
                - bt["fluid"]["mean_plan"]["q10"])
    assert bt["diverged"], "robust gate: CVaR and mean picked one plan"
    assert q10_gain > 0.0, (
        f"robust gate: CVaR q10 {bt['fluid']['cvar_plan']['q10']} <= "
        f"mean-objective q10 {bt['fluid']['mean_plan']['q10']}")
    dt = bt["des_tail"]
    assert (dt["cvar_plan"]["min"] > dt["mean_plan"]["min"]
            and dt["cvar_plan"]["mean"] > dt["mean_plan"]["mean"]), (
        f"robust gate: exact DES does not confirm the tail ranking: {dt}")
    assert all(bt["search"][m]["agreement"] for m in ("mean", "cvar")), (
        "robust gate: screen-tier mis-ranked a final winner")
    report["gates"] = {
        "agreement_tol": AGREEMENT_TOL, "worst_rel_err": round(worst_err, 6),
        "speedup_floor": SPEEDUP_FLOOR, "speedup": thr["speedup"],
        "cvar_q10_gain": round(q10_gain, 4),
        "des_tail_confirms": True,
    }

    out = _out_path(smoke)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"bench_robust: wrote {out} "
          f"(agreement worst err {worst_err:.2e}, "
          f"speedup {thr['speedup']}x, cvar q10 gain {q10_gain:.2f})")
    csv_rows.append(("robust_ensemble_eval",
                     thr["warm_call_s"] / thr["scenario_evals"] * 1e6,
                     f"{thr['speedup']:.0f}x_vs_des"))
    csv_rows.append(("robust_cvar_q10_gain", 0.0, f"{q10_gain:.2f}"))


if __name__ == "__main__":
    rows: List = []
    main(rows, smoke="--smoke" in sys.argv)
