"""Unified Scenario API: builder → spec → JSON round-trip → compile →
unified DES-bridged engine; kernel calibration of flops_per_record; the
deprecated CoSimulator shim delegating to the engine; and the
equivalence regression pinning the engine against the recorded
BENCH_placement.json results (searched ≥ baselines must hold 3/3)."""
import dataclasses
import json
import os

import pytest

from repro.placement import (CoSimConfig, CoSimulator, PlacementPlan,
                             ServicePlacement)
from repro.placement.edge import EdgeSpec
from repro.placement.network import LinkSpec
from repro.scenario import (KernelCalibrator, RateSpec, ScenarioSpec,
                            ServiceSLO, scenario)

_SLO_KW = dict(soft_latency_s=2.0, hard_latency_s=10.0,
               soft_energy_j=2.0, hard_energy_j=100.0)


def _mini_spec(horizon: float = 300.0) -> ScenarioSpec:
    return (scenario("mini")
            .horizon(horizon)
            .farm(n_things=4, seed=3, rate=RateSpec.constant(2.0))
            .service("agg", queue="neubotspeed", column="download_speed",
                     agg="max", width_s=120, slide_s=30)
            .slo(**_SLO_KW).profile(flops_per_record=2e3)
            .service("smooth", queue="agg_out", column="value", agg="mean",
                     width_s=120, slide_s=60)
            .fed_by("agg")
            .slo(**_SLO_KW).profile(flops_per_record=2e3)
            .build())


def _rich_spec() -> ScenarioSpec:
    """Exercises every declarative dimension: multi-site fleet, pinned
    farms, drift kinds, outages, stores, epochs, DC knobs."""
    return (scenario("rich")
            .horizon(1200.0).epochs(300.0)
            .dc(records_per_step=2000, dc_step_floor_s=2e-3)
            .site("gw-a", edge=EdgeSpec(name="gw-a", active_power_w=4.0),
                  link=LinkSpec(uplink_bps=1e6), user=True)
            .site("gw-b")
            .outage("gw-b", 300.0, 600.0)
            .farm(queue="neubotspeed", n_things=3, seed=7, site="gw-a",
                  rate=RateSpec.diurnal(2.0, amplitude=0.5, period_s=1200.0))
            .farm(queue="aux", n_things=2, seed=9, site="gw-b",
                  rate=RateSpec.piecewise([(0.0, 1.0), (600.0, 4.0),
                                           (1200.0, 1.0)]))
            .service("a", queue="neubotspeed", column="download_speed",
                     agg="max", width_s=120, slide_s=60)
            .slo(**_SLO_KW).profile(flops_per_record=3e3)
            .with_store(chunk_seconds=600.0, edge_budget_chunks=4)
            .service("b", queue="aux", column="latency_ms", agg="mean",
                     width_s=120, slide_s=60)
            .slo(**_SLO_KW).profile(flops_per_record=3e3)
            .service("fuse", queue="mix", column="value", agg="mean",
                     width_s=240, slide_s=120)
            .fed_by("a", "b")
            .slo(**_SLO_KW).profile(flops_per_record=3e3)
            .build())


# ---------------------------------------------------------------- builder
def test_builder_topology_and_profiles():
    spec = _mini_spec()
    assert spec.service_names() == ["agg", "smooth"]
    assert spec.topology() == {"agg": [], "smooth": ["agg"]}
    profs = spec.profiles()
    assert profs["agg"].flops_per_record == 2e3
    assert profs["agg"].slo.soft_latency_s == 2.0
    rich = _rich_spec()
    assert rich.topology() == {"a": [], "b": [], "fuse": ["a", "b"]}
    assert {s.name for s in rich.sites} == {"gw-a", "gw-b"}
    assert rich.sites[0].farm_queues == ("neubotspeed",)
    assert rich.user_site == "gw-a"
    assert rich.outage_map() == {"gw-b": ((300.0, 600.0),)}


def test_builder_rejects_bad_wiring():
    with pytest.raises(ValueError, match="consumes"):
        (scenario("dangling")
         .farm().service("x", queue="nobody_publishes_this").build())
    with pytest.raises(ValueError, match="duplicate"):
        (scenario("dup").farm()
         .service("x", queue="neubotspeed")
         .service("x", queue="neubotspeed").build())
    with pytest.raises(ValueError, match="fed_by unknown"):
        (scenario("bad").farm()
         .service("x", queue="neubotspeed")
         .service("y", queue="q2").fed_by("ghost").build())
    with pytest.raises(ValueError, match="reserved"):
        scenario("dcsite").site("dc")


# ------------------------------------------------------------- round-trip
def test_json_roundtrip_mini_and_rich():
    for spec in (_mini_spec(), _rich_spec()):
        back = ScenarioSpec.from_json(spec.to_json())
        assert back == spec
        # and a second trip is stable (canonical form)
        assert back.to_json() == spec.to_json()


def test_rate_spec_curves_match_drift_generators():
    from repro.online import diurnal, piecewise_linear, step_bursts

    h = 600.0
    pairs = [
        (RateSpec.diurnal(4.0, amplitude=0.5, period_s=100.0, phase_s=25.0),
         diurnal(4.0, amplitude=0.5, period_s=100.0, phase_s=25.0)),
        (RateSpec.bursts(1.0, 5.0, [(10.0, 20.0)]),
         step_bursts(1.0, 5.0, [(10.0, 20.0)])),
        (RateSpec.piecewise([(0.0, 1.0), (10.0, 3.0)]),
         piecewise_linear([(0.0, 1.0), (10.0, 3.0)])),
    ]
    for rspec, ref in pairs:
        rt = RateSpec(**json.loads(json.dumps(dataclasses.asdict(rspec))))
        for t in (0.0, 5.0, 15.0, 50.0):
            assert rspec.curve(h)(t) == pytest.approx(ref(t))
            assert rt.curve(h)(t) == pytest.approx(ref(t))


# ----------------------------------------------------------------- engine
def test_compile_run_plan_conserved_and_deterministic():
    spec = _mini_spec()
    names = spec.service_names()
    plan = PlacementPlan({"agg": ServicePlacement("edge"),
                          "smooth": ServicePlacement("dc", chips=4)})
    r1 = spec.compile().run_plan(plan)
    r2 = spec.compile().run_plan(plan)
    assert r1.feasible and r1.ledger.conserved()
    assert r1.vos == r2.vos
    assert r1.ledger.totals() == r2.ledger.totals()
    assert r1.per_service["agg"]["site"] == "edge"
    assert r1.per_service["smooth"]["site"] == "dc[4]@1"
    # all-edge and all-dc also conserve on the same engine instance
    engine = spec.compile()
    for p in (PlacementPlan.all_edge(names),
              PlacementPlan.all_dc(names, chips=4)):
        assert engine.run_plan(p).ledger.conserved()


def test_compiled_multi_site_engine_runs_controllers():
    from repro.online import StaticController

    spec = _rich_spec()
    engine = spec.compile()
    assert len(engine.epochs) == 4
    plan = PlacementPlan({"a": ServicePlacement("gw-a"),
                          "b": ServicePlacement("gw-b"),
                          "fuse": ServicePlacement("dc", chips=4)})
    res = engine.run(StaticController(plan))
    assert res.ledger.conserved()
    assert set(res.per_site) >= {"gw-a", "gw-b", "dc"}
    # outage windows reached the fleet
    assert engine.outages == {"gw-b": ((300.0, 600.0),)}


def test_cosim_shim_matches_engine():
    """The deprecated CoSimulator delegates to the unified engine: same
    build/profiles/cfg must produce bit-identical results."""
    spec = _mini_spec()
    plan = PlacementPlan({"agg": ServicePlacement("edge"),
                          "smooth": ServicePlacement("dc", chips=4)})
    via_spec = spec.compile().run_plan(plan)
    shim = CoSimulator(spec.build_pipeline, spec.profiles(),
                       CoSimConfig(horizon_s=spec.horizon_s))
    via_shim = shim.run(plan)
    assert via_shim.vos == via_spec.vos
    assert via_shim.ledger.totals() == via_spec.ledger.totals()
    assert via_shim.energy_total_j == via_spec.energy_total_j


def test_compile_requires_flops_or_calibrator():
    b = (scenario("uncal").farm(n_things=2, rate=RateSpec.constant(1.0))
         .service("x", queue="neubotspeed", column="latency_ms", agg="mean",
                  width_s=60, slide_s=30)
         .slo(**_SLO_KW).profile(flops_per_record=None))
    spec = b.build()
    with pytest.raises(ValueError, match="flops_per_record"):
        spec.compile()
    spec.compile(calibrator=lambda s: 123.0)   # any callable works


# ------------------------------------------------------------- calibration
def test_kernel_calibrator_measures_and_caches():
    cal = KernelCalibrator(interpret=True)
    c1 = cal.measure("window_agg", agg="max", m=2)
    c2 = cal.measure("window_agg", agg="max", m=2)
    assert c1 is c2                       # cached
    assert c1.flops_per_record > 0
    assert c1.source in ("xla-cost-analysis", "analytic")
    assert len(cal.log) == 1
    # deterministic across instances
    again = KernelCalibrator(interpret=True).measure("window_agg", agg="max",
                                                     m=2)
    assert again.flops_per_record == pytest.approx(c1.flops_per_record)
    with pytest.raises(ValueError, match="unknown operator"):
        cal.measure("not_a_kernel")


def test_kernel_calibrator_compile_errors_raise():
    """Regression: a kernel the compiler refuses must raise, not turn
    quietly into the analytic fallback; only a backend without a cost
    analysis falls back."""
    from repro.scenario.calibrate import _cost_flops

    class _Program:
        def __init__(self, cost=None, error=None):
            self.cost, self.error = cost, error

        def lower(self, *args):
            return self

        def compile(self):
            if self.error is not None:
                raise self.error
            return self

        def cost_analysis(self):
            return self.cost

    with pytest.raises(RuntimeError, match="vmem"):
        _cost_flops(_Program(error=RuntimeError("Ran out of memory in "
                                                "memory space vmem")))
    assert _cost_flops(_Program(cost=None)) is None
    assert _cost_flops(_Program(cost=[{"flops": 8.0}])) == 8.0
    # compiled mode on a CPU cannot lower a Pallas kernel: a loud error
    with pytest.raises(ValueError, match="interpret"):
        KernelCalibrator().measure("window_agg", agg="max", m=2)


def test_calibrated_compile_uses_measured_flops():
    spec = _mini_spec(horizon=120.0)
    cal = KernelCalibrator(interpret=True)
    engine = spec.compile(calibrator=cal)
    for name in ("agg", "smooth"):
        svc = next(s for s in spec.services if s.name == name)
        assert engine.profiles[name].flops_per_record == pytest.approx(
            cal(svc))
        assert engine.profiles[name].flops_per_record != 2e3


# ----------------------------------------------- equivalence regression
def _bench_path() -> str:
    return os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_placement.json")


@pytest.mark.skipif(not os.path.exists(_bench_path()),
                    reason="no recorded BENCH_placement.json")
def test_unified_engine_matches_recorded_placement_bench():
    """Retiring the two-pass scheme must not silently shift VoS: replay
    the recorded searched plans through the unified engine and require
    (a) the recorded VoS reproduces exactly and (b) searched ≥ both
    baselines still holds on all 3 scenarios."""
    with open(_bench_path()) as f:
        rep = json.load(f)
    assert not rep.get("smoke") and not rep.get("calibrated")
    assert len(rep["scenarios"]) == 3
    for name, sc in rep["scenarios"].items():
        spec = ScenarioSpec.from_dict(sc["spec"])
        engine = spec.compile()
        names = list(engine.topology)
        searched = engine.run_plan(
            PlacementPlan.from_dict(sc["search"]["assignments"]))
        assert searched.feasible and searched.ledger.conserved(), name
        assert searched.vos == pytest.approx(sc["searched"]["vos"],
                                             abs=1e-3), name
        chips0 = sc["search"]["chips_options"][0]
        baselines = [engine.run_plan(PlacementPlan.all_edge(names)),
                     engine.run_plan(PlacementPlan.all_dc(names,
                                                          chips=chips0))]
        base_best = max([r.vos for r in baselines if r.feasible]
                        or [float("-inf")])
        assert searched.vos >= base_best - 1e-9, name
        # the recorded baseline VoS must reproduce too (conservation of
        # the whole score surface, not just the winner)
        for key, res in (("all_edge", baselines[0]),
                         ("all_dc", baselines[1])):
            rec = sc[key]["vos"]
            if rec is None:
                assert not res.feasible, (name, key)
            else:
                assert res.vos == pytest.approx(rec, abs=1e-3), (name, key)


def test_slo_dataclass_roundtrip():
    slo = ServiceSLO(soft_latency_s=1.0, hard_latency_s=2.0, gamma=2.0,
                     w_p=0.6, shape="linear")
    assert ServiceSLO(**dataclasses.asdict(slo)) == slo


# ------------------------------------------- two-tier screened search
@pytest.mark.skipif(not os.path.exists(_bench_path()),
                    reason="no recorded BENCH_placement.json")
def test_screened_search_matches_exact_on_recorded_scenarios():
    """The fast path must not change the answer: on every recorded
    placement scenario the two-tier screened search must return the
    same best-plan VoS as the exact exhaustive/greedy search (tier-2
    re-scoring of the top-K survivors + anchors bounds any tier-1
    mis-rank)."""
    from repro.placement import Evaluator, search_placement

    with open(_bench_path()) as f:
        rep = json.load(f)
    assert len(rep["scenarios"]) == 3
    for name, sc in rep["scenarios"].items():
        spec = ScenarioSpec.from_dict(sc["spec"])
        engine = spec.compile()
        chips = tuple(sc["search"]["chips_options"])
        exact = search_placement(engine, chips_options=chips,
                                 dvfs_options=(1.0, 0.7), screen=False)
        ev = Evaluator(engine)
        screened = search_placement(engine, chips_options=chips,
                                    dvfs_options=(1.0, 0.7), evaluator=ev)
        assert screened.screen is not None, name
        assert screened.result.vos == pytest.approx(exact.result.vos,
                                                    abs=1e-9), name
        # the screened tier really did skip most of the exact work
        assert screened.evaluations < exact.evaluations, name
        assert ev.screened >= screened.screen["top_k"], name
        # and the recorded searched VoS is reproduced by the fast path
        assert screened.result.vos == pytest.approx(
            sc["searched"]["vos"], abs=1e-3), name


def test_batch_screening_deterministic_and_matches_single():
    """score_batch is pure array math: identical scores across calls
    and across fresh engines; the single-plan front agrees with the
    batched scores."""
    import numpy as np

    from repro.placement import PlacementPlan, ServicePlacement
    from repro.placement.plan import enumerate_plans

    spec = _mini_spec()
    names = spec.service_names()
    plans = list(enumerate_plans(names, (4, 8), (1.0, 0.7)))
    s1 = spec.compile().screening_model().score_batch(plans)
    s2 = spec.compile().screening_model().score_batch(plans)
    assert np.array_equal(s1, s2)
    sm = spec.compile().screening_model()
    for i in (0, 3, len(plans) - 1):
        r = sm.run(plans[i])
        assert r.vos == pytest.approx(sm.score_batch([plans[i]])[0])
    # RAM-infeasible plans screen to -inf, like the engine's run_plan
    tiny = dataclasses.replace(
        spec, sites=(dataclasses.replace(
            spec.sites[0], edge=EdgeSpec(ram_bytes=1024.0)),))
    r = tiny.compile().screening_model().run(
        PlacementPlan.all_edge(names))
    assert not r.feasible and r.vos == float("-inf")


def test_screened_search_deterministic_on_sampled_spaces():
    """Fleet-scale spaces go through seeded sampling + batched hill
    climbing: a fixed seed must reproduce the same plan, VoS and
    screening stats (a tiny enumerate_limit forces the sampled path)."""
    from repro.placement import screened_search

    spec = _rich_spec()
    spec = dataclasses.replace(spec, epoch_s=None, outages=())
    sites = tuple(s.name for s in spec.sites)
    runs = []
    for _ in range(2):
        engine = spec.compile()
        sr = screened_search(engine, chips_options=(4, 8),
                             dvfs_options=(1.0, 0.7), edge_sites=sites,
                             seed=7, enumerate_limit=8, sample_budget=64,
                             climbers=3, climb_rounds=4)
        runs.append(sr)
    a, b = runs
    assert a.method == "screened-sampled"
    assert a.plan.key() == b.plan.key()
    assert a.result.vos == b.result.vos
    screen_a = {k: v for k, v in a.screen.items() if k != "screen_wall_s"}
    screen_b = {k: v for k, v in b.screen.items() if k != "screen_wall_s"}
    assert screen_a == screen_b
