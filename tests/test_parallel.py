"""Parallel + incremental planning hot path: ParallelEvaluator
bit-equality with the serial evaluator (any worker count, repeated
runs, the in-process path, pools that fail loudly and workers that
stay off JAX), delta-aware block
screening vs the dense screen, cross-epoch evaluator-cache reuse (the
online controller's telemetry counters), and the sealed-plan
regression (mutating a plan after ``key()`` must raise)."""
import numpy as np
import pytest

from repro.online.controller import ForecastModel, OnlineController
from repro.placement.parallel import ParallelEvaluator, default_workers
from repro.placement.plan import (PlacementPlan, ServicePlacement,
                                  service_options)
from repro.placement.search import Evaluator, search_placement
from repro.region import FleetGenSpec, generate_fleet, region_search
from repro.region.search import _partition_from_screener


@pytest.fixture(scope="module")
def small_hier():
    spec = generate_fleet(FleetGenSpec(
        n_sites=24, n_regions=3, seed=5, horizon_s=600.0,
        drift="constant", base_rate_hz=4.0))
    return spec, spec.compile()


def _result_fields(r):
    return (r.vos, r.feasible, r.plan_label)


# ---------------------------------------------------- parallel == serial
def test_parallel_search_matches_serial_bit_identical(small_hier):
    """The whole decomposed search through a 2-worker pool must
    reproduce the serial evaluator exactly: winning plan, exact-DES
    VoS float, and the evaluator bookkeeping (hit/miss counters,
    history order)."""
    spec, eng = small_hier
    ser = Evaluator(eng)
    sr = region_search(eng, chips_options=(4,), seed=0, sweeps=1,
                       evaluator=ser)
    with ParallelEvaluator(eng, workers=2, spec=spec) as pev:
        sr2 = region_search(eng, chips_options=(4,), seed=0, sweeps=1,
                            evaluator=pev)
    assert sr2.plan.key() == sr.plan.key()
    assert sr2.result.vos == sr.result.vos           # exact, not approx
    assert _result_fields(sr2.result) == _result_fields(sr.result)
    assert (pev.hits, pev.misses) == (ser.hits, ser.misses)
    assert pev.history == ser.history                # same order, same vos


@pytest.mark.parametrize("workers", [1, 2, 4])
def test_parallel_determinism_across_worker_counts(small_hier, workers):
    """Worker count is a throughput knob, never a result knob."""
    spec, eng = small_hier
    ref = region_search(eng, chips_options=(4,), seed=0, sweeps=1)
    with ParallelEvaluator(eng, workers=workers, spec=spec) as pev:
        sr = region_search(eng, chips_options=(4,), seed=0, sweeps=1,
                           evaluator=pev)
    assert sr.plan.key() == ref.plan.key()
    assert sr.result.vos == ref.result.vos


def test_parallel_repeated_runs_identical(small_hier):
    spec, eng = small_hier

    def once():
        with ParallelEvaluator(eng, workers=2, spec=spec) as pev:
            sr = region_search(eng, chips_options=(4,), seed=0, sweeps=1,
                               evaluator=pev)
        return sr.plan.key(), sr.result.vos

    assert once() == once()


def test_parallel_in_process_fallback(small_hier):
    """workers<=1 never builds a pool: the batch runs the serial loop
    in the caller's process and the counters say so."""
    _, eng = small_hier
    names = list(eng.topology)
    plans = [PlacementPlan.all_dc(names, chips=c, dvfs_f=1.0)
             for c in (4, 8, 16)]
    pev = ParallelEvaluator(eng, workers=1)
    got = pev.evaluate_batch(plans)
    assert pev._pool is None
    assert pev.parallel_jobs == 0 and pev.serial_jobs == len(plans)
    ser = Evaluator(eng)
    assert [r.vos for r in got] == [ser(p).vos for p in plans]


def test_parallel_pool_needs_spec(small_hier):
    """Spawned workers rebuild the engine from the spec: a pool without
    one is refused up front instead of degrading to serial."""
    _, eng = small_hier
    with pytest.raises(ValueError, match="spec"):
        ParallelEvaluator(eng, workers=2)


def test_parallel_dead_pool_raises(small_hier):
    """A pool that died fails the batch; it never falls back silently."""
    spec, eng = small_hier
    names = list(eng.topology)
    plans = [PlacementPlan.all_dc(names, chips=c, dvfs_f=1.0)
             for c in (4, 8)]
    with ParallelEvaluator(eng, workers=2, spec=spec) as pev:
        pev._ensure_pool().terminate()
        with pytest.raises(ValueError, match="not running"):
            pev.evaluate_batch(plans)
    assert pev.serial_jobs == 0 and pev.parallel_jobs == 0


def test_parallel_workers_stay_off_jax(small_hier):
    """The pool serves a batch and its workers report no JAX backend,
    while the same check in a process that has touched JAX trips."""
    import jax

    from repro.placement import parallel

    spec, eng = small_hier
    names = list(eng.topology)
    plans = [PlacementPlan.all_dc(names, chips=c, dvfs_f=1.0)
             for c in (4, 8, 16)]
    with ParallelEvaluator(eng, workers=2, spec=spec) as pev:
        got = pev.evaluate_batch(plans)
    assert pev.parallel_batches == 1 and pev.parallel_jobs == len(plans)
    assert [r.vos for r in got] == [Evaluator(eng)(p).vos for p in plans]
    jax.devices()
    parallel._WORKER_ENGINE = eng
    try:
        assert parallel._eval_plan(plans[0].to_dict())[1] is True
    finally:
        parallel._WORKER_ENGINE = None


def test_parallel_batch_cache_bookkeeping(small_hier):
    """Duplicate submissions and re-batched plans hit the memo exactly
    as the serial evaluator would."""
    _, eng = small_hier
    names = list(eng.topology)
    a = PlacementPlan.all_dc(names, chips=4, dvfs_f=1.0)
    b = PlacementPlan.all_dc(names, chips=8, dvfs_f=1.0)
    pev = ParallelEvaluator(eng, workers=1)
    pev.evaluate_batch([a, b, a])
    assert (pev.hits, pev.misses) == (1, 2)
    pev.evaluate_batch([b, a])
    assert (pev.hits, pev.misses) == (3, 2)
    assert default_workers() >= 1


# ------------------------------------------------- delta-aware screening
def test_region_search_delta_vs_dense_bit_identical(small_hier):
    """Force the dense per-block screen and re-run: the delta-aware
    path must have produced the same winner from the same screen
    scores (the delta stats prove it actually ran)."""
    _, eng = small_hier
    screener = eng.screening_model()
    before = screener.delta_stats()
    sr_delta = region_search(eng, chips_options=(4,), seed=0, sweeps=1)
    after = screener.delta_stats()
    assert after["delta_calls"] > before["delta_calls"]
    assert after["cells_saved"] > before["cells_saved"]
    screener.score_block = None           # Evaluator falls back to dense
    try:
        sr_dense = region_search(eng, chips_options=(4,), seed=0, sweeps=1)
    finally:
        del screener.score_block
    assert sr_delta.plan.key() == sr_dense.plan.key()
    assert sr_delta.result.vos == sr_dense.result.vos


def test_score_block_matches_dense_direct(small_hier):
    """score_block on a single region's columns == the dense
    score_matrix on the same full-width rows, bit for bit."""
    _, eng = small_hier
    m = eng.screening_model()
    order = list(m.order)
    rank = {s: i for i, s in enumerate(order)}
    fleet = eng.cfg.fleet
    parts = _partition_from_screener(m, fleet, 12)
    all_sites = [s for part in parts for s in part.sites]
    options = service_options((4,), (1.0,), all_sites)
    dc_opts = [i for i, o in enumerate(options) if not o.is_edge]
    site_opt = {o.site: i for i, o in enumerate(options) if o.is_edge}
    base = np.full(len(order), dc_opts[0], dtype=int)
    rng = np.random.default_rng(7)
    ran_delta = False
    for part in parts:
        cols = [rank[s] for s in part.services]
        sub = np.asarray([site_opt[s] for s in part.sites] + dc_opts)
        P = np.tile(base, (32, 1))
        P[:, cols] = sub[rng.integers(0, len(sub), (32, len(cols)))]
        before = m.delta_stats()
        got = m.score_block(P, cols, options)
        if m.delta_stats()["delta_calls"] > before["delta_calls"]:
            ran_delta = True
        want = m.score_matrix(P, options)
        assert np.array_equal(got, want), part.region
    assert ran_delta       # at least one block took the incremental path


def test_score_block_guard_falls_back_dense(small_hier):
    """Pinned occupancy inside the block's own region breaks the
    disjointness guard: score_block must take the dense fallback (and
    count it), still bit-identical."""
    _, eng = small_hier
    m = eng.screening_model()
    order = list(m.order)
    rank = {s: i for i, s in enumerate(order)}
    parts = _partition_from_screener(m, eng.cfg.fleet, 12)
    part = parts[0]
    all_sites = [s for p in parts for s in p.sites]
    options = service_options((4,), (1.0,), all_sites)
    dc_opts = [i for i, o in enumerate(options) if not o.is_edge]
    site_opt = {o.site: i for i, o in enumerate(options) if o.is_edge}
    cols = [rank[s] for s in part.services[:-1]]
    if not cols:
        pytest.skip("single-service partition")
    base = np.full(len(order), dc_opts[0], dtype=int)
    # pin the held-out service onto one of the block's own edge sites
    base[rank[part.services[-1]]] = site_opt[part.sites[0]]
    sub = np.asarray([site_opt[s] for s in part.sites] + dc_opts)
    P = np.tile(base, (8, 1))
    P[:, cols] = sub[np.random.default_rng(3).integers(
        0, len(sub), (8, len(cols)))]
    before = m.delta_stats()["dense_fallbacks"]
    got = m.score_block(P, cols, options)
    assert m.delta_stats()["dense_fallbacks"] == before + 1
    assert np.array_equal(got, m.score_matrix(P, options))


# ------------------------------------------------- cross-epoch cache reuse
def test_evaluator_shared_cache_namespaced_by_prefix(small_hier):
    """One memo dict shared across evaluators: the same model
    fingerprint reuses scores wholesale, a different fingerprint must
    not (stale scores from an old forecast would rank wrongly)."""
    spec, eng = small_hier
    info = eng.info()
    rates = {s: 4.0 for s in eng.order}
    model = ForecastModel(info, rates)
    shared: dict = {}
    ev1 = Evaluator(model, cache=shared, key_prefix=("fp-a",))
    sr1 = search_placement(model, chips_options=(4,), seed=0,
                           edge_sites=info.fleet.site_names, evaluator=ev1)
    assert sr1.cache_misses > 0
    ev2 = Evaluator(model, cache=shared, key_prefix=("fp-a",))
    sr2 = search_placement(model, chips_options=(4,), seed=0,
                           edge_sites=info.fleet.site_names, evaluator=ev2)
    assert sr2.plan.key() == sr1.plan.key()
    assert sr2.cache_misses == 0 and sr2.cache_hits > 0
    ev3 = Evaluator(model, cache=shared, key_prefix=("fp-b",))
    sr3 = search_placement(model, chips_options=(4,), seed=0,
                           edge_sites=info.fleet.site_names, evaluator=ev3)
    assert sr3.cache_misses == sr1.cache_misses    # namespace isolated


def test_controller_telemetry_cross_epoch_counters():
    """Every online epoch reports the run-cumulative shared-cache
    counters; they reconcile with the per-epoch ones and the cache
    actually persists across epochs."""
    spec = generate_fleet(FleetGenSpec(
        n_sites=8, n_regions=2, seed=42, drift="constant",
        horizon_s=600.0, epoch_s=150.0))
    eng = spec.compile()
    ctrl = OnlineController(chips_options=(4,), window=1,
                            switch_margin=0.02, seed=0)
    eng.run(ctrl)
    assert len(ctrl.telemetry) >= 2
    cum_h = cum_m = 0
    for e in ctrl.telemetry:
        s = e["search"]
        assert {"cum_cache_hits", "cum_cache_misses", "cache_plans",
                "model_reused"} <= set(s)
        cum_h += s["cache_hits"]
        cum_m += s["cache_misses"]
        assert s["cum_cache_hits"] == cum_h
        assert s["cum_cache_misses"] == cum_m
        assert s["cache_plans"] > 0           # memo persists across epochs
    assert len(ctrl._xcache) == ctrl.telemetry[-1]["search"]["cache_plans"]


def test_controller_cache_reuse_is_bit_identical():
    """The shared cache is an optimization, not a behavior change: the
    same run with the memo forcibly disabled (cleared each epoch via a
    fresh bind-equivalent) plays the identical plan sequence."""
    spec = generate_fleet(FleetGenSpec(
        n_sites=8, n_regions=2, seed=42, drift="constant",
        horizon_s=600.0, epoch_s=150.0))

    def run(ctrl):
        r = spec.compile().run(ctrl)
        return r.vos, [e["chosen_vos"] for e in ctrl.telemetry]

    a = run(OnlineController(chips_options=(4,), window=1,
                             switch_margin=0.02, seed=0))
    ctrl_nc = OnlineController(chips_options=(4,), window=1,
                               switch_margin=0.02, seed=0)
    orig = ctrl_nc._model_fingerprint
    calls = iter(range(10 ** 6))
    # unique fingerprint per epoch -> every lookup misses -> no reuse
    ctrl_nc._model_fingerprint = (
        lambda *a_, **k: orig(*a_, **k) + (next(calls),))
    b = run(ctrl_nc)
    assert a == b


# ------------------------------------------------------ sealed-plan memo
def test_plan_mutation_after_key_rejected():
    """Regression: key() seals the plan — a mutation afterwards would
    silently alias a stale memo entry onto the wrong plan."""
    plan = PlacementPlan({"agg": ServicePlacement("gw-a"),
                          "smooth": ServicePlacement("gw-a")})
    plan.assignments["smooth"] = ServicePlacement("gw-b")   # still open
    k = plan.key()
    assert plan.key() is k                   # memoized, not recomputed
    with pytest.raises(TypeError):
        plan.assignments["agg"] = ServicePlacement("gw-b")
    with pytest.raises(TypeError):
        del plan.assignments["agg"]
    with pytest.raises(TypeError):
        plan.assignments.update({"agg": ServicePlacement("gw-b")})
    with pytest.raises(TypeError):
        plan.assignments.clear()
    # the sealed plan still reads fine and its key is stable
    assert plan.site("smooth") == "gw-b"
    assert plan.key() == k
