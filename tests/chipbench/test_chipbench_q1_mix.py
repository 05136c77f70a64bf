"""A whole ``q2.q1-mix`` run at a tiny size on the CPU (the harness's
look for a chip skipped, the kernel in interpret mode): sound, under
its control, and with the timed path broken underneath."""
import time

import pytest

from harness import HERE, Run, entry_of, execute, read_json

SEED = 2 ** 31 + 91
THINGS = 4


def _cfg(**kw):
    cfg = read_json(f"{HERE}/configs/neubot-q1q2.json")
    # Q2's window shrinks below the edge budget's new value; Q1's 180
    # records stay under it
    cfg.update({"things": THINGS, "edge_budget": 1000, **kw})
    cfg["window_s"] = 6000
    return cfg


def _run(seconds=2.0, control=False, **kw):
    return execute("q2.q1-mix", SEED, seconds, False,
                   t_start=time.perf_counter(), require_tpu=False,
                   config=_cfg(**kw), control=control)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    # 2 s at 3 slides/s: 30 rounds of Q1, 6 slides of Q2
    assert out["attempted"] == THINGS * (30 + 6) and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "q2_window_p95_s",
                                   "q2_windows_per_s"}
    assert out["checks"]["q1_max_mismatches"]["value"] == 0
    assert out["checks"]["q1_windows_not_at_edge"]["value"] == 0
    assert list(out)[-1] == "checks"


def test_five_q1_windows_at_the_edge_per_offloaded_q2_window():
    mod = entry_of({"entry": "neubot-mix"})
    traffic = read_json(f"{HERE}/traffic/q1-mix.json")
    run = Run("q2.q1-mix", _cfg(), traffic, SEED, 1.0, False, {},
              interpret=True)
    try:
        mod.setup(run)
        mod.window(run)
    finally:
        mod.release(run)
    c = run.counters
    assert c["q1_windows"] == 15 * THINGS and c["q2_windows"] == 3 * THINGS
    assert c["q1_windows"] == 5 * c["q2_windows"]
    assert c["edge_runs"] == c["q1_windows"]
    assert c["offloads"] == c["q2_windows"]
    assert run.state["q1_lat"].size == c["q1_windows"]
    # the end-to-end readers see Q2's windows alone, as in q2.slides
    assert len(run.records) == c["q2_windows"]
    assert c["windows"] == c["q1_windows"] + c["q2_windows"]


def test_control_is_not_correct():
    out = _run(control=True)
    assert not out["correct"]
    for name in ("q1_max_mismatches", "q2_mean_max_rel_err"):
        assert out["checks"][name]["value"] > out["checks"][name]["limit"]


def _edge_altered(orig, factor):
    def f(values, agg):
        return orig(values, agg) * factor
    return f


def _offload_nan(values, agg, **kw):
    import jax.numpy as jnp
    return jnp.float32(jnp.nan)


@pytest.mark.parametrize("fault", ["edge_answer_altered", "q1_offloaded",
                                   "uplink_dropped", "edge_answer_nan",
                                   "offload_answer_nan", "slide_one_altered"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch,
                                          slide_one_altered):
    from repro.pipeline import queries
    import harness
    kw = {}
    if fault == "slide_one_altered":
        monkeypatch.setattr(queries.HybridExecutor, "run_windows",
                            slide_one_altered(1.001), raising=False)
    elif fault in ("edge_answer_altered", "edge_answer_nan"):
        factor = 1 + 1e-6 if fault == "edge_answer_altered" else float("nan")
        monkeypatch.setattr(queries, "aggregate",
                            _edge_altered(queries.aggregate, factor))
    elif fault == "offload_answer_nan":
        monkeypatch.setattr(queries, "offload_aggregate", _offload_nan)
    elif fault == "q1_offloaded":
        kw = {"edge_budget": 0}
    else:
        entry_of_ = harness.entry_of

        def dropped(traffic):
            mod = entry_of_(traffic)
            mod.land = lambda rows, block, start: rows
            return mod
        monkeypatch.setattr(harness, "entry_of", dropped)
    out = _run(seconds=1.0, **kw)
    assert not out["correct"], (fault, out["checks"])
    failed = {k for k, c in out["checks"].items()
              if not c["value"] <= c["limit"]}
    assert failed == {"edge_answer_altered": {"q1_max_mismatches"},
                      "q1_offloaded": {"q1_windows_not_at_edge"},
                      "uplink_dropped": {"q2_mean_max_rel_err"},
                      "edge_answer_nan": {"q1_max_mismatches"},
                      "offload_answer_nan": {"q2_mean_max_rel_err"},
                      "slide_one_altered": {"q2_mean_max_rel_err"}}[fault]
