"""A whole ``q2.slides`` run at a tiny size on the CPU (the harness's
look for a chip skipped, the kernel in interpret mode): sound, under
its control, and with the timed path broken underneath."""
import time

import pytest

from harness import execute, read_json, HERE

SEED = 2 ** 31 + 77


def _run(control=False):
    cfg = read_json(f"{HERE}/configs/neubot-q2.json")
    cfg.update(things=8, window_s=131072 * 2, slide_s=512)
    tr = read_json(f"{HERE}/traffic/slides.json")
    tr.update(slides_per_s=4.0)
    return execute("q2.slides", SEED, 0.5, False,
                   t_start=time.perf_counter(), require_tpu=False,
                   config=cfg, traffic=tr, control=control)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] == 16 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "q2_window_p95_s",
                                   "q2_windows_per_s"}
    assert list(out)[-1] == "checks"


def test_control_is_not_correct():
    out = _run(control=True)
    assert not out["correct"]
    assert out["checks"]["q2_mean_max_rel_err"]["value"] > \
        out["checks"]["q2_mean_max_rel_err"]["limit"]


def _altered(orig):
    def f(values, *, agg, interpret=False):
        return orig(values, agg=agg, interpret=interpret) * 1.001
    return f


def _half(orig):
    def f(values, *, agg, interpret=False):
        return orig(values[: values.shape[0] // 2], agg=agg,
                    interpret=interpret)
    return f


def _one_thing_altered(orig, things=8):
    calls = []

    def f(self, values, agg):
        v = orig(self, values, agg)
        calls.append(v)
        # every eighth call: the same one thing's window in each slide
        return v * 1.001 if len(calls) % things == 0 else v
    return f


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out",
                                   "state_unchanged", "one_thing_altered",
                                   "slide_one_altered", "slide_one_nan"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch,
                                          slide_one_altered):
    from repro.pipeline import queries
    import harness
    if fault == "one_thing_altered":
        # an executor with no run_windows: the entry's stand-in for it
        # calls run_window a window
        monkeypatch.delattr(queries.HybridExecutor, "run_windows",
                            raising=False)
        orig = queries.HybridExecutor.run_window
        monkeypatch.setattr(queries.HybridExecutor, "run_window",
                            _one_thing_altered(orig))
    elif fault in ("slide_one_altered", "slide_one_nan"):
        factor = 1.001 if fault == "slide_one_altered" else float("nan")
        monkeypatch.setattr(queries.HybridExecutor, "run_windows",
                            slide_one_altered(factor), raising=False)
    elif fault == "answer_altered":
        monkeypatch.setattr(queries, "offload_aggregate",
                            _altered(queries.offload_aggregate))
    elif fault == "half_left_out":
        monkeypatch.setattr(queries, "offload_aggregate",
                            _half(queries.offload_aggregate))
    else:
        entry_of = harness.entry_of

        def unchanged(traffic):
            mod = entry_of(traffic)
            mod.ring_write = lambda rows, new, k: rows
            return mod
        monkeypatch.setattr(harness, "entry_of", unchanged)
    out = _run()
    assert not out["correct"], (fault, out["checks"])
    assert out["checks"]["q2_mean_max_rel_err"]["value"] > 1e-5
