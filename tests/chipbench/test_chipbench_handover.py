"""How the Q2 cells hand a slide's windows to ``HybridExecutor``, at a
tiny size on the CPU (the kernel in interpret mode): one
``run_windows`` call a slide, in thing order, where the executor has
the call; a ``run_window`` call a window, through the same loop, where
it has not; and a slide that ends at the window whose ``next()``
raised."""
import math

import numpy as np
import pytest

from harness import HERE, Run, entry_of, read_json

SEED = 2 ** 31 + 113


def _q2_run():
    cfg = read_json(f"{HERE}/configs/neubot-q2.json")
    cfg.update(things=8, window_s=131072 * 2, slide_s=512)
    tr = read_json(f"{HERE}/traffic/slides.json")
    tr.update(slides_per_s=4.0)
    # 0.5 s at 4 slides/s: 2 slides
    return Run("q2.slides", cfg, tr, SEED, 0.5, False, {}, interpret=True)


def _mix_run():
    cfg = read_json(f"{HERE}/configs/neubot-q1q2.json")
    cfg.update(things=4, edge_budget=1000, window_s=6000)
    tr = read_json(f"{HERE}/traffic/q1-mix.json")
    # 1 s at 3 slides/s: 15 rounds of Q1, 3 slides of Q2
    return Run("q2.q1-mix", cfg, tr, SEED, 1.0, False, {}, interpret=True)


CELLS = {"q2-offload": (_q2_run, 2), "neubot-mix": (_mix_run, 3)}


def _recording(calls, run_windows, fail_at=None):
    """``run_windows`` recording each call's windows; on the first call,
    ``next()`` of window ``fail_at`` raises."""
    def f(self, windows, agg):
        calls.append(tuple(windows))
        for j, v in enumerate(run_windows(self, windows, agg)):
            if len(calls) == 1 and j == fail_at:
                raise RuntimeError(f"window {j} failed")
            yield v
    return f


def _drive(entry, before_window=None):
    """One whole run of ``entry`` at its tiny size: the run, its checks
    and its rings as the window left them. ``before_window`` is called
    between set-up and the window."""
    make, _ = CELLS[entry]
    run = make()
    mod = entry_of({"entry": entry})
    try:
        mod.setup(run)
        if before_window:
            before_window()
        mod.window(run)
        hist = run.state["hist"]
    finally:
        mod.release(run)
    return run, mod.checks(run), hist


def _q2_windows(run):
    return run.counters.get("q2_windows", run.counters["windows"])


@pytest.mark.parametrize("entry", list(CELLS))
def test_each_slide_is_one_run_windows_call(entry, monkeypatch,
                                            run_windows):
    from repro.pipeline import queries
    calls = []
    monkeypatch.setattr(queries.HybridExecutor, "run_windows",
                        _recording(calls, run_windows),
                        raising=False)
    run, checks, hist = _drive(entry)
    things, slides = run.state["things"], CELLS[entry][1]
    assert [len(c) for c in calls] == [things] * slides
    assert _q2_windows(run) == things * slides
    # the last slide's windows are the rings in thing order
    assert all(a is b for a, b in zip(calls[-1], hist))
    assert run.failed == 0 and all(c.ok for c in checks), checks


@pytest.mark.parametrize("entry", list(CELLS))
def test_executor_without_run_windows_reads_the_same(entry, monkeypatch,
                                                      run_windows):
    from repro.pipeline import queries
    monkeypatch.delattr(queries.HybridExecutor, "run_windows",
                        raising=False)
    per_window, checks, _ = _drive(entry)
    assert per_window.failed == 0 and all(c.ok for c in checks), checks
    calls = []
    monkeypatch.setattr(queries.HybridExecutor, "run_windows",
                        _recording(calls, run_windows),
                        raising=False)
    per_slide, checks, _ = _drive(entry)
    assert calls and all(c.ok for c in checks), checks
    assert per_window.counters == per_slide.counters
    assert per_window.attempted == per_slide.attempted
    np.testing.assert_array_equal(np.isnan(per_window.records),
                                  np.isnan(per_slide.records))


@pytest.mark.parametrize("entry", list(CELLS))
@pytest.mark.parametrize("j", [0, 1, 3])
def test_a_raising_window_ends_its_slide(entry, j, monkeypatch,
                                         run_windows):
    from repro.pipeline import queries
    calls = []
    monkeypatch.setattr(queries.HybridExecutor, "run_windows",
                        _recording(calls, run_windows, fail_at=j),
                        raising=False)
    run, _, _ = _drive(entry)
    things, slides = run.state["things"], CELLS[entry][1]
    assert len(calls) == slides
    # window j of the first slide and every later one of it failed
    assert run.failed == things - j
    assert run.attempted == run.counters["windows"]
    lat = np.asarray(run.records, float).reshape(slides, things)
    assert np.isnan(lat[0, j:]).all() and not np.isnan(lat[0, :j]).any()
    assert not np.isnan(lat[1:]).any()
    assert math.isfinite(np.nanmax(lat))


@pytest.mark.parametrize("entry", list(CELLS))
@pytest.mark.parametrize("j", [0, 2])
def test_a_raising_run_window_ends_its_slide(entry, j, monkeypatch):
    """Without ``run_windows`` the entries drive ``run_window`` through
    the same loop, so a window that raises ends its slide as well."""
    from repro.pipeline import queries
    monkeypatch.delattr(queries.HybridExecutor, "run_windows",
                        raising=False)
    orig = queries.HybridExecutor.run_window
    offloaded = []

    def run_window(self, values, agg):
        if agg == "mean":   # Q2's windows; the mix's Q1 takes the max
            offloaded.append(values)
            if len(offloaded) == j + 1:
                raise RuntimeError(f"window {j} failed")
        return orig(self, values, agg)

    run, _, _ = _drive(entry, lambda: monkeypatch.setattr(
        queries.HybridExecutor, "run_window", run_window))
    things, slides = run.state["things"], CELLS[entry][1]
    assert run.failed == things - j
    assert run.attempted == run.counters["windows"]
    lat = np.asarray(run.records, float).reshape(slides, things)
    assert np.isnan(lat[0, j:]).all() and not np.isnan(lat[0, :j]).any()
    assert not np.isnan(lat[1:]).any()
