"""The program's spans (repro.utils.tracing), recorded by the profiler on
the CPU with the offload kernel in interpret mode."""
import glob
import os

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.pipeline.queries import HybridExecutor
from repro.utils import tracing


@pytest.fixture
def spans_on():
    was = tracing._on
    tracing.enable()
    yield
    tracing.enable(was)


@pytest.fixture
def spans_off():
    was = tracing._on
    tracing.enable(False)
    yield
    tracing.enable(was)


def _profile(tmp_path, fn):
    """Run ``fn`` under the profiler; the ``repro.*`` host events it
    left, as ``(name, start_ns, end_ns, metadata)`` in start order."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def _window(n, seed=0):
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


def test_spans_off_are_one_shared_noop_and_leave_no_event(tmp_path,
                                                          spans_off):
    assert tracing.span("a") is tracing.span("b", k=1)
    hx = HybridExecutor(edge_budget=0, interpret=True)
    edge = HybridExecutor(edge_budget=10_000, interpret=True)
    x = _window(4093)   # a new shape: the call lowers a program
    events = _profile(tmp_path, lambda: (hx.run_window(x, "max"),
                                         edge.run_window(x, "max")))
    assert events == []
    assert hx.offloads == 1 and edge.edge_runs == 1


def test_offloaded_window_nests_launch_then_fetch(tmp_path, spans_on):
    hx = HybridExecutor(edge_budget=0, interpret=True)
    x = _window(4091)
    hx.run_window(x, "mean")                       # warm
    got = []
    events = _profile(tmp_path,
                      lambda: got.append(hx.run_window(x, "mean")))
    assert got[0] == pytest.approx(float(x.mean()), rel=1e-5)
    names = [e[0] for e in events]
    assert names == ["repro.offload.run_window", "repro.offload.launch",
                     "repro.offload.fetch"]
    (_, ws, we, _), (_, ls, le, _), (_, fs, fe, _) = events
    assert ws <= ls <= le <= fs <= fe <= we


def test_edge_window_has_its_span(tmp_path, spans_on):
    hx = HybridExecutor(edge_budget=10_000, interpret=True)
    x = _window(4089)
    got = []
    events = _profile(tmp_path, lambda: got.append(hx.run_window(x, "max")))
    assert got == [float(x.max())]
    assert [e[0] for e in events] == ["repro.edge.run_window"]
    assert events[0][3] == {}
    assert hx.edge_runs == 1 and hx.offloads == 0


def test_new_shape_lowers_once_and_warm_call_not_at_all(tmp_path,
                                                        spans_on):
    hx = HybridExecutor(edge_budget=0, interpret=True)
    x = _window(4087)
    cold = _profile(tmp_path / "cold", lambda: hx.run_window(x, "sum"))
    lowered = [e for e in cold if e[0] == tracing.LOWERING_SPAN]
    assert len(lowered) == 1
    assert "offload_aggregate" in lowered[0][3]["fun_name"]
    # the lowering happens while the window is being launched
    launch, = [e for e in cold if e[0] == "repro.offload.launch"]
    assert launch[1] <= lowered[0][1] <= launch[2]
    warm = _profile(tmp_path / "warm", lambda: hx.run_window(x, "sum"))
    assert [e for e in warm if e[0] == tracing.LOWERING_SPAN] == []


@pytest.mark.parametrize("with_edge", [False, True])
def test_run_windows_nests_one_launch_then_one_fetch(tmp_path, spans_on,
                                                     with_edge):
    hx = HybridExecutor(edge_budget=1000, interpret=True)
    windows = [_window(4085, seed) for seed in range(3)]
    if with_edge:
        windows.insert(1, _window(500))
    hx.run_window(windows[0], "mean")              # warm
    got = []
    events = _profile(tmp_path,
                      lambda: got.extend(hx.run_windows(windows, "mean")))
    assert got == pytest.approx([float(w.mean()) for w in windows],
                                rel=1e-5)
    names = [e[0] for e in events]
    edge = ["repro.edge.run_window"] if with_edge else []
    assert names == ["repro.offload.run_windows", "repro.offload.launch",
                     *edge, "repro.offload.fetch"]
    (_, ws, we, _), (_, ls, le, _) = events[:2]
    _, fs, fe, _ = events[-1]
    assert ws <= ls <= le <= fs <= fe <= we
    if with_edge:
        assert ls <= events[2][1] <= events[2][2] <= le


def test_warm_run_windows_after_run_window_lowers_nothing(tmp_path,
                                                          spans_on):
    hx = HybridExecutor(edge_budget=0, interpret=True)
    x = _window(4083)
    hx.run_window(x, "sum")
    warm = _profile(tmp_path,
                    lambda: list(hx.run_windows([x, x[::-1].copy()],
                                                "sum")))
    assert [e for e in warm if e[0] == tracing.LOWERING_SPAN] == []
    assert hx.offloads == 3 and hx.offload_waits == 2
