"""The trace reduction, on a synthetic trace and on one recorded here."""
import pytest

import devtrace
from devtrace import Trace

MS = 1e6    # ns


def _trace():
    # window 0..100 ms; device busy 10-30 (two overlapping ops), 50-60
    # and 95-105 (runs past the window's end)
    return Trace(
        device_ops=[("fusion.1", 10 * MS, 25 * MS),
                    ("kernel.segment_reduce", 20 * MS, 30 * MS),
                    ("kernel.segment_reduce", 50 * MS, 60 * MS),
                    ("copy.2", 95 * MS, 105 * MS)],
        device_modules=[("jit_step", 10 * MS, 30 * MS)],
        host_spans=[("bench.window", 0, 100 * MS),
                    ("bench.call", 5 * MS, 45 * MS),
                    ("bench.call", 48 * MS, 70 * MS),
                    ("bench.sync", 30 * MS, 45 * MS)])


def test_union_merges_overlaps():
    assert devtrace.union([(5, 9), (1, 3), (2, 4), (9, 10)]) == [
        (1, 4), (5, 10)]


def test_busy_and_idle_share():
    t = _trace()
    assert t.window() == (0, 100 * MS)
    assert devtrace.busy_ns(t) == pytest.approx(35 * MS)
    assert devtrace.idle_share(t) == pytest.approx(65.0)


def test_gaps_inside_window():
    merged = devtrace.union([(10, 30), (50, 60), (95, 105)])
    assert devtrace.gaps(merged, 0, 100) == [(0, 10), (30, 50), (60, 95)]


def test_idle_gaps_named_by_innermost_span():
    got = dict((n, v) for n, v in devtrace.idle_by_span(_trace()))
    # 0-10: midpoint 5 lies in the first bench.call (5..45)
    # 30-50: midpoint 40 lies in bench.sync (shorter than bench.call)
    # 60-95: midpoint 77.5 lies in no span
    assert got == pytest.approx({"bench.call": 0.010, "bench.sync": 0.020,
                                 "no span": 0.035})


def test_top_ops_clip_to_window():
    got = dict((n, v) for n, v in devtrace.top_ops(_trace()))
    assert got == pytest.approx({"fusion.1": 0.015,
                                 "kernel.segment_reduce": 0.020,
                                 "copy.2": 0.005})


def test_covered_counts_only_the_asked_interval():
    t = _trace()
    merged = devtrace.union((s, e) for _, s, e in t.device_ops)
    assert devtrace.covered(merged, 5 * MS, 45 * MS) == pytest.approx(
        20 * MS)
    assert devtrace.covered(merged, 48 * MS, 70 * MS) == pytest.approx(
        10 * MS)


def test_window_missing_is_an_error():
    with pytest.raises(ValueError):
        Trace([], [], []).window()


def test_capture_reads_back_benchmark_spans():
    import jax
    import jax.numpy as jnp
    from harness import Run
    run = Run("w", {}, {}, 0, 1.0, True, {})
    with devtrace.capture() as holder:
        with run.span(devtrace.WINDOW_SPAN):
            with run.span("bench.inner"):
                jax.block_until_ready(jnp.ones(8) * 2)
    names = [s[0] for s in holder.trace.host_spans]
    assert devtrace.WINDOW_SPAN in names and "bench.inner" in names
    lo, hi = holder.trace.window()
    assert hi > lo
