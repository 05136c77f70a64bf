"""Each metric reader on a synthetic run, and the host watch."""
import gc
import math
import os
import time

import numpy as np
import pytest

from devtrace import Trace
from harness import (HERE, ROOT, HostWatch, Run, entry_of, execute,
                     metric_reader, read_json)

BENCH = read_json(os.path.join(ROOT, "BENCHMARK.json"))
MS = 1e6    # ns
N = 10_368_000
PEAKS = read_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]


def _run(records=(), trace=None, window_s=0.0):
    run = Run("q2.slides", {"window_s": N, "record_hz": 1}, {}, 0, 1.0,
              trace is not None, PEAKS)
    run.records = np.asarray(records, dtype=float)
    run.trace, run.window_s = trace, window_s
    return run


@pytest.mark.parametrize("name", [
    m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
    if m["name"] != "setup_s"])
def test_reader_finds_nothing_in_an_empty_run(name):
    assert metric_reader(name)(_run()) is None


def test_window_p95_is_the_nearest_rank_of_completed_windows():
    lat = list(np.arange(1, 101) / 1000.0) + [math.nan] * 7
    assert metric_reader("q2_window_p95_s")(_run(lat)) == 0.095


def test_windows_per_s_counts_completed_windows_only():
    got = metric_reader("q2_windows_per_s")(
        _run([0.1] * 10 + [math.nan] * 3, window_s=2.0))
    assert got == 5.0


def _offload_trace(calls=4, span_ms=2.0, device_ms=0.5):
    mods, spans = [], [("bench.window", 0, 100 * MS)]
    for j in range(calls):
        t = (10 + 10 * j) * MS
        spans.append(("bench.q2.run_window", t, t + span_ms * MS))
        mods.append(("jit_offload_aggregate", t + 0.2 * MS,
                     t + (0.2 + device_ms) * MS))
    # a program outside the window is not counted
    mods.append(("jit_offload_aggregate", 200 * MS, 201 * MS))
    return Trace(device_ops=[], device_modules=mods, host_spans=spans)


def test_dispatch_ms_is_host_time_per_window():
    got = metric_reader("q2.dispatch_ms")(_run(trace=_offload_trace()))
    assert got == pytest.approx(1.5)


def test_offload_roofline_is_least_bytes_over_peak_over_device_time():
    got = metric_reader("offload_aggregate_roofline")(
        _run(trace=_offload_trace(device_ms=0.1)))
    want = 100.0 * (4 * N / PEAKS["hbm_bytes_per_s"]) / 0.1e-3
    assert got == pytest.approx(want)


def test_host_watch_records_collector_pauses():
    with HostWatch() as watch:
        c0, cpu0, _ = watch.sample()
        gc.collect()
        sum(i * i for i in range(200_000))
        c1, cpu1, _ = watch.sample()
    assert watch.gc and all(s <= e for s, e, _ in watch.gc)
    assert 0.0 < watch.gc_s(c0, c1) <= c1 - c0
    assert watch.gc_s(c1 + 1.0, c1 + 2.0) == 0.0
    assert cpu1 >= cpu0
    assert watch._on_gc not in gc.callbacks


def test_host_report_names_the_slowest_slides(capsys):
    mod = entry_of({"entry": "q2-offload"})
    host = [(0.001, 0.24, 0.23, 0.001, 0.0, 0)] * 5 \
        + [(0.002, 0.9, 0.2, 0.65, 0.0, 40)]
    mod._report_host(host, [(0.0, 0.003, 2)], behind=1, issued=6)
    err = capsys.readouterr().err
    assert "slow slide 5: drain 900.0 ms, thread CPU 200.0 ms, longest " \
        "window 650.0 ms, 40 preemptions" in err
    assert "1 collections" in err and "generation 2" in err


def test_traffic_override_replaces_one_parameter():
    cfg = read_json(f"{HERE}/configs/neubot-q2.json")
    cfg.update(things=4, window_s=131072, slide_s=512)
    out = execute("q2.slides", 2 ** 31 + 5, 0.5, False,
                  t_start=time.perf_counter(), require_tpu=False,
                  config=cfg, traffic_override={"slides_per_s": 8.0})
    assert out["attempted"] == 4 * 4
