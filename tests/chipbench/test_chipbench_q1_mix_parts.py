"""The ``q2.q1-mix`` cell's parts at a tiny size, on the CPU: the Q1
reference against a plain max, and the readers the cell lists, on
synthetic runs."""
import math
import os

import numpy as np
import pytest

import neubot_data as data
from devtrace import Trace
from harness import HERE, ROOT, Run, metric_reader, read_json
from reference import q1_max

BENCH = read_json(os.path.join(ROOT, "BENCHMARK.json"))
MS = 1e6    # ns
N = 4096
THINGS = 8
PEAKS = read_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]


def _run(trace=None, **state):
    run = Run("q2.q1-mix", read_json(f"{HERE}/configs/neubot-q1q2.json"),
              {}, 0, 1.0, trace is not None, PEAKS)
    run.trace, run.state = trace, state
    return run


def test_q1_max_matches_a_plain_max_over_history_then_stream():
    # rounds of 64 records, Q1's window 3 rounds, 5 rounds a slide
    per, window = 64, 192
    key = data.root_key(2 ** 31 + 3)
    hist = np.stack([np.asarray(r) for r in data.history(
        key, things=THINGS, n=N, scale=20e6)])
    new = np.asarray(data.ingest(key, slides=3, things=THINGS,
                                 per=5 * per, scale=20e6))
    rnd, thing = np.meshgrid(np.arange(15), np.arange(THINGS))
    rnd, thing = rnd.ravel(), thing.ravel()
    got = q1_max.window_maxes(key, new, rnd, thing, n=N, window=window,
                              per=per, scale=20e6)
    from_history = 0
    for j, (r, i) in enumerate(zip(rnd, thing)):
        series = np.concatenate([hist[i], new[:, i].reshape(-1)])
        hi = N + (r + 1) * per
        w = series[hi - window:hi].astype(np.float64)
        assert got[j] == w.max()
        # rounds 0 and 1 reach back into the stored history
        assert (hi - window < N) == (r < 2)
        from_history += bool(r < 2 and w.argmax() < N - (hi - window))
    assert from_history > 0     # the test can see a window cut short
    ctl = q1_max.control_maxes(key, new, rnd, thing, n=N, window=window,
                               per=per, scale=20e6)
    assert (ctl != got).all()


def test_q1_window_p95_reads_the_q1_windows_alone():
    run = _run(q1_lat=np.append(np.arange(1, 101) / 1000.0, math.nan))
    run.records = np.full(50, 10.0)
    assert metric_reader("q1.window_p95_s")(run) == 0.095
    assert metric_reader("q2_window_p95_s")(run) == 10.0


def test_q1_edge_us_is_q1_span_time_per_q1_window():
    spans = [("bench.window", 0, 100 * MS),
             ("bench.q1.windows", 10 * MS, 10 * MS + 4e3),
             ("bench.q1.windows", 20 * MS, 20 * MS + 8e3),
             ("bench.q2.run_window", 30 * MS, 31 * MS),
             # a span outside the window is not counted
             ("bench.q1.windows", 200 * MS, 201 * MS)]
    run = _run(Trace(device_ops=[], device_modules=[], host_spans=spans))
    assert metric_reader("q1.edge_us")(run) is None
    run.counters["q1_windows"] = 4      # two rounds of two windows
    assert metric_reader("q1.edge_us")(run) == 3.0


def _offload_trace(calls=4, span_ms=2.0, device_ms=0.5):
    mods, spans = [], [("bench.window", 0, 100 * MS)]
    for j in range(calls):
        t = (10 + 10 * j) * MS
        spans.append(("bench.q2.run_window", t, t + span_ms * MS))
        mods.append(("jit_offload_aggregate", t + 0.2 * MS,
                     t + (0.2 + device_ms) * MS))
    spans.append(("bench.q1.windows", 60 * MS, 61 * MS))
    return Trace(device_ops=list(mods), device_modules=mods,
                 host_spans=spans)


@pytest.mark.parametrize("name", [
    m["name"] for m in BENCH["per_layer"]
    if "q2.q1-mix" in m.get("workloads", ["q2.q1-mix"])])
def test_mix_cell_readers_read_its_own_configuration(name):
    # the mix keeps Q2's keys at neubot-q2's places, so the offload
    # readers read it as they read q2.slides
    run = _run(_offload_trace(), q1_lat=np.full(20, 0.1))
    run.records = np.full(4, 0.2)
    run.counters["q1_windows"] = 256
    got = metric_reader(name)(run)
    assert got is not None and math.isfinite(got) and got > 0
