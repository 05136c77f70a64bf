"""The program's own spans (``repro.*``) beside the benchmark's in a
trace: idle gaps take the innermost span's name, and no reader of an
accepted metric moves when they are there."""
import os

import pytest

import devtrace
from devtrace import Trace
from harness import HERE, Run, metric_reader, read_json

MS = 1e6    # ns
N = 10_368_000
PEAKS = read_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]


def _call(t, program=True):
    """One window's spans at ``t`` ms: the benchmark's around the
    program's (whole call, launch, fetch)."""
    bench = [("bench.q2.run_window", t * MS, (t + 16) * MS)]
    prog = [("repro.offload.run_window", (t + 1) * MS, (t + 15) * MS),
            ("repro.offload.launch", (t + 2) * MS, (t + 6) * MS),
            ("repro.offload.fetch", (t + 6) * MS, (t + 14) * MS)]
    return bench + (prog if program else [])


def _trace(program=True):
    # window 0..40 ms, two calls at 2 and 22 ms; device ops at 0-2 (the
    # ingest), then 5-6 and 9-10 ms into each call (in its launch and its
    # fetch)
    spans = [("bench.window", 0, 40 * MS)]
    ops, mods = [("ingest", 0, 2 * MS)], []
    for t in (2, 22):
        spans += _call(t, program)
        ops += [("pad", (t + 5) * MS, (t + 6) * MS),
                ("window_aggregate", (t + 9) * MS, (t + 10) * MS)]
        mods.append(("jit_offload_aggregate", (t + 5) * MS,
                     (t + 10) * MS))
    return Trace(device_ops=ops, device_modules=mods, host_spans=spans)


def test_idle_gaps_named_by_the_programs_spans():
    got = dict(devtrace.idle_by_span(_trace()))
    # busy 0-2, 7-8, 11-12, 27-28, 31-32 ms. Gaps by midpoint: 2-7
    # (4.5, the first call's launch), 8-11 and 28-31 (each call's
    # fetch), 12-27 (19.5, between calls), 32-40 (36: the second call's
    # fetch has ended, its repro.offload.run_window has not)
    assert got == pytest.approx({"repro.offload.launch": 0.005,
                                 "repro.offload.fetch": 0.006,
                                 "repro.offload.run_window": 0.008,
                                 "no span": 0.015})


def test_idle_gaps_without_the_programs_spans_name_the_benchmarks():
    got = dict(devtrace.idle_by_span(_trace(program=False)))
    assert got == pytest.approx({"bench.q2.run_window": 0.019,
                                 "no span": 0.015})


def _run(trace):
    run = Run("q2.slides", {"window_s": N, "record_hz": 1}, {}, 0, 1.0,
              True, PEAKS)
    run.trace = trace
    return run


@pytest.mark.parametrize("name", [
    "q2.dispatch_ms", "q2.idle_share", "offload_aggregate_roofline",
    "q2.over.dispatch_ms", "q2.over.idle_share"])
def test_accepted_readers_ignore_the_programs_spans(name):
    read = metric_reader(name)
    without = read(_run(_trace(program=False)))
    assert without is not None
    assert read(_run(_trace())) == without
