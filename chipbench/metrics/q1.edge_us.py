"""Host time per Q1 edge window, in us: the benchmark's
``bench.q1.windows`` spans in the traced window (one a round, around
its 256 calls of ``run_window``'s edge branch, numpy on the host),
summed, over the Q1 windows the run issued. The span holds the
benchmark's own loop too (the view's slice, two clock reads and the
stores of latency and value), so it guards the edge branch's cost
with that loop's on top."""


def read(run):
    if run.trace is None or not run.counters.get("q1_windows"):
        return None
    lo, hi = run.trace.window()
    spans = [e - s for n, s, e in run.trace.host_spans
             if n == "bench.q1.windows" and lo <= s < hi]
    if not spans:
        return None
    return sum(spans) / run.counters["q1_windows"] / 1e3
