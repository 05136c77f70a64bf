"""Host time per window, in ms: the mean length of the benchmark's span
around a window's handover (a ``run_window`` call, or one ``next()`` of
``run_windows``; either ends with the result on the host) less
the device time of the ``offload_aggregate`` program per window. Both
come from the trace; the difference does not depend on how the host's
and the device's clocks line up."""
PROGRAM = "jit_offload_aggregate"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    spans = [e - s for n, s, e in run.trace.host_spans
             if n == "bench.q2.run_window"]
    calls = [e - s for n, s, e in run.trace.device_modules
             if n.startswith(PROGRAM) and lo <= s < hi]
    if not spans or not calls:
        return None
    return (sum(spans) / len(spans) - sum(calls) / len(calls)) / 1e6
