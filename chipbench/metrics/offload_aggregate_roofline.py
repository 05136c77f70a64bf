"""The offload program's share of its memory roofline, in %: the least
HBM traffic of a window (``kernel_cost.offload_aggregate_bytes``) over
the chip's HBM bandwidth, divided by the device time of the compiled
``offload_aggregate`` program (fold, Pallas ``window_agg``, lane
combine) in the trace. Memory bounds it."""
from kernel_cost import offload_aggregate_bytes

PROGRAM = "jit_offload_aggregate"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    calls = [(s, e) for n, s, e in run.trace.device_modules
             if n.startswith(PROGRAM) and lo <= s < hi]
    busy = sum(e - s for s, e in calls) / 1e9
    if not calls or busy <= 0:
        return None
    n = int(run.config["window_s"] * run.config["record_hz"])
    least = len(calls) * offload_aggregate_bytes(n) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / busy
