"""Profiler trace capture and its reduction to device-side numbers.

The reduction works on plain interval lists, so a test can feed it a
synthetic trace:

* ``device_ops``: ``(name, start_ns, end_ns)`` of every operation that
  ran on the device (the device plane's ``XLA Ops`` line);
* ``device_modules``: the same for whole compiled programs (its
  ``XLA Modules`` line);
* ``host_spans``: ``(name, start_ns, end_ns)`` of the benchmark's own
  spans (``TraceAnnotation``), on the same clock.

Busy time is the union of the device-op intervals; idle is the rest of
the traced window. Each idle gap is named by the innermost benchmark
span open at its midpoint, which says what the host was doing while the
device waited.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    device_ops: List[Interval]
    device_modules: List[Interval]
    host_spans: List[Interval]

    def window(self) -> Tuple[float, float]:
        """The traced window: the benchmark's ``bench.window`` span."""
        spans = [s for s in self.host_spans if s[0] == WINDOW_SPAN]
        if not spans:
            raise ValueError("trace holds no bench.window span")
        return spans[0][1], spans[0][2]


# ------------------------------------------------------------- intervals
def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> float:
    """Length of ``[lo, hi]`` that the merged intervals cover."""
    starts = [s for s, _ in merged]
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    total = 0.0
    for s, e in merged[i:]:
        if s >= hi:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


def gaps(merged: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """Idle intervals of ``[lo, hi]`` between the merged busy ones."""
    out = []
    cur = lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def busy_ns(trace: Trace, lo: Optional[float] = None,
            hi: Optional[float] = None) -> float:
    """Device busy time inside the window (default: the traced one)."""
    if lo is None or hi is None:
        lo, hi = trace.window()
    return covered(union((s, e) for _, s, e in trace.device_ops), lo, hi)


def idle_share(trace: Trace) -> Optional[float]:
    """Share of the traced window in which no op ran on the device, %."""
    lo, hi = trace.window()
    if hi <= lo:
        return None
    return 100.0 * (1.0 - busy_ns(trace, lo, hi) / (hi - lo))


def innermost(spans: Sequence[Interval], t: float) -> str:
    """Name of the shortest benchmark span open at ``t``."""
    best, best_len = "no span", float("inf")
    for name, s, e in spans:
        if s <= t < e and e - s < best_len:
            best, best_len = name, e - s
    return best


def idle_by_span(trace: Trace, top: int = 10) -> List[List]:
    """Idle device time inside the window, summed by the benchmark span
    open during each gap, longest first: ``[[name, seconds], ...]``."""
    lo, hi = trace.window()
    merged = union((s, e) for _, s, e in trace.device_ops)
    spans = [s for s in trace.host_spans if s[0] != WINDOW_SPAN]
    by: Dict[str, float] = {}
    for s, e in gaps(merged, lo, hi):
        name = innermost(spans, (s + e) / 2)
        by[name] = by.get(name, 0.0) + (e - s)
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    return [[n, v / 1e9] for n, v in ranked]


def top_ops(trace: Trace, top: int = 10) -> List[List]:
    """Device time inside the window summed by op name, largest first."""
    lo, hi = trace.window()
    by: Dict[str, float] = {}
    for name, s, e in trace.device_ops:
        d = max(0.0, min(e, hi) - max(s, lo))
        if d > 0:
            by[name] = by.get(name, 0.0) + d
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    return [[n, v / 1e9] for n, v in ranked]


# --------------------------------------------------------------- capture
@contextlib.contextmanager
def capture():
    """Profile the enclosed block. The holder it yields gets the reduced
    :class:`Trace` once the block ends; the raw trace is written to a
    temporary directory and removed once it is read."""
    import jax
    from jax.profiler import ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    holder = _Holder()
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield holder
        finally:
            jax.profiler.stop_trace()
            found = sorted(glob.glob(os.path.join(
                tmp, "plugins", "profile", "*", "*.xplane.pb")))
            holder.trace = load(found[-1]) if found else None
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


class _Holder:
    trace: Optional[Trace] = None


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``: ops and programs of the first device
    plane that runs XLA ops, and the benchmark's host spans (named
    ``bench.*``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Interval] = []
    modules: List[Interval] = []
    spans: List[Interval] = []
    devices = sorted(p.name for p in pd.planes
                     if p.name.startswith("/device:")
                     and any(ln.name == "XLA Ops" for ln in p.lines))
    first = devices[0] if devices else None
    for plane in pd.planes:
        if plane.name == first:
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(
                    line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    dest.append((ev.name, s, s + float(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = float(ev.start_ns)
                        spans.append((ev.name, s,
                                      s + float(ev.duration_ns)))
    return Trace(device_ops=ops, device_modules=modules, host_spans=spans)
