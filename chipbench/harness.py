"""The benchmark harness: one cell, one run, one result line.

Everything a cell needs is found by name:

* ``BENCHMARK.json`` (the checkout's root) lists the cells and metrics;
* ``configs/<config>.json`` holds the configuration as it is run;
* ``traffic/<traffic>.json`` holds the traffic mix, and names its entry;
* ``entries/<entry>.py`` drives the system under test: ``setup(run)``,
  ``window(run)``, ``release(run)`` and ``checks(run)``;
* ``metrics/<metric>.py`` reads one metric: ``read(run)`` returns a
  number, or ``None`` where the run holds nothing to read.

A new cell, configuration or metric is new files plus new entries in
``BENCHMARK.json``; nothing here names one.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import resource
import sys
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no device this cell can be measured on."""


@dataclasses.dataclass
class Check:
    """One number compared beside its limit; ``ok`` when it is at most
    the limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(math.isfinite(self.value) and self.value <= self.limit)


@dataclasses.dataclass
class Run:
    """What one run knows: its cell, inputs and what it recorded."""
    workload: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    tracing: bool
    peaks: Dict[str, Any]
    interpret: bool = False
    state: Dict[str, Any] = dataclasses.field(default_factory=dict)
    records: List[Any] = dataclasses.field(default_factory=list)
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    window_s: float = 0.0
    trace: Any = None

    @contextlib.contextmanager
    def span(self, name: str):
        """Mark a call into the system; in a traced run the span lands
        in the profiler's trace, where the per-layer readers find it."""
        if not self.tracing:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield


class HostWatch:
    """What the host thread did while it watched: samples of its clock,
    its CPU time and the times the OS took the CPU from it (involuntary
    context switches), and the garbage collector's pauses."""

    def __init__(self):
        self.gc: List[tuple] = []      # (start, end, generation)
        self._t = 0.0

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.gc.append((self._t, time.perf_counter(),
                            info["generation"]))

    @staticmethod
    def sample() -> tuple:
        """``(clock s, thread CPU s, involuntary switches)``, now."""
        r = resource.getrusage(resource.RUSAGE_THREAD)
        return (time.perf_counter(), r.ru_utime + r.ru_stime, r.ru_nivcsw)

    def gc_s(self, lo: float, hi: float) -> float:
        """Seconds of ``[lo, hi]`` the collector held the thread."""
        return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e, _ in self.gc)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def cell_of(bench: Dict, workload: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench: Dict, workload: str, tracing: bool) -> List[Dict]:
    """The cell's end-to-end metrics (``--trace 0``) or its per-layer
    metrics (``--trace 1``): those listing the cell, or listing none."""
    group = bench["per_layer"] if tracing else bench["end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


def metric_reader(name: str) -> Callable:
    return load_module(os.path.join(HERE, "metrics", f"{name}.py"),
                       f"chipbench_metric_{name}").read


def entry_of(traffic: Dict):
    name = traffic["entry"]
    return load_module(os.path.join(HERE, "entries", f"{name}.py"),
                       f"chipbench_entry_{name}")


def use_compile_cache(jax) -> None:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache/`` in the checkout), holding every
    program however small, so only a cell's first run compiles."""
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_check(jax, chips: int, peaks_table: Dict) -> Dict:
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's first device is {dev.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX finds "
                     f"{len(devs)}")
    if dev.device_kind not in peaks_table:
        raise NoChip(f"device kind {dev.device_kind!r} is not in "
                     "chipbench/peaks.json")
    return peaks_table[dev.device_kind]


def memory_peak(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def execute(workload: str, seed: int, seconds: float, tracing: bool, *,
            t_start: float, require_tpu: bool = True,
            config: Optional[Dict] = None,
            traffic: Optional[Dict] = None,
            traffic_override: Optional[Dict] = None,
            control: bool = False, log=sys.stderr) -> Dict:
    """Run one cell once and return the result line as a dict.

    ``require_tpu=False`` (tests only) skips the look for a chip and
    runs the Pallas kernels in interpret mode; ``config`` and
    ``traffic`` replace the cell's files (tests run a tiny size), and
    ``traffic_override`` replaces single traffic parameters.
    ``control=True`` puts the entry's control (the reference in a
    lower precision) in the program's place when deciding ``correct``;
    the benchmark's own runs never do."""
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = cell_of(bench, workload)
    config = config or read_json(
        os.path.join(HERE, "configs", f"{cell['config']}.json"))
    traffic = traffic or read_json(
        os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    traffic = dict(traffic, **(traffic_override or {}))
    peaks_table = read_json(os.path.join(HERE, "peaks.json"))
    wanted = metrics_of(bench, workload, tracing)
    readers = {m["name"]: metric_reader(m["name"]) for m in wanted
               if m["name"] != "setup_s"}
    entry = entry_of(traffic)

    import jax
    if require_tpu:
        use_compile_cache(jax)
        peaks = device_check(jax, int(cell["chips"]), peaks_table)
    else:
        peaks = peaks_table["TPU v5 lite"]
    devs = jax.devices()[:int(cell["chips"])]

    run = Run(workload=workload, config=config, traffic=traffic,
              seed=int(seed), seconds=float(seconds), tracing=tracing,
              peaks=peaks, interpret=devs[0].platform != "tpu")
    from devtrace import (WINDOW_SPAN, busy_ns, capture, idle_by_span,
                          top_ops)
    try:
        entry.setup(run)
        setup_s = time.perf_counter() - t_start
        with (capture() if tracing else contextlib.nullcontext()) as holder:
            with run.span(WINDOW_SPAN):
                entry.window(run)
        run.trace = holder.trace if holder is not None else None
        mem = memory_peak(devs)
    finally:
        entry.release(run)
    checks = entry.checks(run, control=control)
    checks.append(Check("failed_requests", float(run.failed), 0.0))

    metrics: Dict[str, Dict] = {}
    for m in wanted:
        if m["name"] == "setup_s":
            value = setup_s
        else:
            value = readers[m["name"]](run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out: Dict[str, Any] = {
        "correct": all(c.ok for c in checks),
        "attempted": run.attempted, "failed": run.failed,
        "metrics": metrics, "device": device}
    if tracing and run.trace is not None:
        lo, hi = run.trace.window()
        device["busy_s"] = busy_ns(run.trace) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {"device_ops": top_ops(run.trace),
                            "idle_gaps": idle_by_span(run.trace)}
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAIL'}", file=log, flush=True)
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out
