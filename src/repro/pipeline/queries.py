"""The paper's use-case queries (§3) and the just-in-time edge→VDC offload.

  Q1: EVERY 60 s compute the MAX of download_speed over the last 3 min
      FROM cassandra series speedtests AND streaming queue neubotspeed
  Q2: EVERY 5 min compute the MEAN of download_speed over the last 120 d
      FROM the same sources

Both mash a post-mortem store range with the live stream. The
HybridExecutor is the paper's "services interact with the VDC underlying
services only when the process needs more resources": windows whose record
count fits the edge budget aggregate in the service loop (NumPy on host);
larger windows offload to the VDC path — the Pallas window_agg kernel
(+ its roofline-costed submesh, scheduled like any other task).
"""
from __future__ import annotations

import ctypes
import dataclasses
import functools
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.window_agg import window_aggregate
from repro.kernels.window_agg.kernel import INIT
from repro.pipeline.operators import WindowSpec, aggregate
from repro.pipeline.service import ServiceConfig, StreamService
from repro.pipeline.store import TimeSeriesStore
from repro.pipeline.streams import Broker
from repro.utils.tracing import span

EDGE_WINDOW_BUDGET = 100_000  # records an edge service may aggregate inline


def neubot_query_1(broker: Broker, store: TimeSeriesStore) -> StreamService:
    return StreamService(ServiceConfig(
        name="q1_max_speed", queue="neubotspeed", column="download_speed",
        agg="max", window=WindowSpec("sliding", width_s=180.0, slide_s=60.0),
        store=store), broker)


def neubot_query_2(broker: Broker, store: TimeSeriesStore) -> StreamService:
    return StreamService(ServiceConfig(
        name="q2_mean_speed", queue="neubotspeed", column="download_speed",
        agg="mean",
        window=WindowSpec("sliding", width_s=120 * 86400.0, slide_s=300.0),
        store=store), broker)


@dataclasses.dataclass
class OffloadDecision:
    offload: bool
    n_records: int


# Rows of the 128-lane fold that one kernel segment reduces: the kernel
# holds 8 segments (2 MiB of f32) per VMEM block, so a window of any
# length streams through VMEM instead of landing in it whole.
OFFLOAD_SEGMENT_ROWS = 512


@functools.partial(jax.jit,
                   static_argnames=("agg", "interpret", "result_on_host"))
def offload_aggregate(values: jax.Array, *, agg: str,
                      interpret: bool = False,
                      result_on_host: bool = False) -> jax.Array:
    """One whole-range window aggregate on the VDC path.

    Folds the 1-D range into the TPU's 128 lanes, reduces it with the
    Pallas segment kernel in ``OFFLOAD_SEGMENT_ROWS``-row segments (one
    window spanning every segment), then combines the 128 per-lane
    partials. Returns a scalar f32.

    With ``result_on_host`` the program's last instruction copies the
    scalar into the host's memory (a ``copy-start``/``copy-done`` to
    ``jax.memory.Space.Host``), so the program's completion is also the
    result's arrival and reading it starts no second transfer from the
    device. Only a TPU backend can place a result there inside a
    program; elsewhere leave it off."""
    base = "sum" if agg == "mean" else agg
    n = values.shape[0]
    cols, seg_rows = 128, OFFLOAD_SEGMENT_ROWS
    rows = -(-n // (cols * seg_rows)) * seg_rows
    x2 = jnp.pad(values.astype(jnp.float32), (0, rows * cols - n),
                 constant_values=INIT[base]).reshape(rows, cols)
    lanes = window_aggregate(x2, agg=base, window=rows, stride=seg_rows,
                             interpret=interpret)[0]        # [128]
    if base == "max":
        out = jnp.max(lanes)
    elif base == "min":
        out = jnp.min(lanes)
    else:
        total = jnp.sum(lanes)
        out = total / n if agg == "mean" else total
    if result_on_host:
        out = jax.device_put(out, jax.memory.Space.Host)
    return out


class HybridExecutor:
    """Runs a service's window either on the edge or on the VDC path.

    A window of at most ``edge_budget`` records (the paper's Q1, 180
    records at 1 Hz) takes the edge branch: numpy on the host, counted
    in ``edge_runs`` and, with tracing on, marked by the span
    ``repro.edge.run_window``. A larger one (Q2, 10,368,000 records) is
    offloaded, counted in ``offloads`` and marked by the
    ``repro.offload.*`` spans (``run_window``, or ``run_windows`` for a
    list of windows handed over in one call).

    ``interpret`` is the caller's choice for the VDC path's Pallas
    kernel: compiled for the TPU by default, the Pallas interpreter
    where the caller runs on a CPU. ``host_results`` counts the
    offloaded windows whose program wrote its result to host memory
    (every one on a TPU, none elsewhere); ``offload_waits`` counts the
    times the host waited for offloaded results, so ``offloads /
    offload_waits`` is the windows a wait."""

    def __init__(self, edge_budget: int = EDGE_WINDOW_BUDGET, *,
                 interpret: bool = False):
        self.edge_budget = edge_budget
        self.interpret = interpret
        self.offloads = 0
        self.host_results = 0
        self.offload_waits = 0
        self.edge_runs = 0
        self._on_host: dict = {}

    def decide(self, n_records: int) -> OffloadDecision:
        return OffloadDecision(n_records > self.edge_budget, n_records)

    def result_on_host(self, device) -> bool:
        """Whether the offload program on ``device`` writes its result to
        host memory: on a TPU with the kernel compiled, yes; on a CPU
        or in the Pallas interpreter, which cannot place a result there
        inside a program, no. Decided once per device."""
        on = self._on_host.get(device)
        if on is None:
            on = self._on_host[device] = (device.platform == "tpu"
                                          and not self.interpret)
        return on

    def run_window(self, values, agg: str) -> float:
        """Aggregate one window; ``values`` is a host or device array
        (an offloaded window already on the device stays there).

        On a TPU an offloaded window's result lands in host memory as
        the program's last step (``result_on_host``), and the call reads
        it there once the program is done (``_read_host_scalar``): the
        host waits for the program's completion and for no transfer of
        the scalar after it. Elsewhere ``float`` fetches the result.

        With tracing on, an edge window leaves the span
        ``repro.edge.run_window``, and an offloaded window the span
        ``repro.offload.run_window`` holding ``repro.offload.launch``
        (until the unready result comes back) and then
        ``repro.offload.fetch`` (the wait for the result and its read
        from host memory, or off a TPU its copy to the host)."""
        d = self.decide(len(values))
        if not d.offload:
            self.edge_runs += 1
            with span("repro.edge.run_window"):
                return aggregate(np.asarray(values), agg)
        with span("repro.offload.run_window"):
            with span("repro.offload.launch"):
                out, read = self._launch(values, agg)
            with span("repro.offload.fetch"):
                self.offload_waits += 1
                out.block_until_ready()
                return read(out)

    def run_windows(self, windows, agg: str) -> Iterator[float]:
        """Aggregate a list of windows, each routed as ``run_window``
        routes it and giving the same value: an iterator of one float a
        window, in the order given.

        At the first ``next()`` the call launches every offloaded window
        (an edge window runs inline, in its turn), waits for the device
        once, then reads each result from host memory, so a slide of
        windows costs one wait and not one a window. If a launch raises,
        the windows before it are yielded, the exception propagates and
        no later window is launched.

        With tracing on, the call leaves the span
        ``repro.offload.run_windows`` holding one ``repro.offload.launch``
        (every launch, and the edge windows' ``repro.edge.run_window``)
        and then one ``repro.offload.fetch`` (the wait and the reads)."""
        results, launched, error = [], [], None
        with span("repro.offload.run_windows"):
            with span("repro.offload.launch"):
                try:
                    for values in windows:
                        if self.decide(len(values)).offload:
                            out, read = self._launch(values, agg)
                            launched.append(out)
                            results.append((out, read))
                            continue
                        self.edge_runs += 1
                        with span("repro.edge.run_window"):
                            results.append(
                                (aggregate(np.asarray(values), agg), None))
                except Exception as e:  # yield the windows launched first
                    error = e
            with span("repro.offload.fetch"):
                if launched:
                    self.offload_waits += 1
                    jax.block_until_ready(launched)
                got = [read(v) if read else v for v, read in results]
        yield from got
        if error is not None:
            raise error

    def _launch(self, values, agg: str):
        """Start one offloaded window's program. Returns its unready
        result and the function that reads it once the program is
        done."""
        self.offloads += 1
        # jnp.asarray of a device array alone costs ~0.07 ms a call on a
        # TPU v5e host
        x = values if isinstance(values, jax.Array) else jnp.asarray(values)
        if self.result_on_host(x.device):
            self.host_results += 1
            return (offload_aggregate(x, agg=agg, result_on_host=True),
                    _read_host_scalar)
        return (offload_aggregate(x, agg=agg, interpret=self.interpret),
                float)


def _read_host_scalar(out: jax.Array) -> float:
    """The f32 scalar of ``out``, a result in host memory whose program
    is done, read where it lies: one load from memory, where
    ``float(out)`` routes even a host-memory result through the
    runtime's transfer path (~0.15 ms a call on a TPU v5e host). The
    caller waits for the program first; an unready result's memory
    holds no value yet."""
    return ctypes.c_float.from_address(out.unsafe_buffer_pointer()).value
