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

import dataclasses
import functools

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


@functools.partial(jax.jit, static_argnames=("agg", "interpret"))
def offload_aggregate(values: jax.Array, *, agg: str,
                      interpret: bool = False) -> jax.Array:
    """One whole-range window aggregate on the VDC path.

    Folds the 1-D range into the TPU's 128 lanes, reduces it with the
    Pallas segment kernel in ``OFFLOAD_SEGMENT_ROWS``-row segments (one
    window spanning every segment), then combines the 128 per-lane
    partials. Returns a scalar f32."""
    base = "sum" if agg == "mean" else agg
    n = values.shape[0]
    cols, seg_rows = 128, OFFLOAD_SEGMENT_ROWS
    rows = -(-n // (cols * seg_rows)) * seg_rows
    x2 = jnp.pad(values.astype(jnp.float32), (0, rows * cols - n),
                 constant_values=INIT[base]).reshape(rows, cols)
    lanes = window_aggregate(x2, agg=base, window=rows, stride=seg_rows,
                             interpret=interpret)[0]        # [128]
    if base == "max":
        return jnp.max(lanes)
    if base == "min":
        return jnp.min(lanes)
    total = jnp.sum(lanes)
    return total / n if agg == "mean" else total


class HybridExecutor:
    """Runs a service's window either on the edge or on the VDC path.

    ``interpret`` is the caller's choice for the VDC path's Pallas
    kernel: compiled for the TPU by default, the Pallas interpreter
    where the caller runs on a CPU."""

    def __init__(self, edge_budget: int = EDGE_WINDOW_BUDGET, *,
                 interpret: bool = False):
        self.edge_budget = edge_budget
        self.interpret = interpret
        self.offloads = 0
        self.edge_runs = 0

    def decide(self, n_records: int) -> OffloadDecision:
        return OffloadDecision(n_records > self.edge_budget, n_records)

    def run_window(self, values, agg: str) -> float:
        """Aggregate one window; ``values`` is a host or device array
        (an offloaded window already on the device stays there).

        With tracing on, an offloaded window leaves the span
        ``repro.offload.run_window`` holding ``repro.offload.launch``
        (until the unready result comes back) and then
        ``repro.offload.fetch`` (the wait for the result and its copy
        to the host)."""
        d = self.decide(len(values))
        if not d.offload:
            self.edge_runs += 1
            return aggregate(np.asarray(values), agg)
        self.offloads += 1
        with span("repro.offload.run_window"):
            with span("repro.offload.launch"):
                out = offload_aggregate(jnp.asarray(values), agg=agg,
                                        interpret=self.interpret)
            with span("repro.offload.fetch"):
                return float(out)
