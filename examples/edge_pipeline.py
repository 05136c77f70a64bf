"""The paper's §3 use case end-to-end, declared through the Scenario
API: an IoT farm of 'things' measuring network quality, stream services
answering the Neubot queries (Q1 as a ~10-line declarative spec), and
the just-in-time edge→VDC offload when a window outgrows the edge.

  PYTHONPATH=src python examples/edge_pipeline.py [--smoke]
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.pipeline import HybridExecutor  # noqa: E402
from repro.pipeline.operators import kmeans
from repro.scenario import RateSpec, scenario

SMOKE = "--smoke" in sys.argv
HOURS = 0.5 if SMOKE else 4.0

# Q1: EVERY 60s MAX(download_speed) over the last 3 minutes, plus a
# landmark mean-latency mash-up — one declarative spec, JSON round-trip
spec = (scenario("neubot-use-case")
        .horizon(HOURS * 3600.0)
        .farm(queue="neubotspeed", n_things=8, rate=RateSpec.constant(1.0))
        .service("q1_max_speed", queue="neubotspeed",
                 column="download_speed", agg="max",
                 width_s=180.0, slide_s=60.0)
        .with_store(chunk_seconds=3600.0, edge_budget_chunks=6)
        .service("latency_landmark", queue="neubotspeed",
                 column="latency_ms", agg="mean", window_kind="landmark",
                 width_s=0.0, slide_s=300.0)
        .with_store(chunk_seconds=3600.0, edge_budget_chunks=6)
        .build())
assert spec == type(spec).from_json(spec.to_json()), "spec must round-trip"

pipe = spec.build_pipeline()
t0 = time.perf_counter()
out = pipe.advance_to(spec.horizon_s)
wall = time.perf_counter() - t0
q1 = pipe.services[0].results
lmk = pipe.services[1].results
print(f"{HOURS:g}h of streams from 8 things in {wall:.1f}s wall "
      f"(spec: {len(spec.to_json())} JSON bytes)")
print(f"Q1 fired {len(q1)}x; last 3 values "
      f"{[f'{r[1]:.1f}Mbps' for r in [(r['ts'], r['value']/1e6) for r in q1[-3:]]]}")
print(f"landmark latency: {lmk[-1]['value']:.1f} ms "
      f"over {lmk[-1]['n']} records")
store = pipe.services[0].cfg.store
print(f"store: {store.resident_chunks} edge-resident chunks, "
      f"{store.spill_events} spilled to VDC storage")

# Q2-scale: a 120-day history doesn't fit the edge -> JIT offload to the
# VDC (scaled down in --smoke so CI stays fast). This example runs on the
# CPU, so the offload kernel runs in the Pallas interpreter; chip_smoke.py
# runs the same offload compiled on the TPU.
hx = HybridExecutor(edge_budget=100_000, interpret=True)
n_hist = 1_000_000 if SMOKE else 10_368_000   # 120d @ 1Hz when full
history = np.abs(np.random.default_rng(0).standard_normal(
    n_hist)).astype(np.float32) * 20e6
t0 = time.perf_counter()
mean = hx.run_window(history, "mean")
print(f"Q2 ({n_hist:,}-record mean): {mean/1e6:.2f} Mbps in "
      f"{time.perf_counter()-t0:.2f}s via "
      f"{'VDC offload' if hx.offloads else 'edge'} "
      f"(paper: 'order of seconds')")

# downstream analytics service: k-means on (download, latency) features
recs = list(pipe.broker.queue("neubotspeed").buf)[-2000:]
feats = np.array([[r.values["download_speed"] / 1e6,
                   r.values["latency_ms"]] for r in recs], np.float32)
centers, assign = kmeans(feats, k=3, iters=15)
print("k-means connectivity clusters (Mbps, ms):")
for c in np.asarray(centers):
    print(f"  ({c[0]:6.1f}, {c[1]:5.1f})")

if SMOKE:
    assert len(q1) > 0 and lmk, "smoke: queries must fire"
    print("OK")
