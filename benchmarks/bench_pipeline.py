"""Paper §3 use case: the two Neubot queries over an IoT farm — latency of
combining massive post-mortem histories with live streams ("results at
reasonable response times (order of seconds)")."""
from __future__ import annotations

import time

import numpy as np

from repro.pipeline import (Broker, HybridExecutor, NeubotFarm, Pipeline,
                            TimeSeriesStore, neubot_query_1)


def main(csv_rows):
    print("\n== §3 use case: Neubot windowed queries ==")
    broker = Broker()
    store = TimeSeriesStore("speedtests", chunk_seconds=3600)
    farm = NeubotFarm(broker, n_things=8, rate_hz=1.0, seed=0)
    q1 = neubot_query_1(broker, store)
    pipe = Pipeline(broker).add_farm(farm).add_service(q1)

    t0 = time.perf_counter()
    res = pipe.advance_to(3600.0)["q1_max_speed"]  # 1 simulated hour
    dt = time.perf_counter() - t0
    per_fire = dt / max(1, len(res)) * 1e6
    print(f"Q1 (EVERY 60s MAX over last 3min, 8 things): {len(res)} fires, "
          f"{per_fire:.0f} us/fire, wall {dt:.2f}s")
    csv_rows.append(("q1_per_fire", per_fire, f"{len(res)}fires"))

    # Q2-scale history: 120-day mean = 10.4M records/thing at 1Hz; we build
    # a scaled history and compare edge vs VDC(JIT-offload kernel) paths.
    hx = HybridExecutor(edge_budget=100_000, interpret=True)   # host CPU
    for n in (10_000, 1_000_000, 10_368_000):
        vals = np.random.default_rng(0).standard_normal(n).astype(np.float32)
        t0 = time.perf_counter()
        v = hx.run_window(vals, "mean")
        dt = (time.perf_counter() - t0) * 1e6
        path = "VDC(offload)" if n > 100_000 else "edge"
        ok = abs(v - vals.mean()) < 1e-2
        print(f"Q2 window n={n:>10,}: {path:13s} {dt/1e6:7.3f}s "
              f"({'order-of-seconds OK' if dt < 30e6 and ok else 'SLOW/BAD'})")
        csv_rows.append((f"q2_window_{n}", dt, path))
    print(f"offload decisions: edge={hx.edge_runs} vdc={hx.offloads}")


if __name__ == "__main__":
    main([])
