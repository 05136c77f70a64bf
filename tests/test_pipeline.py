"""DS pipeline services: broker semantics, store scans, the Neubot
queries vs a numpy oracle, and the edge→VDC offload decision."""
import numpy as np
import pytest

from repro.pipeline import (Broker, HybridExecutor, NeubotFarm, Pipeline,
                            TimeSeriesStore, neubot_query_1)
from repro.pipeline.operators import WindowSpec, kmeans, linear_regression
from repro.pipeline.service import ServiceConfig, StreamService
from repro.pipeline.streams import Record


def test_queue_offsets_and_bounds():
    b = Broker()
    q = b.queue("q", capacity=10)
    q.register("c1")
    for i in range(15):
        q.publish(Record(ts=float(i), values={"v": float(i)}))
    got = q.fetch("c1")
    assert q.dropped == 5
    assert [r.values["v"] for r in got] == list(range(5, 15))
    assert q.fetch("c1") == []  # offset advanced


def test_store_scan_matches_appended():
    s = TimeSeriesStore("t", chunk_seconds=10.0, edge_budget_chunks=2)
    for i in range(100):
        s.append(Record(ts=float(i), values={"v": float(i)}))
    s.flush()
    vals = s.scan(25.0, 75.0, "v")
    np.testing.assert_array_equal(vals, np.arange(25.0, 75.0))
    assert s.spill_events > 0           # budget forced spills
    assert s.resident_chunks <= 3       # budget + open chunk slack


def test_q1_windowed_max_vs_oracle():
    broker = Broker()
    store = TimeSeriesStore("speed", chunk_seconds=600)
    farm = NeubotFarm(broker, n_things=3, rate_hz=1.0, seed=1)
    q1 = neubot_query_1(broker, store)
    pipe = Pipeline(broker).add_farm(farm).add_service(q1)
    res = pipe.advance_to(600.0)["q1_max_speed"]
    assert len(res) == 10  # every 60 s
    # oracle: regenerate the same records
    farm2 = NeubotFarm(Broker(), n_things=3, rate_hz=1.0, seed=1)
    q = farm2.producers[0].q
    farm2.advance_to(600.0)
    recs = list(q.buf)
    for r in res:
        now = r["ts"]
        vals = [x.values["download_speed"] for x in recs
                if now - 180.0 <= x.ts < now]
        assert abs(r["value"] - max(vals)) < 1e-6


def test_service_buffer_eviction_spills_to_store():
    broker = Broker()
    store = TimeSeriesStore("s", chunk_seconds=100)
    svc = StreamService(ServiceConfig(
        name="tiny", queue="q", column="v", agg="mean",
        window=WindowSpec("sliding", 50.0, 10.0), buffer_budget=16,
        store=store), broker)
    q = broker.queue("q")
    for i in range(200):
        q.publish(Record(ts=float(i), values={"v": 1.0}))
    svc.run_until(200.0)
    assert svc.buffer_evictions > 0
    assert len(svc.buffer) <= 16 + 1


def test_fetch_spill_accounting_is_exact():
    """Fetch's data-management strategy, pinned record by record: stale
    records (older than the window) spill first, then budget overflow
    evicts the oldest in-window records; every eviction increments
    ``buffer_evictions`` exactly once and lands in the store."""
    broker = Broker()
    store = TimeSeriesStore("s", chunk_seconds=1000.0)
    svc = StreamService(ServiceConfig(
        name="tiny", queue="q", column="v", agg="sum",
        window=WindowSpec("sliding", 50.0, 10.0), buffer_budget=16,
        store=store), broker)
    q = broker.queue("q")
    for i in range(100):                       # ts 0..99, one record each
        q.publish(Record(ts=float(i), values={"v": float(i)}))
    n = svc.fetch()
    assert n == 100
    # horizon = 99 - 50 = 49 → 49 stale (ts 0..48); 51 in-window > 16
    # budget → 35 more evicted (ts 49..83); buffer keeps ts 84..99
    assert svc.buffer_evictions == 49 + 35
    assert [r.ts for r in svc.buffer] == [float(i) for i in range(84, 100)]
    store.flush()
    spilled = store.scan(0.0, 84.0, "v")
    assert len(spilled) == 84                  # all evictions retained
    np.testing.assert_array_equal(np.sort(spilled), np.arange(84.0))
    # the operator can still see spilled history through the store
    res = svc.fire(100.0)
    assert res["n"] == 50                      # window [50, 100): 34+16


def test_fetch_eviction_without_store_loses_records():
    """Same pressure, no store: the counter still counts, the records
    are gone (the co-sim ledgers classify these as evicted_lost)."""
    broker = Broker()
    svc = StreamService(ServiceConfig(
        name="lossy", queue="q", column="v", agg="count",
        window=WindowSpec("sliding", 50.0, 10.0), buffer_budget=16), broker)
    q = broker.queue("q")
    for i in range(100):
        q.publish(Record(ts=float(i), values={"v": 1.0}))
    svc.fetch()
    assert svc.buffer_evictions == 84
    assert len(svc.buffer) == 16
    res = svc.fire(100.0)
    assert res["n"] == 16                      # only the buffer survives


def test_buffer_evictions_counter_accumulates_across_fetches():
    """Incremental fetches: the counter is monotone and equals the total
    number of records ever removed from the buffer, not a per-fetch
    snapshot; in-window records under budget are never evicted."""
    broker = Broker()
    svc = StreamService(ServiceConfig(
        name="inc", queue="q", column="v", agg="mean",
        window=WindowSpec("sliding", 1000.0, 10.0), buffer_budget=8), broker)
    q = broker.queue("q")
    for i in range(8):                         # fits: no evictions
        q.publish(Record(ts=float(i), values={"v": 1.0}))
    svc.fetch()
    assert svc.buffer_evictions == 0 and len(svc.buffer) == 8
    for i in range(8, 12):                     # 4 over budget
        q.publish(Record(ts=float(i), values={"v": 1.0}))
    svc.fetch()
    assert svc.buffer_evictions == 4
    for i in range(12, 14):                    # 2 more
        q.publish(Record(ts=float(i), values={"v": 1.0}))
    svc.fetch()
    assert svc.buffer_evictions == 6
    assert [r.ts for r in svc.buffer] == [float(i) for i in range(6, 14)]


def test_offload_decision_boundary():
    hx = HybridExecutor(edge_budget=1000, interpret=True)
    assert not hx.decide(1000).offload
    assert hx.decide(1001).offload
    big = np.random.default_rng(0).standard_normal(4096).astype(np.float32)
    v = hx.run_window(big, "max")
    assert abs(v - big.max()) < 1e-5
    assert hx.offloads == 1


@pytest.mark.parametrize("agg", ["mean", "max"])
def test_offload_result_stays_on_device_off_the_tpu(agg):
    """On a CPU, in interpret mode, the program returns its result as
    before: same value as ``offload_aggregate``'s default, and no
    window counted as written to host memory."""
    import jax.numpy as jnp
    from repro.pipeline.queries import offload_aggregate

    hx = HybridExecutor(edge_budget=1000, interpret=True)
    big = np.random.default_rng(1).standard_normal(4096).astype(np.float32)
    v = hx.run_window(big, agg)
    assert v == float(offload_aggregate(jnp.asarray(big), agg=agg,
                                        interpret=True))
    assert hx.offloads == 1 and hx.host_results == 0
    assert not hx.result_on_host(jnp.asarray(big).device)


class _Device:
    """A device stub that counts how often its platform is read."""

    def __init__(self, platform):
        self._platform, self.reads = platform, 0

    @property
    def platform(self):
        self.reads += 1
        return self._platform


def test_result_on_host_decided_once_per_device():
    hx = HybridExecutor()
    tpu, cpu = _Device("tpu"), _Device("cpu")
    assert hx.result_on_host(tpu) and hx.result_on_host(tpu)
    assert not hx.result_on_host(cpu) and not hx.result_on_host(cpu)
    assert tpu.reads == 1 and cpu.reads == 1
    assert not HybridExecutor(interpret=True).result_on_host(_Device("tpu"))


def test_executor_asks_a_tpu_for_the_host_result(monkeypatch):
    """Where the window's device reports a TPU, the executor asks the
    program for its result in host memory, reads it in place and counts
    it. (A CPU array's buffer is host memory too, so the read is real.)"""
    import types

    import jax.numpy as jnp

    from repro.pipeline import queries

    tpu = _Device("tpu")
    window = types.SimpleNamespace(device=tpu)
    asked = []

    def program(x, *, agg, interpret=False, result_on_host=False):
        asked.append(result_on_host)
        return jnp.float32(2.5)

    monkeypatch.setattr(queries, "jnp",
                        types.SimpleNamespace(asarray=lambda v: window))
    monkeypatch.setattr(queries, "offload_aggregate", program)
    hx = HybridExecutor(edge_budget=1000)
    big = np.zeros(4096, np.float32)
    assert [hx.run_window(big, "mean") for _ in range(2)] == [2.5, 2.5]
    assert asked == [True, True] and tpu.reads == 1
    assert hx.host_results == hx.offloads == 2


def _mixed_windows():
    """Offloaded windows of two lengths around one edge-sized window,
    for an executor whose ``edge_budget`` is 1000."""
    rng = np.random.default_rng(2)
    return [rng.standard_normal(n).astype(np.float32)
            for n in (4096, 5000, 700, 4096, 5000)]


@pytest.mark.parametrize("agg", ["mean", "max"])
def test_run_windows_yields_run_window_values_in_order(agg):
    ws = _mixed_windows()
    one = HybridExecutor(edge_budget=1000, interpret=True)
    want = [one.run_window(w, agg) for w in ws]
    hx = HybridExecutor(edge_budget=1000, interpret=True)
    got = list(hx.run_windows(ws, agg))
    assert got == want      # bit for bit, edge window included
    for h in (one, hx):
        assert (h.offloads, h.edge_runs, h.host_results) == (4, 1, 0)
    assert one.offload_waits == 4 and hx.offload_waits == 1


@pytest.mark.parametrize("sizes, offloads, edge_runs, waits", [
    ((4096, 700, 5000), 2, 1, 1),
    ((4096,), 1, 0, 1),
    ((700, 10), 0, 2, 0),
    ((), 0, 0, 0),
])
def test_run_windows_waits_once_a_call_that_offloads(sizes, offloads,
                                                     edge_runs, waits):
    hx = HybridExecutor(edge_budget=1000, interpret=True)
    ws = [np.ones(n, np.float32) for n in sizes]
    assert list(hx.run_windows(ws, "sum")) == [float(n) for n in sizes]
    assert (hx.offloads, hx.edge_runs, hx.offload_waits) == (
        offloads, edge_runs, waits)
    assert list(hx.run_windows(ws, "sum")) == [float(n) for n in sizes]
    assert hx.offload_waits == 2 * waits


@pytest.mark.parametrize("j", [0, 1, 3])
def test_run_windows_launch_raising_at_j_yields_those_before(j,
                                                             monkeypatch):
    from repro.pipeline import queries

    ws = [w for w in _mixed_windows() if len(w) > 1000]
    want = [HybridExecutor(edge_budget=1000, interpret=True)
            .run_window(w, "mean") for w in ws]
    real, launched = queries.offload_aggregate, []

    def program(x, **kw):
        launched.append(x)
        if len(launched) == j + 1:
            raise RuntimeError(f"launch {j} failed")
        return real(x, **kw)

    monkeypatch.setattr(queries, "offload_aggregate", program)
    hx = HybridExecutor(edge_budget=1000, interpret=True)
    got = []
    with pytest.raises(RuntimeError, match=f"launch {j} failed"):
        for v in hx.run_windows(ws, "mean"):
            got.append(v)
    assert got == want[:j]
    assert len(launched) == j + 1      # nothing launched after window j
    assert hx.offload_waits == (1 if j else 0)


def test_run_windows_asks_a_tpu_for_host_results_and_waits_once(
        monkeypatch):
    """On a (stubbed) TPU every window of the call asks the program for
    its result in host memory, the call waits for the device once, and
    each value is read in place in the order given."""
    import types

    import jax
    import jax.numpy as jnp

    from repro.pipeline import queries

    tpu = _Device("tpu")
    window = types.SimpleNamespace(device=tpu)
    asked, waits = [], []

    def program(x, *, agg, interpret=False, result_on_host=False):
        asked.append(result_on_host)
        return jnp.float32(len(asked))

    def block_until_ready(x):
        waits.append(len(x))
        return real_wait(x)

    real_wait = jax.block_until_ready
    monkeypatch.setattr(queries, "jnp",
                        types.SimpleNamespace(asarray=lambda v: window))
    monkeypatch.setattr(queries, "offload_aggregate", program)
    monkeypatch.setattr(jax, "block_until_ready", block_until_ready)
    hx = HybridExecutor(edge_budget=1000)
    ws = [np.zeros(4096, np.float32)] * 3
    assert list(hx.run_windows(ws, "mean")) == [1.0, 2.0, 3.0]
    assert asked == [True] * 3 and tpu.reads == 1
    assert waits == [3] and hx.offload_waits == 1
    assert hx.host_results == hx.offloads == 3


def test_kmeans_and_linreg_services():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    xs = np.concatenate([rng.normal(0, .5, (50, 2)),
                         rng.normal(5, .5, (50, 2))])
    centers, assign = kmeans(jnp.asarray(xs, jnp.float32), k=2, iters=25)
    d = abs(float(centers[0, 0]) - float(centers[1, 0]))
    assert d > 3.0  # separated the clusters
    x = jnp.linspace(0, 1, 100)
    y = 2.0 + 3.0 * x
    beta, resid = linear_regression(x, y)
    np.testing.assert_allclose(np.asarray(beta), [2.0, 3.0], atol=1e-4)


def test_cnn_classifier_service():
    """The paper's CNN analytics operator: a tiny conv net separates
    synthetic 'stable' from 'bursty' connectivity windows after a few
    gradient steps (trained as any analytics service would be)."""
    import jax
    import jax.numpy as jnp
    from repro.pipeline.operators import cnn_classify, init_cnn_classifier

    rng = np.random.default_rng(0)
    stable = rng.normal(1.0, 0.05, (64, 64)).astype(np.float32)
    bursty = (rng.normal(1.0, 0.05, (64, 64))
              + (rng.random((64, 64)) < 0.15) * rng.normal(4, 1, (64, 64))
              ).astype(np.float32)
    x = jnp.asarray(np.concatenate([stable, bursty]))
    y = jnp.asarray([0] * 64 + [1] * 64)

    params = init_cnn_classifier(jax.random.PRNGKey(0), n_classes=2)

    def loss(p):
        logits = cnn_classify(p, x)
        return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(128), y])

    g = jax.jit(jax.grad(loss))
    for _ in range(60):
        grads = g(params)
        params = jax.tree.map(lambda p, gr: p - 0.3 * gr, params, grads)
    acc = float(jnp.mean(jnp.argmax(cnn_classify(params, x), -1) == y))
    assert acc > 0.9, acc
