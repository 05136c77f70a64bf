"""The paper's Neubot Q2 on the offload path, open loop.

Every thing's 120-day history stays on the device as a ring, one
buffer per thing, as a store keeps each series. Slides
come due at the traffic's fixed rate; each slide first lands its new
records in the ring, then makes every thing's window due at once, and
the slide's windows go to the executor's offload path (fold, Pallas
``window_agg``, lane combine), each to a number on the host, handed
over in one ``run_windows`` call a slide (``run_slide``). A window's
latency runs from its slide's due time to its result on the host.
"""
from __future__ import annotations

import functools
import math
import sys
import time

import jax
import numpy as np

import neubot_data as data
from harness import Check, HostWatch


def _shape(run):
    cfg = run.config
    n = int(cfg["window_s"] * cfg["record_hz"])
    per = int(cfg["slide_s"] * cfg["record_hz"])
    if n % per:
        raise ValueError("the window must hold a whole number of slides")
    slides = max(1, math.ceil(run.seconds * run.traffic["slides_per_s"]))
    if slides * per > n:
        raise ValueError("the run's ingest would wrap the ring")
    return n, per, int(cfg["things"]), slides


@functools.partial(jax.jit, donate_argnums=0)
def ring_write(rows, new, k):
    """Land slide ``k``'s records in every thing's ring, in place."""
    per = new.shape[2]
    block = jax.lax.dynamic_index_in_dim(new, k, keepdims=False)
    start = (k * per) % rows[0].shape[0]
    return tuple(jax.lax.dynamic_update_slice(r, block[i], (start,))
                 for i, r in enumerate(rows))


def setup(run) -> None:
    from repro.pipeline.queries import HybridExecutor

    n, per, things, slides = _shape(run)
    scale = float(run.config["scale_bps"])
    key = data.root_key(run.seed)
    hist = data.history(key, things=things, n=n, scale=scale)
    new = data.ingest(key, slides=slides, things=things, per=per,
                      scale=scale)
    hx = HybridExecutor(interpret=run.interpret)
    # warm every program the window runs; slide 0 lands again, with the
    # same records, when the window starts
    hist = ring_write(hist, new, np.int32(0))
    hx.run_window(hist[0], run.config["agg"])
    jax.block_until_ready(hist)
    run.state.update(hist=hist, new=new, hx=hx, key=key, n=n, per=per,
                     things=things, slides=slides, scale=scale,
                     offloads0=hx.offloads)


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(left - 5e-4 if left > 1e-3 else 0)


def run_slide(run, hx, rows, agg, due, lat, vals):
    """Hand one slide's windows, one per thing in thing order, to
    ``hx.run_windows`` in one call; an executor without that call gets
    a ``run_window`` call a window, from a generator standing in for it.
    Each ``next()`` runs in the span ``bench.q2.run_window``, and a
    window's latency from ``due`` is taken when its value is yielded;
    latencies and values land in ``lat`` and ``vals``. An exception
    ends the slide: that window and every later one count as failed.
    Returns the longest window's seconds and the clock at the last
    result (0 where none came)."""
    handover = getattr(hx, "run_windows", None) or (
        lambda rows, agg: (hx.run_window(r, agg) for r in rows))
    results = None
    longest, done = 0.0, 0.0
    for i in range(len(rows)):
        run.attempted += 1
        start = time.perf_counter()
        try:
            with run.span("bench.q2.run_window"):
                if results is None:
                    results = iter(handover(rows, agg))
                v = next(results)
        except Exception as e:  # the slide ends; its windows are counted
            left = len(rows) - i
            run.attempted += left - 1
            run.failed += left
            print(f"q2: window {i} of a slide failed, with the slide's "
                  f"{left - 1} after it: {e!r}", file=sys.stderr)
            break
        done = time.perf_counter()
        longest = max(longest, done - start)
        lat[i], vals[i] = done - due, v
    return longest, done


def window(run) -> None:
    st = run.state
    hx, agg = st["hx"], run.config["agg"]
    things, slides = st["things"], st["slides"]
    rate = float(run.traffic["slides_per_s"])
    hist, new = st["hist"], st["new"]
    lat = np.full(slides * things, np.nan)
    vals = np.full(slides * things, np.nan)
    host = []   # per slide: late, drain, thread CPU, longest window, gc
    #             (s) and involuntary switches
    behind, issued = 0, 0
    t0 = time.perf_counter()
    done = t0
    with HostWatch() as watch:
        for k in range(slides):
            due = t0 + k / rate
            if time.perf_counter() - t0 >= run.seconds:
                break   # above the knee: the window ends with its time
            if time.perf_counter() < due:
                _sleep_until(due)
            else:
                behind += 1
            issued += 1
            c0, cpu0, sw0 = watch.sample()
            with run.span("bench.q2.ingest"):
                hist = ring_write(hist, new, np.int32(k))
            at = slice(k * things, (k + 1) * things)
            longest, last = run_slide(run, hx, hist, agg, due, lat[at],
                                      vals[at])
            done = max(done, last)
            c1, cpu1, sw1 = watch.sample()
            host.append((c0 - due, c1 - c0, cpu1 - cpu0, longest,
                         watch.gc_s(c0, c1), sw1 - sw0))
    st["hist"] = hist
    run.window_s = done - t0
    run.records = lat
    st["vals"] = vals
    run.counters.update(windows=issued * things,
                        offloads=hx.offloads - st["offloads0"])
    _report_host(host, watch.gc, behind, issued)


def _report_host(host, pauses, behind, issued) -> None:
    """Where the host's time went, slide by slide: the slowest drains
    beside their thread CPU time, their longest single window, the OS's
    preemptions (where the machine counts them) and the collector's
    pauses, so a wait can be told from work."""
    if not host:
        return
    h = np.asarray(host)
    ms = lambda x: f"{x * 1e3:.1f}"
    print(f"q2: {issued} slides, {behind} started behind schedule; "
          f"lateness median {ms(np.median(h[:, 0]))} ms, max "
          f"{ms(h[:, 0].max())} ms; drain median {ms(np.median(h[:, 1]))}"
          f" ms (thread CPU {ms(np.median(h[:, 2]))} ms)", file=sys.stderr)
    for j in np.argsort(-h[:, 1])[:3]:
        late, drain, cpu, longest, gc_s, sw = h[j]
        print(f"q2: slow slide {j}: drain {ms(drain)} ms, thread CPU "
              f"{ms(cpu)} ms, longest window {ms(longest)} ms, "
              f"{int(sw)} preemptions, gc {ms(gc_s)} ms, started "
              f"{ms(late)} ms late", file=sys.stderr)
    if pauses:
        worst = max(pauses, key=lambda p: p[1] - p[0])
        print(f"q2: gc: {len(pauses)} collections, "
              f"{ms(sum(e - s for s, e, _ in pauses))} ms in all, longest "
              f"{ms(worst[1] - worst[0])} ms (generation {worst[2]})",
              file=sys.stderr)


def release(run) -> None:
    st = run.state
    if "new" in st:
        st["new_host"] = np.asarray(st.pop("new"))
    for row in st.pop("hist", ()):
        row.delete()
    st.pop("hx", None)


def checks(run, control: bool = False):
    """Every window the run completed against the float64 reference.
    The control (bfloat16 on the device, one call per window) is read at
    one window per thing, its slide drawn from the seed: a widest gap
    over fewer windows can only read lower."""
    from reference.q2_mean import control_means, ring_means

    st = run.state
    vals = st["vals"]
    things = st["things"]
    # completed: a latency was recorded, whatever value came back, so a
    # NaN counts against the run
    done = np.flatnonzero(np.isfinite(run.records))
    if control:
        rng = np.random.default_rng([run.seed & 0xFFFFFFFF,
                                     run.seed >> 32, 2])
        pick = np.array([rng.choice(done[done % things == i])
                         for i in range(things)
                         if (done % things == i).any()], dtype=int)
    else:
        pick = done
    slide, thing = pick // things, pick % things
    t0 = time.perf_counter()
    ref = ring_means(st["key"], st["new_host"], slide, thing, n=st["n"],
                     scale=st["scale"])
    got = (control_means(st["key"], st["new_host"], slide, thing,
                         n=st["n"], scale=st["scale"]) if control
           else vals[pick])
    print(f"q2: reference over {pick.size} windows of "
          f"{np.unique(thing).size} things took "
          f"{time.perf_counter() - t0:.2f} s", file=sys.stderr)
    rel = np.abs(got - ref) / np.abs(ref)
    rel[np.isnan(rel)] = math.inf
    offload_miss = run.counters["windows"] - run.counters["offloads"]
    return [
        Check("q2_mean_max_rel_err",
              float(rel.max()) if pick.size else math.inf,
              float(run.traffic["mean_rel_err_limit"])),
        Check("q2_windows_not_offloaded", float(offload_miss), 0.0),
    ]
