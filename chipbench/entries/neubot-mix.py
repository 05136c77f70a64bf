"""The paper's Neubot Q1 and Q2 on one host thread, open loop.

Every minute of records is a round. At its due time the round uplinks
its ``[things, per]`` block from the edge's host memory to the device
in one transfer and lands it in every thing's ring, in place; then
each thing's Q1 window (its last 3 minutes, a view of the edge's host
stream) goes through ``HybridExecutor.run_window``'s edge branch. On
every fifth round Q2's slide is due too, and each thing's 120-day ring
goes through the same executor's offload path, handed over in one
``run_windows`` call, as in ``q2-offload``.
Work runs in due-time order, one piece after another: a round that
comes due while a slide drains waits for it. A window's latency runs
from its round's due time to its result on the host. ``run.records``
holds Q2's windows alone, so ``q2_window_p95_s`` and
``q2_windows_per_s`` mean here what they mean in ``q2.slides``; Q1's
latencies are kept apart for ``q1.window_p95_s``.

The configuration is ``neubot-q2``'s, Q2's keys at its top level, with
Q1 in its group ``q1``.
"""
from __future__ import annotations

import functools
import math
import sys
import time

import jax
import numpy as np

import neubot_data as data
from harness import Check, HostWatch, entry_of

_q2 = entry_of({"entry": "q2-offload"})


def _shape(run):
    cfg = run.config
    hz = cfg["record_hz"]
    q1 = cfg["q1"]
    n = int(cfg["window_s"] * hz)
    per = int(q1["slide_s"] * hz)
    w1 = int(q1["window_s"] * hz)
    if cfg["slide_s"] % q1["slide_s"]:
        raise ValueError("Q2's slide must hold a whole number of rounds")
    every = int(cfg["slide_s"] // q1["slide_s"])
    if not per <= w1 <= n or n % (per * every):
        raise ValueError("each window must hold whole rounds, Q1's in a ring")
    rounds = max(every, math.ceil(
        run.seconds * run.traffic["slides_per_s"] * every))
    slides = math.ceil(rounds / every)
    if slides * every * per > n:
        raise ValueError("the run's ingest would wrap the ring")
    return n, per, w1, every, int(cfg["things"]), rounds, slides


@functools.partial(jax.jit, donate_argnums=0)
def land(rows, block, start):
    """Land one round's records (``block``, ``[things, per]``) in every
    thing's ring at ``start``, in place."""
    return tuple(jax.lax.dynamic_update_slice(r, block[i], (start,))
                 for i, r in enumerate(rows))


def setup(run) -> None:
    from repro.pipeline.queries import HybridExecutor

    n, per, w1, every, things, rounds, slides = _shape(run)
    scale = float(run.config["scale_bps"])
    key = data.root_key(run.seed)
    hist = data.history(key, things=things, n=n, scale=scale)
    # the same ingest as q2.slides' for the seed, so each Q2 window is
    # that cell's window
    new = data.ingest(key, slides=slides, things=things, per=every * per,
                      scale=scale)
    new_host = np.asarray(new)
    new.delete()
    back = w1 - per
    # the edge's buffer: each thing's last ``back`` stored records, then
    # its live stream
    stream = np.concatenate(
        [np.stack([np.asarray(r[n - back:]) for r in hist]),
         new_host.transpose(1, 0, 2).reshape(things, -1)], axis=1)
    hx = HybridExecutor(int(run.config["edge_budget"]),
                        interpret=run.interpret)
    # warm every program the window runs; round 0 lands again, with the
    # same records, when the window starts
    hist = land(hist, jax.device_put(stream[:, back:back + per]),
                np.int32(0))
    hx.run_window(hist[0], run.config["agg"])
    hx.run_window(stream[0, :w1], run.config["q1"]["agg"])
    jax.block_until_ready(hist)
    run.state.update(hist=hist, stream=stream, new_host=new_host, hx=hx,
                     key=key, n=n, per=per, w1=w1, every=every,
                     things=things, rounds=rounds, slides=slides,
                     scale=scale, offloads0=hx.offloads,
                     edge_runs0=hx.edge_runs)


def _windows(run, hx, views, agg, due, lat, vals):
    """Run one Q1 window per thing through ``hx``; each one's latency
    from ``due`` and its value land in ``lat`` and ``vals``. Returns the
    longest window's seconds and the clock at the last result."""
    longest, done = 0.0, 0.0
    for i, x in enumerate(views):
        run.attempted += 1
        start = time.perf_counter()
        try:
            v = hx.run_window(x, agg)
        except Exception as e:  # a window that fails is counted
            run.failed += 1
            print(f"mix: {agg} window of thing {i} failed: {e!r}",
                  file=sys.stderr)
            continue
        done = time.perf_counter()
        longest = max(longest, done - start)
        lat[i], vals[i] = done - due, v
    return longest, done


def window(run) -> None:
    st = run.state
    hx, cfg = st["hx"], run.config
    hist, stream = st["hist"], st["stream"]
    n, per, w1, every = st["n"], st["per"], st["w1"], st["every"]
    things, rounds, slides = st["things"], st["rounds"], st["slides"]
    rate = float(run.traffic["slides_per_s"]) * every    # rounds a second
    q1_lat = np.full((rounds, things), np.nan)
    q1_val = np.full((rounds, things), np.nan)
    q2_lat = np.full((slides, things), np.nan)
    q2_val = np.full((slides, things), np.nan)
    host = []   # per round, as q2-offload's per slide
    drains = []   # per Q2 slide: its 256 windows' seconds
    behind, issued = 0, 0
    t0 = time.perf_counter()
    done = t0
    with HostWatch() as watch:
        for r in range(rounds):
            due = t0 + r / rate
            if time.perf_counter() - t0 >= run.seconds:
                break
            if time.perf_counter() < due:
                _q2._sleep_until(due)
            else:
                behind += 1
            issued += 1
            c0, cpu0, sw0 = watch.sample()
            # the stream's columns [a, a + w1) are round r's Q1 window;
            # its last per are the round's new records
            a = r * per
            with run.span("bench.q1.ingest"):
                hist = land(hist, jax.device_put(stream[:, a + w1 - per:
                                                        a + w1]),
                            np.int32(a % n))
            # one span a round: a span a window would make the trace's
            # reduction (devtrace.idle_by_span, spans × idle gaps) run
            # for minutes
            with run.span("bench.q1.windows"):
                longest, last = _windows(
                    run, hx, (stream[i, a:a + w1] for i in range(things)),
                    cfg["q1"]["agg"], due, q1_lat[r], q1_val[r])
            if r % every == every - 1:
                k = r // every
                t2 = time.perf_counter()
                slow, last2 = _q2.run_slide(
                    run, hx, hist, cfg["agg"], due, q2_lat[k], q2_val[k])
                drains.append(time.perf_counter() - t2)
                longest, last = max(longest, slow), max(last, last2)
            done = max(done, last)
            c1, cpu1, sw1 = watch.sample()
            host.append((c0 - due, c1 - c0, cpu1 - cpu0, longest,
                         watch.gc_s(c0, c1), sw1 - sw0))
    st["hist"] = hist
    run.window_s = done - t0
    run.records = q2_lat.ravel()
    st.update(q1_lat=q1_lat.ravel(), q1_val=q1_val, q2_lat=q2_lat,
              q2_val=q2_val)
    q2_issued = issued // every
    run.counters.update(windows=(issued + q2_issued) * things,
                        q1_windows=issued * things,
                        q2_windows=q2_issued * things,
                        offloads=hx.offloads - st["offloads0"],
                        edge_runs=hx.edge_runs - st["edge_runs0"])
    if drains:
        pos = [np.nanmedian(q1_lat[o:issued:every]) * 1e3
               for o in range(min(every, issued))]
        print(f"mix: a Q2 slide's windows took {np.median(drains) * 1e3:.1f}"
              f" ms (median of {len(drains)}, max {max(drains) * 1e3:.1f});"
              f" Q1's median latency in rounds 1-{every} of a slide (its Q2"
              f" windows run after round {every}):"
              f" {', '.join(f'{x:.1f}' for x in pos)} ms", file=sys.stderr)
    print(f"mix: the host report below counts rounds of Q1 (each with "
          f"its Q2 slide every {every}th) as slides", file=sys.stderr)
    _q2._report_host(host, watch.gc, behind, issued)


def release(run) -> None:
    st = run.state
    for row in st.pop("hist", ()):
        row.delete()
    st.pop("stream", None)
    st.pop("hx", None)


def checks(run, control: bool = False):
    """Every completed Q1 window against the float64 max of the same
    seeded records, and every completed Q2 window against the float64
    mean of its ring. A window is completed when its latency was
    recorded, whatever value came back, so a NaN counts against it.
    The controls put the reference in bfloat16 in the program's place;
    Q2's (bfloat16 on the device, one call per window) is read at one
    window per thing, its slide drawn from the seed, as ``q2-offload``
    does."""
    from reference.q1_max import control_maxes, window_maxes
    from reference.q2_mean import control_means, ring_means

    st, c = run.state, run.counters
    key, new = st["key"], st["new_host"]
    q1_val = st["q1_val"]
    rnd, thing = np.nonzero(np.isfinite(st["q1_lat"].reshape(q1_val.shape)))
    kw = dict(n=st["n"], window=st["w1"], per=st["per"], scale=st["scale"])
    ref1 = window_maxes(key, new, rnd, thing, **kw)
    got1 = (control_maxes(key, new, rnd, thing, **kw) if control
            else q1_val[rnd, thing])
    miss = float(np.count_nonzero(got1 != ref1)) if rnd.size else math.inf

    slide, thing = np.nonzero(np.isfinite(st["q2_lat"]))
    if control and slide.size:
        rng = np.random.default_rng([run.seed & 0xFFFFFFFF,
                                     run.seed >> 32, 2])
        pick = [rng.choice(np.flatnonzero(thing == i))
                for i in np.unique(thing)]
        slide, thing = slide[pick], thing[pick]
    t0 = time.perf_counter()
    ref2 = ring_means(key, new, slide, thing, n=st["n"], scale=st["scale"])
    got2 = (control_means(key, new, slide, thing, n=st["n"],
                          scale=st["scale"]) if control
            else st["q2_val"][slide, thing])
    print(f"mix: references over {rnd.size} Q1 and {slide.size} Q2 "
          f"windows took {time.perf_counter() - t0:.2f} s", file=sys.stderr)
    rel = np.abs(got2 - ref2) / np.abs(ref2)
    rel[np.isnan(rel)] = math.inf
    return [
        Check("q1_max_mismatches", miss, 0.0),
        Check("q2_mean_max_rel_err",
              float(rel.max()) if slide.size else math.inf,
              float(run.traffic["mean_rel_err_limit"])),
        Check("q1_windows_not_at_edge",
              float(c["q1_windows"] - c["edge_runs"]), 0.0),
        Check("q2_windows_not_offloaded",
              float(c["q2_windows"] - c["offloads"]), 0.0),
    ]
