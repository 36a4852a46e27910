"""Open-loop serving: requests fall due on the wall clock at the cell's
fixed Poisson rate, whatever the engine does, and each is timed from when
it was due.  Requests due inside the window are followed to completion
after it closes, for at most ``drain_s`` seconds; one still open then, or
one that ends in any state but DONE, counts as failed and as missing
every latency limit.  In a traced run the schedule is held while the
profiler starts and stops (stopping it can take a minute): requests not
yet due fall due that much later, so the stretch after it sees the same
load as a plain run.

End-to-end: ``ttft_p95_ms`` (due to first token) and ``itl_p99_ms`` (every
gap between consecutive tokens of those requests), exact quantiles over
all samples; ``setup_s``.  Correct: the served tokens of a seeded sample
of finished requests, the longest answer among them, against the float32
reference.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

import serving
import traffic


def run(run):
    wl, tr = run.workload, run.workload["traffic"]
    engine, params = serving.build_engine(run)
    vocab = run.reference.dims(run.config)["V"]
    tracked = [serving.Tracked(r) for r in
               traffic.requests(tr, vocab, run.seed, run.seconds)]
    sink = serving.attach_tracer(engine) if run.trace else None
    client = serving.Client(run, engine)
    lo, hi = wl["trace_window_s"]
    setup_s = run.setup_done()

    t0 = run.clock()
    shift = 0.0         # seconds the schedule was held for the profiler
    deadline = run.seconds + tr["drain_s"]
    i, n = 0, len(tracked)
    stretch, pauses = None, []

    def hold(now: float) -> None:
        nonlocal shift
        pauses.append((now, run.clock()))
        shift += pauses[-1][1] - now

    while True:
        now = run.clock()
        while i < n and t0 + shift + tracked[i].req.due_s <= now:
            client.submit(tracked[i], now, t0 + shift + tracked[i].req.due_s)
            i += 1
        if run.trace and stretch is None and now - t0 - shift >= lo:
            run.start_trace()
            stretch = [len(client.steps), None]
            hold(now)
        elif stretch is not None and stretch[1] is None \
                and now - t0 - shift >= hi:
            stretch[1] = len(client.steps)
            run.stop_trace()
            hold(now)
        if client.open:
            client.step()
        elif i < n:
            with run.annotate("wait"):
                time.sleep(max(0.0, t0 + shift + tracked[i].req.due_s
                               - run.clock()))
        else:
            break
        if run.clock() - t0 - shift > deadline:
            break
    if stretch is not None and stretch[1] is None:
        stretch[1] = len(client.steps)
        run.stop_trace()
        hold(run.clock())
    t_end = run.clock()
    memory = run.memory_peak_bytes()

    ttft, itl, failed = [], [], 0
    for t in tracked:
        due = t.due_at if t.rid >= 0 else t0 + shift + t.req.due_s
        if t.state != "DONE":
            failed += 1
            ttft.append(t_end - due)
            continue
        ttft.append(t.times[0] - due)
        itl.extend(np.diff(t.times).tolist())
    late = client.lateness or [0.0]
    print(f"open loop: {n} requests due in {run.seconds:g} s "
          f"({n / run.seconds:.3f}/s), {n - failed} done, {failed} failed; "
          f"generator late p99 {traffic.percentile(late, 99) * 1e3:.3f} ms "
          f"max {max(late) * 1e3:.3f} ms; drain ended "
          f"{t_end - t0 - shift - run.seconds:.3f} s after the window; "
          f"{len(client.steps)} engine steps; set-up {setup_s:.3f} s; "
          f"schedule held {[round(b - a, 3) for a, b in pauses]} s "
          f"for the profiler",
          file=sys.stderr, flush=True)
    pct = traffic.percentile
    e2e = {"ttft_p95_ms": pct(ttft, 95) * 1e3,
           "itl_p99_ms": pct(itl, 99) * 1e3, "setup_s": setup_s}
    print("open loop: ttft p50 p90 p95 p99 "
          f"{[round(pct(ttft, q) * 1e3, 3) for q in (50, 90, 95, 99)]} ms; "
          "itl p50 p90 p95 p99 "
          f"{[round(pct(itl, q) * 1e3, 3) for q in (50, 90, 95, 99)]} ms "
          f"over {len(itl)} gaps", file=sys.stderr, flush=True)
    if run.trace:
        run.record.update(
            spans=serving.spans(sink.events), pauses=pauses,
            traced_steps=client.steps[stretch[0]:stretch[1]])

    states = engine.request_states()
    picked = serving.sample(tracked, wl["check"]["sample"], run.seed)
    served = [(t.req.prompt, states[t.rid]["tokens"]) for t in picked]
    run.record["served"] = served
    del engine, client
    gc.collect()
    t_ref = run.clock()
    g = serving.served_gaps(run, params, served)
    print(f"check: {g['tokens']} served tokens of {len(served)} requests "
          f"against the float32 reference, {g['not_argmax']} not its "
          f"argmax ({run.clock() - t_ref:.1f} s)", file=sys.stderr,
          flush=True)
    limit = wl["check"]["limits"]["served_gap"]
    return {"e2e": e2e, "attempted": n, "failed": failed,
            "memory_peak_bytes": memory,
            "checks": [{"name": "served_gap", "value": g["worst_gap"],
                        "limit": limit}]}
