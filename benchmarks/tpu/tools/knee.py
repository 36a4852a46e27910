#!/usr/bin/env python3
"""Find an open-loop cell's knee once: the highest Poisson rate the
engine sustains without a growing queue.

    python benchmarks/tpu/tools/knee.py --workload glm4-9b-serve.chat \\
        --rates 4,6,8,10 --seconds 20 --seed 5

One process builds and warms the cell's engine once, then offers each
rate for ``--seconds`` with the cell's length mix, follows every request
due to its end (at most ``--drain`` seconds), and prints per rate: the
requests due and done, the queue left when the window closed, time to
first token and inter-token gap (p50, p95), and how late the generator
ran.  A cell is then fixed at a rate below the knee; the benchmark itself
never searches for one.  Runs only on a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--drain", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    bench.enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("knee: JAX found no TPU", file=sys.stderr)
        return 3
    import serving
    import traffic

    wl = bench.load_json(os.path.join(HERE, "workloads",
                                      args.workload + ".json"))
    cfg = bench.load_json(os.path.join(HERE, "configs",
                                       wl["config"] + ".json"))
    run = bench.Run(wl, cfg, seed=args.seed, seconds=args.seconds,
                    trace=False, t_process=time.perf_counter())
    engine, _ = serving.build_engine(run)
    vocab = run.reference.dims(cfg)["V"]
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(wl["traffic"], rate_per_s=rate)
        tracked = [serving.Tracked(r) for r in
                   traffic.requests(mix, vocab, args.seed, args.seconds)]
        client = serving.Client(run, engine)
        t0 = time.perf_counter()
        i, queued_at_close = 0, None
        while True:
            now = time.perf_counter()
            while i < len(tracked) and t0 + tracked[i].req.due_s <= now:
                client.submit(tracked[i], now, t0 + tracked[i].req.due_s)
                i += 1
            if queued_at_close is None and now - t0 >= args.seconds:
                queued_at_close = sum(not t.times for t in client.open.values())
            if client.open:
                client.step()
            elif i < len(tracked):
                time.sleep(max(0.0, t0 + tracked[i].req.due_s
                               - time.perf_counter()))
            else:
                break
            if now - t0 > args.seconds + args.drain:
                break
        done = [t for t in tracked if t.state == "DONE"]
        ttft = [t.times[0] - (t0 + t.req.due_s) for t in done]
        itl = [b - a for t in done for a, b in zip(t.times, t.times[1:])]
        pct = traffic.percentile
        print(json.dumps({
            "rate": rate, "due": len(tracked), "done": len(done),
            "queued_at_close": queued_at_close,
            "drain_s": time.perf_counter() - t0 - args.seconds,
            "ttft_p50_ms": pct(ttft, 50) * 1e3,
            "ttft_p95_ms": pct(ttft, 95) * 1e3,
            "itl_p50_ms": pct(itl, 50) * 1e3, "itl_p99_ms": pct(itl, 99) * 1e3,
            "late_p99_ms": pct(client.lateness, 99) * 1e3,
            "steps": len(client.steps)}), flush=True)
        engine.drain()
        engine.reset()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
