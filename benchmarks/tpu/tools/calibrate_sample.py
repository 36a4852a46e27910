#!/usr/bin/env python3
"""Readings of a serving cell's served-token checks over many seeds, fast.

    python benchmarks/tpu/tools/calibrate_sample.py \\
        --workload glm47-flash-serve.longctx --seeds 11,12,13

One process builds and warms the cell's engine once.  For each seed it
makes that seed's weights, draws the seed's traffic for a whole window
(``--seconds``, the benchmark's ``run_seconds``), takes the requests that
the cell's check would take were every request of the window finished
(``serving.sample``: the longest answer and a seeded pick of the rest),
serves just those through the engine, and prints one JSON line: the
program's widest gap (``served_gap``) and count of tokens that are not
the reference's argmax (``not_argmax``, of ``tokens``), and the same for
the fp8 control (the tokens the fp8 reference ranks first at the same
positions), computed as ``serving.served_gaps`` computes them, with
each sequence padded to a
power of two of at least 1024 instead of to ``max_len`` (causal
attention keeps the padding out of every served row).  The workload's
limits are set between the two sets of readings.  Runs only on a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import bench  # noqa: E402


def gaps(ref, params, config, served, *, fp8: bool) -> dict:
    import jax.numpy as jnp
    worst, n, flips = 0.0, 0, 0
    for prompt, toks in served:
        ctx = list(prompt) + list(toks[:-1])
        pad = max(1024, 1 << (len(ctx) - 1).bit_length())
        seq = np.zeros(pad, np.int32)
        seq[:len(ctx)] = ctx
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
        z = np.asarray(ref.logits_at(params, config, seq, rows),
                       np.float64)
        if fp8:
            toks = np.asarray(jnp.argmax(ref.logits_at(
                params, config, seq, rows, fp8=True), -1))
        toks = np.asarray(toks)
        g = (z.max(-1) - z[np.arange(len(toks)), toks]) / np.abs(z).max(-1)
        worst = max(worst, float(g.max()))
        n += len(toks)
        flips += int((toks != z.argmax(-1)).sum())
    return {"served_gap": worst, "tokens": n, "not_argmax": flips}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    bench.enable_compile_cache()
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("calibrate_sample: JAX found no TPU", file=sys.stderr)
        return 3
    import serving
    import traffic

    wl = bench.load_json(os.path.join(HERE, "workloads",
                                      args.workload + ".json"))
    cfg = bench.load_json(os.path.join(HERE, "configs",
                                       wl["config"] + ".json"))
    seeds = [int(s) for s in args.seeds.split(",")]
    run = bench.Run(wl, cfg, seed=seeds[0], seconds=args.seconds,
                    trace=False, t_process=time.perf_counter())
    engine, params = serving.build_engine(run)
    ref, vocab = run.reference, run.reference.dims(cfg)["V"]
    for seed in seeds:
        t = time.perf_counter()
        if seed != seeds[0]:
            engine.params = params = None
            engine.params = params = ref.make_params(
                cfg, seed, jnp.dtype(cfg["weight_dtype"]))
        tracked = [serving.Tracked(r, state="DONE") for r in
                   traffic.requests(wl["traffic"], vocab, seed,
                                    args.seconds)]
        for tr in tracked:
            tr.times = [0.0] * tr.req.max_new_tokens
        picked = serving.sample(tracked, wl["check"]["sample"], seed)
        rids = [engine.submit(p.req.prompt, p.req.max_new_tokens)
                for p in picked]
        while engine.scheduler.has_work():
            engine.step()
        states = engine.request_states()
        served = [(p.req.prompt, states[r]["tokens"])
                  for p, r in zip(picked, rids)]
        t_serve = time.perf_counter() - t
        line = {"seed": seed, "lengths": [[len(p), len(s)]
                                          for p, s in served],
                "program": gaps(ref, params, cfg, served, fp8=False),
                "control": gaps(ref, params, cfg, served, fp8=True),
                "serve_s": t_serve, "seconds": time.perf_counter() - t}
        engine.reset()
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
