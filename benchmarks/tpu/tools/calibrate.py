#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, in one process.

    python benchmarks/tpu/tools/calibrate.py --workload glm4-9b-serve.chat \\
        --seeds 11,12,13 --seconds 10

For each seed it runs the cell as the benchmark does, with a window of
``--seconds`` at the cell's own load and sizes, and prints one JSON line:
the program's numbers (the lower readings), and the same numbers for the
control, the plain reference computed in fp8 in the program's place
(served cells: the tokens the fp8 reference ranks first at the served
positions; training cells: fp8 loss, gradients and AdamW).  Training
cells also read the fault of a batch half left out (the reference over
the first half of the rows) where the batch has more than one row.  The
limits in the workload files are set between these readings.  Runs only
on a TPU.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    bench.enable_compile_cache()
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 3
    import serving

    wl = bench.load_json(os.path.join(HERE, "workloads",
                                      args.workload + ".json"))
    cfg = bench.load_json(os.path.join(HERE, "configs",
                                       wl["config"] + ".json"))
    driver = bench.load_module(os.path.join(HERE, "drivers",
                                            wl["driver"] + ".py"))
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        run = bench.Run(wl, cfg, seed=seed, seconds=args.seconds,
                        trace=False, t_process=t)
        res = driver.run(run)
        line = {"seed": seed, "failed": res["failed"],
                "program": {c["name"]: c["value"] for c in res["checks"]}}
        if args.control and "served" in run.record:
            params = run.reference.make_params(cfg, seed,
                                               jnp.dtype(cfg["weight_dtype"]))
            g = serving.served_gaps(run, params, run.record["served"],
                                    fp8=True)
            line["control"] = {"served_gap": g["worst_gap"],
                               "not_argmax": g["not_argmax"],
                               "tokens": g["tokens"]}
            del params
        elif args.control:
            b, s = wl["traffic"]["batch"], wl["traffic"]["seq"]
            want = run.record["want"]
            limits = wl["check"]["limits"]
            got = driver.reference_steps(run, b, s, fp8=True)
            line["control"] = {c["name"]: c["value"] for c in
                               driver.compare(got, want, limits)}
            if b > 1:
                got = driver.reference_steps(run, b, s, rows=b // 2)
                line["half_batch"] = {c["name"]: c["value"] for c in
                                      driver.compare(got, want, limits)}
        line["seconds"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
