#!/usr/bin/env python3
"""The on-chip benchmark: one cell of ``BENCHMARK.json``, run once.

    python benchmarks/tpu/bench.py --workload glm4-9b-serve.chat \\
        --seed 1234 --seconds 30 --trace 0

Everything a cell needs is found by name beside this file:

* ``workloads/<cell>.json``: its configuration, chips, driver, traffic,
  engine or trainer settings and the limits of its correctness check;
* ``configs/<config>.json``: the model's published keys, what was cut
  (``reduced``), ``assumed``, the program's settings, and the plain
  reference (``configs/<reference>.py``) beside it;
* ``drivers/<driver>.py``: ``run(run) -> result``, which builds the
  system under test from ``src/``, warms it up, measures for
  ``--seconds``, and checks what the timed path produced against the
  reference;
* ``metrics/<metric>.py``: ``read(record) -> number or None`` for each
  per-layer metric that ``BENCHMARK.json`` lists for the cell.  A reader
  that finds nothing to read leaves its metric out of the line; the run
  names it on standard error and under ``missing`` in a traced result.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the per-layer ones, read from the program's spans, the
benchmark's own counts and a profiler trace of a stretch of the window.
The last line of standard output is one JSON object; the numbers that
decide ``correct`` are also printed, each beside its limit, as the last
lines of standard error.  Exits non-zero, with no result, when JAX finds
no TPU or fewer chips than the cell asks for, or when ``src/repro`` is
not in the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, with no size limit: with one, JAX evicts by access-time
    files, and a directory holding an entry without its access-time file
    refuses every later write."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


class Run:
    """What a driver sees of one run: the cell, its seed and window, the
    reference, clocks, and the host annotations and profiler stretch."""

    def __init__(self, workload: dict, config: dict, *, seed: int,
                 seconds: float, trace: bool, t_process: float,
                 bench_dir: str = HERE):
        self.workload, self.config = workload, config
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.t_process = t_process
        self.reference = load_module(os.path.join(
            bench_dir, "configs", config["reference"] + ".py"))
        self.record: dict = {}
        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_") \
            if trace else None
        self._window = None

    def program_config(self):
        """The program's ``ModelConfig``: the configuration file's
        architecture in the program's registry, with its overrides."""
        import dataclasses

        from repro import configs
        prog = self.config["program"]
        return dataclasses.replace(configs.get_config(prog["arch"]),
                                   **prog["overrides"])

    @staticmethod
    def clock() -> float:
        return time.perf_counter()

    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start_trace(self) -> None:
        import jax
        # device ops and host annotations only: the Python tracer, on by
        # default, slows every host call of the traced stretch
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("window")
        self._window.__enter__()

    def stop_trace(self) -> None:
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def setup_done(self) -> float:
        """Seconds from process start to now: the run's set-up."""
        return self.clock() - self.t_process

    @staticmethod
    def memory_peak_bytes() -> int:
        """The allocator's peak on the fullest chip (0 where the backend
        keeps no such count, as the CPU's)."""
        import jax
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.devices())

    def cleanup(self) -> None:
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def run_cell(cell: str, *, seed: int, seconds: float, trace: bool,
             t_process: float, peaks: dict, bench_dir: str = HERE,
             bench: dict | None = None) -> dict:
    """One run of ``cell``; returns the result object (without printing).
    The caller has checked the platform and chosen ``peaks``."""
    import jax

    bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    wl = load_json(os.path.join(bench_dir, "workloads", cell + ".json"))
    cfg = load_json(os.path.join(bench_dir, "configs",
                                 wl["config"] + ".json"))
    run = Run(wl, cfg, seed=seed, seconds=seconds, trace=trace,
              t_process=t_process, bench_dir=bench_dir)
    driver = load_module(os.path.join(bench_dir, "drivers",
                                      wl["driver"] + ".py"))
    try:
        res = driver.run(run)
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": jax.device_count(),
                  "memory_peak_bytes": int(res["memory_peak_bytes"])}
        metrics, breakdown, missing = {}, None, []
        if not trace:
            for m in bench["end_to_end"]:
                if applies(m, cell):
                    metrics[m["name"]] = {"value": res["e2e"][m["name"]],
                                          "unit": m["unit"]}
        else:
            import trace_reduce
            reduced = trace_reduce.reduce(
                trace_reduce.find_trace(run.trace_dir),
                platform=dev.platform)
            record = dict(run.record, trace=reduced, peaks=peaks,
                          dims=run.reference.dims(cfg), workload=wl,
                          memory_peak_bytes=device["memory_peak_bytes"])
            for m in bench["per_layer"]:
                if not applies(m, cell):
                    continue
                reader = load_module(os.path.join(bench_dir, "metrics",
                                                  m["name"] + ".py"))
                value = reader.read(record)
                if value is None:
                    missing.append(m["name"])
                    print(f"bench: per-layer metric {m['name']} found "
                          f"nothing to read in this run", file=sys.stderr)
                else:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = trace_reduce.breakdown(reduced)
    finally:
        run.cleanup()
    checks = res["checks"]
    correct = (res["failed"] == 0 and res["attempted"] > 0
               and all(c["value"] <= c["limit"] for c in checks))
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
        out["missing"] = missing
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def print_result(out: dict) -> None:
    sys.stdout.flush()
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no src/repro in {ROOT}: the system under test is "
              f"missing", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    sys.path.insert(0, HERE)
    enable_compile_cache()
    import jax

    wl = load_json(os.path.join(HERE, "workloads", args.workload + ".json"))
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 3
    if len(devices) < int(wl["chips"]):
        print(f"bench: the cell needs {wl['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if devices[0].device_kind not in table:
        print(f"bench: no peaks for device kind "
              f"{devices[0].device_kind!r} in peaks.json", file=sys.stderr)
        return 3
    out = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t_process=T_PROCESS,
                   peaks=table[devices[0].device_kind])
    print_result(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
