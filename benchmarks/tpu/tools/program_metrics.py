#!/usr/bin/env python3
"""One traced run of a cell that also reads the per-layer metrics of the
program's own spans, counters and named scopes.

    python benchmarks/tpu/tools/program_metrics.py \\
        --workload glm4-9b-serve.chat --seed 1234 --seconds 51

The metrics are defined in ``program_metrics.json`` in the form of
``BENCHMARK.json``'s ``per_layer`` entries, and read by their files in
``metrics/``; ``BENCHMARK.json`` does not list them yet.  This runs
``bench.py`` with ``--trace 1``, with those metrics added to the cell's
per-layer list and with ``program_trace.reduce``'s keys
(``program_spans``, ``program_gaps``) added to the reduced trace, and
prints the result line as ``bench.py`` does.  Runs only on a TPU.
"""
from __future__ import annotations

import functools
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import bench  # noqa: E402
import program_trace  # noqa: E402
import trace_reduce  # noqa: E402


def with_program_metrics(benchmark: dict, bench_dir: str = HERE) -> dict:
    """``benchmark`` with the program's per-layer metrics appended."""
    extra = bench.load_json(os.path.join(bench_dir, "program_metrics.json"))
    return dict(benchmark,
                per_layer=benchmark["per_layer"] + extra["per_layer"])


def reduce_with_program(path: str, *, platform: str = "tpu",
                        _reduce=trace_reduce.reduce) -> dict:
    """``trace_reduce.reduce`` with ``program_trace.reduce``'s keys."""
    return {**_reduce(path, platform=platform),
            **program_trace.reduce(path, platform=platform)}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    benchmark = with_program_metrics(
        bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json")))
    bench.run_cell = functools.partial(bench.run_cell, bench=benchmark)
    trace_reduce.reduce = reduce_with_program
    return bench.main(argv + ["--trace", "1"])


if __name__ == "__main__":
    raise SystemExit(main())
