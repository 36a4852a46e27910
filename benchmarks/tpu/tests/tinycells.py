"""Tiny copies of the benchmark's cells for its CPU tests, run end to end
with the look for a chip skipped.  Nothing here describes a topology or
loads the TPU's library."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

#: invented peaks for CPU runs: only tests use them, no result names them
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
             "hbm_bytes_per_s": 1e11, "hbm_bytes": 2 ** 34}

TINY = {
    "name": "tiny", "source": "a tiny copy of the GLM-4 block for tests",
    "reference": "chatglm", "num_layers": 2, "hidden_size": 64,
    "ffn_hidden_size": 128, "kv_channels": 16, "num_attention_heads": 4,
    "multi_query_group_num": 2, "padded_vocab_size": 512,
    "layernorm_epsilon": 1e-5, "rope_ratio": 1, "reduced": [],
    "assumed": {}, "weight_dtype": "bfloat16",
    "program": {"arch": "glm4-9b", "overrides": {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv": 2, "d_ff": 128,
        "vocab": 512, "head_dim": 16, "norm_eps": 1e-5,
        "rope_theta": 10000.0}},
}

OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "grad_clip": 1.0, "warmup_steps": 100, "total_steps": 10000,
       "min_lr_frac": 0.1}

CELLS = {
    "tiny.chat": {
        "config": "tiny", "chips": 1, "driver": "serve_open_loop",
        "why": "tiny open loop",
        "traffic": {"rate_per_s": 12,
                    "prompt": {"median": 20, "sigma": 0.9, "min": 8,
                               "max": 64},
                    "output": {"median": 6, "sigma": 0.9, "min": 2,
                               "max": 16},
                    "drain_s": 30},
        "engine": {"max_slots": 4, "max_len": 96, "prompt_buckets": [32, 64],
                   "policy": "bf16", "max_prefill_per_step": 1},
        "trace_window_s": [0.2, 0.8],
        "check": {"sample": 3, "limits": {"served_gap": 0.05}}},
    "tiny.train": {
        "config": "tiny-train", "chips": 1, "driver": "train_steps",
        "why": "tiny train",
        "traffic": {"batch": 2, "seq": 64},
        "trainer": {"policy": "bf16", "remat_policy": "full",
                    "mem_budget_mb": 0, "ahead_steps": 2},
        "optimizer": OPT, "trace_steps": [0, 2],
        "check": {"limits": {"loss_gap": 1e-3, "grad_gap": 0.05,
                             "change_gap": 0.05}}},
}
CELLS["tiny.long"] = dict(CELLS["tiny.train"], why="tiny long rows",
                          traffic={"batch": 1, "seq": 128})


def end_to_end():
    return [
        {"name": "ttft_p95_ms", "unit": "ms", "workloads": ["tiny.chat"]},
        {"name": "itl_p99_ms", "unit": "ms", "workloads": ["tiny.chat"]},
        {"name": "train_tokens_per_s", "unit": "tokens/s",
         "workloads": ["tiny.train", "tiny.long"]},
        {"name": "setup_s", "unit": "s"}]


@pytest.fixture
def tiny_bench(tmp_path):
    return make_tiny_bench(tmp_path)


def make_tiny_bench(tmp_path):
    """A copy of the benchmark's files with the tiny cells added, and the
    ``BENCHMARK.json`` object that lists them with every metric of the
    repository's own ``BENCHMARK.json``."""
    import pathlib
    d = pathlib.Path(tmp_path) / "tpu"
    shutil.copytree(BENCH, d, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    with open(d / "configs" / "tiny.json", "w") as f:
        json.dump(TINY, f)
    train = dict(TINY, name="tiny-train", weight_dtype="float32",
                 padded_vocab_size=256)
    train["program"] = {"arch": "glm4-9b", "overrides": dict(
        TINY["program"]["overrides"], vocab=256)}
    with open(d / "configs" / "tiny-train.json", "w") as f:
        json.dump(train, f)
    for name, cell in CELLS.items():
        with open(d / "workloads" / f"{name}.json", "w") as f:
            json.dump(dict(cell, name=name), f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    # tiny.long (one long row per step) reads the metrics of seq4k too
    names = {"glm4-9b-serve.chat": ["tiny.chat"],
             "glm4-9b-train.seq4k": ["tiny.train", "tiny.long"]}
    per_layer = [dict(m, workloads=sorted({t for w in m["workloads"]
                                           for t in names[w]}))
                 for m in real["per_layer"]]
    return str(d), {"end_to_end": end_to_end(), "per_layer": per_layer}


def run_tiny(bench_dir, bench, cell, *, seed=3, seconds=1.5, trace=False):
    import time

    import bench as harness
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            t_process=time.perf_counter(), peaks=CPU_PEAKS,
                            bench_dir=bench_dir, bench=bench)
