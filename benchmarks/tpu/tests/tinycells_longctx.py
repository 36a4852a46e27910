"""A tiny copy of the latent-attention, held-expert serving cell for the
benchmark's CPU tests, and the tiny benchmark with every cell of
``BENCHMARK.json`` standing on a tiny cell.

``make_tiny_bench`` extends ``tinycells.make_tiny_bench`` to the cells
added after it: ``glm47-flash-serve.longctx`` stands on ``tiny.longctx``
and ``glm4-9b-train.seq16k`` (one long row per step) on ``tiny.long``.
The tests' ``conftest.py`` puts it in ``tinycells``' place."""
from __future__ import annotations

import json
import os
import shutil

import tinycells
from tinycells import BENCH, CELLS, ROOT, TINY

#: a GLM-4.7-Flash block at test size: 2 dense-led layers of MLA, a
#: sigmoid router over 8 experts of which this chip holds 4 (2-5)
TINY_MLA = {
    "name": "tiny-mla", "source": "a tiny copy of the GLM-4.7-Flash block",
    "reference": "glm4moe_lite", "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 32, "n_routed_experts": 4,
    "held_first_expert": 2, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "routed_scaling_factor": 1.8,
    "vocab_size": 512, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "reduced": ["n_routed_experts"], "published": {"n_routed_experts": 8},
    "assumed": {}, "weight_dtype": "bfloat16",
    "program": {"arch": "glm47-flash", "overrides": {
        "n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv": 4, "d_ff": 96,
        "vocab": 512, "head_dim": 32, "norm_eps": 1e-5,
        "rope_theta": 10000.0, "attn_backend": "interpret",
        "mla": {"q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_dim": 24,
                "qk_rope_dim": 8, "v_head_dim": 32},
        "moe": {"num_experts": 8, "top_k": 2, "d_expert": 32,
                "num_shared": 1, "d_shared": 32, "capacity_factor": 0.0,
                "scoring": "sigmoid", "routed_scale": 1.8,
                "held": [2, 4]}}},
}

LONGCTX = {
    "config": "tiny-mla", "chips": 1, "driver": "serve_open_loop_argmax",
    "why": "tiny long-context open loop",
    "traffic": {"rate_per_s": 10, "block": 3,
                "prompt": {"median": 40, "sigma": 0.6, "min": 16,
                           "max": 100},
                "output": {"median": 6, "sigma": 0.6, "min": 2, "max": 12},
                "drain_s": 30},
    "engine": {"max_slots": 4, "max_len": 128,
               "prompt_buckets": [32, 64, 128], "policy": "bf16",
               "max_prefill_per_step": 1},
    "trace_window_s": [0.2, 0.8],
    "check": {"sample": 3, "limits": {"argmax_miss": 0.15}}}

#: the tiny cells that stand for each cell of BENCHMARK.json
STANDS_FOR = {"glm4-9b-serve.chat": ["tiny.chat"],
              "glm4-9b-train.seq4k": ["tiny.train", "tiny.long"],
              "glm4-9b-train.seq16k": ["tiny.long"],
              "glm47-flash-serve.longctx": ["tiny.longctx"]}


def end_to_end():
    out = []
    for m in tinycells.end_to_end():
        if m["name"] == "ttft_p95_ms":
            m = dict(m, workloads=m["workloads"] + ["tiny.longctx"])
        out.append(m)
    return out


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
    with open(d / "configs" / "tiny-mla.json", "w") as f:
        json.dump(TINY_MLA, f)
    for name, cell in dict(CELLS, **{"tiny.longctx": LONGCTX}).items():
        with open(d / "workloads" / f"{name}.json", "w") as f:
            json.dump(dict(cell, name=name), f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    per_layer = [dict(m, workloads=sorted({t for w in m["workloads"]
                                           for t in STANDS_FOR[w]}))
                 for m in real["per_layer"]]
    return str(d), {"end_to_end": end_to_end(), "per_layer": per_layer}
