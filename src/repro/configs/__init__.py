"""Architecture registry: ``get_config(arch_id)``, reduced ``smoke_config``,
and ``input_specs`` (ShapeDtypeStruct stand-ins for the dry-run)."""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import (EncoderConfig, MLAConfig, ModelConfig,
                                 MoEConfig, SSMConfig)

ARCHS = [
    "deepseek_moe_16b", "granite_moe_3b_a800m", "stablelm_12b",
    "minicpm3_4b", "glm4_9b", "llama3_8b", "whisper_base", "hymba_1_5b",
    "qwen2_vl_2b", "mamba2_130m", "glm47_flash",
]

# canonical ids use dashes (CLI); module names use underscores
def _mod(arch_id: str) -> str:
    return arch_id.replace("-", "_").replace(".", "_")


def list_archs() -> list[str]:
    return [a.replace("_", "-") for a in ARCHS]


def get_config(arch_id: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_mod(arch_id)}")
    return mod.get_config()


# ---------------------------------------------------------------------------
# Reduced smoke variants: same family/code paths, laptop-sized.
# ---------------------------------------------------------------------------
def smoke_config(arch_id: str) -> ModelConfig:
    cfg = get_config(arch_id)
    kw: dict = dict(
        n_layers=2, d_model=64, n_heads=4, n_kv=min(cfg.n_kv, 2) or 0,
        d_ff=128 if cfg.d_ff else 0, vocab=256, head_dim=16,
        global_layers=(0,) if cfg.global_layers else (),
        window=16 if cfg.window else 0,
    )
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe, num_experts=8, top_k=2, d_expert=32,
            d_shared=64 if cfg.moe.num_shared else 0)
        kw["d_ff"] = 128 if cfg.dense_layers else 0
        kw["dense_layers"] = min(cfg.dense_layers, 1)
    if cfg.mla is not None:
        kw["mla"] = MLAConfig(q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=8,
                              qk_rope_dim=8, v_head_dim=8)
        kw["head_dim"] = 16
    if cfg.ssm is not None:
        kw["ssm"] = SSMConfig(d_state=16, d_inner=64, head_p=16, chunk=32)
    if cfg.encoder is not None:
        kw["encoder"] = EncoderConfig(n_layers=2, n_frames=32)
    if cfg.mrope_sections is not None:
        kw["mrope_sections"] = (4, 2, 2)
    return dataclasses.replace(cfg, **kw)


# ---------------------------------------------------------------------------
# Assigned input shapes.
# ---------------------------------------------------------------------------
SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}


def applicable_shapes(cfg: ModelConfig) -> list[str]:
    """Per instructions: long_500k only for sub-quadratic archs."""
    shapes = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.subquadratic:
        shapes.append("long_500k")
    return shapes


def input_specs(cfg: ModelConfig, shape_name: str, *, reduced: bool = False
                ) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    ``kind='train'``  -> train_step inputs {tokens, labels[, frames/patches]}
    ``kind='prefill'``-> forward(+build_cache) inputs
    ``kind='decode'`` -> serve_step inputs {tokens_t, cache[, enc_out]}
    """
    sh = dict(SHAPES[shape_name])
    if reduced:
        sh.update(seq=min(sh["seq"], 64), batch=min(sh["batch"], 4))
    b, s = sh["batch"], sh["seq"]
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct

    def _extras(specs: dict, seq: int) -> dict:
        if cfg.encoder is not None:
            specs["frames"] = sds((b, cfg.encoder.n_frames, cfg.d_model), f32)
        if cfg.family == "vlm":
            sp = min(1024, seq // 4)
            specs["patches"] = sds((b, sp, cfg.d_model), f32)
            specs["positions"] = sds((3, b, seq), i32)
        return specs

    if sh["kind"] in ("train", "prefill"):
        specs = {"tokens": sds((b, s), i32)}
        if sh["kind"] == "train":
            specs["labels"] = sds((b, s), i32)
        return _extras(specs, s)

    # decode: one new token against a cache of length seq
    from repro.models import transformer
    cache = jax.eval_shape(
        lambda: transformer.init_cache(cfg, b, s, quantized=True))
    specs = {"tokens_t": sds((b,), i32), "cache": cache}
    if cfg.encoder is not None:
        specs["enc_out"] = sds((b, cfg.encoder.n_frames, cfg.d_model), f32)
    if cfg.family == "vlm":
        pass  # decode steps are pure-text continuation (positions tracked 1D)
    return specs
