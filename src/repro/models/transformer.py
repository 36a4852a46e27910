"""The unified model: one scan-based block stack covering every assigned
family (dense/GQA, MLA, MoE, SSM, hybrid, encoder-decoder, VLM).

Params are pure pytrees; per-layer params are *stacked* along a leading
layer axis and executed with ``repro.core.checkpoint.remat_scan`` so depth
never inflates the HLO and OpTorch's S-C applies per segment.  A MoE
stack's leading dense layers (``cfg.dense_layers``) are stacked apart,
under ``dense_blocks``, and run first; the cache stays one stack of all
``n_layers``.

Public entry points:
  init_params(cfg, key)                -> params
  forward(params, cfg, batch, ...)     -> logits (B, S, V)
  loss_fn(params, cfg, batch, ...)     -> (scalar, aux)
  init_cache(cfg, batch, s_max, ...)   -> decode cache pytree
  decode_step(params, cfg, cache, ...) -> (logits (B, V), cache)
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

from repro.core.checkpoint import CheckpointConfig, remat_scan
from repro.core.mixed_precision import Policy
from repro.kernels.kvq import ops as kvq_ops
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import (dense_init, embed_init, gelu_mlp, rms_norm,
                                 swiglu)

# ---------------------------------------------------------------------------
# Initialization.
# ---------------------------------------------------------------------------
def _init_attn(cfg: ModelConfig, key) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    ks = jax.random.split(key, 8)
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "q_a": dense_init(ks[0], (d, m.q_lora_rank)),
            "q_a_norm": jnp.ones((m.q_lora_rank,)),
            "q_b": dense_init(ks[1], (m.q_lora_rank,
                                      h * (m.qk_nope_dim + m.qk_rope_dim))),
            "kv_a": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_dim)),
            "kv_a_norm": jnp.ones((m.kv_lora_rank,)),
            "kv_b": dense_init(ks[3], (m.kv_lora_rank,
                                       h * (m.qk_nope_dim + m.v_head_dim))),
            "wo": dense_init(ks[4], (h * m.v_head_dim, d)),
        }
    return {
        "wq": dense_init(ks[0], (d, h * hd)),
        "wk": dense_init(ks[1], (d, hkv * hd)),
        "wv": dense_init(ks[2], (d, hkv * hd)),
        "wo": dense_init(ks[3], (h * hd, d)),
    }


def _init_ssm(cfg: ModelConfig, key) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    conv_dim = s.d_inner + 2 * s.d_state
    ks = jax.random.split(key, 4)
    return {
        "in_proj": dense_init(ks[0], (d, 2 * s.d_inner + 2 * s.d_state + s.heads)),
        "conv_w": dense_init(ks[1], (s.conv_kernel, conv_dim), in_axis=0),
        "dt_bias": jnp.zeros((s.heads,)),
        "a_log": jnp.zeros((s.heads,)),         # A = -exp(0) = -1
        "d_skip": jnp.ones((s.heads,)),
        "norm_w": jnp.ones((s.d_inner,)),
        "out_proj": dense_init(ks[2], (s.d_inner, d)),
    }


def _init_ffn(cfg: ModelConfig, key) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    if cfg.moe is not None:
        m = cfg.moe
        p = {
            "router": dense_init(ks[0], (d, m.num_experts)),
            "w_gate": dense_init(ks[1], (m.n_held, d, m.d_expert), in_axis=1),
            "w_up": dense_init(ks[2], (m.n_held, d, m.d_expert), in_axis=1),
            "w_down": dense_init(ks[3], (m.n_held, m.d_expert, d), in_axis=1),
        }
        if m.scoring == "sigmoid":
            p["router_bias"] = jnp.zeros((m.num_experts,))
        if m.num_shared:
            p.update(
                shared_gate=dense_init(ks[4], (d, m.d_shared)),
                shared_up=dense_init(ks[5], (d, m.d_shared)),
                shared_down=dense_init(ks[6], (m.d_shared, d)),
            )
        return p
    if cfg.mlp_kind == "gelu":
        return {
            "w1": dense_init(ks[0], (d, cfg.d_ff)), "b1": jnp.zeros((cfg.d_ff,)),
            "w2": dense_init(ks[1], (cfg.d_ff, d)), "b2": jnp.zeros((d,)),
        }
    return {
        "w_gate": dense_init(ks[0], (d, cfg.d_ff)),
        "w_up": dense_init(ks[1], (d, cfg.d_ff)),
        "w_down": dense_init(ks[2], (cfg.d_ff, d)),
    }


def _init_block(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": jnp.ones((cfg.d_model,)),
                         "ln2": jnp.ones((cfg.d_model,))}
    if cfg.mixer in ("attn", "hybrid"):
        p["attn"] = _init_attn(cfg, ks[0])
    if cfg.mixer in ("ssm", "hybrid"):
        p["ssm"] = _init_ssm(cfg, ks[1])
    if cfg.mixer == "hybrid":
        p["mix_norm_attn"] = jnp.ones((cfg.d_model,))
        p["mix_norm_ssm"] = jnp.ones((cfg.d_model,))
    if cfg.moe is not None or cfg.d_ff:
        p["ffn"] = _init_ffn(cfg, ks[2])
    if cfg.encoder is not None:  # decoder cross-attention
        p["xattn"] = _init_attn(dataclass_no_mla(cfg), ks[3])
        p["ln_x"] = jnp.ones((cfg.d_model,))
    return p


def dataclass_no_mla(cfg):
    import dataclasses
    return dataclasses.replace(cfg, mla=None) if cfg.mla is not None else cfg


def _kv_entry(k, v, cfg, mesh, *, quantized: bool = True):
    """Per-layer prefill cache entry: quantize + reshard INSIDE the scan.

    Quantizing per layer (int8 + scales) before the layer stack is stacked
    quarters the bytes that must move when XLA reshards the (head-sharded)
    attention K/V into the (sequence-sharded) cache layout; the sharding
    constraint makes that reshard happen on the small per-layer slice
    instead of the full (L, ...) stack.
    k, v: (B, S, Hkv, hd) -> int8 entries in cache axis order (B, Hkv, S, hd).
    """
    k = jnp.moveaxis(k, 2, 1)                        # (B, Hkv, S, hd)
    v = jnp.moveaxis(v, 2, 1)
    if quantized:
        kq, ks = kvq_ops.quantize_kv(k)
        vq, vs = kvq_ops.quantize_kv(v)
    else:
        kq, vq = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        ks = jnp.zeros(k.shape[:-1], jnp.float32)
        vs = jnp.zeros(v.shape[:-1], jnp.float32)
    if mesh is not None and "model" in mesh.axis_names:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import sharding as shd
        dp = shd.dp_axes(mesh)
        b = k.shape[0]
        b_ax = dp if b % shd.dp_size(mesh) == 0 else None
        if cfg.n_kv % mesh.shape["model"] == 0:
            kv_spec = P(b_ax, "model", None, None)
            sc_spec = P(b_ax, "model", None)
        else:
            seq_ax = "model" if b_ax is not None else ("data", "model")
            kv_spec = P(b_ax, None, seq_ax, None)
            sc_spec = P(b_ax, None, seq_ax)
        cons = lambda x, s: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, s))
        kq, vq = cons(kq, kv_spec), cons(vq, kv_spec)
        ks, vs = cons(ks, sc_spec), cons(vs, sc_spec)
    return {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}


def _init_enc_block(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 2)
    return {
        "ln1": jnp.ones((cfg.d_model,)), "ln2": jnp.ones((cfg.d_model,)),
        "attn": _init_attn(dataclass_no_mla(cfg), ks[0]),
        "ffn": _init_ffn(dataclass_no_moe(cfg), ks[1]),
    }


def dataclass_no_moe(cfg):
    """The config of a dense-MLP layer of ``cfg`` (the encoder's, or a
    leading dense layer of a MoE stack)."""
    import dataclasses
    return dataclasses.replace(cfg, moe=None, dense_layers=0) \
        if cfg.moe is not None else cfg


def init_params(cfg: ModelConfig, key) -> dict:
    k_embed, k_blocks, k_head, k_enc = jax.random.split(key, 4)
    n_dense = cfg.dense_layers
    blocks = jax.vmap(lambda k: _init_block(cfg, k))(
        jax.random.split(k_blocks, cfg.n_layers - n_dense))
    params = {
        "embed": embed_init(k_embed, (cfg.padded_vocab, cfg.d_model)),
        "blocks": blocks,
        "final_norm": jnp.ones((cfg.d_model,)),
    }
    if n_dense:
        params["dense_blocks"] = jax.vmap(
            lambda k: _init_block(dataclass_no_moe(cfg), k))(
            jax.random.split(jax.random.fold_in(k_blocks, 1), n_dense))
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.padded_vocab))
    if cfg.encoder is not None:
        params["enc_blocks"] = jax.vmap(lambda k: _init_enc_block(cfg, k))(
            jax.random.split(k_enc, cfg.encoder.n_layers))
        params["enc_norm"] = jnp.ones((cfg.d_model,))
    if cfg.family == "vlm":
        params["patch_proj"] = dense_init(k_enc, (cfg.d_model, cfg.d_model))
    return params


def _mask_padded_vocab(logits, cfg: ModelConfig):
    """-inf the dead padded-vocab tail (shards cleanly: iota compare)."""
    if cfg.padded_vocab == cfg.vocab:
        return logits
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    return jnp.where(vocab_iota < cfg.vocab, logits,
                     jnp.asarray(-1e30, logits.dtype))


# ---------------------------------------------------------------------------
# Per-layer window schedule (hybrid / windowed archs).
# ---------------------------------------------------------------------------
def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """(L,) int32: 0 = full causal, else sliding-window size for that layer."""
    w = jnp.full((cfg.n_layers,), cfg.window, jnp.int32)
    if cfg.global_layers:
        w = w.at[jnp.array(cfg.global_layers)].set(0)
    return w


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------
def _ffn_apply(p, x, cfg, mesh=None):
    if cfg.moe is not None:
        return moe_mod.moe_ffn(p, x, cfg, mesh=mesh)
    if cfg.mlp_kind == "gelu":
        return gelu_mlp(x, p["w1"], p["b1"], p["w2"], p["b2"]), 0.0
    return swiglu(x, p["w_gate"], p["w_up"], p["w_down"]), 0.0


def _block_apply(p, x, cfg, *, positions, window, ssd_backend="ref",
                 enc_kv=None, collect_cache: bool = False, mesh=None,
                 cache_quantized: bool = True, flash_resid_dtype=None):
    cache_entry = {}
    h = rms_norm(x, p["ln1"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
    if cfg.mixer == "attn":
        if cfg.mla is not None:
            mix, (lat, kr) = attn.mla_block(p["attn"], h, cfg,
                                            positions=positions)
            if collect_cache:
                cache_entry = {"mla_lat": lat,
                               "mla_rope": jnp.swapaxes(kr[:, :, 0], 1, 2)}
        else:
            mix, (k, v) = attn.attn_block(p["attn"], h, cfg,
                                          positions=positions,
                                          layer_window=window, mesh=mesh,
                                          flash_resid_dtype=flash_resid_dtype)
            if collect_cache:
                cache_entry = _kv_entry(k, v, cfg, mesh,
                                        quantized=cache_quantized)
    elif cfg.mixer == "ssm":
        mix, st = ssm_mod.ssm_block(p["ssm"], h, cfg, ssd_backend=ssd_backend,
                                    return_state=collect_cache)
        if collect_cache:
            cache_entry = st
    else:  # hybrid: parallel attention + SSM heads, norm-and-average fusion
        a_out, (k, v) = attn.attn_block(p["attn"], h, cfg, positions=positions,
                                        layer_window=window, mesh=mesh,
                                        flash_resid_dtype=flash_resid_dtype)
        s_out, st = ssm_mod.ssm_block(p["ssm"], h, cfg, ssd_backend=ssd_backend,
                                      return_state=collect_cache)
        if collect_cache:
            cache_entry = {**_kv_entry(k, v, cfg, mesh,
                                       quantized=cache_quantized), **st}
        mix = 0.5 * (rms_norm(a_out, p["mix_norm_attn"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
                     + rms_norm(s_out, p["mix_norm_ssm"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad))
    x = x + _checkpoint_name(mix, "attn_out")
    if enc_kv is not None:
        hx = rms_norm(x, p["ln_x"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
        x = x + attn.cross_attn_block(p["xattn"], hx, enc_kv, cfg)
    if "ffn" not in p:                       # pure-SSM blocks have no MLP
        return x, 0.0, cache_entry
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
    ffn_out, aux = _ffn_apply(p["ffn"], h2, cfg, mesh=mesh)
    return x + _checkpoint_name(ffn_out, "ffn_out"), aux, \
        cache_entry


def _run_encoder(params, cfg, frames, policy: Policy):
    """Whisper-style encoder over precomputed (stub) frame embeddings."""
    x = frames.astype(policy.compute_dtype)
    b, se, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(se)[None], (b, se))

    # python loop (encoder stacks are shallow): every layer appears in the
    # HLO, so dry-run cost analysis counts the encoder exactly.
    n_enc = jax.tree_util.tree_leaves(params["enc_blocks"])[0].shape[0]
    for i in range(n_enc):
        p_layer = jax.tree_util.tree_map(lambda a: a[i], params["enc_blocks"])
        h = rms_norm(x, p_layer["ln1"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
        a_out, _ = attn.attn_block(p_layer["attn"], h, cfg, positions=pos,
                                   causal=False)  # bidirectional encoder
        x = x + a_out
        h2 = rms_norm(x, p_layer["ln2"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
        f, _ = _ffn_apply(p_layer["ffn"], h2, dataclass_no_moe(cfg))
        x = x + f

    return rms_norm(x, params["enc_norm"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)


def forward(params, cfg: ModelConfig, batch: dict, *,
            policy: Policy = Policy.full(),
            remat: CheckpointConfig = CheckpointConfig(),
            ssd_backend: str = "ref", build_cache: bool = False,
            cache_quantized: bool = True, scan_unroll: int = 1, mesh=None,
            return_hidden: bool = False):
    """batch: {tokens (B,S)[, positions, frames (B,Se,D), patches (B,Sp,D)]}.

    Returns (logits (B, S, V) in policy.output_dtype, aux dict).  With
    ``build_cache`` (serving prefill) aux carries a decode cache positioned
    at S, in the ``init_cache`` layout (int8-quantized when requested).
    ``return_hidden`` skips the LM head (chunked-CE path in loss_fn).

    ``remat`` is the single S-C entry point: a plan-bearing
    ``CheckpointConfig`` (``remat.plan`` from ``repro.plan``) applies
    profile-solved, possibly non-uniform segment boundaries to the block
    scan; ``segment_size`` is the uniform fallback.  The plan is validated
    against ``cfg.n_layers`` inside ``remat_scan``.
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    params = policy.cast_to_compute(params)

    x = params["embed"][tokens]                             # (B, S, D)
    if cfg.family == "vlm" and "patches" in batch:
        # stub frontend: precomputed patch embeddings occupy the prefix
        patches = batch["patches"].astype(x.dtype) @ params["patch_proj"]
        sp = patches.shape[1]
        x = jnp.concatenate([patches, x[:, sp:]], axis=1)

    if "positions" in batch:
        positions = batch["positions"]
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(positions[None], (3, b, s))

    enc_kv = None
    if cfg.encoder is not None:
        enc_out = _run_encoder(params, cfg, batch["frames"], policy)
        # precompute cross K/V once (shared by all decoder layers' xattn via
        # per-layer projections — so pass encoder output and project inside).
        enc_kv = enc_out

    # Uniform window schedules (no per-layer overrides) pass the window as
    # a STATIC python int so attn_block can dispatch to the flash kernel
    # (its gate requires a non-traced window); hybrid archs with
    # global-layer overrides scan the (L,) window array and take the jnp
    # attention path — the documented fallback.
    static_window = int(cfg.window) if not cfg.global_layers else None
    windows = None if static_window is not None else layer_windows(cfg)

    def make_body(bcfg):
        def body(carry, xs):
            if static_window is None:
                p_layer, win = xs
            else:
                p_layer, win = xs, static_window
            ekv = None
            if enc_kv is not None:
                hkv, hd = cfg.n_kv, cfg.head_dim
                bb, se, _ = enc_kv.shape
                k = (enc_kv @ p_layer["xattn"]["wk"]).reshape(bb, se, hkv, hd)
                v = (enc_kv @ p_layer["xattn"]["wv"]).reshape(bb, se, hkv, hd)
                ekv = (k, v)
            out, aux, entry = _block_apply(
                p_layer, carry, bcfg, positions=positions, window=win,
                ssd_backend=ssd_backend, enc_kv=ekv,
                collect_cache=build_cache, mesh=mesh,
                cache_quantized=cache_quantized,
                flash_resid_dtype=policy.flash_resid_dtype)
            return out, (aux, entry)
        return body

    if cfg.dense_layers:
        # the leading dense layers: their own stack, before the MoE scan
        x, (_, dense_entries) = jax.lax.scan(
            make_body(dataclass_no_moe(cfg)), x, params["dense_blocks"])
    x, (auxes, entries) = remat_scan(
        make_body(cfg), x,
        params["blocks"] if static_window is not None
        else (params["blocks"], windows),
        config=remat, unroll=scan_unroll)
    if cfg.dense_layers and build_cache:
        entries = {k: jnp.concatenate([dense_entries[k], v])
                   for k, v in entries.items()}
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
    aux_out = {"moe_aux": jnp.mean(auxes) if cfg.moe is not None else 0.0}
    if build_cache:
        aux_out["cache"] = _assemble_cache(cfg, entries, s,
                                           quantized=cache_quantized)
    if return_hidden:
        return x, aux_out
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head).astype(policy.output_dtype)
    logits = _mask_padded_vocab(logits, cfg)
    return logits, aux_out


def head_logits(params, cfg: ModelConfig, x, *,
                policy: Policy = Policy.full()):
    """LM-head logits of final hidden rows ``x`` (``forward(...,
    return_hidden=True)``'s output, or rows of it)."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head.astype(policy.compute_dtype)).astype(
        policy.output_dtype)
    return _mask_padded_vocab(logits, cfg)


def _assemble_cache(cfg: ModelConfig, entries: dict, s: int, *,
                    quantized: bool) -> dict:
    """Stacked per-layer prefill outputs -> init_cache layout, pos = S."""
    cache: dict[str, Any] = {"pos": jnp.int32(s)}
    if "k" in entries:
        # entries are per-layer quantized + laid out by _kv_entry already:
        # stacked to (L, B, Hkv, S, hd) by the scan
        cache.update(k=entries["k"], k_scale=entries["k_scale"],
                     v=entries["v"], v_scale=entries["v_scale"])
    if "mla_lat" in entries:
        cache.update(mla_lat=entries["mla_lat"].astype(jnp.bfloat16),
                     mla_rope=entries["mla_rope"].astype(jnp.bfloat16))
    if "ssm" in entries:
        cache.update(ssm=entries["ssm"].astype(jnp.float32),
                     conv=entries["conv"].astype(jnp.bfloat16))
    return cache


def loss_fn(params, cfg: ModelConfig, batch: dict, *,
            policy: Policy = Policy.full(),
            remat: CheckpointConfig = CheckpointConfig(),
            ssd_backend: str = "ref", moe_aux_weight: float = 0.01,
            scan_unroll: int = 1, mesh=None, ce_chunk: int = 0):
    labels = batch["labels"]
    mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
    hidden, aux = forward(params, cfg, batch, policy=policy, remat=remat,
                          ssd_backend=ssd_backend, scan_unroll=scan_unroll,
                          mesh=mesh, return_hidden=True)
    with jax.named_scope("lm_head_loss"):
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(policy.compute_dtype)
        if ce_chunk > 0:
            # Chunked CE (perf iteration): the LM head + softmax runs per
            # sequence chunk under remat, so the (B, S, V) logits never
            # materialize — peak is (B, chunk, V) + recompute in bwd.
            @jax.checkpoint
            def chunk_nll(x_c, lab_c, mask_c):
                logits = _mask_padded_vocab(
                    (x_c @ head).astype(jnp.float32), cfg)
                m = jax.lax.stop_gradient(logits.max(-1, keepdims=True))
                shifted = logits - m
                lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
                vi = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
                ll = jnp.sum(jnp.where(vi == lab_c[..., None], shifted,
                                       0.0), -1)
                return ((lse - ll) * mask_c).sum()

            s = hidden.shape[1]
            n_chunks = -(-s // ce_chunk)
            total = jnp.float32(0)
            for c in range(n_chunks):
                sl = slice(c * ce_chunk, (c + 1) * ce_chunk)
                total += chunk_nll(hidden[:, sl], labels[:, sl],
                                   mask[:, sl])
            loss = total / jnp.maximum(mask.sum(), 1.0)
        else:
            logits = _mask_padded_vocab(
                (hidden @ head).astype(policy.output_dtype), cfg)
            # Sharding-friendly CE: never gathers the (model-sharded)
            # vocab dim.  label logit via a masked sum (iota compare
            # shards cleanly; a take_along_axis gather would force an
            # all-gather of the logits).
            logits32 = logits.astype(jnp.float32)
            m = jax.lax.stop_gradient(logits32.max(-1, keepdims=True))
            shifted = logits32 - m
            lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
            vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                                  logits.ndim - 1)
            label_logit = jnp.sum(
                jnp.where(vocab_iota == labels[..., None], shifted, 0.0),
                axis=-1)
            nll = lse - label_logit
            loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    if cfg.moe is not None:
        loss = loss + moe_aux_weight * aux["moe_aux"]
    return loss, {"nll": loss, **aux}


# ---------------------------------------------------------------------------
# KV / state cache and single-token decode.
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, s_max: int, *,
               quantized: bool = True, dtype=jnp.bfloat16) -> dict:
    L = cfg.n_layers
    cache: dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.mixer in ("attn", "hybrid"):
        if cfg.mla is not None:
            m = cfg.mla
            # the rope keys lie sequence-minor, (L, B, dr, S): a 64-wide
            # minor axis would be laid out transposed on a TPU anyway
            cache["mla_lat"] = jnp.zeros((L, batch, s_max, m.kv_lora_rank), dtype)
            cache["mla_rope"] = jnp.zeros((L, batch, m.qk_rope_dim, s_max),
                                          dtype)
        else:
            hkv, hd = cfg.n_kv, cfg.head_dim
            kv_dtype = jnp.int8 if quantized else dtype
            cache["k"] = jnp.zeros((L, batch, hkv, s_max, hd), kv_dtype)
            cache["v"] = jnp.zeros((L, batch, hkv, s_max, hd), kv_dtype)
            cache["k_scale"] = jnp.zeros((L, batch, hkv, s_max), jnp.float32)
            cache["v_scale"] = jnp.zeros((L, batch, hkv, s_max), jnp.float32)
    if cfg.mixer in ("ssm", "hybrid"):
        s = cfg.ssm
        conv_dim = s.d_inner + 2 * s.d_state
        cache["conv"] = jnp.zeros((L, batch, s.conv_kernel - 1, conv_dim), dtype)
        cache["ssm"] = jnp.zeros((L, batch, s.heads, s.d_state, s.head_p),
                                 jnp.float32)
    return cache


#: cache leaves with a sequence axis, and which axis it is — the single
#: source for growing / scattering caches (serve pool, prefill prealloc).
CACHE_SEQ_AXES = {"k": 3, "v": 3, "k_scale": 3, "v_scale": 3,
                  "mla_lat": 2, "mla_rope": 3}


def grow_cache(cache: dict, s_max: int) -> dict:
    """Zero-pad every sequence-bearing cache leaf out to ``s_max`` slots.

    Replaces the post-hoc ``tree_map_with_path`` pad the serve driver used
    to apply OUTSIDE the jit: growing inside the prefill step means the
    decode cache is preallocated at its final length in one compiled
    program and no second buffer materializes at the host boundary.
    SSM/conv state and ``pos`` have no sequence axis and pass through.
    """
    out = dict(cache)
    for name, ax in CACHE_SEQ_AXES.items():
        if name not in cache:
            continue
        x = cache[name]
        pad = s_max - x.shape[ax]
        if pad < 0:
            raise ValueError(f"grow_cache: {name} already has "
                             f"{x.shape[ax]} > {s_max} slots")
        if pad:
            out[name] = jnp.pad(
                x, [(0, pad) if i == ax else (0, 0) for i in range(x.ndim)])
    return out


def decode_step(params, cfg: ModelConfig, cache: dict, tokens_t, *,
                policy: Policy = Policy.full(), quantized: bool = True,
                kvq_backend: str = "ref", kvq_splits: int = 1, enc_out=None,
                active=None, scan_unroll: int = 1, mesh=None):
    """tokens_t: (B,) int32 current token.  Returns (logits (B,V), cache).

    Uniform window schedules pass the window as a STATIC python int (same
    gate as ``forward``), so ``attn_decode`` can take the lengths-aware
    kvq path — per-batch lengths + split-K tile skipping instead of a
    dense (B, S) bias; per-layer overrides (``cfg.global_layers``, as in
    hymba) scan a traced window and take the dense (B, S) bias fallback.

    Slot-pooled serving (``repro.serve``): when ``cache['pos']`` is a
    per-row (B,) vector, every row decodes at its OWN position — RoPE,
    cache write, and length mask are all per-row, so one compiled step
    serves a ragged pool of in-flight requests.  ``active`` ((B,) bool)
    then gates the position increment: inactive (free) slots stay frozen
    instead of drifting, and their lengths clamp to >= 1 so the masked
    softmax never normalizes over an empty row (their logits are garbage
    by contract and never read).  Occupancy is pure data — joining or
    retiring a request never changes a traced shape, hence no recompile.
    Per slot, the stacked cache rides the layer scan's carry and each
    layer writes its token and reads its layer of the cache in place:
    ``_decode_slots_gqa`` (int8 K/V) and ``_decode_slots_mla`` (latents).
    Of the per-slot rounds, only the mesh's sequence-sharded int8 pool
    keeps the per-layer scan below (the lockstep one), for its
    write+flash-combine collective.
    """
    params = policy.cast_to_compute(params)
    pos = cache["pos"]
    per_slot = getattr(pos, "ndim", 0) == 1
    if per_slot and cfg.mixer != "attn":
        raise NotImplementedError(
            "per-slot decode (vector cache['pos']) is only supported for "
            "attention caches (the kvq layout or MLA latents); SSM/hybrid "
            "archs serve through the scalar-pos paths")
    if active is not None and not per_slot:
        raise ValueError("decode_step: active mask requires a per-slot "
                         "(vector) cache['pos']")
    if per_slot and cfg.mla is not None:
        return _decode_slots_mla(params, cfg, cache, tokens_t, policy=policy,
                                 backend=kvq_backend, active=active,
                                 scan_unroll=scan_unroll)
    if cfg.dense_layers:
        raise NotImplementedError("decode_step: leading dense layers are "
                                  "served by the per-slot MLA path only")
    # per-slot pos is >= 0 by construction (pool zeros / scatter lengths),
    # so lengths = pos+1 >= 1 and every row's softmax normalizer is
    # non-empty on every backend — free slots never produce NaNs
    static_window = int(cfg.window) if not cfg.global_layers else None
    windows = None if static_window is not None else layer_windows(cfg)

    # mesh-aware cache layout (serve pool): "heads" runs the kernel per
    # device on its kv heads, "seq" switches attn_decode to the
    # write+flash-combine collective
    kv_shard = "none"
    if mesh is not None and "k" in cache and cfg.mla is None:
        from repro.distributed import sharding as shd
        kv_shard = shd.serve_kv_shard(mesh, cfg.n_kv, cache["k"].shape[3])
    if per_slot and not (kv_shard == "seq" and quantized):
        if cfg.encoder is not None:
            raise NotImplementedError(
                "per-slot decode (vector cache['pos']) serves decoder-only "
                "stacks; encoder-decoder archs serve through the "
                "scalar-pos path")
        return _decode_slots_gqa(
            params, cfg, cache, tokens_t, policy=policy,
            window=static_window, windows=windows, quantized=quantized,
            backend=kvq_backend, splits=kvq_splits, active=active,
            scan_unroll=scan_unroll, mesh=mesh, kv_shard=kv_shard)
    x = params["embed"][tokens_t]                           # (B, D)

    layer_caches = {k: v for k, v in cache.items() if k != "pos"}

    def body(carry, xs):
        p_layer, lc = xs["p"], xs["c"]
        win = static_window if static_window is not None else xs["w"]
        x = carry
        h = rms_norm(x[:, None], p_layer["ln1"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)[:, 0]
        new_lc = dict(lc)
        if cfg.mixer in ("attn", "hybrid") and cfg.mla is not None:
            mix, (cl, cr) = attn.mla_decode(p_layer["attn"], h, cfg,
                                            lc["mla_lat"], lc["mla_rope"], pos)
            new_lc.update(mla_lat=cl, mla_rope=cr)
        elif cfg.mixer in ("attn", "hybrid"):
            mix, (ck, csk, cv, csv) = attn.attn_decode(
                p_layer["attn"], h, cfg, lc["k"], lc["k_scale"], lc["v"],
                lc["v_scale"], pos, window=win, quantized=quantized,
                backend=kvq_backend, splits=kvq_splits, mesh=mesh,
                kv_shard=kv_shard)
            new_lc.update(k=ck, k_scale=csk, v=cv, v_scale=csv)
        if cfg.mixer == "ssm":
            mix, nconv, nssm = ssm_mod.ssm_decode_step(
                p_layer["ssm"], h, cfg, lc["conv"], lc["ssm"])
            new_lc.update(conv=nconv, ssm=nssm)
        elif cfg.mixer == "hybrid":
            s_mix, nconv, nssm = ssm_mod.ssm_decode_step(
                p_layer["ssm"], h, cfg, lc["conv"], lc["ssm"])
            new_lc.update(conv=nconv, ssm=nssm)
            mix = 0.5 * (
                rms_norm(mix[:, None], p_layer["mix_norm_attn"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)[:, 0]
                + rms_norm(s_mix[:, None], p_layer["mix_norm_ssm"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)[:, 0])
        x = x + mix
        if cfg.encoder is not None:
            hx = rms_norm(x[:, None], p_layer["ln_x"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
            hkv, hd = cfg.n_kv, cfg.head_dim
            bb, se, _ = enc_out.shape
            k = (enc_out @ p_layer["xattn"]["wk"]).reshape(bb, se, hkv, hd)
            v = (enc_out @ p_layer["xattn"]["wv"]).reshape(bb, se, hkv, hd)
            x = x + attn.cross_attn_block(p_layer["xattn"], hx, (k, v), cfg)[:, 0]
        if "ffn" in p_layer:
            h2 = rms_norm(x[:, None], p_layer["ln2"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
            ffn_out, _ = _ffn_apply(p_layer["ffn"], h2, cfg, mesh=mesh)
            x = x + ffn_out[:, 0]
        return x, new_lc

    xs = {"p": params["blocks"], "c": layer_caches}
    if static_window is None:
        xs["w"] = windows
    with jax.named_scope("layers"):
        x, new_caches = jax.lax.scan(body, x, xs, unroll=scan_unroll)
    with jax.named_scope("lm_head"):
        x = rms_norm(x[:, None], params["final_norm"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)[:, 0]
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = _mask_padded_vocab((x @ head).astype(policy.output_dtype), cfg)
    if active is not None:
        new_caches["pos"] = pos + active.astype(jnp.int32)
    else:
        new_caches["pos"] = pos + 1
    return logits, new_caches


def _decode_slots(params, cfg: ModelConfig, cache: dict, tokens_t, names,
                  attend, *, policy: Policy, active, scan_unroll: int,
                  mesh=None):
    """Per-slot decode over the slot pool's stacked cache.

    The cache leaves ``names`` ride the layer scan's carry whole, and each
    layer's ``attend(p_attn, h, leaves, layer) -> (mix, leaves)`` writes
    its token and reads its layer of them in place: no layer's slice of
    the cache is scanned in or out, so the round makes no copy of the
    cache.  Leading dense layers (``cfg.dense_layers``) scan first, over
    the first layers of the same cache.  Rows that ``active`` marks free
    keep their position."""
    pos = cache["pos"]
    x = params["embed"][tokens_t]                           # (B, D)

    def make_body(lcfg):
        def body(carry, xs):
            x, leaves = carry[0], carry[1:]
            p_layer, layer = xs
            h = rms_norm(x[:, None], p_layer["ln1"], cfg.norm_eps,
                         bf16_grad=cfg.norm_bf16_grad)[:, 0]
            mix, leaves = attend(p_layer["attn"], h, leaves, layer)
            x = x + mix
            if "ffn" in p_layer:
                h2 = rms_norm(x[:, None], p_layer["ln2"], cfg.norm_eps,
                              bf16_grad=cfg.norm_bf16_grad)
                ffn_out, _ = _ffn_apply(p_layer["ffn"], h2, lcfg, mesh=mesh)
                x = x + ffn_out[:, 0]
            return (x, *leaves), None
        return body

    carry = (x, *(cache[n] for n in names))
    n_dense = cfg.dense_layers
    with jax.named_scope("layers"):
        if n_dense:
            carry, _ = jax.lax.scan(
                make_body(dataclass_no_moe(cfg)), carry,
                (params["dense_blocks"], jnp.arange(n_dense)))
        carry, _ = jax.lax.scan(
            make_body(cfg), carry,
            (params["blocks"], jnp.arange(n_dense, cfg.n_layers)),
            unroll=scan_unroll)
    x = carry[0]
    with jax.named_scope("lm_head"):
        x = rms_norm(x[:, None], params["final_norm"], cfg.norm_eps,
                     bf16_grad=cfg.norm_bf16_grad)[:, 0]
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = _mask_padded_vocab((x @ head).astype(policy.output_dtype),
                                    cfg)
    step = 1 if active is None else active.astype(jnp.int32)
    return logits, {"pos": pos + step, **dict(zip(names, carry[1:]))}


def _decode_slots_gqa(params, cfg: ModelConfig, cache: dict, tokens_t, *,
                      policy: Policy, window, windows, quantized: bool,
                      backend: str, splits: int, active, scan_unroll: int,
                      mesh, kv_shard: str):
    """Per-slot decode of a GQA stack over the slot pool's K/V cache
    (``attention.attn_decode_slots``).  ``window`` is the static window,
    or None with ``windows`` the per-layer ones.  Every row, free ones
    too, writes its token at its position and attends over its first
    ``pos + 1`` positions."""
    pos = cache["pos"]

    def attend(p_attn, h, leaves, layer):
        win = window if window is not None else windows[layer]
        return attn.attn_decode_slots(
            p_attn, h, cfg, *leaves, layer, pos, window=win,
            quantized=quantized, backend=backend, splits=splits, mesh=mesh,
            kv_shard=kv_shard)

    return _decode_slots(params, cfg, cache, tokens_t,
                         ("k", "k_scale", "v", "v_scale"), attend,
                         policy=policy, active=active,
                         scan_unroll=scan_unroll, mesh=mesh)


def _decode_slots_mla(params, cfg: ModelConfig, cache: dict, tokens_t, *,
                      policy: Policy, backend: str, active, scan_unroll: int):
    """Per-slot decode of an MLA stack over the slot pool's latent cache
    (``attention.mla_decode_slots``).  Rows that ``active`` marks free
    read one position."""
    pos = cache["pos"]
    lengths = pos + 1 if active is None else jnp.where(active, pos + 1, 1)

    def attend(p_attn, h, leaves, layer):
        mix, lat, rope = attn.mla_decode_slots(
            p_attn, h, cfg, *leaves, layer, pos, lengths, backend=backend)
        return mix, (lat, rope)

    return _decode_slots(params, cfg, cache, tokens_t,
                         ("mla_lat", "mla_rope"), attend, policy=policy,
                         active=active, scan_unroll=scan_unroll)
