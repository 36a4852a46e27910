"""Attention: GQA with full / sliding-window masks, memory-efficient chunked
softmax for long prefill, MLA (multi-head latent attention), and cached
decode paths (optionally over an int8-quantized cache via kernels/kvq)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.kvq import ops as kvq_ops
from repro.models.layers import apply_rope, rms_norm

NEG_INF = -1e30
CHUNKED_THRESHOLD = 4096   # S*S f32 scores above this use the chunked path
KV_CHUNK = 1024


def _mask_bias(q_pos, k_pos, window, dtype):
    """(..., Sq, Sk) additive bias: causal + optional sliding window.

    ``window`` may be a python int or a traced scalar (hybrid archs switch
    window/global per layer inside a scan); window <= 0 means full causal.
    """
    dist = q_pos[..., :, None] - k_pos[..., None, :]
    ok = dist >= 0
    if isinstance(window, int):
        if window > 0:
            ok &= dist < window
    else:
        ok &= jnp.where(window > 0, dist < window, True)
    return jnp.where(ok, 0.0, NEG_INF).astype(dtype)


def gqa_attention(q, k, v, *, q_pos, k_pos, window: int = 0,
                  causal: bool = True, sm_scale: Optional[float] = None):
    """q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D) -> (B, Sq, H, D).

    Uses a one-shot einsum for short sequences and a KV-chunked
    online-softmax scan (flash-style, O(Sq * chunk) live scores) for long
    ones — the S-C idea (recompute over store) applied to attention scores.
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // hkv
    scale = sm_scale if sm_scale is not None else d ** -0.5
    qg = q.reshape(b, sq, hkv, g, d)

    if sq * sk <= CHUNKED_THRESHOLD ** 2 // 4 or sk <= KV_CHUNK:
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        if causal:
            logits += _mask_bias(q_pos, k_pos, window, jnp.float32
                                 )[:, None, None]
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
        return out.reshape(b, sq, h, dv).astype(q.dtype)

    # ---- chunked path (python loop: unrolled in HLO so dry-run cost
    # analysis counts every chunk; XLA's buffer allocator still reuses the
    # per-chunk score buffers, keeping live scores O(Sq x chunk)) ----
    nchunk = -(-sk // KV_CHUNK)
    pad = nchunk * KV_CHUNK - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=2 ** 30)

    m = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, hkv, g, sq), jnp.float32)
    acc = jnp.zeros((b, hkv, g, sq, dv), jnp.float32)
    qf = qg.astype(jnp.float32)
    for c in range(nchunk):
        sl = slice(c * KV_CHUNK, (c + 1) * KV_CHUNK)
        kc, vc, pc = k[:, sl], v[:, sl], k_pos[:, sl]
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf,
                            kc.astype(jnp.float32)) * scale
        if causal:
            logits += _mask_bias(q_pos, pc, window, jnp.float32)[:, None, None]
        m_new = jnp.maximum(m, logits.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vc.astype(jnp.float32))
        l = l * alpha + p.sum(-1)
        m = m_new
    out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,Hkv,G,Sq,Dv)
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, h, dv)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Standard GQA block (projections + rope + attention).
# ---------------------------------------------------------------------------
def attn_block(p, x, cfg, *, positions, window: int = 0, layer_window=None,
               causal: bool = True, mesh=None, flash_resid_dtype=None):
    """x: (B, S, D_model).  p holds wq/wk/wv/wo.  Returns (out, (k, v)).

    ``flash_resid_dtype`` is the mixed-precision policy for the flash
    custom_vjp's saved (q, k, v, o) residuals (see Policy.flash_resid_dtype);
    it only matters on the flash branch — jnp autodiff owns its own
    residuals."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    k = (x @ p["wk"]).reshape(b, s, hkv, hd)
    v = (x @ p["wv"]).reshape(b, s, hkv, hd)
    # NOTE (tried & refuted, EXPERIMENTS §Perf): forcing MQA-style TP here
    # (q head-sharded, k/v replicated) when kv-heads don't divide the model
    # axis made llama3/glm4 15% MORE collective-bound — XLA's own hybrid
    # layout beats forced replication.  The deployed fix for mismatched
    # head counts is a per-arch mesh shape (TP width divides kv-heads;
    # e.g. granite trains on (32, 8): collective 7502 -> 538 ms).
    rope_pos = positions
    q = apply_rope(q, rope_pos, cfg.rope_theta, cfg.rope_fraction,
                   cfg.mrope_sections)
    k = apply_rope(k, rope_pos, cfg.rope_theta, cfg.rope_fraction,
                   cfg.mrope_sections)
    pos1d = positions[0] if positions.ndim == 3 else positions
    w = window if layer_window is None else layer_window
    impl = cfg.attn_impl()
    if (impl != "jnp" and causal and isinstance(w, int)
            and positions.ndim < 3):
        # Pallas flash kernel (prefill/training hot path) — differentiable
        # via its custom_vjp with O(S*D) residuals, so this branch is legal
        # under jax.grad.  Traced per-layer windows (hybrid scan) and
        # M-RoPE take the jnp paths below.
        from repro.kernels.flash import ops as flash_ops
        fa = functools.partial(
            flash_ops.flash_attention, causal=True, window=w,
            backend=impl, resid_dtype=flash_resid_dtype)
        if mesh is not None:
            # shard_map over (data, model): batch rows and whole GQA groups
            # stay shard-local, so each device runs the UNCHANGED kernel on
            # its slice — no XLA partitioning decisions inside the kernel,
            # and the custom_vjp residuals are per-device by construction.
            # flash_shard_specs is None when the mesh can't split cleanly
            # (then the unsharded dispatch below lets XLA place it).
            from repro.distributed import sharding as shd
            spec = shd.flash_shard_specs(mesh, b, h, hkv)
            if spec is not None:
                fa = jax.shard_map(fa, mesh=mesh,
                                   in_specs=(spec, spec, spec),
                                   out_specs=spec, check_vma=False)
        out = fa(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                 jnp.swapaxes(v, 1, 2))
        out = jnp.swapaxes(out, 1, 2)
    else:
        out = gqa_attention(q, k, v, q_pos=pos1d, k_pos=pos1d, window=w,
                            causal=causal)
    return out.reshape(b, s, h * hd) @ p["wo"], (k, v)


def cross_attn_block(p, x, enc_kv, cfg):
    """Decoder cross-attention over precomputed encoder K/V (no rope)."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    k, v = enc_kv                                  # (B, Se, Hkv, hd)
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    se = k.shape[1]
    g = h // hkv
    qg = q.reshape(b, s, hkv, g, hd).astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    logits = logits * hd ** -0.5
    pr = jax.nn.softmax(logits, -1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", pr, v.astype(jnp.float32))
    out = out.reshape(b, s, h * hd).astype(x.dtype)
    return out @ p["wo"]


# ---------------------------------------------------------------------------
# MLA (MiniCPM3 / DeepSeek-V2 style multi-head latent attention).
# ---------------------------------------------------------------------------
def mla_block(p, x, cfg, *, positions):
    """Latent-compressed attention; returns (out, (kv_latent, k_rope))."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim

    q_lat = rms_norm(x @ p["q_a"], p["q_a_norm"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
    q = (q_lat @ p["q_b"]).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]

    kv_all = x @ p["kv_a"]                               # (B,S,kv_lora+dr)
    kv_lat = rms_norm(kv_all[..., : m.kv_lora_rank], p["kv_a_norm"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
    k_rope = kv_all[..., m.kv_lora_rank:].reshape(b, s, 1, dr)

    kv = (kv_lat @ p["kv_b"]).reshape(b, s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]

    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    qf = jnp.concatenate([q_nope, q_rope], -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))], -1)
    out = gqa_attention(qf, kf, v, q_pos=positions, k_pos=positions,
                        sm_scale=(dn + dr) ** -0.5)
    out = out.reshape(b, s, h * dv)
    return out @ p["wo"], (kv_lat, k_rope)


def mla_decode(p, x_t, cfg, cache_lat, cache_rope, pos):
    """One-token MLA decode with weight absorption.

    The latent cache stores only (kv_lora + rope_dim) floats/token — MLA's
    whole point.  Scores and outputs are computed in latent space:
      score = (q_nope @ Wk_b) . kv_lat + q_rope . k_rope
      out   = (softmax . kv_lat) @ Wv_b
    cache_lat: (B, S, kv_lora); cache_rope: (B, S, dr); pos scalar.
    """
    m = cfg.mla
    b = x_t.shape[0]
    h = cfg.n_heads
    dn, dr, dv = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim
    s_max = cache_lat.shape[1]

    q_lat = rms_norm(x_t @ p["q_a"], p["q_a_norm"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
    q = (q_lat @ p["q_b"]).reshape(b, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    pos_arr = jnp.full((b, 1), pos, jnp.int32)
    q_rope = apply_rope(q_rope[:, None], pos_arr, cfg.rope_theta)[:, 0]

    kv_all = x_t @ p["kv_a"]
    lat_new = rms_norm(kv_all[..., : m.kv_lora_rank], p["kv_a_norm"], cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
    kr_new = apply_rope(kv_all[..., m.kv_lora_rank:][:, None, None],
                        pos_arr, cfg.rope_theta)[:, 0, 0]

    cl = jax.lax.dynamic_update_slice(
        cache_lat, lat_new[:, None].astype(cache_lat.dtype), (0, pos, 0))
    cr = jax.lax.dynamic_update_slice(
        cache_rope, kr_new[:, None].astype(cache_rope.dtype), (0, pos, 0))

    kv_b = p["kv_b"].reshape(m.kv_lora_rank, h, dn + dv)
    wk_b, wv_b = kv_b[..., :dn], kv_b[..., dn:]
    q_abs = jnp.einsum("bhd,lhd->bhl", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    scores = jnp.einsum("bhl,bsl->bhs", q_abs, cl.astype(jnp.float32))
    scores += jnp.einsum("bhd,bsd->bhs", q_rope.astype(jnp.float32),
                         cr.astype(jnp.float32))
    scores = scores * (dn + dr) ** -0.5
    valid = jnp.arange(s_max)[None, :] <= pos
    scores = jnp.where(valid[:, None], scores, NEG_INF)
    pr = jax.nn.softmax(scores, -1)
    o_lat = jnp.einsum("bhs,bsl->bhl", pr, cl.astype(jnp.float32))
    out = jnp.einsum("bhl,lhd->bhd", o_lat, wv_b.astype(jnp.float32))
    out = out.reshape(b, h * dv).astype(x_t.dtype)
    return out @ p["wo"], (cl, cr)


# ---------------------------------------------------------------------------
# Cached single-token decode.
# ---------------------------------------------------------------------------
def _write_token(cache, new, at):
    """Write one token into the S axis of a per-layer cache leaf.

    cache: (B, Hkv, S, hd) or (B, Hkv, S); new: (B, Hkv, hd) / (B, Hkv);
    at: scalar int32 (lockstep batch — every row writes the same slot) or
    (B,) int32 (slot-pooled serving — each row writes at its own length).
    The vector case lowers to a per-row dynamic_update_slice under vmap
    (a scatter), keeping the write O(1) in S instead of a full-cache
    ``where`` rewrite.
    """
    if at.ndim == 0:
        return jax.lax.dynamic_update_slice(
            cache, new[:, :, None], (0, 0, at, 0)[:cache.ndim])
    if cache.ndim == 4:
        return jax.vmap(lambda c, n, a: jax.lax.dynamic_update_slice(
            c, n[:, None], (0, a, 0)))(cache, new, at)
    return jax.vmap(lambda c, n, a: jax.lax.dynamic_update_slice(
        c, n[:, None], (0, a)))(cache, new, at)


def attn_decode(p, x_t, cfg, cache_k, cache_s_k, cache_v, cache_s_v, pos,
                *, window: int = 0, quantized: bool = True, backend: str = "ref",
                splits: int = 1, rolling: bool = False, mesh=None,
                kv_shard: str = "none"):
    """One-token GQA decode against a (possibly int8) cache.

    x_t: (B, D_model); cache_k/v: (B, Hkv, S, hd) int8 (or bf16 when not
    quantized, scales ignored); pos: scalar int32 current position, or a
    per-row (B,) int32 vector for slot-pooled continuous batching
    (``repro.serve``) — each batch row then RoPE-rotates, writes, and
    masks at its OWN position, so one jitted step serves a ragged pool of
    in-flight requests with static shapes.
    ``rolling``: the cache is a circular window buffer of size S — writes
    land at ``pos % S`` and every filled slot is in-window by construction
    (two-tier cache for windowed layers; EXPERIMENTS §Perf).

    Masking is length-first: rolling buffers and full-causal (static
    window <= 0) schedules pass per-batch ``lengths`` through to
    ``decode_attention`` — the split-K kernel skips fully-padded KV tiles
    and masks the straddling tile with an in-kernel iota compare, and no
    (B, S) f32 bias tensor is built on ANY backend.  Only schedules
    lengths can't express (a window band over a non-rolling cache, or a
    traced per-layer window) fall back to the dense bias.  ``splits``
    selects the kernel's split-K fan-out.

    ``kv_shard`` (from ``sharding.serve_kv_shard``) names how the cache is
    laid out under ``mesh``: under "heads" XLA keeps the per-kv-head
    einsums and token write shard-local, and a kernel backend runs under
    ``shard_map`` over the kv heads (Mosaic calls are not partitionable) —
    while "seq"
    routes through ``collectives.sp_decode_attention_int8`` so the token
    write and softmax run per-shard with one flash-combine, instead of XLA
    re-sharding the cache around a dynamic_update_slice on its sharded
    sequence axis.  "seq" requires a quantized cache (the serve pool's
    only layout).
    Returns (attn_out (B, D_model), new k/v token (B, Hkv, hd)).
    """
    b, _ = x_t.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    s_max = cache_k.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1                       # slot-pooled ragged batch
    q = (x_t @ p["wq"]).reshape(b, 1, h, hd)
    k_t = (x_t @ p["wk"]).reshape(b, 1, hkv, hd)
    v_t = (x_t @ p["wv"]).reshape(b, 1, hkv, hd)
    pos_arr = jnp.broadcast_to(pos[:, None] if per_row else pos, (b, 1))
    if cfg.mrope_sections is not None:
        pos3 = jnp.broadcast_to(pos_arr[None], (3, b, 1))
        q = apply_rope(q, pos3, cfg.rope_theta, cfg.rope_fraction,
                       cfg.mrope_sections)
        k_t = apply_rope(k_t, pos3, cfg.rope_theta, cfg.rope_fraction,
                         cfg.mrope_sections)
    else:
        q = apply_rope(q, pos_arr, cfg.rope_theta, cfg.rope_fraction)
        k_t = apply_rope(k_t, pos_arr, cfg.rope_theta, cfg.rope_fraction)
    q = q[:, 0]                                            # (B, H, hd)
    k_new = k_t[:, 0]
    v_new = v_t[:, 0]

    kv_pos = jnp.arange(s_max)
    pos_col = pos[:, None] if per_row else pos    # broadcasts vs (·, S)
    lengths = bias = None
    if rolling:
        write_at = pos % s_max
        # slot j is filled iff j <= pos (pre-wrap) or always (post-wrap);
        # all filled slots are within the window by construction
        lengths = jnp.broadcast_to(jnp.minimum(pos + 1, s_max), (b,))
    else:
        write_at = pos
        if isinstance(window, int) and window <= 0:
            lengths = jnp.broadcast_to(pos + 1, (b,))      # includes current
        else:
            valid = kv_pos[None, :] <= pos_col             # includes current
            if isinstance(window, int):
                valid &= kv_pos[None, :] > pos_col - window
            else:
                valid &= jnp.where(window > 0,
                                   kv_pos[None, :] > pos_col - window, True)
            bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
            bias = jnp.broadcast_to(bias, (b, s_max))

    if quantized:
        kq_new, ks_new = kvq_ops.quantize_kv(k_new)
        vq_new, vs_new = kvq_ops.quantize_kv(v_new)
        if kv_shard == "seq" and mesh is not None and not rolling:
            from repro.distributed import collectives
            out, ck, csk, cv, csv = collectives.sp_decode_attention_int8(
                q, cache_k, cache_s_k, cache_v, cache_s_v,
                (kq_new, ks_new, vq_new, vs_new),
                jnp.broadcast_to(write_at, (b,)), mesh,
                sm_scale=hd ** -0.5, lengths=lengths, bias=bias)
        else:
            ck = _write_token(cache_k, kq_new, write_at)
            cv = _write_token(cache_v, vq_new, write_at)
            csk = _write_token(cache_s_k, ks_new, write_at)
            csv = _write_token(cache_s_v, vs_new, write_at)
            mask_kw = "lengths" if lengths is not None else "bias"

            def decode(q, ck, csk, cv, csv, mask):
                return kvq_ops.decode_attention(
                    q, ck, csk, cv, csv, backend=backend, splits=splits,
                    **{mask_kw: mask})

            if kv_shard == "heads" and backend != "ref":
                # XLA cannot partition a Mosaic kernel: each device runs it
                # on its own kv heads (whole GQA groups, never split)
                heads = P(None, "model")
                decode = jax.shard_map(
                    decode, mesh=mesh, in_specs=(heads,) * 5 + (P(),),
                    out_specs=heads, check_vma=False)
            out = decode(q, ck, csk, cv, csv,
                         lengths if lengths is not None else bias)
    else:
        ck = _write_token(cache_k, k_new.astype(cache_k.dtype), write_at)
        cv = _write_token(cache_v, v_new.astype(cache_v.dtype), write_at)
        csk, csv = cache_s_k, cache_s_v
        g = h // hkv
        qg = q.reshape(b, hkv, g, hd).astype(jnp.float32)
        # one arithmetic source for the decode mask (lengths iota compare /
        # bias add): shared with the kvq ref oracle so paths can't drift
        from repro.kernels.kvq.ref import masked_decode_logits
        logits = masked_decode_logits(qg, ck.astype(jnp.float32),
                                      hd ** -0.5, bias, lengths)
        pr = jax.nn.softmax(logits, -1)
        out = jnp.einsum("bhgs,bhsd->bhgd", pr, cv.astype(jnp.float32)
                         ).reshape(b, h, hd)
    out = out.reshape(b, h * hd).astype(x_t.dtype)
    return out @ p["wo"], (ck, csk, cv, csv)
