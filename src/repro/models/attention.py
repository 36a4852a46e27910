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
    # NOTE (tried & refuted): forcing MQA-style TP here
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
    """Latent-compressed attention; returns (out, (kv_latent, k_rope)).

    Prefill and training: the latent is expanded to per-head keys
    (``qk_nope + qk_rope`` wide, the rotary part shared by every head) and
    values, and attention runs as multi-head attention through the
    platform's path (``cfg.attn_impl()``): the flash kernel, or jnp."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim

    with jax.named_scope("mla_prefill"):
        q_lat = rms_norm(x @ p["q_a"], p["q_a_norm"], cfg.norm_eps,
                         bf16_grad=cfg.norm_bf16_grad)
        q = (q_lat @ p["q_b"]).reshape(b, s, h, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]

        kv_all = x @ p["kv_a"]                           # (B,S,kv_lora+dr)
        kv_lat = rms_norm(kv_all[..., : m.kv_lora_rank], p["kv_a_norm"],
                          cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
        k_rope = kv_all[..., m.kv_lora_rank:].reshape(b, s, 1, dr)

        kv = (kv_lat @ p["kv_b"]).reshape(b, s, h, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]

        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
        k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
        qf = jnp.concatenate([q_nope, q_rope], -1)
        kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope,
                                                       (b, s, h, dr))], -1)
        sm_scale = (dn + dr) ** -0.5
        impl = cfg.attn_impl()
        if impl != "jnp" and dn + dr == dv:
            from repro.kernels.flash import ops as flash_ops
            out = flash_ops.flash_attention(
                jnp.swapaxes(qf, 1, 2), jnp.swapaxes(kf, 1, 2),
                jnp.swapaxes(v, 1, 2), causal=True, sm_scale=sm_scale,
                backend=impl)
            out = jnp.swapaxes(out, 1, 2)
        else:
            out = gqa_attention(qf, kf, v, q_pos=positions, k_pos=positions,
                                sm_scale=sm_scale)
        out = out.reshape(b, s, h * dv)
    return out @ p["wo"], (kv_lat, k_rope)


def _mla_decode_inputs(p, x_t, cfg, pos_arr):
    """One token's absorbed decode inputs: (q_abs (B, H, kv_lora) f32,
    q_rope (B, H, dr), the token's latent (B, kv_lora) and rotated rope
    key (B, dr)) at positions ``pos_arr`` (B, 1)."""
    m = cfg.mla
    b = x_t.shape[0]
    h = cfg.n_heads
    dn, dr, dv = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim
    q_lat = rms_norm(x_t @ p["q_a"], p["q_a_norm"], cfg.norm_eps,
                     bf16_grad=cfg.norm_bf16_grad)
    q = (q_lat @ p["q_b"]).reshape(b, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope[:, None], pos_arr, cfg.rope_theta)[:, 0]

    kv_all = x_t @ p["kv_a"]
    lat_new = rms_norm(kv_all[..., : m.kv_lora_rank], p["kv_a_norm"],
                       cfg.norm_eps, bf16_grad=cfg.norm_bf16_grad)
    kr_new = apply_rope(kv_all[..., m.kv_lora_rank:][:, None, None],
                        pos_arr, cfg.rope_theta)[:, 0, 0]
    wk_b = p["kv_b"].reshape(m.kv_lora_rank, h, dn + dv)[..., :dn]
    q_abs = jnp.einsum("bhd,lhd->bhl", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    return q_abs, q_rope, lat_new, kr_new


def _mla_decode_output(p, x_t, cfg, o_lat):
    """Latent attention output (B, H, kv_lora) -> (B, D_model): the value
    up-projection, then the output projection."""
    m = cfg.mla
    h = cfg.n_heads
    wv_b = p["kv_b"].reshape(m.kv_lora_rank, h,
                             m.qk_nope_dim + m.v_head_dim)[..., m.qk_nope_dim:]
    out = jnp.einsum("bhl,lhd->bhd", o_lat, wv_b.astype(jnp.float32))
    out = out.reshape(x_t.shape[0], h * m.v_head_dim).astype(x_t.dtype)
    return out @ p["wo"]


def mla_decode(p, x_t, cfg, cache_lat, cache_rope, pos):
    """One-token MLA decode with weight absorption (lockstep batch).

    The latent cache stores only (kv_lora + rope_dim) floats/token — MLA's
    whole point.  Scores and outputs are computed in latent space:
      score = (q_nope @ Wk_b) . kv_lat + q_rope . k_rope
      out   = (softmax . kv_lat) @ Wv_b
    cache_lat: (B, S, kv_lora); cache_rope: (B, dr, S); pos scalar.
    """
    from repro.kernels.mla import ref as mla_ref
    m = cfg.mla
    b = x_t.shape[0]
    pos_arr = jnp.full((b, 1), pos, jnp.int32)
    q_abs, q_rope, lat_new, kr_new = _mla_decode_inputs(p, x_t, cfg, pos_arr)
    cl = jax.lax.dynamic_update_slice(
        cache_lat, lat_new[:, None].astype(cache_lat.dtype), (0, pos, 0))
    cr = jax.lax.dynamic_update_slice(
        cache_rope, kr_new[:, :, None].astype(cache_rope.dtype), (0, 0, pos))
    o_lat = mla_ref.mla_decode_ref(
        q_abs, q_rope, cl[None], cr[None], jnp.broadcast_to(pos + 1, (b,)),
        0, sm_scale=(m.qk_nope_dim + m.qk_rope_dim) ** -0.5)
    return _mla_decode_output(p, x_t, cfg, o_lat), (cl, cr)


def mla_decode_slots(p, x_t, cfg, lat, rope, layer, pos, lengths, *,
                     backend: str = "ref"):
    """One-token MLA decode of a slot pool, each row at its own position.

    ``lat`` (L, B, S, kv_lora) and ``rope`` (L, B, dr, S) are the whole
    stacked cache: the row's new latent and rope key are written at
    ``(layer, b, pos[b])`` in place, and the attention reads ``layer`` of
    the cache where it lies (``kernels.mla``: the ``mla_decode_pallas``
    kernel, or its jnp ``ref``), over each row's first ``lengths[b]``
    positions.  Returns (out (B, D_model), lat, rope)."""
    m = cfg.mla
    b = x_t.shape[0]
    with jax.named_scope("mla_decode"):
        q_abs, q_rope, lat_new, kr_new = _mla_decode_inputs(
            p, x_t, cfg, pos[:, None])
        rows = jnp.arange(b)
        lat = lat.at[layer, rows, pos].set(lat_new.astype(lat.dtype),
                                           mode="drop")
        rope = rope.at[layer, rows, :, pos].set(kr_new.astype(rope.dtype),
                                                mode="drop")
        from repro.kernels.mla import ops as mla_ops
        o_lat = mla_ops.mla_decode_attention(
            q_abs, q_rope, lat, rope, lengths, layer,
            sm_scale=(m.qk_nope_dim + m.qk_rope_dim) ** -0.5,
            backend=backend)
        out = _mla_decode_output(p, x_t, cfg, o_lat)
    return out, lat, rope


# ---------------------------------------------------------------------------
# Cached single-token decode.
# ---------------------------------------------------------------------------
def _write_token(cache, new, at):
    """Write one token at scalar position ``at`` of the S axis of a
    per-layer cache leaf (B, Hkv, S, hd) or (B, Hkv, S); new: (B, Hkv, hd)
    / (B, Hkv) — every row writes the same position (lockstep batch)."""
    return jax.lax.dynamic_update_slice(
        cache, new[:, :, None], (0, 0, at, 0)[:cache.ndim])


def _decode_qkv(p, x_t, cfg, pos):
    """One token's query (B, H, hd) and new key and value (B, Hkv, hd),
    rotated at ``pos``: a scalar, or one position per row (B,)."""
    b, _ = x_t.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = (x_t @ p["wq"]).reshape(b, 1, h, hd)
    k_t = (x_t @ p["wk"]).reshape(b, 1, hkv, hd)
    v_t = (x_t @ p["wv"]).reshape(b, 1, hkv, hd)
    pos_arr = jnp.broadcast_to(pos[:, None] if pos.ndim == 1 else pos,
                               (b, 1))
    if cfg.mrope_sections is not None:
        pos3 = jnp.broadcast_to(pos_arr[None], (3, b, 1))
        q = apply_rope(q, pos3, cfg.rope_theta, cfg.rope_fraction,
                       cfg.mrope_sections)
        k_t = apply_rope(k_t, pos3, cfg.rope_theta, cfg.rope_fraction,
                         cfg.mrope_sections)
    else:
        q = apply_rope(q, pos_arr, cfg.rope_theta, cfg.rope_fraction)
        k_t = apply_rope(k_t, pos_arr, cfg.rope_theta, cfg.rope_fraction)
    return q[:, 0], k_t[:, 0], v_t[:, 0]


def _decode_mask(pos, b, s_max, window):
    """(write position, lengths, bias) of a decode token at ``pos``.

    Full-causal (static window <= 0) schedules give per-row ``lengths``
    (bias None); a window band, static or a traced per-layer window,
    gives the dense (B, S) ``bias`` (lengths None)."""
    if isinstance(window, int) and window <= 0:
        return pos, jnp.broadcast_to(pos + 1, (b,)), None  # incl. current
    kv_pos = jnp.arange(s_max)
    pos_col = pos[:, None] if pos.ndim == 1 else pos   # vs (·, S)
    valid = kv_pos[None, :] <= pos_col                   # includes current
    if isinstance(window, int):
        valid &= kv_pos[None, :] > pos_col - window
    else:
        valid &= jnp.where(window > 0, kv_pos[None, :] > pos_col - window,
                           True)
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    return pos, None, jnp.broadcast_to(bias, (b, s_max))


def _kvq_attend(q, ck, csk, cv, csv, layer, lengths, bias, *, backend,
                splits, mesh, kv_shard):
    """int8 decode attention over ``layer`` of a stacked (L, B, Hkv, S, hd)
    cache, masked by ``lengths`` or ``bias``; (B, H, hd) f32."""
    mask_kw = "lengths" if lengths is not None else "bias"

    def decode(q, ck, csk, cv, csv, layer, mask):
        return kvq_ops.decode_attention(
            q, ck, csk, cv, csv, layer=layer, backend=backend,
            splits=splits, **{mask_kw: mask})

    if kv_shard == "heads" and backend != "ref":
        # XLA cannot partition a Mosaic kernel: each device runs it on its
        # own kv heads (whole GQA groups, never split)
        heads = P(None, None, "model")
        decode = jax.shard_map(
            decode, mesh=mesh,
            in_specs=(P(None, "model"),) + (heads,) * 4 + (P(), P()),
            out_specs=P(None, "model"), check_vma=False)
    return decode(q, ck, csk, cv, csv, jnp.asarray(layer, jnp.int32),
                  lengths if lengths is not None else bias)


def _plain_attend(q, ck, cv, lengths, bias, cfg):
    """Decode attention over one layer's unquantized (B, Hkv, S, hd)
    cache; (B, H, hd) f32."""
    b = q.shape[0]
    h, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    qg = q.reshape(b, hkv, h // hkv, hd).astype(jnp.float32)
    # one arithmetic source for the decode mask (lengths iota compare /
    # bias add): shared with the kvq ref oracle so paths can't drift
    from repro.kernels.kvq.ref import masked_decode_logits
    logits = masked_decode_logits(qg, ck.astype(jnp.float32), hd ** -0.5,
                                  bias, lengths)
    pr = jax.nn.softmax(logits, -1)
    return jnp.einsum("bhgs,bhsd->bhgd", pr, cv.astype(jnp.float32)
                      ).reshape(b, h, hd)


def attn_decode(p, x_t, cfg, cache_k, cache_s_k, cache_v, cache_s_v, pos,
                *, window: int = 0, quantized: bool = True, backend: str = "ref",
                splits: int = 1, mesh=None, kv_shard: str = "none"):
    """One-token GQA decode against one layer of a (possibly int8) cache.

    x_t: (B, D_model); cache_k/v: (B, Hkv, S, hd) int8 (or bf16 when not
    quantized, scales ignored); pos: scalar int32 current position
    (lockstep batch), or a per-row (B,) int32 vector on the mesh's
    sequence-sharded serve pool (``kv_shard == "seq"``) — each batch row
    then RoPE-rotates, writes, and masks at its OWN position.  The serve
    pool's other per-row rounds decode through :func:`attn_decode_slots`.

    Masking is length-first: full-causal (static window <= 0) schedules
    pass per-batch ``lengths`` through to ``decode_attention`` — the
    split-K kernel skips fully-padded KV tiles and masks the straddling
    tile with an in-kernel iota compare, and no (B, S) f32 bias tensor is
    built on ANY backend.  Only schedules lengths can't express (a window
    band, static or a traced per-layer window) fall back to the dense
    bias.  ``splits`` selects the kernel's split-K fan-out.

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
    Returns (attn_out (B, D_model), the layer's updated k, k_scale, v,
    v_scale).
    """
    b, _ = x_t.shape
    pos = jnp.asarray(pos, jnp.int32)
    q, k_new, v_new = _decode_qkv(p, x_t, cfg, pos)
    write_at, lengths, bias = _decode_mask(pos, b, cache_k.shape[2], window)
    if quantized:
        kq_new, ks_new = kvq_ops.quantize_kv(k_new)
        vq_new, vs_new = kvq_ops.quantize_kv(v_new)
        if kv_shard == "seq" and mesh is not None:
            from repro.distributed import collectives
            out, ck, csk, cv, csv = collectives.sp_decode_attention_int8(
                q, cache_k, cache_s_k, cache_v, cache_s_v,
                (kq_new, ks_new, vq_new, vs_new),
                jnp.broadcast_to(write_at, (b,)), mesh,
                sm_scale=cfg.head_dim ** -0.5, lengths=lengths, bias=bias)
        else:
            ck = _write_token(cache_k, kq_new, write_at)
            cv = _write_token(cache_v, vq_new, write_at)
            csk = _write_token(cache_s_k, ks_new, write_at)
            csv = _write_token(cache_s_v, vs_new, write_at)
            out = _kvq_attend(q, ck[None], csk[None], cv[None], csv[None], 0,
                              lengths, bias, backend=backend, splits=splits,
                              mesh=mesh, kv_shard=kv_shard)
    else:
        ck = _write_token(cache_k, k_new.astype(cache_k.dtype), write_at)
        cv = _write_token(cache_v, v_new.astype(cache_v.dtype), write_at)
        csk, csv = cache_s_k, cache_s_v
        out = _plain_attend(q, ck, cv, lengths, bias, cfg)
    out = out.reshape(b, -1).astype(x_t.dtype)
    return out @ p["wo"], (ck, csk, cv, csv)


def attn_decode_slots(p, x_t, cfg, k, k_scale, v, v_scale, layer, pos, *,
                      window=0, quantized: bool = True, backend: str = "ref",
                      splits: int = 1, mesh=None, kv_shard: str = "none"):
    """One-token GQA decode of a slot pool, each row at its own position.

    ``k``/``v`` (L, B, Hkv, S, hd) and ``k_scale``/``v_scale`` (L, B, Hkv,
    S) are the whole stacked cache (int8 and f32 scales; bf16 with unused
    scales when not ``quantized``).  Each row's new token is written at
    ``(layer, b, :, pos[b])`` with one scatter a leaf, every row at once;
    a position past the cache clamps to its last slot, as a
    ``dynamic_update_slice`` would.  The int8 attention reads ``layer``
    of the cache where it lies (``kernels.kvq`` with the layer on its
    scalar-prefetch lane), so no layer of the cache is copied; masks,
    ``window``, ``backend``, ``splits`` and ``kv_shard`` as in
    :func:`attn_decode`.  Returns (out (B, D_model), (k, k_scale, v,
    v_scale))."""
    b, _ = x_t.shape
    s_max = k.shape[3]
    q, k_new, v_new = _decode_qkv(p, x_t, cfg, pos)
    _, lengths, bias = _decode_mask(pos, b, s_max, window)
    # Each scatter's window is the leaf's minor dim, so that the leaf
    # keeps the layout the kernel reads and XLA copies none of it: the
    # K/V leaves take one index per (row, kv head) and a head-dim window,
    # the scales one index per row and a window over the heads.
    hkv = cfg.n_kv
    at = jnp.clip(pos, 0, s_max - 1)
    rows = jnp.arange(b)
    idx_kv = (layer, jnp.repeat(rows, hkv), jnp.tile(jnp.arange(hkv), b),
              jnp.repeat(at, hkv))

    def put(leaf, new):
        if leaf.ndim == 5:
            idx, new = idx_kv, new.reshape(b * hkv, -1)
        else:
            idx = (layer, rows, slice(None), at)
        return leaf.at[idx].set(new.astype(leaf.dtype),
                                mode="promise_in_bounds")

    if quantized:
        kq_new, ks_new = kvq_ops.quantize_kv(k_new)
        vq_new, vs_new = kvq_ops.quantize_kv(v_new)
        k, k_scale = put(k, kq_new), put(k_scale, ks_new)
        v, v_scale = put(v, vq_new), put(v_scale, vs_new)
        out = _kvq_attend(q, k, k_scale, v, v_scale, layer, lengths, bias,
                          backend=backend, splits=splits, mesh=mesh,
                          kv_shard=kv_shard)
    else:
        k, v = put(k, k_new), put(v, v_new)
        out = _plain_attend(q, k[layer], v[layer], lengths, bias, cfg)
    out = out.reshape(b, -1).astype(x_t.dtype)
    return out @ p["wo"], (k, k_scale, v, v_scale)
