"""Oracle for int8-KV flash-decode: quantization + exact softmax attention.

E-D applied to serving: the KV cache is *stored encoded* (int8 + per-token,
per-head scales = 2.06 bytes/elem vs 2 bytes bf16 -> ~2x vs fp32, ~1.94x vs
bf16 counting scales) and *decoded on read* inside the attention kernel,
halving the HBM stream that dominates decode latency.

Two oracles:

  * :func:`decode_attention_ref` — one exact softmax over the whole cache.
    ``lengths`` masks via an in-body iota compare (no (B, S) bias tensor
    is ever materialized on the lengths path, mirroring the kernel).
  * :func:`decode_attention_splitk_ref` — the split-K oracle: per-split
    masked-softmax partials merged with the same online-softmax merge as
    ``kernel.combine_splits``, in pure jnp.  Validates the split/merge
    arithmetic independently of Pallas; must agree with the plain oracle
    to float tolerance for every split count.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import tiling
from repro.kernels.tiling import NEG_INF


def quantize_kv(x: jax.Array):
    """(..., S, D) float -> (int8 values, float32 scales (..., S))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale[..., None]


def masked_decode_logits(q, k, sm_scale, bias, lengths):
    """(B, Hkv, G, S) masked decode logits; lengths mask via an in-body
    iota compare only (never a (B, S) bias tensor).  The ONE jnp source of
    the decode mask contract — the ref oracles here and the unquantized
    fallback in ``models.attention.attn_decode`` both call it, so the
    lengths semantics cannot drift between serve paths."""
    logits = jnp.einsum("bhgd,bhsd->bhgs", q.astype(jnp.float32), k) * sm_scale
    if lengths is not None:
        kpos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 3)
        logits = jnp.where(kpos < lengths[:, None, None, None], logits,
                           NEG_INF)
    elif bias is not None:
        logits = logits + bias[:, None, None, :]
    return logits


def decode_attention_ref(q, k_q, k_s, v_q, v_s, bias, sm_scale: float,
                         lengths=None, layer=None):
    """Exact reference.

    q:   (B, Hkv, G, D) f32      — G = query heads per KV head (GQA group)
    k_q: (B, Hkv, S, D) int8,  k_s: (B, Hkv, S) f32
    v_q: (B, Hkv, S, D) int8,  v_s: (B, Hkv, S) f32
    bias:(B, S) f32 additive mask (0 valid / -inf padded), or None
    lengths: (B,) int32 valid prefix lengths — masked with an in-body iota
         compare, never a broadcast bias tensor (exclusive with ``bias``)
    layer: with it, the cache leaves are stacked, (L, ...) ahead of the
         shapes above, and this layer of them is read
    ->   (B, Hkv, G, D) f32
    """
    assert bias is None or lengths is None
    if layer is not None:
        k_q, k_s, v_q, v_s = (c[layer] for c in (k_q, k_s, v_q, v_s))
    k = dequantize_kv(k_q, k_s)
    v = dequantize_kv(v_q, v_s)
    logits = masked_decode_logits(q, k, sm_scale, bias, lengths)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhgs,bhsd->bhgd", p, v)


def decode_attention_splitk_ref(q, k_q, k_s, v_q, v_s, sm_scale: float, *,
                                lengths=None, bias=None,
                                block_s: int = tiling.DEFAULT_DECODE_BS,
                                splits: int = 1):
    """Split-K oracle: partials over each KV shard + online-softmax merge.

    Mirrors the kernel's split/merge arithmetic in pure jnp — same shard
    boundaries (``tiling.resolve_decode_grid``), unnormalized per-split
    accumulators, same merge as ``kernel.combine_splits`` — so the merge
    math has an oracle of its own rather than only the end-to-end output.
    """
    assert bias is None or lengths is None
    b, hkv, g, d = q.shape
    s = k_q.shape[2]
    bs, ns, n_sp, spt = tiling.resolve_decode_grid(s, block_s=block_s,
                                                   splits=splits)
    k = dequantize_kv(k_q, k_s)
    v = dequantize_kv(v_q, v_s)
    logits = masked_decode_logits(q, k, sm_scale, bias, lengths)   # (B,Hkv,G,S)
    valid = logits > NEG_INF / 2                  # live positions, post-mask

    m_p, l_p, o_p = [], [], []
    for sp in range(n_sp):
        sl = slice(sp * spt * bs, min((sp + 1) * spt, ns) * bs)
        if sl.start >= sl.stop:
            # empty final shard (splits don't divide the tile count): the
            # kernel's t < ns early-out leaves its init state — dead partials
            m_p.append(jnp.full(logits.shape[:-1], NEG_INF))
            l_p.append(jnp.zeros(logits.shape[:-1]))
            o_p.append(jnp.zeros(q.shape))
            continue
        lg, ok = logits[..., sl], valid[..., sl]
        m = jnp.where(ok.any(-1), lg.max(-1), NEG_INF)
        p = jnp.where(ok, jnp.exp(lg - m[..., None]), 0.0)
        m_p.append(m)
        l_p.append(p.sum(-1))
        o_p.append(jnp.einsum("bhgs,bhsd->bhgd", p, v[:, :, sl]))
    stack = lambda xs, ax=2: jnp.stack(xs, axis=ax)
    from repro.kernels.kvq import kernel
    return kernel.combine_splits(stack(o_p), stack(m_p), stack(l_p),
                                 q.dtype)
