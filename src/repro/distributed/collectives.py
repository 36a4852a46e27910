"""Explicit collectives built on shard_map: compressed DP all-reduce and
sequence-parallel decode attention (flash-combine across the model axis).

The pjit training path leaves gradient reduction to XLA; these are the
hand-rolled equivalents for (a) gradient compression over slow cross-pod
links, (b) serving long contexts with the KV sequence dim sharded.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def compressed_psum_grads(grads, mesh: Mesh, axis: str | tuple, key,
                          *, codec: str = "int8"):
    """Mean all-reduce of PER-DEVICE grads over a DP axis, int8 payloads.

    ``grads`` leaves carry a leading device axis of size ``prod(axis)`` —
    one microbatch-grad per DP rank, the tensor each device holds after
    its local backward.  Each rank quantizes its OWN slice to int8 with a
    rank-folded stochastic-rounding key (decorrelated noise is what makes
    the mean unbiased — a shared key would correlate the rounding errors
    and they'd no longer average out), the psum runs on the dequantized
    values (XLA reduces fp32; on real interconnect the int8 payload is
    what crosses links — we account bytes, not wire format, see
    benchmarks/bench_compression.py), and every rank gets the replicated
    mean with the device axis dropped.
    """
    from repro.optim import compression

    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    n = 1
    for a in axes:
        n *= mesh.shape[a]

    def local_reduce(g):
        rank = jnp.int32(0)
        for a in axes:
            rank = rank * mesh.shape[a] + jax.lax.axis_index(a)
        rkey = jax.random.fold_in(key, rank)

        def per_leaf(x, k):
            q, s = compression.quantize_int8(x[0], k)
            deq = compression.dequantize_int8(q, s)
            return jax.lax.psum(deq, axes) / n

        leaves, treedef = jax.tree_util.tree_flatten(g)
        keys = jax.random.split(rkey, len(leaves))
        return treedef.unflatten(
            [per_leaf(x, k) for x, k in zip(leaves, keys)])

    in_spec = jax.tree_util.tree_map(
        lambda x: P(axes if len(axes) > 1 else axes[0],
                    *([None] * (x.ndim - 1))), grads)
    out_spec = jax.tree_util.tree_map(lambda _: P(), grads)
    return jax.shard_map(local_reduce, mesh=mesh, in_specs=(in_spec,),
                         out_specs=out_spec, check_vma=False)(grads)


def sp_decode_attention(q, k_cache, v_cache, bias, mesh: Mesh, *,
                        sm_scale: float, seq_axis: str = "model"):
    """Decode attention with the KV sequence dim sharded over ``seq_axis``.

    Each shard computes local flash statistics (m_i, l_i, o_i); a psum-style
    renormalization combines them — one small collective instead of
    all-gathering the cache:
      m = max_i m_i;  l = sum_i l_i e^{m_i - m};  o = sum_i o_i l_i e^{m_i-m} / l
    q: (B, H, D); k/v_cache: (B, H, S, D); bias: (B, S).
    """
    def local(q_l, k_l, v_l, b_l):
        logits = jnp.einsum("bhd,bhsd->bhs", q_l.astype(jnp.float32),
                            k_l.astype(jnp.float32)) * sm_scale
        logits = logits + b_l[:, None, :]
        m_i = logits.max(-1)                                   # (B, H)
        p = jnp.exp(logits - m_i[..., None])
        l_i = p.sum(-1)
        o_i = jnp.einsum("bhs,bhsd->bhd", p, v_l.astype(jnp.float32))
        m = jax.lax.pmax(m_i, seq_axis)
        corr = jnp.exp(m_i - m)
        l = jax.lax.psum(l_i * corr, seq_axis)
        o = jax.lax.psum(o_i * corr[..., None], seq_axis)
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q_l.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(None, None, seq_axis, None),
                  P(None, None, seq_axis, None), P(None, seq_axis)),
        out_specs=P(), check_vma=False)(q, k_cache, v_cache, bias)


NEG_INF = -1e30


def sp_decode_attention_int8(q, k_q, k_s, v_q, v_s, write, write_at,
                             mesh: Mesh, *, sm_scale: float, lengths=None,
                             bias=None, seq_axis: str = "model"):
    """One-token GQA decode over an int8 cache whose SEQUENCE dim is
    sharded over ``seq_axis`` — the serve fallback when kv-heads don't
    divide the model axis (:func:`repro.distributed.sharding.serve_kv_shard`).

    The token WRITE happens inside the same shard_map: each shard tests
    whether ``write_at`` lands in its slice and applies a local
    dynamic_update_slice (a DUS on a sharded dim outside shard_map would
    make XLA re-shard the cache — exactly the all-gather this path
    exists to avoid).  Attention is the cross-device twin of the split-K
    kernel: per-shard masked softmax partials (m_i, l_i, o_i) merged with
    one flash-combine (pmax/psum) — small collectives over (B, H)-sized
    stats, never the cache.

    A fully-masked shard (every owned position beyond the row's length)
    contributes m_i = -inf, l_i = o_i = 0 and underflows out of the
    combine; ``lengths >= 1`` (the engine's free-slot clamp) guarantees at
    least one live shard per row.

    q: (B, H, D); k_q/v_q: (B, Hkv, S, D) int8; k_s/v_s: (B, Hkv, S) f32;
    write: (kq_new (B,Hkv,D) int8, ks_new (B,Hkv) f32, vq_new, vs_new);
    write_at: (B,) int32 global positions; lengths: (B,) int32 XOR
    bias: (B, S) additive mask.  Returns (out (B, H, D) f32, then the
    four updated cache shards).
    """
    assert (lengths is None) != (bias is None), \
        "exactly one of lengths/bias"
    b, h, d = q.shape
    hkv = k_q.shape[1]
    g = h // hkv
    have_lengths = lengths is not None

    def local_write(c_l, new, local_at, own):
        if c_l.ndim == 4:
            upd = jax.vmap(lambda c, n_, a: jax.lax.dynamic_update_slice(
                c, n_[:, None], (0, a, 0)))(c_l, new, local_at)
        else:
            upd = jax.vmap(lambda c, n_, a: jax.lax.dynamic_update_slice(
                c, n_[:, None], (0, a)))(c_l, new, local_at)
        return jnp.where(own.reshape((-1,) + (1,) * (c_l.ndim - 1)),
                         upd, c_l)

    def local(q_l, kq_l, ks_l, vq_l, vs_l, kqn, ksn, vqn, vsn, at, mask):
        s_l = kq_l.shape[2]
        offset = jax.lax.axis_index(seq_axis) * s_l
        local_at = jnp.clip(at - offset, 0, s_l - 1)
        own = (at >= offset) & (at < offset + s_l)
        kq_l = local_write(kq_l, kqn, local_at, own)
        ks_l = local_write(ks_l, ksn, local_at, own)
        vq_l = local_write(vq_l, vqn, local_at, own)
        vs_l = local_write(vs_l, vsn, local_at, own)

        k = kq_l.astype(jnp.float32) * ks_l[..., None]
        v = vq_l.astype(jnp.float32) * vs_l[..., None]
        qg = q_l.astype(jnp.float32).reshape(b, hkv, g, d)
        logits = jnp.einsum("bhgd,bhsd->bhgs", qg, k) * sm_scale
        if have_lengths:
            kv_pos = offset + jnp.arange(s_l)
            valid = kv_pos[None, :] < mask[:, None]            # (B, S_l)
            logits = jnp.where(valid[:, None, None], logits, NEG_INF)
        else:
            logits = logits + mask[:, None, None, :]
        ok = logits > NEG_INF / 2
        m_i = jnp.where(ok.any(-1), logits.max(-1), NEG_INF)   # (B,Hkv,G)
        p = jnp.where(ok, jnp.exp(logits - m_i[..., None]), 0.0)
        l_i = p.sum(-1)
        o_i = jnp.einsum("bhgs,bhsd->bhgd", p, v)
        m = jax.lax.pmax(m_i, seq_axis)
        corr = jnp.exp(m_i - m)
        l = jax.lax.psum(l_i * corr, seq_axis)
        o = jax.lax.psum(o_i * corr[..., None], seq_axis)
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(b, h, d), kq_l, ks_l, vq_l, vs_l

    kv_spec = P(None, None, seq_axis, None)
    sc_spec = P(None, None, seq_axis)
    mask = lengths if have_lengths else bias
    mask_spec = P() if have_lengths else P(None, seq_axis)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), kv_spec, sc_spec, kv_spec, sc_spec,
                  P(), P(), P(), P(), P(), mask_spec),
        out_specs=(P(), kv_spec, sc_spec, kv_spec, sc_spec),
        check_vma=False)(q, k_q, k_s, v_q, v_s, *write, write_at, mask)
