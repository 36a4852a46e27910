"""Oracle for absorbed latent-attention decode (MLA), in jnp and float32.

With the key and value up-projections absorbed, one decode token of one
slot attends over that slot's cached latents directly:

    score[h, s] = (q_abs[h] . lat[s] + q_rope[h] . rope[s]) * sm_scale
    out[h]      = sum_s softmax(score[h])[s] * lat[s]

``q_abs`` is the no-position part of the query times the key
up-projection (``kv_lora``-wide), ``q_rope`` its rotary part; the caller
applies the value up-projection to ``out``.  Positions at or past a
slot's ``length`` are masked by an iota compare, as in the kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.tiling import NEG_INF


def mla_decode_ref(q_abs, q_rope, lat, rope, lengths, layer, *,
                   sm_scale: float):
    """q_abs: (B, H, C); q_rope: (B, H, R); lat: (L, B, S, C); rope:
    (L, B, R, S); lengths: (B,) int32; layer: scalar int32.
    Returns (B, H, C) float32."""
    lat = jax.lax.dynamic_index_in_dim(lat, layer, 0, keepdims=False)
    rope = jax.lax.dynamic_index_in_dim(rope, layer, 0, keepdims=False)
    f32 = jnp.float32
    lat = lat.astype(f32)
    scores = jnp.einsum("bhc,bsc->bhs", q_abs.astype(f32), lat)
    scores += jnp.einsum("bhr,brs->bhs", q_rope.astype(f32),
                         rope.astype(f32))
    scores = scores * sm_scale
    kpos = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    scores = jnp.where(kpos < lengths[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, -1)
    return jnp.einsum("bhs,bsc->bhc", p, lat)
