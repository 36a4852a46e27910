"""Shared building blocks: norms, MLPs, rotary embeddings, initializers."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps: float = 1e-5, *, bf16_grad: bool = False):
    """RMSNorm with f32 internals.

    ``bf16_grad`` swaps in a custom-vjp variant whose input cotangent is
    emitted in ``x.dtype`` instead of f32: under tensor parallelism the
    backward all-reduce of dx then moves half the bytes.  Forward values
    are bit-identical.
    """
    if bf16_grad:
        return _rms_norm_bf16g(x, w, eps)
    return _rms_norm_fwd_value(x, w, eps)


def _rms_norm_fwd_value(x, w, eps):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dtype) * w.astype(dtype)


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm_bf16g(x, w, eps):
    return _rms_norm_fwd_value(x, w, eps)


def _rms_norm_bf16g_fwd(x, w, eps):
    return _rms_norm_fwd_value(x, w, eps), (x, w)


def _rms_norm_bf16g_bwd(eps, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xf * inv
    dw = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
    gw = gf * wf
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    # the one deliberate change: cotangent leaves in x.dtype (bf16 under
    # M-P), so TP's dx all-reduce runs at half width
    return dx.astype(x.dtype), dw.astype(w.dtype)


_rms_norm_bf16g.defvjp(_rms_norm_bf16g_fwd, _rms_norm_bf16g_bwd)


def gated_rms_norm(x, z, w, eps: float = 1e-5):
    """Mamba2 output norm: RMSNorm(x * silu(z))."""
    return rms_norm(x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype), w, eps)


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_mlp(x, w1, b1, w2, b2):
    return jax.nn.gelu(x @ w1 + b1, approximate=True) @ w2 + b2


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard, fractional, and M-RoPE).
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float, fraction: float = 1.0):
    """Inverse frequencies for the rotated sub-dimension."""
    rot = int(head_dim * fraction) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x, positions, theta: float, fraction: float = 1.0,
               mrope_sections=None):
    """x: (..., S, H, D); positions: (..., S) int or (3, ..., S) for M-RoPE."""
    d = x.shape[-1]
    inv, rot = rope_freqs(d, theta, fraction)
    if mrope_sections is not None:
        # positions (3, B, S): temporal/height/width streams; each frequency
        # band uses the stream its section assigns (Qwen2-VL M-RoPE).
        sec = jnp.concatenate([
            jnp.full((s,), i, jnp.int32) for i, s in enumerate(mrope_sections)
        ])  # (rot/2,)
        onehot = jax.nn.one_hot(sec, 3, dtype=jnp.float32)  # (rot/2, 3)
        ang = jnp.einsum("tbs,ft->bsf", positions.astype(jnp.float32),
                         onehot) * inv[None, None, :]
    else:
        ang = positions.astype(jnp.float32)[..., None] * inv  # (..., S, rot/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :].astype(x.dtype)  # broadcast over heads
    sin = sin[..., None, :].astype(x.dtype)
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., : rot // 2], x_rot[..., rot // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


# ---------------------------------------------------------------------------
# Initializers.
# ---------------------------------------------------------------------------
def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis] if isinstance(in_axis, int) else 1
    scale = (1.0 / max(1, fan_in)) ** 0.5
    return jax.random.normal(key, shape, dtype) * scale


def embed_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * 0.02
