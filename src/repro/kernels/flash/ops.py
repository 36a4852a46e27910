"""Public flash-attention op: (B, H, S, D) GQA layout, backend dispatch.

One differentiable entry point for all three backends:

  * ``ref``        exact jnp softmax (``ref.flash_ref``), differentiated by
                   plain jax autodiff — the gradient oracle.  O(S^2)
                   residuals.
  * ``interpret``  the Pallas kernels run through the Pallas interpreter —
                   same tiling/masking semantics as TPU, runs anywhere.
  * ``pallas``     compiled Mosaic TPU kernels.

For ``interpret``/``pallas`` the op is a ``jax.custom_vjp``: the forward
saves residuals (q, k, v, o, m, l) — O(S*D) per head instead of the
O(S^2) probability matrix — and the backward runs the recompute-based
Pallas kernels (``kernel.flash_attention_bwd_pallas``), so
``jax.grad`` through ``attn_backend="pallas"`` is legal and memory-cheap.

``pallas`` never substitutes another backend: a shape Mosaic cannot
lower raises Mosaic's own error.  (Small head_dims and sub-128
sequences lower too, because their blocks span the whole array
dimension; ``tests/test_tpu_compile.py`` compiles both.)

``resid_dtype`` applies a mixed-precision policy to the SAVED residual
tuple: (q, k, v, o) are stored between forward and backward in that dtype
(e.g. bf16 — halving the dominant O(S*D) term of f32 training) while the
(m, l) softmax stats always stay f32 (they sit inside an exp/log and the
two rows are byte-trivial).  Gradients are cast back to the primal input
dtypes, so the trade is purely recompute precision in the backward score
recomputation.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash import kernel, ref


class _FlashSpec(NamedTuple):
    """Hashable static config threaded through the custom_vjp as a
    nondiff arg (causal/window/scale/kv_len are compile-time for the
    kernels; ``interpret`` picks the Pallas interpreter vs Mosaic).

    ``resid_dtype`` (a dtype NAME, kept hashable) is the storage dtype of
    the saved (q, k, v, o) residuals; ``grad_dtypes`` are the primal
    (q, k, v) dtypes the backward must cast its cotangents back to when a
    residual policy is active."""

    causal: bool
    window: int
    sm_scale: Optional[float]
    kv_len: int
    interpret: bool
    resid_dtype: Optional[str] = None
    grad_dtypes: Optional[tuple] = None


def padded_seq_len(s: int) -> int:
    """Sequence length after ``flash_attention``'s lane padding (S rounded
    to a 128 block, or to 8 sublanes below one block).  The planner and
    benchmarks size tile grids with this so their visited-tile counts
    match what the kernels actually execute."""
    return s + ((-s) % 128 if s > 128 else (-s) % 8)


# ---------------------------------------------------------------------------
# custom_vjp core (flat (B*H, S, D) layout; padding/GQA folding outside).
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(spec: _FlashSpec, q, k, v):
    o, _, _ = kernel.flash_attention_fwd_pallas(
        q, k, v, causal=spec.causal, window=spec.window,
        sm_scale=spec.sm_scale, kv_len=spec.kv_len,
        interpret=spec.interpret)
    return o


def _flash_fwd(spec: _FlashSpec, q, k, v):
    o, m, l = kernel.flash_attention_fwd_pallas(
        q, k, v, causal=spec.causal, window=spec.window,
        sm_scale=spec.sm_scale, kv_len=spec.kv_len,
        interpret=spec.interpret)
    if spec.resid_dtype is not None:       # policy-cast saved (q, k, v, o);
        rd = jnp.dtype(spec.resid_dtype)   # (m, l) stats stay f32
        q, k, v, o_r = (x.astype(rd) for x in (q, k, v, o))
    else:
        o_r = o
    return o, (q, k, v, o_r, m, l)        # O(S*D) residuals + f32 stat rows


def _flash_bwd(spec: _FlashSpec, residuals, do):
    q, k, v, o, m, l = residuals
    # grad_dtypes makes the kernels emit cotangents at the PRIMAL dtypes
    # straight from their f32 accumulators — bf16-stored residuals must
    # not round-trip the gradients through bf16 on the way out.
    dq, dk, dv = kernel.flash_attention_bwd_pallas(
        q, k, v, o, m, l, do, causal=spec.causal, window=spec.window,
        sm_scale=spec.sm_scale, kv_len=spec.kv_len,
        interpret=spec.interpret, grad_dtypes=spec.grad_dtypes)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Public op.
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    sm_scale: float | None = None, backend: str = "ref",
                    resid_dtype=None):
    """q: (B, H, S, D); k, v: (B, Hkv, S, D) -> (B, H, S, D).

    Differentiable on every backend; ``interpret``/``pallas`` use the
    recompute-based Pallas backward via ``jax.custom_vjp``.

    ``resid_dtype`` (dtype or name, e.g. ``"bfloat16"``) stores the saved
    (q, k, v, o) residual tuple in that dtype between forward and backward
    — the mixed-precision residual policy; (m, l) stats stay f32 and
    gradients come back in the primal dtypes.  Ignored on the ``ref``
    path (plain autodiff owns its residuals there).
    """
    if backend not in ("ref", "interpret", "pallas"):
        raise ValueError(f"flash_attention: unknown backend {backend!r} "
                         "(expected 'ref', 'interpret' or 'pallas')")
    if k.shape[1] == 0 or q.shape[1] % k.shape[1]:
        raise ValueError(
            f"flash_attention: n_heads={q.shape[1]} must be a non-zero "
            f"multiple of n_kv={k.shape[1]} (GQA) for q{tuple(q.shape)}, "
            f"k{tuple(k.shape)} — every backend groups query heads over "
            f"KV heads")
    if backend == "ref":
        return ref.flash_ref(q, k, v, causal=causal, window=window,
                             sm_scale=sm_scale)
    b, h, s, d = q.shape
    hkv = k.shape[1]
    pad = padded_seq_len(s) - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    rd = None if resid_dtype is None else jnp.dtype(resid_dtype).name
    if rd is not None and all(jnp.dtype(x.dtype).name == rd
                              for x in (q, k, v)):
        rd = None                          # residuals already follow inputs
    spec = _FlashSpec(causal=bool(causal), window=int(window),
                      sm_scale=sm_scale, kv_len=s,
                      interpret=(backend == "interpret"),
                      resid_dtype=rd,
                      grad_dtypes=None if rd is None else tuple(
                          jnp.dtype(x.dtype).name for x in (q, k, v)))
    out = _flash(spec, q.reshape(b * h, s + pad, d),
                 k.reshape(b * hkv, s + pad, d),
                 v.reshape(b * hkv, s + pad, d))
    out = out.reshape(b, h, s + pad, d)
    return out[:, :, :s] if pad else out
