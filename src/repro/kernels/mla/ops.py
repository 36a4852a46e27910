"""Public op: absorbed latent-attention decode over a stacked latent cache.

``mla_decode_attention`` dispatches ``pallas`` (the compiled kernel),
``interpret`` (the same kernel in the Pallas interpreter, for CPU tests)
or ``ref`` (the float32 jnp oracle).
"""
from __future__ import annotations

from repro.kernels.mla import kernel, ref

BACKENDS = ("ref", "interpret", "pallas")


def mla_decode_attention(q_abs, q_rope, lat, rope, lengths, layer, *,
                         sm_scale: float, backend: str = "ref",
                         block_s: int | None = None):
    """q_abs: (B, H, C); q_rope: (B, H, R); lat: (L, B, S, C); rope:
    (L, B, R, S), sequence-minor; lengths: (B,) valid positions of each
    slot; layer: the cache layer to read.  Returns (B, H, C) float32."""
    if backend not in BACKENDS:
        raise ValueError(f"mla_decode_attention: unknown backend "
                         f"{backend!r} (expected one of {BACKENDS})")
    if backend == "ref":
        return ref.mla_decode_ref(q_abs, q_rope, lat, rope, lengths, layer,
                                  sm_scale=sm_scale)
    kw = {} if block_s is None else {"block_s": block_s}
    return kernel.mla_decode_pallas(
        q_abs, q_rope, lat, rope, lengths, layer, sm_scale=sm_scale,
        interpret=(backend == "interpret"), **kw)
