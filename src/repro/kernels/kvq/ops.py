"""Public op: GQA decode attention over an int8 KV cache.

``decode_attention`` accepts the *deployed* layout — query heads flat,
cache pre-quantized — reshapes to the kernel's grouped layout, and
dispatches pallas / interpret / ref.  ``splits`` selects the split-K
decode grid (``kernel.flash_decode_pallas``); ``lengths`` rides the
scalar-prefetch lane and skips fully-padded KV tiles instead of paying a
dense (B, S) bias add — on EVERY backend, ref included, the lengths path
never materializes a bias tensor.  ``layer`` reads one layer of a stacked
``(L, B, Hkv, S, D)`` cache where it lies (the serve pool's decode round);
without it the cache is one layer's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import tiling
from repro.kernels.kvq import kernel, ref
from repro.kernels.kvq.ref import dequantize_kv, quantize_kv  # re-export

BACKENDS = ("ref", "interpret", "pallas")


def resolve_splits(s: int, splits: int,
                   block_s: int = kernel.DEFAULT_BS) -> int:
    """The split count the kernel will actually run for a length-S cache
    (clamped to the KV tile count) — what honest banners should print."""
    return tiling.resolve_decode_grid(s, block_s=block_s, splits=splits)[2]


def decode_attention(q, k_q, k_s, v_q, v_s, *, layer=None, lengths=None,
                     bias=None, sm_scale: float | None = None,
                     backend: str = "ref", splits: int = 1,
                     block_s: int | None = None, debug_counts: bool = False):
    """q: (B, H, D); cache: (B, Hkv, S, D) int8 (+ (B, Hkv, S) scales), or
    with ``layer`` (int or traced scalar) the stacked (L, B, Hkv, S, D)
    cache (+ (L, B, Hkv, S) scales) and the layer to read.

    lengths: (B,) valid cache lengths — compared against a per-tile iota
    inside the kernel/ref body (never a broadcast bias tensor) and, on the
    kernel backends, used to early-out fully-padded KV tiles and shrink
    their DMAs.  bias: explicit (B, S) f32 additive mask for schedules
    lengths can't express (exclusive with ``lengths``).  With neither,
    every cache slot is valid and NO mask operand exists at all.

    ``splits`` fans the KV axis over a parallel split-K grid axis
    (kernel backends; ref is a single exact softmax).  ``debug_counts``
    (kernel backends only) also returns (B, Hkv, splits) executed
    tile-step counters — the measured twin of
    ``tiling.decode_tile_step_counts``.
    Returns (B, H, D) f32 (or (out, counts) with ``debug_counts``).
    """
    if backend not in BACKENDS:
        raise ValueError(f"decode_attention: unknown backend {backend!r} "
                         f"(expected one of {BACKENDS})")
    if lengths is not None and bias is not None:
        raise ValueError("decode_attention: lengths and bias are exclusive")
    b, h, d = q.shape
    hkv = k_q.shape[-3]
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    sm = sm_scale if sm_scale is not None else d ** -0.5
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
    qg = q.astype(jnp.float32).reshape(b, hkv, g, d)
    if backend == "ref":
        if debug_counts:
            raise ValueError("decode_attention: debug_counts needs a kernel "
                             "backend (interpret/pallas); ref runs no grid")
        out = ref.decode_attention_ref(qg, k_q, k_s, v_q, v_s, bias, sm,
                                       lengths=lengths, layer=layer)
    else:
        kw = dict(sm_scale=sm, splits=splits,
                  interpret=(backend == "interpret"),
                  debug_counts=debug_counts)
        if block_s is not None:
            kw["block_s"] = block_s
        if layer is None:                  # one layer: a unit layer axis
            k_q, k_s, v_q, v_s = (c[None] for c in (k_q, k_s, v_q, v_s))
            layer = 0
        out = kernel.flash_decode_pallas(qg, k_q, k_s, v_q, v_s, bias,
                                         lengths, layer, **kw)
        if debug_counts:
            out, counts = out
            return out.reshape(b, h, d), counts
    return out.reshape(b, h, d)
