"""Pallas split-K flash-decode over an int8-quantized KV cache.

Grid ``(B, Hkv, splits, steps_per_split)``: the KV axis is sharded over a
parallel split-K axis, each split running the classic online-softmax
recurrence over its KV shard in VMEM scratch and emitting *partial*
(acc, m, l) accumulators; a jnp reduction (:func:`combine_splits`) merges
the partials with the standard online-softmax merge.  Decode latency at
large S goes from O(S) sequential chunks to O(S / splits) + O(splits).
The single-split case (every default call site) keeps the pre-split-K
fast path: normalize-and-cast happens in the kernel finalize and no
partial arrays ever reach HBM.

The int8->f32 dequant happens *after* the chunk is resident in VMEM, so
HBM sees only 1 byte/elem + 4 B/token scales — the paper's store-encoded /
decode-on-read trade applied to the decode-latency-dominant stream.

The cache is the stacked one, ``(L, B, Hkv, S, D)`` with ``(L, B, Hkv,
S)`` scales, and the layer to read rides the scalar-prefetch lane
(``pltpu.PrefetchScalarGridSpec``): the K/V index maps lead with it, so
the kernel reads that layer where it lies and no slice of the cache is
copied out for it.  A per-layer cache is passed with a leading unit axis
at layer 0.  The scales are read a ``(Hkv, bs)`` block at a time, whole
along the head axis as Mosaic's layout rules allow, and the kernel takes
its head's row.

Length-aware tile skipping: per-batch ``lengths`` arrive as a second
scalar-prefetch operand, so

  * the kernel body ``pl.when``-early-outs every KV tile whose start lies
    beyond ``lengths[b]`` (plus the last split's structural padding tiles
    when splits don't divide the tile count) — a ragged batch stops
    paying for the longest sequence in it;
  * the BlockSpec index maps clamp skipped steps to the batch row's last
    live tile (``tiling.decode_last_live_tile``), so Pallas re-uses the
    resident block instead of issuing a DMA for data the kernel won't
    touch;
  * in-tile masking of the straddling tile compares a per-tile iota
    against ``lengths[b]`` — no dense (B, S) bias tensor exists anywhere
    on this path (the sole remaining ``bias`` operand serves the
    traced-window decode fallback).

``debug_counts=True`` additionally returns a (B, Hkv, splits) int32 array
counting the KV tile-steps whose matmuls executed — the measured twin of
:func:`repro.kernels.tiling.decode_tile_step_counts`, asserted
tile-for-tile in tests and benchmarks, same contract as the flash grids.

VMEM per step (BS=512, D<=128, G<=32, Hkv<=8):
  K,V chunks int8: 2*BS*D      = 128 KiB
  scales f32:      2*Hkv*BS*4  <= 32 KiB
  dequant f32:     2*BS*D*4    = 512 KiB
  scratch acc:     G*D*4       <= 16 KiB         (fits VMEM with headroom)

MXU shapes: (G, D) x (D, BS) and (G, BS) x (BS, D); D=64..128, BS multiple
of 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling
from repro.kernels.tiling import NEG_INF, imin as _imin

DEFAULT_BS = tiling.DEFAULT_DECODE_BS
#: the kernel's name in compiled programs and device traces
KERNEL_NAME = "flash_decode_pallas"


def _flash_decode_kernel(layer_ref, *refs, sm_scale, bs, ns, spt,
                         has_bias, has_lengths, fused, count):
    # arg order: layer, [lengths] (scalar prefetch), q, k_q, k_s, v_q, v_s,
    #            [bias], o[, m, l][, counts], scratch (m, l, acc,
    #            [count acc]).
    # ``fused`` (single split): normalize in-kernel and write the final
    # output — no partial (o, m, l) HBM round-trip, no jnp combine.
    del layer_ref                       # used by the index maps only
    if has_lengths:
        lengths_ref, *refs = refs
    q_ref, kq_ref, ks_ref, vq_ref, vs_ref, *refs = refs
    if has_bias:
        bias_ref, *refs = refs
    if not fused:
        o_ref, m_out_ref, l_out_ref, *refs = refs
    else:
        o_ref, *refs = refs
    if count:
        cnt_ref, m_ref, l_ref, acc_ref, cnt_acc = refs
    else:
        m_ref, l_ref, acc_ref = refs

    i = pl.program_id(0)
    j = pl.program_id(1)
    split = pl.program_id(2)
    step = pl.program_id(3)
    t = split * spt + step                     # global KV tile this step

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        if count:
            cnt_acc[0] = 0

    # early-out: structural padding tiles of the last split, and (with
    # lengths) every tile fully beyond this batch row's valid prefix
    live = t < ns
    if has_lengths:
        live &= t * bs < lengths_ref[i]

    def _step():
        q = q_ref[...][0, 0].astype(jnp.float32)                 # (G, D)
        k = kq_ref[...][0, 0, 0].astype(jnp.float32) * _head_row(ks_ref, j)
        v = vq_ref[...][0, 0, 0].astype(jnp.float32) * _head_row(vs_ref, j)
        logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if has_bias:
            logits = logits + bias_ref[...][0]                    # (G, BS)
        if has_lengths:
            # straddling tile: mask the tail with a per-tile iota compare —
            # never a materialized (B, S) bias tensor
            kpos = t * bs + jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, 1)
            logits = jnp.where(kpos < lengths_ref[i], logits, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new[:, None])
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        m_ref[...] = m_new
        if count:
            cnt_acc[0] += 1

    pl.when(live)(_step)

    @pl.when(step == spt - 1)
    def _finish():
        if fused:
            # single split owns every tile: normalize and cast in VMEM,
            # exactly the pre-split-K finalize
            denom = jnp.maximum(l_ref[...], 1e-30)
            o_ref[...] = (acc_ref[...] / denom[:, None])[None, None].astype(
                o_ref.dtype)
        else:
            # UNNORMALIZED partials: combine_splits owns the final divide.
            # A split with zero executed steps writes its init state
            # (acc=0, l=0, m=NEG_INF) and contributes nothing to the merge.
            o_ref[...] = acc_ref[...][None, None, None]
            m_out_ref[...] = m_ref[...].reshape(m_out_ref.shape)
            l_out_ref[...] = l_ref[...].reshape(l_out_ref.shape)
        if count:
            cnt_ref[...] = jnp.full(cnt_ref.shape, cnt_acc[0], jnp.int32)


def _head_row(scale_ref, j):
    """Head ``j``'s scales as a (bs, 1) column, from a (1, 1, Hkv, bs)
    block of the stacked scales."""
    blk = scale_ref[...][0, 0]                                 # (Hkv, bs)
    rows = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
    return jnp.sum(jnp.where(rows == j, blk, 0.0), axis=0)[:, None]


def combine_splits(o_p, m_p, l_p, dtype):
    """Online-softmax merge of split-K partials (the lax-reduction half).

    o_p: (B, Hkv, splits, G, D) unnormalized accumulators;
    m_p, l_p: (B, Hkv, splits, G) running max / denominator.
    Dead splits carry (0, NEG_INF, 0) and drop out of the merge (their
    alpha underflows to 0 against any live max).
    """
    m_max = m_p.max(axis=2)                                   # (B, Hkv, G)
    alpha = jnp.exp(m_p - m_max[:, :, None])                  # (B,Hkv,S,G)
    l_tot = (l_p * alpha).sum(axis=2)
    acc = (o_p * alpha[..., None]).sum(axis=2)
    return (acc / jnp.maximum(l_tot, 1e-30)[..., None]).astype(dtype)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "block_s", "splits", "interpret", "debug_counts"))
def flash_decode_pallas(q, k_q, k_s, v_q, v_s, bias=None, lengths=None,
                        layer=0, *, sm_scale: float,
                        block_s: int = DEFAULT_BS, splits: int = 1,
                        interpret: bool = False, debug_counts: bool = False):
    """q: (B, Hkv, G, D); k_q/v_q: (L, B, Hkv, S, D) int8 and k_s/v_s:
    (L, B, Hkv, S) f32, the stacked cache; ``layer`` the one to read.
    Block size shrinks to divide S.

    ``layer`` and ``lengths`` (B,) int32 ride the scalar-prefetch lane;
    ``lengths`` drives the tile early-outs + in-tile iota mask; ``bias``
    (B, S) f32 is the dense fallback for masks lengths can't express
    (mutually exclusive).  With neither, the unmasked kernel variant runs
    (no mask operand at all).  With ``debug_counts`` also returns
    (B, Hkv, splits) executed-step counters.
    """
    assert bias is None or lengths is None, "bias and lengths are exclusive"
    b, hkv, g, d = q.shape
    s = k_q.shape[3]
    bs, ns, n_sp, spt = tiling.resolve_decode_grid(s, block_s=block_s,
                                                   splits=splits)
    grid = (b, hkv, n_sp, spt)
    has_lengths = lengths is not None
    has_bias = bias is not None

    def _tile(i, split, step, len_ref=None):
        t = split * spt + step
        hi = ns - 1 if len_ref is None else tiling.decode_last_live_tile(
            len_ref[i], bs=bs, ns=ns)
        return _imin(t, hi)

    # index maps get (grid ids, layer ref[, lengths ref])
    q_map = lambda i, j, k, st, *_: (i, j, 0, 0)
    kv_map = lambda i, j, k, st, ly, *lr: (ly[0], i, j,
                                           _tile(i, k, st, *lr), 0)
    sc_map = lambda i, j, k, st, ly, *lr: (ly[0], i, 0,
                                           _tile(i, k, st, *lr))
    o_map = lambda i, j, k, st, *_: (i, j, k, 0, 0)

    # The scales' block spans every kv head, (Hkv, bs) against the array's
    # (Hkv, S), a layout Mosaic accepts with no copy of the stacked leaf;
    # the bias gets a unit axis for the same reason, as do the (1, g) stat
    # rows and (1, 1) counters below.
    kv_spec = pl.BlockSpec((1, 1, 1, bs, d), kv_map)
    sc_spec = pl.BlockSpec((1, 1, hkv, bs), sc_map)
    in_specs = [pl.BlockSpec((1, 1, g, d), q_map),
                kv_spec, sc_spec, kv_spec, sc_spec]
    args = [q, k_q, k_s, v_q, v_s]
    if has_bias:
        bias_map = lambda i, j, k, st, *_: (i, 0, _tile(i, k, st))
        in_specs.append(pl.BlockSpec((1, 1, bs), bias_map))
        args.append(bias.reshape(b, 1, s))

    fused = n_sp == 1            # single split: finalize in-kernel, no
    if fused:                    # partial HBM round-trip or jnp combine
        out_specs = [pl.BlockSpec((1, 1, g, d), q_map)]
        out_shape = [jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype)]
    else:
        out_specs = [
            pl.BlockSpec((1, 1, 1, g, d), o_map),
            pl.BlockSpec((1, 1, 1, 1, g), o_map),
            pl.BlockSpec((1, 1, 1, 1, g), o_map),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((b, hkv, n_sp, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, n_sp, 1, g), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, n_sp, 1, g), jnp.float32),
        ]
    if debug_counts:
        out_specs.append(pl.BlockSpec((1, 1, 1, 1, 1), o_map))
        out_shape.append(jax.ShapeDtypeStruct((b, hkv, n_sp, 1, 1),
                                              jnp.int32))

    scratch_shapes = [
        pltpu.VMEM((g,), jnp.float32),                               # m
        pltpu.VMEM((g,), jnp.float32),                               # l
        pltpu.VMEM((g, d), jnp.float32),                             # acc
    ] + ([pltpu.SMEM((1,), jnp.int32)] if debug_counts else [])

    kern = functools.partial(
        _flash_decode_kernel, sm_scale=sm_scale, bs=bs, ns=ns, spt=spt,
        has_bias=has_bias, has_lengths=has_lengths, fused=fused,
        count=debug_counts)
    # the split-K point: (batch, kv-head, split) are PARALLEL — Mosaic may
    # run the splits concurrently (this is where O(S) -> O(S/splits) comes
    # from on hardware); only the per-split KV sweep is sequential
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))
    scalars = [jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))]
    if has_lengths:
        scalars.append(jnp.asarray(lengths, jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch_shapes)
    out = pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                         compiler_params=params, interpret=interpret,
                         name=KERNEL_NAME)(*scalars, *args)

    if fused:
        o = out[0]
    else:
        o_p, m_p, l_p = out[:3]
        o = combine_splits(o_p, m_p[..., 0, :], l_p[..., 0, :], q.dtype)
    return (o, out[-1].reshape(b, hkv, n_sp)) if debug_counts else o
