"""Pallas TPU flash-attention: forward AND backward (trainable), on
SPARSITY-AWARE grids.

Forward — classic tiling: grid (B*H, nQ, kv_steps) with the KV axis
innermost (sequential on TPU), online-softmax running stats in VMEM scratch
per Q tile.  GQA is handled in the BlockSpec index maps (KV tiles load from
head h // group).  The forward also emits the per-row softmax stats (m, l)
so the backward can recompute probabilities without the (S x S) matrix.

Backward — the Chen et al. recompute-over-store trade applied inside the
attention op, split into three kernels:

  * ``_bwd_delta_kernel``  D_i = rowsum(dO_i * O_i), grid (B*H, nQ) — the
    softmax-backward correction term, one f32 per row.
  * ``_bwd_dq_kernel``     grid (B*H, nQ, kv_steps), KV innermost:
    recompute P = exp(S - lse) from (m, l), dP = dO V^T, dS = P (dP - D),
    and accumulate dQ += dS K * scale in VMEM scratch.
  * ``_bwd_dkv_kernel``    grid (B*Hkv, nK, group, q_steps), Q innermost
    with the GQA group as the next-inner axis so dK/dV accumulate over
    every query head sharing the KV head before the single output write:
    dV += P^T dO, dK += dS^T Q * scale.

Residuals between fwd and bwd are q, k, v, o, m, l — O(S*D) per head, not
O(S^2); the score/probability matrices are recomputed tile-by-tile (an
extra ~2x of the forward QK^T FLOPs across dQ+dKV, the flash trade).

Sparse grids — Pallas grids are dense rectangles, but masked schedules
(causal / sliding window / padded kv_len) leave whole tiles with no live
position.  ``kv_tile_bounds`` / ``q_tile_bounds`` (hoisted into
``repro.kernels.tiling``, shared with the kvq split-K decode kernel)
derive, from the same geometry as ``_position_mask``, the inclusive tile
range each grid row actually has to visit, and the kernels exploit them
three ways:

  1. the forward and dQ grids remap their KV axis to a *wedge*: step ``j``
     of q tile ``qi`` loads KV tile ``lo(qi) + j`` and the axis extent is
     ``max_i (hi(i) - lo(i) + 1)`` — for windowed schedules the grid itself
     shrinks to ~W/S of the dense step count;
  2. the dKV grid mirrors the trick on its innermost Q axis
     (``qi ∈ [first_unmasked_q(ki), nQ)`` for causal, banded for window);
  3. where the extent cannot shrink statically (causal: the last q tile
     still needs every KV tile), a ``pl.when`` whole-tile early-out skips
     the QK/PV matmuls of unvisited steps while the online-softmax carry /
     accumulators thread through untouched.  The online-softmax init /
     finalize move to the remapped first / last *visited* step.

Skipped steps clamp their BlockSpec index to the last visited tile, so
Pallas re-uses the resident block instead of issuing a new DMA.  With
``debug_counts=True`` (interpret or compiled) every kernel additionally
returns per-tile-row counters of how many inner steps actually executed
their matmuls — the measured visited-tile counts that tests, benchmarks
and the memory planner's FLOP budgets are validated against
(:func:`tile_step_counts` is the analytic twin).

MXU shapes: every contraction is (128, D) x (D, 128) or (128, 128) x
(128, D) with D in {64, 128} — lane-aligned (ops.py guards other shapes).

Causal/window masking inside a visited tile still compares absolute
positions built from the (remapped) grid indices; ``kv_len`` masks padded
KV columns so ops.py's length padding is safe for non-causal attention too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The tile-bounds machinery lives in repro.kernels.tiling (shared with the
# kvq split-K decode grids); re-exported here because this module is the
# flash family's historical home for it.
from repro.kernels.tiling import (DEFAULT_BK, DEFAULT_BQ, NEG_INF,  # noqa: F401
                                  imax as _imax, imin as _imin,
                                  kv_tile_bounds, q_tile_bounds,
                                  kv_visits as _kv_visits,
                                  q_visits as _q_visits, tile_step_counts,
                                  when as _when)


def _position_mask(qi, ki, *, bq, bk, causal, window, kv_len, s_len):
    """(BQ, BK) bool validity mask from grid indices, or None if trivial.

    ``qi``/``ki`` are LOGICAL tile indices — on the sparse grids they are
    the remapped values (e.g. ``lo(qi) + j``), not raw program ids."""
    if not causal and kv_len >= s_len:
        return None
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = k_pos < kv_len
    if causal:
        ok &= q_pos >= k_pos
        if window > 0:
            ok &= (q_pos - k_pos) < window
    return ok


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------
def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref, *refs,
                  sm_scale, bq, bk, causal, window, kv_len, s_len, count):
    if count:
        (cnt_ref, m_ref, l_ref, acc_ref, cnt_acc) = refs
    else:
        (m_ref, l_ref, acc_ref) = refs
    qi = pl.program_id(1)
    ji = pl.program_id(2)                      # wedge step, NOT the KV tile
    lo, hi = kv_tile_bounds(qi, bq=bq, bk=bk, causal=causal, window=window,
                            kv_len=kv_len)
    ki = lo + ji                               # logical KV tile this step
    n_vis = hi - lo + 1
    # Static bounds (non-causal) shrink the grid axis to exactly n_vis, so
    # every step is visited; traced bounds (causal) keep a dense axis and
    # early-out the unvisited tail.
    visited = True if isinstance(n_vis, int) else ji < n_vis

    @pl.when(ji == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        if count:
            cnt_acc[0] = 0

    def _step():
        q = q_ref[...][0].astype(jnp.float32)                  # (BQ, D)
        k = k_ref[...][0].astype(jnp.float32)                  # (BK, D)
        v = v_ref[...][0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale

        ok = _position_mask(qi, ki, bq=bq, bk=bk, causal=causal,
                            window=window, kv_len=kv_len, s_len=s_len)
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        m_ref[...] = m_new
        if count:
            cnt_acc[0] += 1

    _when(visited, _step)

    @pl.when(ji == n_vis - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom[:, None])[None].astype(o_ref.dtype)
        m_out_ref[...] = m_ref[...].reshape(m_out_ref.shape)
        l_out_ref[...] = l_ref[...].reshape(l_out_ref.shape)
        if count:
            cnt_ref[...] = jnp.full(cnt_ref.shape, cnt_acc[0], jnp.int32)


def _kv_wedge_index(group, bounds_kw):
    """Index map for K/V on the (h, qi, j) wedge grids: step j of q tile i
    loads logical KV tile min(lo(i) + j, hi(i)) — clamping the unvisited
    tail to the last visited tile makes Pallas re-use the resident block
    (no DMA) on exactly the steps the kernel early-outs."""
    def index(h, i, j, g=group, kw=bounds_kw):
        lo, hi = kv_tile_bounds(i, **kw)
        return (h // g, _imin(lo + j, hi), 0)
    return index


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "sm_scale", "bq", "bk", "kv_len", "interpret",
    "debug_counts"))
def flash_attention_fwd_pallas(q, k, v, *, causal: bool = True,
                               window: int = 0,
                               sm_scale: float | None = None,
                               bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                               kv_len: int | None = None,
                               interpret: bool = False,
                               debug_counts: bool = False):
    """q: (BH, S, D); k, v: (BHkv, S, D) with BH = BHkv * group.

    Returns (o, m, l): output plus the per-row online-softmax stats
    (running max, running denominator), both (BH, S) f32 — the residuals
    the backward kernels recompute probabilities from.  With
    ``debug_counts`` also returns a (BH, nQ) int32 array counting the KV
    steps whose matmuls executed per q tile (the measured sparse-grid
    visit counts; compare against :func:`tile_step_counts`).

    Flat batch*head layout; the wrapper in ops.py folds (B, H) and GQA.
    S % bq == 0 and S % bk == 0 (ops.py pads); ``kv_len`` (< S when ops.py
    padded) masks the padded KV columns.
    """
    bh, s_len, d = q.shape
    bhkv = k.shape[0]
    group = bh // bhkv
    bq = min(bq, s_len)
    bk = min(bk, s_len)
    assert s_len % bq == 0 and s_len % bk == 0, (s_len, bq, bk)
    n_q = s_len // bq
    scale = sm_scale if sm_scale is not None else d ** -0.5
    kv_len = s_len if kv_len is None else kv_len
    bounds_kw = dict(bq=bq, bk=bk, causal=causal, window=window,
                     kv_len=kv_len)
    kv_steps = max(_kv_visits(s_len, **bounds_kw))

    out_specs = [
        pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        pl.BlockSpec((1, 1, bq), lambda h, i, j: (h, 0, i)),
        pl.BlockSpec((1, 1, bq), lambda h, i, j: (h, 0, i)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bh, s_len, d), q.dtype),
        jax.ShapeDtypeStruct((bh, 1, s_len), jnp.float32),
        jax.ShapeDtypeStruct((bh, 1, s_len), jnp.float32),
    ]
    if debug_counts:
        out_specs.append(pl.BlockSpec((1, 1, 1, 1), lambda h, i, j: (h, i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, n_q, 1, 1), jnp.int32))

    o, m, l, *cnt = pl.pallas_call(
        functools.partial(_flash_kernel, sm_scale=scale, s_len=s_len,
                          count=debug_counts, **bounds_kw),
        grid=(bh, n_q, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), _kv_wedge_index(group, bounds_kw)),
            pl.BlockSpec((1, bk, d), _kv_wedge_index(group, bounds_kw)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),        # running max
            pltpu.VMEM((bq,), jnp.float32),        # running denom
            pltpu.VMEM((bq, d), jnp.float32),      # output accumulator
        ] + ([pltpu.SMEM((1,), jnp.int32)] if debug_counts else []),
        interpret=interpret,
    )(q, k, v)
    m, l = m.reshape(bh, s_len), l.reshape(bh, s_len)
    return (o, m, l, cnt[0].reshape(bh, n_q)) if debug_counts else (o, m, l)


# ---------------------------------------------------------------------------
# Backward.
# ---------------------------------------------------------------------------
def _bwd_delta_kernel(o_ref, do_ref, delta_ref):
    """D = rowsum(dO * O): the softmax-backward correction, (BQ,) f32."""
    o = o_ref[...][0].astype(jnp.float32)
    do = do_ref[...][0].astype(jnp.float32)
    delta_ref[...] = (o * do).sum(axis=-1).reshape(delta_ref.shape)


def _recompute_probs(q, k, m, l, ok, *, sm_scale):
    """P = exp(S - lse) from saved stats; masked entries exactly zero."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    p = jnp.exp(s - lse[:, None])
    if ok is not None:
        p = jnp.where(ok, p, 0.0)
    return p


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, delta_ref,
                   dq_ref, *refs, sm_scale, bq, bk, causal, window, kv_len,
                   s_len, count):
    if count:
        (cnt_ref, acc_ref, cnt_acc) = refs
    else:
        (acc_ref,) = refs
    qi = pl.program_id(1)
    ji = pl.program_id(2)
    lo, hi = kv_tile_bounds(qi, bq=bq, bk=bk, causal=causal, window=window,
                            kv_len=kv_len)
    ki = lo + ji
    n_vis = hi - lo + 1
    visited = True if isinstance(n_vis, int) else ji < n_vis

    @pl.when(ji == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        if count:
            cnt_acc[0] = 0

    def _step():
        q = q_ref[...][0].astype(jnp.float32)                  # (BQ, D)
        k = k_ref[...][0].astype(jnp.float32)                  # (BK, D)
        v = v_ref[...][0].astype(jnp.float32)
        do = do_ref[...][0].astype(jnp.float32)
        m = m_ref[...][0, 0]
        l = l_ref[...][0, 0]
        delta = delta_ref[...][0, 0]

        ok = _position_mask(qi, ki, bq=bq, bk=bk, causal=causal,
                            window=window, kv_len=kv_len, s_len=s_len)
        p = _recompute_probs(q, k, m, l, ok, sm_scale=sm_scale)  # (BQ, BK)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        acc_ref[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)
        if count:
            cnt_acc[0] += 1

    _when(visited, _step)

    @pl.when(ji == n_vis - 1)
    def _done():
        dq_ref[...] = (acc_ref[...] * sm_scale)[None].astype(dq_ref.dtype)
        if count:
            cnt_ref[...] = jnp.full(cnt_ref.shape, cnt_acc[0], jnp.int32)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, delta_ref,
                    dk_ref, dv_ref, *refs, sm_scale, group, n_q, bq, bk,
                    causal, window, kv_len, s_len, count):
    # grid (B*Hkv, nK, group, q_steps): Q tiles innermost, then the GQA
    # group so dK/dV accumulate over every query head sharing this KV head
    # before the single output write.  The Q axis is the wedge: step ii of
    # KV tile ki touches logical q tile lo(ki) + ii.
    if count:
        (cnt_ref, dk_acc, dv_acc, cnt_acc) = refs
    else:
        (dk_acc, dv_acc) = refs
    ki = pl.program_id(1)
    gi = pl.program_id(2)
    ii = pl.program_id(3)
    lo, hi = q_tile_bounds(ki, bq=bq, bk=bk, causal=causal, window=window,
                           n_q=n_q, kv_len=kv_len)
    qi = lo + ii
    n_vis = hi - lo + 1
    visited = True if isinstance(n_vis, int) else ii < n_vis
    if kv_len < s_len:
        # whole-KV-tile early-out: a fully padded tile has no live q tile
        # at all (its dK/dV are zeros) — this axis can't shrink statically
        # because its neighbours still need their full Q range.
        live = ki * bk < kv_len
        visited = live if visited is True else visited & live

    @pl.when((gi == 0) & (ii == 0))
    def _init():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)
        if count:
            cnt_acc[0] = 0

    def _step():
        q = q_ref[...][0].astype(jnp.float32)                  # (BQ, D)
        k = k_ref[...][0].astype(jnp.float32)                  # (BK, D)
        v = v_ref[...][0].astype(jnp.float32)
        do = do_ref[...][0].astype(jnp.float32)
        m = m_ref[...][0, 0]
        l = l_ref[...][0, 0]
        delta = delta_ref[...][0, 0]

        ok = _position_mask(qi, ki, bq=bq, bk=bk, causal=causal,
                            window=window, kv_len=kv_len, s_len=s_len)
        p = _recompute_probs(q, k, m, l, ok, sm_scale=sm_scale)  # (BQ, BK)
        dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        if count:
            cnt_acc[0] += 1

    _when(visited, _step)

    @pl.when((gi == group - 1) & (ii == n_vis - 1))
    def _done():
        dk_ref[...] = (dk_acc[...] * sm_scale)[None].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...][None].astype(dv_ref.dtype)
        if count:
            cnt_ref[...] = jnp.full(cnt_ref.shape, cnt_acc[0], jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "sm_scale", "bq", "bk", "kv_len", "interpret",
    "debug_counts", "grad_dtypes"))
def flash_attention_bwd_pallas(q, k, v, o, m, l, do, *, causal: bool = True,
                               window: int = 0,
                               sm_scale: float | None = None,
                               bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                               kv_len: int | None = None,
                               interpret: bool = False,
                               debug_counts: bool = False,
                               grad_dtypes: "tuple | None" = None):
    """Backward from saved residuals: (dq, dk, dv).

    q, do: (BH, S, D); k, v: (BHkv, S, D); o: (BH, S, D); m, l: (BH, S)
    f32 stats from ``flash_attention_fwd_pallas``.  The score matrix is
    recomputed tile-by-tile in both the dQ and dKV kernels — residual
    memory stays O(S*D) — and both grids are sparse (see module docs).
    With ``debug_counts`` additionally returns (dq_counts (BH, nQ),
    dkv_counts (BHkv, nK)) of executed inner steps (the dKV counter sums
    over the GQA group: group * visited q tiles when the KV tile is live).

    ``grad_dtypes`` (dtype names for dq, dk, dv) overrides the output
    dtypes, which default to following q/k/v — under a residual policy
    the saved q/k/v are bf16 but the gradients should leave the f32 VMEM
    accumulators at the PRIMAL precision, not round-trip through bf16.
    """
    bh, s_len, d = q.shape
    bhkv = k.shape[0]
    group = bh // bhkv
    bq = min(bq, s_len)
    bk = min(bk, s_len)
    assert s_len % bq == 0 and s_len % bk == 0, (s_len, bq, bk)
    n_q, n_k = s_len // bq, s_len // bk
    scale = sm_scale if sm_scale is not None else d ** -0.5
    kv_len = s_len if kv_len is None else kv_len
    bounds_kw = dict(bq=bq, bk=bk, causal=causal, window=window,
                     kv_len=kv_len)
    mask_kw = dict(causal=causal, window=window, kv_len=kv_len, s_len=s_len)
    kv_steps = max(_kv_visits(s_len, **bounds_kw))
    q_steps = max(hi - lo + 1 for lo, hi in
                  (q_tile_bounds(j, bq=bq, bk=bk, causal=causal,
                                 window=window, n_q=n_q, kv_len=kv_len)
                   for j in range(n_k)))
    dq_dt, dk_dt, dv_dt = (q.dtype, k.dtype, v.dtype) if grad_dtypes is \
        None else (jnp.dtype(t) for t in grad_dtypes)

    delta = pl.pallas_call(
        _bwd_delta_kernel,
        grid=(bh, n_q),
        in_specs=[pl.BlockSpec((1, bq, d), lambda h, i: (h, i, 0)),
                  pl.BlockSpec((1, bq, d), lambda h, i: (h, i, 0))],
        out_specs=pl.BlockSpec((1, 1, bq), lambda h, i: (h, 0, i)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, s_len), jnp.float32),
        interpret=interpret,
    )(o, do)

    dq_out_specs = [pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0))]
    dq_out_shape = [jax.ShapeDtypeStruct((bh, s_len, d), dq_dt)]
    if debug_counts:
        dq_out_specs.append(pl.BlockSpec((1, 1, 1, 1),
                                         lambda h, i, j: (h, i, 0, 0)))
        dq_out_shape.append(jax.ShapeDtypeStruct((bh, n_q, 1, 1), jnp.int32))

    m, l = m.reshape(bh, 1, s_len), l.reshape(bh, 1, s_len)
    row_spec = pl.BlockSpec((1, 1, bq), lambda h, i, j: (h, 0, i))
    dq_out = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=scale, s_len=s_len,
                          count=debug_counts, **bounds_kw),
        grid=(bh, n_q, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), _kv_wedge_index(group, bounds_kw)),
            pl.BlockSpec((1, bk, d), _kv_wedge_index(group, bounds_kw)),
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            row_spec, row_spec, row_spec,
        ],
        out_specs=dq_out_specs,
        out_shape=dq_out_shape,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)]
        + ([pltpu.SMEM((1,), jnp.int32)] if debug_counts else []),
        interpret=interpret,
    )(q, k, v, do, m, l, delta)
    dq = dq_out[0]                 # out_shape is a list even without counts

    def _q_head(hk, j, gi, i, g=group):
        del j, i
        return hk * g + gi

    def _q_tile(hk, j, gi, i):
        del hk, gi
        lo, hi = q_tile_bounds(j, bq=bq, bk=bk, causal=causal, window=window,
                               n_q=n_q, kv_len=kv_len)
        return _imin(lo + i, hi)

    dkv_out_specs = [
        pl.BlockSpec((1, bk, d), lambda hk, j, gi, i: (hk, j, 0)),
        pl.BlockSpec((1, bk, d), lambda hk, j, gi, i: (hk, j, 0)),
    ]
    dkv_out_shape = [
        jax.ShapeDtypeStruct((bhkv, s_len, d), dk_dt),
        jax.ShapeDtypeStruct((bhkv, s_len, d), dv_dt),
    ]
    if debug_counts:
        dkv_out_specs.append(pl.BlockSpec(
            (1, 1, 1, 1), lambda hk, j, gi, i: (hk, j, 0, 0)))
        dkv_out_shape.append(jax.ShapeDtypeStruct((bhkv, n_k, 1, 1),
                                                  jnp.int32))
    q_row_spec = pl.BlockSpec(
        (1, 1, bq), lambda hk, j, gi, i: (_q_head(hk, j, gi, i), 0,
                                          _q_tile(hk, j, gi, i)))

    dkv_out = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=scale, n_q=n_q,
                          group=group, count=debug_counts, bq=bq, bk=bk,
                          **mask_kw),
        grid=(bhkv, n_k, group, q_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d),
                         lambda hk, j, gi, i: (_q_head(hk, j, gi, i),
                                               _q_tile(hk, j, gi, i), 0)),
            pl.BlockSpec((1, bk, d), lambda hk, j, gi, i: (hk, j, 0)),
            pl.BlockSpec((1, bk, d), lambda hk, j, gi, i: (hk, j, 0)),
            pl.BlockSpec((1, bq, d),
                         lambda hk, j, gi, i: (_q_head(hk, j, gi, i),
                                               _q_tile(hk, j, gi, i), 0)),
            q_row_spec, q_row_spec, q_row_spec,
        ],
        out_specs=dkv_out_specs,
        out_shape=dkv_out_shape,
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)]
        + ([pltpu.SMEM((1,), jnp.int32)] if debug_counts else []),
        interpret=interpret,
    )(q, k, v, do, m, l, delta)
    if debug_counts:
        dk, dv, dkv_counts = dkv_out
        return (dq, dk, dv, dq_out[1].reshape(bh, n_q),
                dkv_counts.reshape(bhkv, n_k))
    dk, dv = dkv_out
    return dq, dk, dv
