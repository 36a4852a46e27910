"""Shared tile-bounds machinery for the sparse Pallas grids.

Both kernel families that walk a KV axis — ``kernels/flash`` (prefill /
training attention) and ``kernels/kvq`` (split-K int8 decode) — shape their
grids from the same idea: masked schedules (causal, sliding window, padded
``kv_len``, per-batch decode ``lengths``) leave whole tiles with no live
position, and the bounds that say *which* tiles are live are pure
arithmetic that can run on Python ints (static grid sizing, planner
budgets, analytic visit counts) and on traced values (BlockSpec index
maps, scalar-prefetch refs, kernel bodies) alike.  This module is that one
arithmetic source; the kernels, the memory planner and the tests all
import it so measured and budgeted tile counts can never drift apart
silently.

Flash (prefill/training) bounds: :func:`kv_tile_bounds`,
:func:`q_tile_bounds`, :func:`tile_step_counts` — see
``kernels/flash/kernel.py`` for how the wedge grids consume them.
:func:`flash_tiles` picks each flash kernel's (bq, bk) from the shape,
and :func:`flash_vmem_bytes` is the VMEM estimate it checks them against.

Decode (split-K) bounds: :func:`resolve_decode_grid` sizes the
(splits, steps-per-split) axes, :func:`decode_last_live_tile` turns a
per-batch ``length`` into the last KV tile worth visiting (Python int or
traced scalar-prefetch read), and :func:`decode_tile_step_counts` is the
analytic twin of the decode kernel's ``debug_counts`` counters.
"""
from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30
DEFAULT_BQ = 128
DEFAULT_BK = 128
DEFAULT_DECODE_BS = 512

#: the flash kernels whose tiles :func:`flash_tiles` chooses
FLASH_KERNELS = ("fwd", "dq", "dkv")
#: the largest flash tile side; past it a v5e gains under 10% a call
FLASH_TILE_CAP = 1024
#: VMEM a Mosaic kernel may use on a TPU v5e without asking for more
V5E_SCOPED_VMEM = 16 * 2**20
#: (bq, bk)-sized f32 values that each kernel body keeps in VMEM at once,
#: calibrated from the least scoped limit Mosaic compiles each kernel under
_FLASH_TILE_TEMPS = {"fwd": 2, "dq": 2, "dkv": 3}


def imin(a, b):
    """min that stays a Python int on Python ints (static grid sizing)
    and lowers to jnp.minimum on traced indices (index maps, kernels)."""
    if isinstance(a, int) and isinstance(b, int):
        return min(a, b)
    return jnp.minimum(a, b)


def imax(a, b):
    if isinstance(a, int) and isinstance(b, int):
        return max(a, b)
    return jnp.maximum(a, b)


def when(pred, fn):
    """pl.when that constant-folds Python-bool predicates."""
    from jax.experimental import pallas as pl
    if pred is True:
        fn()
    elif pred is not False:
        pl.when(pred)(fn)


# ---------------------------------------------------------------------------
# Flash (prefill / training) grids.
# ---------------------------------------------------------------------------
def kv_tile_bounds(qi, *, bq, bk, causal, window, kv_len):
    """Inclusive KV-tile range [lo, hi] that q tile ``qi`` must visit.

    Derived from the same geometry as the flash kernels' position mask: a
    KV tile outside [lo, hi] contains no (q_pos, k_pos) pair that the mask
    admits for any row of q tile ``qi``.  Pure arithmetic — ``qi`` may be
    a Python int (static grid sizing, visit counting) or a traced grid
    index (BlockSpec index maps, kernel bodies); non-causal bounds are
    always Python ints, so a padded KV tail shrinks the grid statically.

    ``hi`` is clamped >= ``lo`` so every q tile visits at least one step
    (the online-softmax finalize needs a step to run on; a fully-masked
    row zeroes itself through the in-tile mask).
    """
    hi_valid = -(-kv_len // bk) - 1            # last non-padded KV tile
    if not causal:
        return 0, hi_valid
    hi = imin(hi_valid, ((qi + 1) * bq - 1) // bk)
    lo = 0
    if window > 0:
        lo = imax(0, (qi * bq - (window - 1)) // bk)
        hi = imax(hi, lo)
    return lo, hi


def q_tile_bounds(ki, *, bq, bk, causal, window, n_q, kv_len):
    """Inclusive Q-tile range [lo, hi] that KV tile ``ki`` must visit on
    the dKV grid (which q tiles can attend into this KV tile).  Same
    contract as :func:`kv_tile_bounds`; the window reach is measured from
    the last LIVE position of the tile (``kv_len`` ragged tail), so the
    bounds are tight even on the ragged tile.  Fully-padded KV tiles
    (beyond ``kv_len``) keep a one-step range and are compute-skipped
    in-kernel via the ``pl.when`` early-out instead (their dK/dV are
    zeros)."""
    if not causal:
        return 0, n_q - 1
    lo = imin((ki * bk) // bq, n_q - 1)
    hi = n_q - 1
    if window > 0:
        khi = imax(imin((ki + 1) * bk, kv_len), ki * bk + 1) - 1
        hi = imin(hi, (khi + window - 1) // bq)
        hi = imax(hi, lo)
    return lo, hi


def kv_visits(s_len, *, bq, bk, causal, window, kv_len):
    """Per-q-tile visited KV-step counts (Python ints; fwd and dQ grids)."""
    return [hi - lo + 1 for lo, hi in
            (kv_tile_bounds(i, bq=bq, bk=bk, causal=causal, window=window,
                            kv_len=kv_len) for i in range(s_len // bq))]


def q_visits(s_len, *, bq, bk, causal, window, kv_len):
    """Per-KV-tile visited Q-step counts (dKV grid, per GQA group member).
    Fully-padded KV tiles count 0 — the kernel's early-out skips them."""
    n_q = s_len // bq
    out = []
    for j in range(s_len // bk):
        if j * bk >= kv_len:
            out.append(0)
            continue
        lo, hi = q_tile_bounds(j, bq=bq, bk=bk, causal=causal, window=window,
                               n_q=n_q, kv_len=kv_len)
        out.append(hi - lo + 1)
    return out


def tile_step_counts(s_len, *, bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                     causal: bool = True, window: int = 0,
                     kv_len: int | None = None) -> dict:
    """Analytic visited-vs-dense tile-step counts, per attention head.

    The exact twin of the flash kernels' ``debug_counts`` counters:
    ``fwd`` and ``dq`` sum the wedge-grid KV steps whose matmuls execute,
    ``dkv`` the Q steps per GQA group member, and ``dense`` is the
    nQ * nK rectangle a mask-blind grid would run.  The planner's flash
    FLOP budgets (``repro.plan.flash_bwd_recompute_flops``) and the
    benchmark claw-back numbers are both computed from these counts, so
    kernel, planner and report can never drift apart silently.
    """
    kv_len = s_len if kv_len is None else kv_len
    bq, bk = min(bq, s_len), min(bk, s_len)
    kw = dict(bq=bq, bk=bk, causal=causal, window=window, kv_len=kv_len)
    fwd = sum(kv_visits(s_len, **kw))
    dkv = sum(q_visits(s_len, **kw))
    return {"fwd": fwd, "dq": fwd, "dkv": dkv,
            "dense": (s_len // bq) * (s_len // bk),
            "bq": bq, "bk": bk}


def flash_vmem_bytes(bq: int, bk: int, d: int, *, kernel: str) -> int:
    """Estimated VMEM of one flash kernel at tiles (bq, bk), head dim d.

    Pallas double-buffers every block it pipelines; scratch and the
    body's live values are single.  Blocks are counted at 4 bytes an
    element, which bounds both bf16 and f32 callers.  A (1, bq) f32 row
    block (m, l, delta) fills 8 sublanes.
    """
    row = 8 * 4 * bq
    if kernel == "fwd":      # q, k, v, o; m, l; scratch acc, m, l
        blocks = (2 * bq + 2 * bk) * d * 4 + 2 * row
        scratch = bq * d * 4 + 2 * row
        wide = bk * d * 4                           # v widened for P.V
    elif kernel == "dq":     # q, dO, dQ, k, v; m, l, delta; scratch acc
        blocks = (3 * bq + 2 * bk) * d * 4 + 3 * row
        scratch = bq * d * 4
        wide = bk * d * 4                           # k widened for dS.K
    elif kernel == "dkv":    # q, dO, k, v, dK, dV; m, l, delta; dK/dV acc
        blocks = (2 * bq + 4 * bk) * d * 4 + 3 * row
        scratch = 2 * bk * d * 4
        wide = 2 * bq * d * 4                       # dO and q widened
    else:
        raise ValueError(f"flash_vmem_bytes: unknown kernel {kernel!r}; "
                         f"expected one of {FLASH_KERNELS}")
    temps = _FLASH_TILE_TEMPS[kernel] * bq * bk * 4
    return 2 * blocks + scratch + wide + temps


def flash_tiles(s_len: int, d: int, *, window: int = 0,
                kernel: str = "fwd") -> tuple[int, int]:
    """(bq, bk) for one flash kernel on a (padded) sequence of ``s_len``.

    Every grid step costs a fixed fraction of a microsecond on top of its
    matmuls, so each kernel takes the largest tiles that
    :data:`FLASH_TILE_CAP`, the sequence and the v5e's default scoped VMEM
    (:func:`flash_vmem_bytes`) allow.  Tiles are multiples of 128 that
    divide ``s_len``; a sequence of one block or less is one tile.  On a
    windowed layer neither side exceeds the window (rounded down to 128),
    so the band is not swamped by masked area.  Among equal areas the
    squarer pair wins, as it wastes less of the causal diagonal, then the
    wider KV side.
    """
    if kernel not in FLASH_KERNELS:
        raise ValueError(f"flash_tiles: unknown kernel {kernel!r}; "
                         f"expected one of {FLASH_KERNELS}")
    if s_len <= 128:
        return s_len, s_len
    if s_len % 128:
        raise ValueError(f"flash_tiles: S={s_len} is not a multiple of 128 "
                         "(flash/ops.padded_seq_len pads it)")
    cap = FLASH_TILE_CAP
    if window > 0:
        cap = min(cap, max(128, window // 128 * 128))
    sides = [t for t in range(128, min(cap, s_len) + 1, 128) if s_len % t == 0]
    fits = [(bq, bk) for bq in sides for bk in sides
            if flash_vmem_bytes(bq, bk, d, kernel=kernel) <= V5E_SCOPED_VMEM]
    return max(fits, key=lambda t: (t[0] * t[1], -abs(t[0] - t[1]), t[1]))


# ---------------------------------------------------------------------------
# Split-K decode grid (kernels/kvq).
# ---------------------------------------------------------------------------
def resolve_decode_block(s: int, block_s: int) -> int:
    """Largest power-of-two-ish shrink of ``block_s`` that divides S."""
    bs = min(block_s, s)
    while s % bs:
        bs //= 2
    assert bs >= 1, (s, block_s)
    return bs


def resolve_decode_grid(s: int, *, block_s: int = DEFAULT_DECODE_BS,
                        splits: int = 1) -> tuple[int, int, int, int]:
    """-> (bs, ns, splits_eff, steps_per_split) for a length-S KV cache.

    ``splits`` is clamped to the tile count (a split with no tiles would
    be pure overhead); the last split's structural padding tiles
    (``splits_eff * steps_per_split > ns``) are early-outed in-kernel and
    never counted by :func:`decode_tile_step_counts`.
    """
    bs = resolve_decode_block(s, block_s)
    ns = s // bs
    splits_eff = max(1, min(int(splits), ns))
    spt = -(-ns // splits_eff)
    return bs, ns, splits_eff, spt


def decode_last_live_tile(length, *, bs, ns):
    """Last KV tile a batch row with ``length`` valid slots must visit
    (inclusive; clamped to [0, ns-1] so index maps always point at a real
    tile).  ``length`` may be a Python int or a traced scalar-prefetch
    read — same dual contract as :func:`kv_tile_bounds`."""
    return imin(ns - 1, imax(0, (length + bs - 1) // bs - 1))


def decode_tile_step_counts(s: int, lengths=None, *,
                            block_s: int = DEFAULT_DECODE_BS,
                            splits: int = 1) -> dict:
    """Analytic twin of the split-K decode kernel's ``debug_counts``.

    ``lengths``: per-batch valid cache lengths (ints), or None (= every
    slot valid).  ``counts[b][k]`` is the number of KV tile-steps split
    ``k`` of batch row ``b`` actually executes — tiles whose start lies
    below ``lengths[b]`` — exactly the kernel's ``pl.when`` predicate.
    ``dense`` is the B * ns tile-steps a length-blind sequential sweep
    pays per kv head.  The planner's decode report
    (``repro.plan.decode_tile_report``) builds on these counts.
    """
    bs, ns, splits_eff, spt = resolve_decode_grid(s, block_s=block_s,
                                                  splits=splits)
    lens = [s] if lengths is None else [int(x) for x in lengths]
    counts = []
    for ln in lens:
        if ln <= 0:
            counts.append([0] * splits_eff)
            continue
        hi = decode_last_live_tile(ln, bs=bs, ns=ns)
        counts.append([max(0, min(hi, min((k + 1) * spt, ns) - 1)
                           - k * spt + 1)
                       for k in range(splits_eff)])
    visited = sum(sum(row) for row in counts)
    return {"bs": bs, "ns": ns, "splits": splits_eff, "spt": spt,
            "counts": counts, "visited": visited,
            "dense": len(lens) * ns}
