"""Chain profiling: measured per-layer activation bytes + recompute FLOPs.

The planner (``repro.plan.solver``) needs, for every candidate checkpoint
site, (a) how many bytes the activation at that site occupies and (b) how
expensive the layers before it are to re-run.  This module measures both
WITHOUT allocating anything:

  * activation bytes via ``jax.eval_shape`` walked layer-by-layer
    (``_tree_bytes`` — same accounting as
    ``repro.core.checkpoint.activation_bytes_of``, one fn at a time);
  * FLOPs via XLA's lowered cost analysis per layer (cheap — no compile),
    falling back to an analytic estimate when the backend refuses.

Two concrete chain walkers cover every model stack in the repo:

  * ``profile_resnet``      — the explicit ``cnn.layer_fns`` list (the
    paper's own experiment models; UNet-shaped byte profiles).
  * ``profile_transformer`` — the homogeneous block scan: bytes are the
    scan carry, FLOPs are analytic per block (window-aware, so hybrid
    archs with mixed global/sliding layers profile heterogeneously).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.plan.solver import (RematPlan, budget_boundaries,
                               min_peak_boundaries, plan_metrics)


@dataclasses.dataclass(frozen=True)
class ChainProfile:
    """Per-layer costs of a sequential chain (index i = layer i's output).

    ``resid_bytes`` (optional, same length) are per-layer BACKWARD
    residuals: bytes live while that layer's segment backward runs, beyond
    the checkpointable carry — e.g. the jnp attention path's f32 (S x ctx)
    probability matrix, or the flash custom_vjp path's O(S*D) softmax
    stats.  They widen the planner's live-set term but are never stored at
    checkpoint boundaries.
    """

    act_bytes: tuple[int, ...]
    flops: tuple[float, ...]
    labels: tuple[str, ...] = ()
    resid_bytes: tuple[int, ...] = ()

    def __post_init__(self):
        if len(self.act_bytes) != len(self.flops):
            raise ValueError("act_bytes and flops length mismatch")
        if self.resid_bytes and len(self.resid_bytes) != len(self.act_bytes):
            raise ValueError("resid_bytes and act_bytes length mismatch")

    @property
    def n_layers(self) -> int:
        return len(self.act_bytes)

    @property
    def resid_or_none(self) -> "tuple[int, ...] | None":
        """What the solvers take: None when no residuals were profiled."""
        return self.resid_bytes or None

    def total_bytes(self) -> int:
        return int(sum(self.act_bytes))

    def total_resid_bytes(self) -> int:
        return int(sum(self.resid_bytes))

    def total_flops(self) -> float:
        return float(sum(self.flops))

    def to_json(self) -> str:
        return json.dumps({"act_bytes": list(self.act_bytes),
                           "flops": list(self.flops),
                           "labels": list(self.labels),
                           "resid_bytes": list(self.resid_bytes)})

    @classmethod
    def from_json(cls, text: str) -> "ChainProfile":
        d = json.loads(text)
        return cls(tuple(d["act_bytes"]), tuple(d["flops"]),
                   tuple(d.get("labels", ())),
                   tuple(d.get("resid_bytes", ())))


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def _layer_flops(fn: Callable, x_sds) -> float:
    """XLA lowered cost analysis; analytic fallback (2 flops/output elem)."""
    try:
        cost = jax.jit(fn).lower(x_sds).cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        f = float(cost.get("flops", 0.0))
        if f > 0:
            return f
    except Exception:  # noqa: BLE001 - backend-dependent API; fall back
        pass
    out = jax.eval_shape(fn, x_sds)
    return float(2 * sum(x.size for x in jax.tree_util.tree_leaves(out)))


# ---------------------------------------------------------------------------
# Chain walkers.
# ---------------------------------------------------------------------------
def profile_sequential(layer_fns: Sequence[Callable], x0,
                       labels: Sequence[str] = ()) -> ChainProfile:
    """Walk an explicit layer-fn chain with eval_shape; never allocates."""
    x = jax.eval_shape(lambda a: a, x0)
    act, flops = [], []
    for fn in layer_fns:
        flops.append(_layer_flops(fn, x))
        x = jax.eval_shape(fn, x)
        act.append(_tree_bytes(x))
    return ChainProfile(tuple(act), tuple(flops),
                        tuple(labels) if labels else ())


def profile_resnet(params, cfg, image_sds) -> ChainProfile:
    """Profile the ResNet layer list ``checkpoint_sequential`` consumes."""
    from repro.models import cnn
    fns = cnn.layer_fns(params, cfg)
    labels = ["stem"] + [f"block{i}" for i in range(len(fns) - 2)] + ["head"]
    return profile_sequential(fns, image_sds, labels)


def flash_training_eligible(cfg) -> bool:
    """Would the training forward ACTUALLY dispatch to the flash kernel?

    Mirrors the dispatch gates end to end — ``transformer.forward`` (a
    uniform window schedule is required to pass a static window into the
    scan), ``attention.attn_block`` (non-MLA attention, 1-D rope
    positions) and ``ModelConfig.attn_impl`` ("auto" resolved by
    platform).  The planner must budget what the model will really do: a
    config whose attention runs the jnp path pays O(S^2) residuals.
    """
    if cfg.mixer not in ("attn", "hybrid") or cfg.mla is not None:
        return False
    if cfg.attn_impl() == "jnp":
        return False
    return not (cfg.global_layers or cfg.mrope_sections is not None)


def attn_resid_bytes(cfg, b: int, s: int, ctx: int,
                     dtype_bytes: int = 2,
                     flash_resid_bytes: "int | None" = None,
                     model_shards: int = 1) -> int:
    """Backward-residual bytes of one attention layer, backend-aware.

    Both paths keep q/o per query head and k/v per KV head alive between
    forward and backward.  On top of that the jnp path's autodiff saves
    the f32 (S x ctx) probability matrix per head — the O(S^2) term —
    while the flash custom_vjp saves only the two f32 softmax stat rows
    (m, l) per head and recomputes scores tile-by-tile in the backward
    kernels.  This is the modelling change that stops RematPlans budgeting
    phantom S^2 score tensors once the flash kernel really dispatches
    (:func:`flash_training_eligible` — NOT merely when the config asks
    for a flash backend).

    ``flash_resid_bytes`` is the per-element width of the SAVED flash
    (q, k, v, o) tuple when a ``Policy.flash_resid_dtype`` residual policy
    is active (e.g. 2 for bf16-stored residuals under f32 compute);
    default: residuals follow the compute dtype.  The (m, l) stats are
    budgeted at f32 regardless — exactly the kernel contract.

    ``model_shards`` divides the per-HEAD terms (q/o, k/v, probs, stats)
    when heads shard over the mesh's model axis (both head counts must
    divide — the same gate ``sharding.flash_shard_specs`` applies to the
    kernel dispatch, so the planner budgets exactly what each chip holds);
    an indivisible head count leaves residuals whole, matching the
    replicated fallback.
    """
    if cfg.mixer not in ("attn", "hybrid"):
        return 0
    ms = model_shards if (model_shards > 1
                          and cfg.n_heads % model_shards == 0
                          and cfg.n_kv % model_shards == 0) else 1
    if not flash_training_eligible(cfg):
        qo_kv = (2 * cfg.n_heads + 2 * cfg.n_kv) * b * s * cfg.head_dim \
            * dtype_bytes
        return (qo_kv + 4 * b * cfg.n_heads * s * ctx) // ms   # f32 probs
    rb = dtype_bytes if flash_resid_bytes is None else flash_resid_bytes
    qo_kv = (2 * cfg.n_heads + 2 * cfg.n_kv) * b * s * cfg.head_dim * rb
    return (qo_kv + 2 * 4 * b * cfg.n_heads * s) // ms     # f32 m, l rows


def _flash_tile_counts(cfg, s: int, tiles=None) -> "list[dict]":
    """Per layer, each flash kernel's visited/dense tile-step counts.

    Computed on the PADDED grid the kernels actually run (ops.py rounds S
    up to the 128-lane block and masks the tail via ``kv_len``), at the
    tiles each kernel runs (:func:`repro.kernels.tiling.flash_tiles`), or
    at ``tiles`` (one (bq, bk) for every grid) when given, from the same
    :func:`repro.kernels.tiling.tile_step_counts` bounds the kernels build
    their wedge grids from — planner budgets and measured ``debug_counts``
    counters agree tile-for-tile by construction.  Each layer maps a
    kernel name to ``{"steps", "area", "dense", "bq", "bk"}``: ``area`` is
    the visited positions per head, steps times bq * bk.
    """
    from repro.kernels import tiling
    from repro.kernels.flash import ops as flash_ops
    from repro.models import transformer
    s_pad = flash_ops.padded_seq_len(s)
    layers = []
    for w in (int(x) for x in transformer.layer_windows(cfg)):
        per = {}
        for kn in tiling.FLASH_KERNELS:
            bq, bk = tiles or tiling.flash_tiles(s_pad, cfg.head_dim,
                                                 window=w, kernel=kn)
            c = tiling.tile_step_counts(s_pad, bq=bq, bk=bk, causal=True,
                                        window=w, kv_len=s)
            per[kn] = {"steps": c[kn], "area": c[kn] * c["bq"] * c["bk"],
                       "dense": c["dense"], "bq": c["bq"], "bk": c["bk"]}
        layers.append(per)
    return layers


def flash_bwd_recompute_flops(cfg, b: int, s: int) -> tuple[float, ...]:
    """Per-layer extra FLOPs the flash backward spends recomputing scores.

    Both the dQ and dKV kernels re-run the QK^T contraction from the
    saved stats instead of loading a stored probability matrix — but only
    on the tiles their sparse grids actually visit: ``2 * BQ * BK * D``
    FLOPs per visited tile-step per (batch x head), summed over the dQ
    and dKV grids at their own tiles (causal visits ~1/2 of the dense
    rectangle at small tiles, window ~W/S).  Zero when the flash kernel
    would not actually dispatch (:func:`flash_training_eligible`) — e.g.
    ``attn_backend="jnp"`` (scores are stored, not recomputed) or
    non-attention layers.
    """
    if not flash_training_eligible(cfg):
        return tuple(0.0 for _ in range(cfg.n_layers))
    bh = b * cfg.n_heads * cfg.head_dim
    return tuple(2.0 * bh * (c["dq"]["area"] + c["dkv"]["area"])
                 for c in _flash_tile_counts(cfg, s))


def flash_attn_flop_report(cfg, b: int, s: int, *, tiles=None) -> dict:
    """Dense-vs-visited attention FLOPs across the three sparse grids.

    Counts every matmul each grid runs per visited tile-step — forward
    (QK^T, PV: 4·BQ·BK·D flops), dQ (score recompute, dP, dS·K: 6), dKV
    (score recompute, P^T·dO, dP, dS^T·Q: 8) — against the same matmuls
    on the dense nQ x nK rectangle a mask-blind grid executes, each grid
    at the tiles it runs.  This is what dryrun train cells and the trainer
    banner report as the sparse-grid FLOP claw-back.
    ``tiles`` counts every grid at one (bq, bk) instead, e.g. (128, 128)
    for the claw-back on fine grids.
    """
    if not flash_training_eligible(cfg):
        return {"eligible": False, "dense_flops": 0.0, "visited_flops": 0.0,
                "skip_frac": 0.0, "visited_tile_steps": 0,
                "dense_tile_steps": 0}
    bh = b * cfg.n_heads * cfg.head_dim
    dense = visited = 0.0
    vis_steps = dense_steps = 0
    for c in _flash_tile_counts(cfg, s, tiles):
        for kn, per_pos in (("fwd", 4.0), ("dq", 6.0), ("dkv", 8.0)):
            k = c[kn]
            visited += bh * per_pos * k["area"]
            dense += bh * per_pos * k["dense"] * k["bq"] * k["bk"]
            vis_steps += k["steps"]
            dense_steps += k["dense"]
    return {"eligible": True, "dense_flops": dense, "visited_flops": visited,
            "skip_frac": 1.0 - (vis_steps / dense_steps if dense_steps
                                else 0.0),
            "visited_tile_steps": vis_steps, "dense_tile_steps": dense_steps}


def decode_tile_report(cfg, b: int, s: int, *, lengths=None, splits: int = 1,
                       block_s: int | None = None) -> dict:
    """Visited-vs-dense tile accounting for split-K int8 KV decode.

    The serve-side mirror of :func:`flash_attn_flop_report`: per layer,
    how many KV tile-steps the length-aware split-K decode kernel
    (``kernels/kvq``) actually executes versus the dense per-(batch,
    kv-head) sweep a length- and window-blind kernel over the full
    S-slot single-tier cache would pay, with the FLOPs and int8 cache
    bytes those tiles carry.  Visited counts come from the SAME
    ``tiling.decode_tile_step_counts`` bounds the kernel builds its grid
    and early-outs from, so the report and the measured ``debug_counts``
    counters agree tile-for-tile by construction.

    Two-tier geometry is honored: windowed layers (``cfg.window`` > 0,
    not in ``cfg.global_layers``) serve from a rolling W-slot buffer, so
    their per-layer cache length — and with it the split-K axis — shrinks
    statically to ~W/BS tiles (``min(window, s)``), and per-batch
    ``lengths`` clamp to it.  ``lengths=None`` budgets a full cache
    (steady-state worst case); pass the ragged batch for serving-time
    accounting.
    """
    from repro.kernels import tiling
    from repro.models import transformer
    zeros = {"eligible": False, "visited_tile_steps": 0,
             "dense_tile_steps": 0, "visited_flops": 0.0, "dense_flops": 0.0,
             "visited_kv_bytes": 0, "dense_kv_bytes": 0, "skip_frac": 0.0,
             "per_layer": []}
    if cfg.mixer not in ("attn", "hybrid") or cfg.mla is not None:
        return zeros                 # MLA/SSM caches aren't the kvq layout
    if lengths is not None and len(lengths) != b:
        raise ValueError(f"decode_tile_report: {len(lengths)} lengths for "
                         f"batch {b} — the visited/dense ratio would mix "
                         f"batch sizes")
    lens = [s] * b if lengths is None else [int(x) for x in lengths]
    hkv, g, d = cfg.n_kv, cfg.n_heads // cfg.n_kv, cfg.head_dim
    bs_kw = {} if block_s is None else {"block_s": block_s}
    # dense baseline: the old sequential sweep over a full S-slot
    # single-tier cache, every tile visited (no lengths, no two-tier)
    c_full = tiling.decode_tile_step_counts(s, None, **bs_kw)
    per_layer = []
    visited = dense = vis_fl = den_fl = vis_by = den_by = 0
    for w in (int(x) for x in transformer.layer_windows(cfg)):
        s_l = s if w <= 0 else min(w, s)
        c = tiling.decode_tile_step_counts(
            s_l, [min(ln, s_l) for ln in lens], splits=splits, **bs_kw)
        vis, den = c["visited"], b * c_full["ns"]
        # per (batch, kv-head) tile-step: QK^T (G,D)x(D,BS) + PV
        # (G,BS)x(BS,D) = 4*G*D*BS flops; int8 K+V tiles + f32 scales
        tile_fl = lambda bs_: 4.0 * g * d * bs_ * hkv
        tile_by = lambda bs_: hkv * (2 * bs_ * d + 2 * bs_ * 4)
        per_layer.append({"window": w, "cache_len": s_l, "bs": c["bs"],
                          "splits": c["splits"], "visited": vis,
                          "dense": den})
        visited += vis
        dense += den
        vis_fl += vis * tile_fl(c["bs"])
        den_fl += den * tile_fl(c_full["bs"])
        vis_by += vis * tile_by(c["bs"])
        den_by += den * tile_by(c_full["bs"])
    return {"eligible": True, "visited_tile_steps": visited,
            "dense_tile_steps": dense, "visited_flops": vis_fl,
            "dense_flops": den_fl, "visited_kv_bytes": vis_by,
            "dense_kv_bytes": den_by,
            "skip_frac": 1.0 - (visited / dense if dense else 0.0),
            "per_layer": per_layer}


def kv_cache_report(cfg, b: int, s: int) -> dict:
    """int8-vs-f32 KV-cache bytes at serve time, two-tier aware.

    int8 counts the deployed encoding (1 B/elem K+V plus the two f32
    per-token scale rows); f32 is the un-encoded strawman.  Windowed
    layers are sized at their rolling ``min(window, s)`` buffer — the
    same geometry :func:`decode_tile_report` budgets tiles on.
    """
    from repro.models import transformer
    if cfg.mixer not in ("attn", "hybrid") or cfg.mla is not None:
        return {"eligible": False, "int8_bytes": 0, "f32_bytes": 0,
                "ratio": 0.0}
    hkv, d = cfg.n_kv, cfg.head_dim
    int8 = f32 = 0
    for w in (int(x) for x in transformer.layer_windows(cfg)):
        s_l = s if w <= 0 else min(w, s)
        tokens = b * hkv * s_l
        int8 += 2 * tokens * d + 2 * tokens * 4
        f32 += 2 * tokens * d * 4
    return {"eligible": True, "int8_bytes": int8, "f32_bytes": f32,
            "ratio": f32 / int8 if int8 else 0.0}


def serve_capacity_report(cfg, s_max: int, budget_bytes: int, *,
                          quantized: bool = True,
                          params_bytes: int = 0, mesh=None) -> dict:
    """Max resident request slots a serve-memory budget admits.

    The serving mirror of the training budget solver: the slot pool
    (``repro.serve``) preallocates its decode cache at ``(max_slots,
    s_max)``, so capacity is ``(budget - params) // bytes_per_slot``.
    ``bytes_per_slot`` is EXACT — eval_shape over ``init_cache`` at batch
    1, counting every leaf the pool actually allocates (int8 K/V + f32
    scale rows, or the bf16 leaves when not quantized, plus SSM/conv
    state on hybrid archs).  ``kv_int8_bytes_per_slot`` cross-references
    :func:`kv_cache_report`'s two-tier accounting for the attention share.

    With ``mesh``, ``budget_bytes`` means bytes PER CHIP (the same
    contract the training planner applies): each K/V leaf divides by the
    shard factor ``sharding.serve_kv_shard`` actually applies on that
    mesh, giving ``bytes_per_slot_per_device``, and ``max_slots`` becomes
    what one chip's budget admits — slots are replicated across the mesh
    (every device holds its slice of EVERY slot), so one chip bounds
    residency.  ``bytes_per_slot_per_device x model_shards >=
    bytes_per_slot`` never rounds capacity up.
    """
    from repro.models import transformer
    cache_sds = jax.eval_shape(
        lambda: transformer.init_cache(cfg, 1, s_max, quantized=quantized))
    bytes_per_slot = sum(x.size * x.dtype.itemsize
                         for k, x in cache_sds.items() if k != "pos")
    shard = 1
    kv_mode = "none"
    devices = 1
    if mesh is not None:
        from repro.distributed import sharding as shd
        devices = mesh.size
        kv_mode = shd.serve_kv_shard(mesh, cfg.n_kv, s_max)
        if kv_mode != "none":
            shard = mesh.shape["model"]
    per_dev = sum(
        (x.size * x.dtype.itemsize)
        // (shard if k in ("k", "v", "k_scale", "v_scale") else 1)
        for k, x in cache_sds.items() if k != "pos")
    kv_rep = kv_cache_report(cfg, 1, s_max)
    usable = max(0, int(budget_bytes) - int(params_bytes))
    return {
        "eligible": bytes_per_slot > 0,
        "bytes_per_slot": int(bytes_per_slot),
        "bytes_per_slot_per_device": int(per_dev),
        "kv_int8_bytes_per_slot": int(kv_rep["int8_bytes"]),
        "budget_bytes": int(budget_bytes),
        "params_bytes": int(params_bytes),
        "max_slots": (usable // per_dev) if per_dev else 0,
        "devices": int(devices),
        "model_shards": int(shard),
        "kv_shard": kv_mode,
        "s_max": int(s_max),
        "quantized": bool(quantized),
    }


def profile_transformer(cfg, batch_sds, *, dtype_bytes: int = 2,
                        flash_resid_bytes: "int | None" = None,
                        model_shards: int = 1) -> ChainProfile:
    """Profile the block scan: carry bytes + window-aware analytic FLOPs.

    ``batch_sds`` is the train input-spec dict ({tokens: (B, S), ...}).
    The checkpointable site between scanned blocks is the (B, S, D) carry;
    per-block FLOPs are 2 * tokens * block_params (matmuls) plus the
    attention-score term, which varies per layer for windowed/hybrid archs
    (``cfg.window`` + ``cfg.global_layers``) — the source of heterogeneity
    the budget solver exploits.  ``resid_bytes`` carries the backend-aware
    attention backward residuals (:func:`attn_resid_bytes`): O(S^2) on the
    jnp path, O(S*D) on the flash (interpret/pallas) path;
    ``flash_resid_bytes`` forwards a residual-policy dtype width
    (``Policy.flash_resid_dtype``).

    Attention-score FLOPs are dispatch-honest: the jnp paths execute the
    dense (masked) score matmul, but the flash kernels run SPARSE grids
    that skip whole-masked KV tiles — so flash-eligible layers are
    budgeted at the visited-tile count (causal ~1/2 of dense, window
    ~W/S), exactly what the remat DP pays to recompute that layer.

    ``model_shards`` (the mesh's TP width) makes the profile PER-DEVICE:
    ``batch_sds`` is already the per-device microbatch (DP divides batch
    upstream, ``train_step.microbatch_specs``), the (B, S, D) carry is
    replicated over the model axis so it stays whole, and the attention
    residuals divide by the head shards each chip actually holds
    (:func:`attn_resid_bytes`) — together ``--mem-budget-mb`` means bytes
    per CHIP, on every mesh.
    """
    from repro.models import transformer
    b, s = batch_sds["tokens"].shape
    carry_bytes = b * s * cfg.d_model * dtype_bytes

    params_sds = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    block_elems = sum(x.size for x in
                      jax.tree_util.tree_leaves(params_sds["blocks"]))
    per_block_params = block_elems / cfg.n_layers

    windows = [int(w) for w in transformer.layer_windows(cfg)]
    flash = flash_training_eligible(cfg)
    tile_counts = _flash_tile_counts(cfg, s) if flash else None
    act, flops, labels, resid = [], [], [], []
    for i, w in enumerate(windows):
        ctx = s if w == 0 else min(w, s)
        attn_flops = 0.0
        if cfg.mixer in ("attn", "hybrid"):
            if flash:
                attn_flops = 4.0 * b * cfg.n_heads * cfg.head_dim \
                    * tile_counts[i]["fwd"]["area"]
            else:
                attn_flops = 4.0 * b * s * ctx * cfg.n_heads * cfg.head_dim
        flops.append(2.0 * b * s * per_block_params + attn_flops)
        act.append(carry_bytes)
        resid.append(attn_resid_bytes(cfg, b, s, ctx, dtype_bytes,
                                      flash_resid_bytes=flash_resid_bytes,
                                      model_shards=model_shards))
        labels.append(f"block{i}" + ("" if w == 0 else f"@w{w}"))
    return ChainProfile(tuple(act), tuple(flops), tuple(labels),
                        tuple(resid))


# ---------------------------------------------------------------------------
# Profile -> plan.
# ---------------------------------------------------------------------------
def plan_min_peak(profile: ChainProfile, num_checkpoints: int,
                  policy: str = "full") -> RematPlan:
    """Dual solver: best placement of a fixed number of checkpoints."""
    bounds = min_peak_boundaries(profile.act_bytes, num_checkpoints,
                                 resid_bytes=profile.resid_or_none)
    return RematPlan(profile.n_layers, tuple(bounds), policy,
                     source=f"min_peak:k={num_checkpoints}")


def plan_for_budget(profile: ChainProfile, budget_bytes: float,
                    policy: str = "full") -> RematPlan:
    """Primal solver: min recompute FLOPs with peak bytes <= budget.

    An unsatisfiable budget yields the peak-minimal best-effort plan,
    tagged ``:infeasible`` in ``source`` AND warned about — every consumer
    (trainer --remat auto, TrainConfig.mem_budget_mb)
    funnels through here, so the violated constraint is never silent.
    """
    import warnings

    bounds, feasible = budget_boundaries(profile.act_bytes, profile.flops,
                                         budget_bytes,
                                         resid_bytes=profile.resid_or_none)
    tag = f"budget:{int(budget_bytes)}" + ("" if feasible else ":infeasible")
    if not feasible:
        peak = plan_metrics(profile.act_bytes, profile.flops, bounds,
                            resid_bytes=profile.resid_or_none)["peak_bytes"]
        warnings.warn(
            f"remat budget {budget_bytes/2**20:.1f} MiB is infeasible for "
            f"this chain; best-effort plan peaks at {peak/2**20:.1f} MiB "
            f"(min achievable)", stacklevel=2)
    return RematPlan(profile.n_layers, tuple(bounds), policy, source=tag)


def plan_report(profile: ChainProfile, plan: RematPlan) -> dict:
    """Human/JSON-facing summary of a plan against its profile."""
    m = plan_metrics(profile.act_bytes, profile.flops, plan.boundaries,
                     resid_bytes=profile.resid_or_none)
    return {
        "source": plan.source,
        "n_layers": plan.n_layers,
        "boundaries": list(plan.boundaries),
        "segment_sizes": plan.segment_sizes(),
        **m,
        "recompute_frac": (m["recompute_flops"] / profile.total_flops()
                           if profile.total_flops() else 0.0),
        "no_remat_bytes": profile.total_bytes(),
        "resid_bytes_total": profile.total_resid_bytes(),
    }
