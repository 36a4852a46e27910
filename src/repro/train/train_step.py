"""Sharded training step factory: pjit(DP+TP) x OpTorch S-C x M-P x accum.

``make_train_step`` assembles the full production step:
  - mixed precision (Policy + optional fp16 dynamic loss scaling),
  - sequential-checkpoint remat over the layer scan,
  - gradient accumulation (lax.scan over microbatches, fp32 accumulators),
  - AdamW with clipping/schedule,
and jits it with explicit in/out shardings from repro.distributed.sharding.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.checkpoint import CheckpointConfig
from repro.core.mixed_precision import LossScale, Policy, get_policy, \
    scaled_value_and_grad
from repro.distributed import sharding as shd
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.optim import adamw


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    policy: str = "bf16"
    remat: CheckpointConfig = CheckpointConfig(enabled=True, policy="full",
                                               segment_size=1)
    accum: int = 1                      # gradient-accumulation microbatches
    scan_unroll: int = 1                # layer-scan unroll (dry-run costing)
    use_loss_scale: bool = False        # fp16 path
    skip_nonfinite: bool = False        # NaN/Inf-grad steps apply no update
    #   (fp16 loss scaling always skips; this extends the in-jit guard to
    #   the other policies — see train/guards.py for the escalation layer)
    opt: adamw.AdamWConfig = adamw.AdamWConfig()
    mem_budget_mb: int = 0              # >0: auto-solve a RematPlan to fit


def microbatch_specs(batch_sds: dict, *, accum: int = 1, mesh=None) -> dict:
    """PER-DEVICE microbatch token spec — the unit the remat planner must
    budget for: global batch / (data-parallel shards x accum steps).
    The ONE place this formula lives; launch/train and dryrun reuse it."""
    b, s = batch_sds["tokens"].shape
    dp = shd.dp_size(mesh) if mesh is not None else 1
    return {"tokens": jax.ShapeDtypeStruct(
        (max(1, b // (dp * max(1, accum))), s), jnp.int32)}


def plan_profile(cfg: ModelConfig, tc: TrainConfig, batch_sds: dict,
                 mesh=None):
    """The ChainProfile the planner budgets against for this train config:
    per-device microbatch, in the policy's compute dtype.  Single source —
    resolve_remat and the launcher's --remat auto both use it."""
    from repro import plan as plan_mod
    pol = get_policy(tc.policy)
    dtype_bytes = jnp.dtype(pol.compute_dtype).itemsize
    flash_resid_bytes = None if pol.flash_resid_dtype is None else \
        jnp.dtype(pol.flash_resid_dtype).itemsize
    model_shards = 1
    if mesh is not None and "model" in mesh.axis_names:
        model_shards = mesh.shape["model"]
    return plan_mod.profile_transformer(
        cfg, microbatch_specs(batch_sds, accum=tc.accum, mesh=mesh),
        dtype_bytes=dtype_bytes, flash_resid_bytes=flash_resid_bytes,
        model_shards=model_shards)


def resolve_remat(cfg: ModelConfig, tc: TrainConfig, batch_sds: dict,
                  mesh=None) -> TrainConfig:
    """Fill ``tc.remat.plan`` from the memory planner when a budget is set.

    Profiles the block scan at per-device MICROBATCH shape (the remat'd
    unit under DP sharding + gradient accumulation) in the policy's
    compute dtype, and solves min-recompute s.t. peak <= budget.  A plan
    already present (e.g. loaded from a run's plan.json) wins; an explicit
    plan is validated against the model depth either way.
    """
    if tc.remat.plan is not None:
        tc.remat.validated_plan(cfg.n_layers)
        return tc
    if tc.mem_budget_mb <= 0 or not tc.remat.enabled:
        return tc
    from repro import plan as plan_mod
    prof = plan_profile(cfg, tc, batch_sds, mesh=mesh)
    rp = plan_mod.plan_for_budget(prof, tc.mem_budget_mb * 2 ** 20,
                                  policy=tc.remat.policy)
    return dataclasses.replace(
        tc, remat=dataclasses.replace(tc.remat, plan=rp))


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def build_train_step(cfg: ModelConfig, tc: TrainConfig, mesh=None):
    """The pure step function (jit-agnostic; used by tests directly)."""
    policy = get_policy(tc.policy)
    loss_scale_proto = LossScale.init() if tc.use_loss_scale else None

    def loss_for(p, mb):
        return transformer.loss_fn(p, cfg, mb, policy=policy, remat=tc.remat,
                                    scan_unroll=tc.scan_unroll, mesh=mesh)

    vg = scaled_value_and_grad(loss_for, policy, loss_scale_proto)

    def compute_grads(params, ls: Optional[LossScale], batch):
        nonlocal_vg = scaled_value_and_grad(loss_for, policy, ls) \
            if ls is not None else vg
        if tc.accum <= 1:
            (loss, _aux), grads, finite = nonlocal_vg(params, batch)
            return loss, grads, finite
        # microbatch split along the batch axis (positions: (3, B, S))
        def split(path, x):
            name = str(path[-1].key) if path else ""
            if name == "positions" and x.ndim == 3:
                return x.reshape(3, tc.accum, -1, *x.shape[2:]).swapaxes(0, 1)
            return x.reshape(tc.accum, x.shape[0] // tc.accum, *x.shape[1:])

        mbs = jax.tree_util.tree_map_with_path(split, batch)

        def body(carry, mb):
            loss_acc, grad_acc, fin = carry
            (loss, _aux), grads, finite = nonlocal_vg(params, mb)
            grads = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), grad_acc, grads)
            return (loss_acc + loss, grads, fin & finite), None

        zero_grads = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grads, finite), _ = jax.lax.scan(
            body, (jnp.float32(0), zero_grads, jnp.bool_(True)), mbs)
        inv = 1.0 / tc.accum
        grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
        return loss * inv, grads, finite

    def train_step(params, opt_state, loss_scale, batch):
        ls = loss_scale if tc.use_loss_scale else None
        loss, grads, finite = compute_grads(params, ls, batch)
        skip = ~finite if (tc.use_loss_scale or tc.skip_nonfinite) else None
        new_params, new_opt, metrics = adamw.update(
            tc.opt, grads, opt_state, params, skip=skip)
        new_ls = loss_scale.update(finite) if tc.use_loss_scale else loss_scale
        metrics = {"loss": loss, "grads_finite": finite, **metrics}
        return new_params, new_opt, new_ls, metrics

    return train_step


def make_train_step(cfg: ModelConfig, mesh, tc: TrainConfig,
                    batch_sds: dict, *, donate: bool = True):
    """jit-compiled sharded step + the sharding trees used to place state."""
    tc = resolve_remat(cfg, tc, batch_sds, mesh=mesh)
    step = build_train_step(cfg, tc, mesh=mesh)
    params_sds = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    p_spec = shd.param_specs(cfg, params_sds, mesh=mesh)
    p_shard = shd.to_shardings(mesh, p_spec)
    opt_shard = adamw.AdamWState(mu=p_shard, nu=p_shard,
                                 count=NamedSharding(mesh, P()))
    b_spec = shd.batch_specs(cfg, batch_sds, mesh)
    b_shard = shd.to_shardings(mesh, b_spec)

    # loss-scale state is tiny: replicated, pinned on both sides so the
    # state a step returns feeds the next step without a recompile
    rep = NamedSharding(mesh, P())
    jitted = jax.jit(
        step,
        in_shardings=(p_shard, opt_shard, rep, b_shard),
        out_shardings=(p_shard, opt_shard, rep, None),
        donate_argnums=(0, 1) if donate else (),
    )
    return jitted, dict(params=p_shard, opt=opt_shard, batch=b_shard,
                        loss_scale=rep)
