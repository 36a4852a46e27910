"""Mixture-of-Experts FFN: dropless sort + ragged_dot dispatch.

Baseline sharding is TP-experts (expert hidden dim sharded over the model
axis; every device holds a slice of every expert).  ``expert_mode='ep'``
switches to expert parallelism via shard_map + all_to_all — a perf-iteration
path (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import swiglu


def router_topk(x, w_router, k: int):
    """Returns (weights (T,k) f32, idx (T,k) i32, aux load-balance loss)."""
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    weights = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux: E * sum_e f_e * p_e
    e = w_router.shape[-1]
    f = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    p_mean = probs.mean(0)
    aux = e * jnp.sum(f * p_mean)
    return weights, top_i, aux


def _moe_capacity_local(p, x, cfg, expert_offset=None):
    """Capacity-based dispatch on LOCAL tokens (runs per data shard).

    Tokens scatter into a fixed (E, C, D) buffer (C = T*k/E * capacity
    factor; overflow drops, Switch-style), experts run as one batched
    matmul, results gather back and combine with router weights.  Static
    shapes everywhere -> XLA-friendly on every backend, and the FLOP count
    is exactly E*C*D*F (the deployed TPU cost), unlike ragged_dot whose
    CPU lowering densifies to all experts.
    """
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    weights, top_i, aux = router_topk(xf, p["router"], m.top_k)

    tk = t * m.top_k
    cap = max(8, int(tk / m.num_experts * m.capacity_factor) // 8 * 8)
    e_local = p["w_gate"].shape[0]                            # E or E/shards
    flat_e = top_i.reshape(-1)                                # (Tk,)
    if expert_offset is not None:                             # EP: own a slice
        flat_e = flat_e - expert_offset
    in_range = (flat_e >= 0) & (flat_e < e_local)
    flat_e_c = jnp.clip(flat_e, 0, e_local - 1)
    oh = jax.nn.one_hot(flat_e_c, e_local, dtype=jnp.int32) \
        * in_range[:, None].astype(jnp.int32)
    pos = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1           # rank in expert
    keep = (pos < cap) & in_range
    dst = jnp.where(keep, flat_e_c * cap + pos, e_local * cap)  # OOB -> drop
    xs = jnp.repeat(xf, m.top_k, axis=0)                      # (Tk, D)
    buf = jnp.zeros((e_local * cap, d), xs.dtype)
    buf = buf.at[dst].set(xs * keep[:, None].astype(xs.dtype), mode="drop")
    buf = buf.reshape(e_local, cap, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    ys = y.reshape(e_local * cap, d)
    ys = jnp.take(ys, jnp.clip(dst, 0, ys.shape[0] - 1), axis=0) \
        * keep[:, None].astype(y.dtype)

    w_flat = weights.reshape(-1).astype(ys.dtype)
    tok_idx = jnp.repeat(jnp.arange(t), m.top_k)
    out = jnp.zeros((t, d), ys.dtype).at[tok_idx].add(ys * w_flat[:, None])
    if m.num_shared:
        out = out + swiglu(xf, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
    return out.reshape(b, s, d).astype(x.dtype), aux


def _moe_local(p, x, cfg):
    """Dispatch + grouped matmuls on LOCAL tokens (runs per data shard).

    Dropless: replicate each token k times, sort the T*k rows by expert id,
    run grouped matmuls with lax.ragged_dot, un-sort, combine with router
    weights.  Shared experts (DeepSeek) run densely on the side.
    The expert FFN hidden shard may be a TP shard; the caller psums.
    """
    m = cfg.moe
    if m.capacity_factor > 0:
        return _moe_capacity_local(p, x, cfg)
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    weights, top_i, aux = router_topk(xf, p["router"], m.top_k)

    flat_expert = top_i.reshape(-1)                         # (T*k,)
    flat_token = jnp.repeat(jnp.arange(t), m.top_k)
    order = jnp.argsort(flat_expert)
    tok_sorted = flat_token[order]
    exp_sorted = flat_expert[order]
    group_sizes = jnp.zeros((m.num_experts,), jnp.int32).at[exp_sorted].add(1)

    xs = xf[tok_sorted]                                     # (T*k, D)
    h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], group_sizes)) * \
        jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    ys = jax.lax.ragged_dot(h, p["w_down"], group_sizes)    # (T*k, D)

    w_sorted = weights.reshape(-1)[order].astype(ys.dtype)
    out = jnp.zeros((t, d), ys.dtype).at[tok_sorted].add(ys * w_sorted[:, None])

    if m.num_shared:
        out = out + swiglu(xf, p["shared_gate"], p["shared_up"], p["shared_down"])
    return out.reshape(b, s, d).astype(x.dtype), aux


def moe_ffn(p, x, cfg, mesh=None):
    """x: (B, S, D) -> (B, S, D), plus aux loss.

    Without a mesh: single-shard path (tests/CPU).  With a mesh: the
    token sort/gather/scatter runs INSIDE shard_map so dispatch stays local
    to each data shard (a global argsort under pjit would replicate the
    whole token stream), and the TP-expert hidden shard is psum-combined
    over the model axis.
    """
    if mesh is None or "model" not in mesh.axis_names:
        return _moe_local(p, x, cfg)

    from jax.sharding import PartitionSpec as P

    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    b = x.shape[0]
    b_ax = dp if b % n_dp == 0 else None

    ep = cfg.moe.expert_mode == "ep" and cfg.moe.capacity_factor > 0

    def local(x_l, p_l):
        if ep:
            e_local = p_l["w_gate"].shape[0]
            off = jax.lax.axis_index("model") * e_local
            out, aux = _moe_capacity_local(p_l, x_l, cfg, expert_offset=off)
        else:
            out, aux = _moe_local(p_l, x_l, cfg)
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, dp) if b_ax is not None else aux
        return out, aux

    def w_spec(path_leaf_name, leaf):
        nd = leaf.ndim
        name = path_leaf_name
        if name in ("w_gate", "w_up") and nd == 3:
            return P("model", None, None) if ep else P(None, None, "model")
        if name == "w_down" and nd == 3:
            return P("model", None, None) if ep else P(None, "model", None)
        if name in ("shared_gate", "shared_up"):
            return P(None, "model")
        if name == "shared_down":
            return P("model", None)
        return P()

    p_specs = {k: w_spec(k, v) for k, v in p.items()}
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b_ax, None, None), p_specs),
        out_specs=(P(b_ax, None, None), P()),
        check_vma=False,
    )(x, p)
