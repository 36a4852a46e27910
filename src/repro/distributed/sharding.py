"""Sharding rules: parameter, activation, and cache PartitionSpecs.

Mesh axes: ``("pod", "data", "model")`` multi-pod or ``("data", "model")``
single-pod.  DP runs over (pod, data); TP over model.  Rules are name-based
over the param tree:

  * last-dim "model"      : wq wk wv w_gate w_up q_b kv_b w1 b1 shared_* lm_head
  * penultimate "model"   : wo w_down w2 shared_down embed
  * MoE EP mode           : experts sharded on the expert axis instead
  * SSM params            : replicated (small; heads rarely divide 16 —
                            DESIGN.md §5 records this choice)

Caches shard batch over DP when divisible, KV-heads over model when
divisible, otherwise the *sequence* dim over model (long-context serving).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

_LAST = {"wq", "wk", "wv", "w_gate", "w_up", "q_b", "kv_b", "w1", "b1",
         "shared_gate", "shared_up", "lm_head"}
_PENULT = {"wo", "w_down", "w2", "shared_down", "embed"}


def dp_axes(mesh: Mesh):
    # a bare axis name (not a 1-tuple) so PartitionSpec entries compare
    # equal across jax versions that do / don't normalize singleton tuples
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def dp_size(mesh: Mesh) -> int:
    axes = dp_axes(mesh)
    s = 1
    for a in ((axes,) if isinstance(axes, str) else axes):
        s *= mesh.shape[a]
    return s


def _path_names(path) -> list[str]:
    names = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            names.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            names.append(str(p.idx))
    return names


def param_specs(cfg: ModelConfig | None, params_shape,
                mesh: Mesh | None = None) -> Any:
    """PartitionSpec tree matching ``params_shape`` (shapes or arrays).

    With ``mesh``, specs are validated against the actual model-axis width:
    any dim the rule would put on "model" but whose size doesn't divide
    ``mesh.shape["model"]`` falls back to replicated for that leaf — so the
    same rule table serves production 16-wide TP and a 2-wide CPU-CI mesh
    without per-arch special cases.  Without ``mesh`` the raw (production)
    rules are returned unchanged.
    """
    ep = cfg is not None and cfg.moe is not None and cfg.moe.expert_mode == "ep"
    n_model = None
    if mesh is not None:
        n_model = mesh.shape["model"] if "model" in mesh.axis_names else 1

    def fit(spec: P, shape) -> P:
        if n_model is None:
            return spec
        out = []
        for ax, name in enumerate(spec):
            if name == "model" and (n_model == 1 or shape[ax] % n_model):
                out.append(None)
            else:
                out.append(name)
        return P(*out)

    def spec_for(path, leaf):
        names = _path_names(path)
        name = names[-1]
        nd = len(leaf.shape)
        in_ssm = "ssm" in names
        if in_ssm:
            return P()
        if ep and name in ("w_gate", "w_up", "w_down") and nd == 4:
            return fit(P(None, "model", None, None), leaf.shape)
        if name in _LAST and nd >= 1:
            return fit(P(*([None] * (nd - 1) + ["model"])), leaf.shape)
        if name in _PENULT and nd >= 2:
            return fit(P(*([None] * (nd - 2) + ["model", None])), leaf.shape)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def flash_shard_specs(mesh: Mesh | None, batch: int, heads: int,
                      kv_heads: int) -> "P | None":
    """The PartitionSpec to shard_map flash attention with, or None.

    Flash q/k/v/o all travel in (B, H|Hkv, S, D) layout and shard the same
    way: batch over DP, heads over "model".  Head sharding needs BOTH head
    counts to divide the model axis — contiguous equal blocks keep every
    GQA group (q-head j with kv-head j // g) on one shard, so the kernel
    never crosses shards.  None means the mesh can't split the call
    cleanly (or is trivial) and the caller should dispatch unsharded.
    """
    if mesh is None or "model" not in mesh.axis_names:
        return None
    n_model = mesh.shape["model"]
    dp = dp_axes(mesh)
    n_dp = dp_size(mesh)
    b_ax = dp if (n_dp > 1 and batch % n_dp == 0) else None
    h_ax = "model" if (n_model > 1 and heads % n_model == 0
                       and kv_heads % n_model == 0) else None
    if b_ax is None and h_ax is None:
        return None
    return P(b_ax, h_ax, None, None)


def serve_kv_shard(mesh: Mesh | None, kv_heads: int, s: int) -> str:
    """How the serve pool's (B, Hkv, S, hd) cache shards under ``mesh``.

    "heads": kv-heads over "model" (the natural GQA split); "seq": the
    sequence axis over "model" with the flash-combine collective merging
    per-shard softmax partials; "none": replicated.  The slot (batch) axis
    is NEVER sharded — data parallelism in serving is separate engine
    replicas, and a sharded slot axis would turn ``scatter_request``'s
    join into a cross-device scatter.  The ONE rule ``serve_cache_specs``,
    ``attn_decode``, and the capacity planner all consult, so placement
    and compute can't drift.
    """
    if mesh is None or "model" not in mesh.axis_names:
        return "none"
    n_model = mesh.shape["model"]
    if n_model == 1:
        return "none"
    if kv_heads % n_model == 0:
        return "heads"
    if s % n_model == 0:
        return "seq"
    return "none"


def serve_cache_specs(cfg: ModelConfig, cache_shape, mesh: Mesh) -> Any:
    """Slot-pool cache specs for the continuous-batching engine.

    Per :func:`serve_kv_shard`; leaves the engine doesn't shard (per-slot
    ``pos`` lengths, SSM/conv state) are replicated."""

    def spec_for(path, leaf):
        name = _path_names(path)[-1]
        shape = leaf.shape
        if name in ("k", "v") and len(shape) == 5:       # (L, B, Hkv, S, hd)
            mode = serve_kv_shard(mesh, shape[2], shape[3])
            if mode == "heads":
                return P(None, None, "model", None, None)
            if mode == "seq":
                return P(None, None, None, "model", None)
        if name in ("k_scale", "v_scale") and len(shape) == 4:  # (L,B,Hkv,S)
            mode = serve_kv_shard(mesh, shape[2], shape[3])
            if mode == "heads":
                return P(None, None, "model", None)
            if mode == "seq":
                return P(None, None, None, "model")
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, cache_shape)


def spec_shards(mesh: Mesh, spec: P) -> int:
    """Number of devices a PartitionSpec splits one array across."""
    n = 1
    for entry in spec:
        if entry is None:
            continue
        for ax in ((entry,) if isinstance(entry, str) else entry):
            n *= mesh.shape[ax]
    return n


def batch_specs(cfg: ModelConfig, batch_shape, mesh: Mesh) -> Any:
    """Input-batch specs: leading batch dim over DP (positions: dim 1)."""
    dp = dp_axes(mesh)

    def spec_for(path, leaf):
        name = _path_names(path)[-1]
        nd = len(leaf.shape)
        if name == "positions" and nd == 3:          # (3, B, S) M-RoPE
            return P(None, dp, None)
        return P(*([dp] + [None] * (nd - 1)))

    return jax.tree_util.tree_map_with_path(spec_for, batch_shape)


def cache_specs(cfg: ModelConfig, cache_shape, mesh: Mesh) -> Any:
    """Decode-cache specs (see module docstring for the policy)."""
    dp = dp_axes(mesh)
    n_dp = dp_size(mesh)
    n_model = mesh.shape["model"]

    def spec_for(path, leaf):
        name = _path_names(path)[-1]
        shape = leaf.shape
        if name == "pos":
            return P()
        b = shape[1] if len(shape) > 1 else 0
        b_ax = dp if (b and b % n_dp == 0) else None
        if name in ("k", "v", "gk", "gv", "wk", "wv"):   # (L, B, Hkv, S, hd)
            hkv, s = shape[2], shape[3]
            if hkv % n_model == 0:
                return P(None, b_ax, "model", None, None)
            seq_ax = ("data", "model") if b_ax is None else "model"
            n_seq = n_model if b_ax is not None else (
                n_dp * n_model // mesh.shape.get("pod", 1))
            if s % n_seq:                # rolling window buffers stay local
                seq_ax = None
            return P(None, b_ax, None, seq_ax, None)
        if name in ("k_scale", "v_scale", "gk_scale", "gv_scale",
                    "wk_scale", "wv_scale"):             # (L, B, Hkv, S)
            hkv, s = shape[2], shape[3]
            if hkv % n_model == 0:
                return P(None, b_ax, "model", None)
            seq_ax = ("data", "model") if b_ax is None else "model"
            n_seq = n_model if b_ax is not None else (
                n_dp * n_model // mesh.shape.get("pod", 1))
            if s % n_seq:
                seq_ax = None
            return P(None, b_ax, None, seq_ax)
        if name in ("mla_lat", "mla_rope"):          # (L, B, S, r)
            seq_ax = ("data", "model") if b_ax is None else "model"
            return P(None, b_ax, seq_ax, None)
        if name in ("ssm", "conv"):                  # small states: DP only
            return P(None, b_ax)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, cache_shape)


def to_shardings(mesh: Mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def init_sharded_params(cfg: ModelConfig, key, mesh: Mesh | None = None):
    """``transformer.init_params`` compiled with ``param_specs`` as its
    output shardings: every device draws only its own shard, so no chip
    ever holds the whole model (without a mesh: a plain jitted init).
    The values equal an unsharded init's (threefry is partitionable)."""
    from repro.models import transformer
    init = functools.partial(transformer.init_params, cfg)
    if mesh is None:
        return jax.jit(init)(key)
    specs = param_specs(cfg, jax.eval_shape(init, key), mesh=mesh)
    return jax.jit(init, out_shardings=to_shardings(mesh, specs))(key)
