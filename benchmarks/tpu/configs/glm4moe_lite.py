"""Plain reference of the GLM-4.7-Flash decoder (``glm4_moe_lite``), and
its seeded weights.

The benchmark's yardstick for every configuration whose file names
``"reference": "glm4moe_lite"``.  It reads the published keys of that
file (``num_hidden_layers``, ``hidden_size``, ``intermediate_size``,
``moe_intermediate_size``, ``num_attention_heads``, ``q_lora_rank``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``n_routed_experts``, ``num_experts_per_tok``,
``n_shared_experts``, ``routed_scaling_factor``, ``first_k_dense_replace``,
``vocab_size``, ``rms_norm_eps``, ``rope_theta``), and imports nothing
of the program.  ``n_routed_experts`` counts the experts this chip holds,
from ``held_first_expert`` on; the router spans the published count
(``published.n_routed_experts``, or all of them if none is given).

One block, as published: RMSNorm, then multi-head latent attention; a
residual add; RMSNorm, then an MLP; a residual add.  A final RMSNorm and
an untied LM head.  The attention, written out without absorbing the
up-projections:

* q = RMSNorm(x W_qa) W_qb, per head ``qk_nope`` + ``qk_rope`` wide;
* [c, k_r] = x W_kva; c = RMSNorm(c) (the ``kv_lora`` latent);
  [k_nope, v] = c W_kvb per head; rotary embeddings (base ``rope_theta``)
  on q's rope part and on k_r, which every head shares;
* causal softmax attention over q . [k_nope, k_r] at scale
  ``(qk_nope + qk_rope) ** -0.5``, computed in blocks of query rows.

The first ``first_k_dense_replace`` layers have a SwiGLU MLP of
``intermediate_size``.  The others route each token over all
``n_routed_experts`` (``noaux_tc`` with one group): sigmoid scores of
x W_router, the top ``num_experts_per_tok`` of the scores plus a
per-expert correction bias, weights = the chosen scores over their sum,
times ``routed_scaling_factor``.  The layer adds the weighted SwiGLU
experts of ``moe_intermediate_size`` that this chip holds (the others'
part is left out, as on the chip that holds only these) and the shared
expert of ``n_shared_experts * moe_intermediate_size``, once.

Departures, listed in the configuration file too: the rotary pairs are
the two halves of each rotated slice, not interleaved neighbours (a fixed
permutation of the q_b and kv_a rope columns); the multi-token-prediction
layer (``num_nextn_predict_layers``) is left out, as it serves only
speculative decoding.

Everything here runs in float32 at ``highest`` matmul precision.  With
``fp8=True`` every projection, expert, MLP and LM-head matmul takes its
operands rounded to float8 e4m3 (per-row scales for activations, one
scale per weight matrix): the control that a lower precision than the
configuration's must fail.

Weights are made here, from the seed, in the program's parameter layout
(the dense layers' leaves stacked under ``dense_blocks``, the MoE layers'
under ``blocks``), so the program and the reference read the same
numbers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
#: the seeded correction bias's standard deviation (an assumption of the
#: configuration file: the published checkpoint's values are not used)
ROUTER_BIAS_STD = 0.1


def dims(c: dict) -> dict:
    """The model's sizes from its published keys (or ``c`` itself, when
    it already holds them)."""
    if "L" in c:
        return dict(c)
    held = int(c["n_routed_experts"])
    every = int(c.get("published", {}).get("n_routed_experts", held))
    return dict(
        L=int(c["num_hidden_layers"]),
        dense=int(c["first_k_dense_replace"]), D=int(c["hidden_size"]),
        H=int(c["num_attention_heads"]), q_lora=int(c["q_lora_rank"]),
        kv_lora=int(c["kv_lora_rank"]), dn=int(c["qk_nope_head_dim"]),
        dr=int(c["qk_rope_head_dim"]), dv=int(c["v_head_dim"]),
        F=int(c["intermediate_size"]), Fe=int(c["moe_intermediate_size"]),
        Fs=int(c["n_shared_experts"]) * int(c["moe_intermediate_size"]),
        E=every, k=int(c["num_experts_per_tok"]),
        first=int(c.get("held_first_expert", 0)), held=held,
        scale=float(c["routed_scaling_factor"]), V=int(c["vocab_size"]),
        eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]))


def seed_key(seed: int):
    """A JAX key from any whole number (seeds may exceed 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def _attn_specs(group: str, n: int, d: dict) -> list:
    D, H = d["D"], d["H"]
    qk = d["dn"] + d["dr"]
    return [
        ((group, "ln1"), (n, D), None),
        ((group, "ln2"), (n, D), None),
        ((group, "attn", "q_a"), (n, D, d["q_lora"]), D ** -0.5),
        ((group, "attn", "q_a_norm"), (n, d["q_lora"]), None),
        ((group, "attn", "q_b"), (n, d["q_lora"], H * qk),
         d["q_lora"] ** -0.5),
        ((group, "attn", "kv_a"), (n, D, d["kv_lora"] + d["dr"]), D ** -0.5),
        ((group, "attn", "kv_a_norm"), (n, d["kv_lora"]), None),
        ((group, "attn", "kv_b"), (n, d["kv_lora"], H * (d["dn"] + d["dv"])),
         d["kv_lora"] ** -0.5),
        ((group, "attn", "wo"), (n, H * d["dv"], D), (H * d["dv"]) ** -0.5),
    ]


def leaf_specs(c: dict) -> list:
    """(path, shape, init std or None for ones) of every weight, in order."""
    d = dims(c)
    D, nd, nm = d["D"], d["dense"], d["L"] - d["dense"]
    e, Fe, Fs, F = d["held"], d["Fe"], d["Fs"], d["F"]
    specs = [(("embed",), (d["V"], D), 0.02)]
    specs += _attn_specs("dense_blocks", nd, d)
    specs += [
        (("dense_blocks", "ffn", "w_gate"), (nd, D, F), D ** -0.5),
        (("dense_blocks", "ffn", "w_up"), (nd, D, F), D ** -0.5),
        (("dense_blocks", "ffn", "w_down"), (nd, F, D), F ** -0.5),
    ]
    specs += _attn_specs("blocks", nm, d)
    specs += [
        (("blocks", "ffn", "router"), (nm, D, d["E"]), D ** -0.5),
        (("blocks", "ffn", "router_bias"), (nm, d["E"]), ROUTER_BIAS_STD),
        (("blocks", "ffn", "w_gate"), (nm, e, D, Fe), D ** -0.5),
        (("blocks", "ffn", "w_up"), (nm, e, D, Fe), D ** -0.5),
        (("blocks", "ffn", "w_down"), (nm, e, Fe, D), Fe ** -0.5),
        (("blocks", "ffn", "shared_gate"), (nm, D, Fs), D ** -0.5),
        (("blocks", "ffn", "shared_up"), (nm, D, Fs), D ** -0.5),
        (("blocks", "ffn", "shared_down"), (nm, Fs, D), Fs ** -0.5),
        (("final_norm",), (D,), None),
        (("lm_head",), (D, d["V"]), D ** -0.5),
    ]
    return specs


def _leaf(key, i, shape, std, dtype):
    if std is None:
        return jnp.ones(shape, dtype)
    x = jax.random.normal(jax.random.fold_in(key, i), shape, F32) * std
    return x.astype(dtype)


def _put(tree, path, x):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = x


def make_params(c: dict, seed: int, dtype, out_shardings=None):
    """Every weight, made on the device in one jitted call."""
    specs = leaf_specs(c)

    def build(key):
        tree: dict = {}
        for i, (path, shape, std) in enumerate(specs):
            _put(tree, path, _leaf(key, i, shape, std, dtype))
        return tree

    return jax.jit(build, out_shardings=out_shardings)(seed_key(seed))


def _static(c: dict) -> tuple:
    """The configuration's model keys, hashable (a static jit argument)."""
    return tuple(sorted(dims(c).items()))


# ---------------------------------------------------------------------------
# The forward pass.
# ---------------------------------------------------------------------------
def _q_rows(x):
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(F32) * scale


def _q_tensor(w):
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / FP8_MAX
    return (w / scale).astype(FP8).astype(F32) * scale


def _mm(a, w, fp8: bool):
    w = w.astype(F32)
    return _q_rows(a) @ _q_tensor(w) if fp8 else a @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, pos, theta):
    """x: (S, heads, r); rotary on all r dims, pairs are the two halves."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=F32) / r)
    ang = pos.astype(F32)[:, None] * inv                  # (S, r/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, qblock: int):
    """Causal multi-head attention of one sequence in blocks of query
    rows.  q, k: (S, H, dq); v: (S, H, dv) -> (S, H * dv)."""
    s, h, dq = q.shape
    qblock = min(qblock, s)
    if s % qblock:
        raise ValueError(f"sequence {s} is not a multiple of {qblock}")
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qblock, qblock, 0)
        sc = jnp.einsum("qhd,khd->hqk", qi, k) * dq ** -0.5
        qpos = i * qblock + jnp.arange(qblock)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    out = jax.lax.map(block, jnp.arange(s // qblock))
    return out.reshape(s, h * v.shape[-1])


def attention(x, p, d: dict, fp8: bool, qblock: int):
    """Latent attention of one sequence; x: (S, D) float32 (normed)."""
    s = x.shape[0]
    H, dn, dr, dv = d["H"], d["dn"], d["dr"], d["dv"]
    pos = jnp.arange(s)
    q = _mm(_rms(_mm(x, p["q_a"], fp8), p["q_a_norm"], d["eps"]),
            p["q_b"], fp8).reshape(s, H, dn + dr)
    kv = _mm(x, p["kv_a"], fp8)
    c = _rms(kv[:, :d["kv_lora"]], p["kv_a_norm"], d["eps"])
    k_r = _rope(kv[:, None, d["kv_lora"]:], pos, d["theta"])  # (S, 1, dr)
    kvb = _mm(c, p["kv_b"], fp8).reshape(s, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, d["theta"])],
                        -1)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(k_r, (s, H, dr))],
                        -1)
    o = _attention(q, k, kvb[..., dn:], qblock)
    return _mm(o, p["wo"], fp8)


def _swiglu(x, g, u, dn, fp8):
    return _mm(jax.nn.silu(_mm(x, g, fp8)) * _mm(x, u, fp8), dn, fp8)


def routed(x, f, d: dict, fp8: bool):
    """The held experts' part of the routed sum, and the shared expert.
    x: (S, D) float32 (normed)."""
    scores = jax.nn.sigmoid(_mm(x, f["router"], fp8))     # (S, E)
    _, top = jax.lax.top_k(scores + f["router_bias"].astype(F32), d["k"])
    chosen = jnp.take_along_axis(scores, top, -1)
    w = chosen / jnp.sum(chosen, -1, keepdims=True) * d["scale"]
    out = _swiglu(x, f["shared_gate"], f["shared_up"], f["shared_down"], fp8)
    for j in range(d["held"]):
        gate = jnp.sum(jnp.where(top == d["first"] + j, w, 0.0), -1)
        out = out + gate[:, None] * _swiglu(
            x, f["w_gate"][j], f["w_up"][j], f["w_down"][j], fp8)
    return out


def layer(x, p, c: dict, fp8: bool, qblock: int, dense: bool):
    """One block; x: (B, S, D) float32."""
    d = dims(c)
    att = jax.vmap(lambda h: attention(h, p["attn"], d, fp8, qblock))
    x = x + att(_rms(x, p["ln1"], d["eps"]))
    h = _rms(x, p["ln2"], d["eps"])
    f = p["ffn"]
    if dense:
        return x + _swiglu(h, f["w_gate"], f["w_up"], f["w_down"], fp8)
    return x + jax.vmap(lambda r: routed(r, f, d, fp8))(h)


def _block_slice(blocks, i):
    return jax.tree_util.tree_map(lambda a: a[i], blocks)


@jax.jit
def _embed(params, tokens):
    return params["embed"][tokens].astype(F32)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _one_layer(x, blocks, i, c_items, fp8, qblock, dense):
    with jax.default_matmul_precision("highest"):
        return layer(x, _block_slice(blocks, i), dict(c_items), fp8, qblock,
                     dense)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_rows(params, x, rows, c_items, fp8):
    d = dims(dict(c_items))
    with jax.default_matmul_precision("highest"):
        xr = _rms(x[0][rows], params["final_norm"], d["eps"])
        return _mm(xr, params["lm_head"], fp8)


def hidden(params, c: dict, tokens, *, fp8: bool = False, qblock: int = 512):
    """The last layer's output (B, S, D) for ``tokens`` (B, S)."""
    ci = _static(c)
    d = dims(c)
    x = _embed(params, jnp.asarray(tokens, jnp.int32))
    for i in range(d["L"]):
        dense = i < d["dense"]
        group = params["dense_blocks"] if dense else params["blocks"]
        j = i if dense else i - d["dense"]
        x = _one_layer(x, group, jnp.int32(j), ci, fp8, qblock, dense)
    return x


def logits_at(params, c: dict, tokens, rows, *, fp8: bool = False,
              qblock: int = 512):
    """Logits (len(rows), V) of one sequence at positions ``rows``,
    computed layer by layer.  ``tokens`` is padded to a multiple of
    ``qblock``; causality keeps the padding out of every earlier row."""
    x = hidden(params, c, jnp.asarray(tokens, jnp.int32)[None], fp8=fp8,
               qblock=qblock)
    return _head_rows(params, x, jnp.asarray(rows, jnp.int32), _static(c),
                      fp8)
