"""Plain reference of the ChatGLM / GLM-4 decoder, and its seeded weights.

The benchmark's yardstick for every configuration whose file names
``"reference": "chatglm"``.  It reads the published keys of that file
(``num_layers``, ``hidden_size``, ``ffn_hidden_size``, ``kv_channels``,
``num_attention_heads``, ``multi_query_group_num``, ``padded_vocab_size``,
``layernorm_epsilon``, ``rope_ratio``) and imports nothing of the program.

One GLM-4 block, as published: RMSNorm, then grouped-query attention
(``num_attention_heads`` query heads of ``kv_channels``, sharing
``multi_query_group_num`` key/value heads) with rotary embeddings on the
first half of each head (base ``10000 * rope_ratio``), a residual add,
RMSNorm, a SwiGLU MLP of width ``ffn_hidden_size``, a residual add; a
final RMSNorm and an untied LM head.  Departures, listed in the
configuration files too: no QKV bias (the program has none), and the
rotary pairs are the two halves of the rotated slice, not interleaved
neighbours (a fixed permutation of the q/k projection columns).

Everything here runs in float32 at ``highest`` matmul precision.  With
``fp8=True`` every projection, MLP and LM-head matmul takes its operands
rounded to float8 e4m3 (per-row scales for activations and cotangents,
one scale per weight matrix): the control that a lower precision than the
configuration's must fail.

Weights are made here, from the seed, in the program's parameter layout
(per-layer leaves stacked on a leading layer axis), so the program and
the reference read the same numbers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def dims(c: dict) -> dict:
    """The model's sizes from its published keys (or ``c`` itself, when
    it already holds them)."""
    if "L" in c:
        return dict(c)
    return dict(L=int(c["num_layers"]), D=int(c["hidden_size"]),
                H=int(c["num_attention_heads"]),
                Hkv=int(c["multi_query_group_num"]),
                hd=int(c["kv_channels"]), F=int(c["ffn_hidden_size"]),
                V=int(c["padded_vocab_size"]),
                eps=float(c["layernorm_epsilon"]),
                theta=10000.0 * float(c.get("rope_ratio", 1)))


def seed_key(seed: int):
    """A JAX key from any whole number (seeds may exceed 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def leaf_specs(c: dict) -> list:
    """(path, shape, init std or None for ones) of every weight, in order."""
    d = dims(c)
    L, D, H, Hkv, hd, F, V = (d[k] for k in ("L", "D", "H", "Hkv", "hd",
                                             "F", "V"))
    return [
        (("embed",), (V, D), 0.02),
        (("blocks", "ln1"), (L, D), None),
        (("blocks", "ln2"), (L, D), None),
        (("blocks", "attn", "wq"), (L, D, H * hd), D ** -0.5),
        (("blocks", "attn", "wk"), (L, D, Hkv * hd), D ** -0.5),
        (("blocks", "attn", "wv"), (L, D, Hkv * hd), D ** -0.5),
        (("blocks", "attn", "wo"), (L, H * hd, D), (H * hd) ** -0.5),
        (("blocks", "ffn", "w_gate"), (L, D, F), D ** -0.5),
        (("blocks", "ffn", "w_up"), (L, D, F), D ** -0.5),
        (("blocks", "ffn", "w_down"), (L, F, D), F ** -0.5),
        (("final_norm",), (D,), None),
        (("lm_head",), (D, V), D ** -0.5),
    ]


def _leaf(key, i, shape, std, dtype):
    if std is None:
        return jnp.ones(shape, dtype)
    x = jax.random.normal(jax.random.fold_in(key, i), shape, F32) * std
    return x.astype(dtype)


def _put(tree, path, x):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = x


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


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


@functools.partial(jax.jit, static_argnums=(1,))
def _change_norms(params, c_items, key):
    c = dict(c_items)
    out = []
    for i, (path, shape, std) in enumerate(leaf_specs(c)):
        p0 = _leaf(key, i, shape, std, F32)
        out.append(jnp.sqrt(jnp.sum(jnp.square(
            _get(params, path).astype(F32) - p0))))
    return jnp.stack(out)


def change_norms(params, c: dict, seed: int) -> dict:
    """Per leaf ||params - initial params||, the initial ones made anew
    from the seed on the device."""
    norms = np.asarray(_change_norms(params, _static(c), seed_key(seed)))
    return {".".join(p): float(n)
            for (p, _, _), n in zip(leaf_specs(c), norms)}


def leaf_norm_vector(tree, c: dict):
    """Each weight's L2 norm, in ``leaf_specs`` order (traceable)."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(_get(tree, p).astype(F32))))
                      for p, _, _ in leaf_specs(c)])


def named_norms(vector, c: dict) -> dict:
    return {".".join(p): float(n)
            for (p, _, _), n in zip(leaf_specs(c), np.asarray(vector))}


def leaf_norms(tree, c: dict) -> dict:
    return named_norms(leaf_norm_vector(tree, c), c)


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


@jax.custom_vjp
def _mm8(a, w):
    return _q_rows(a) @ _q_tensor(w)


def _mm8_fwd(a, w):
    qa, qw = _q_rows(a), _q_tensor(w)
    return qa @ qw, (qa, qw)


def _mm8_bwd(res, g):
    qa, qw = res
    gq = _q_rows(g)
    dw = jnp.einsum("...k,...n->kn", qa, gq)
    return gq @ qw.T, dw


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def _mm(a, w, fp8: bool):
    w = w.astype(F32)
    return _mm8(a, w) if fp8 else a @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, pos, theta):
    """x: (B, S, heads, hd); rotary on the first half of each head."""
    rot = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = pos.astype(F32)[:, None] * inv                  # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _attention(q, k, v, qblock: int):
    """Causal GQA for one sequence in blocks of query rows.
    q: (S, H, hd); k, v: (S, Hkv, hd) -> (S, H * hd)."""
    s, h, hd = q.shape
    hkv = k.shape[1]
    qblock = min(qblock, s)
    if s % qblock:
        raise ValueError(f"sequence {s} is not a multiple of {qblock}")
    qg = q.reshape(s, hkv, h // hkv, hd)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i * qblock, qblock, 0)
        sc = jnp.einsum("qhgd,khd->hgqk", qi, k) * hd ** -0.5
        qpos = i * qblock + jnp.arange(qblock)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(sc, -1), v)

    out = jax.lax.map(block, jnp.arange(s // qblock))
    return out.reshape(s, h * hd)


def layer(x, p, c: dict, fp8: bool, qblock: int):
    """One block; x: (B, S, D) float32."""
    d = dims(c)
    b, s, _ = x.shape
    pos = jnp.arange(s)
    h = _rms(x, p["ln1"], d["eps"])
    q = _mm(h, p["attn"]["wq"], fp8).reshape(b, s, d["H"], d["hd"])
    k = _mm(h, p["attn"]["wk"], fp8).reshape(b, s, d["Hkv"], d["hd"])
    v = _mm(h, p["attn"]["wv"], fp8).reshape(b, s, d["Hkv"], d["hd"])
    q, k = _rope(q, pos, d["theta"]), _rope(k, pos, d["theta"])
    o = jax.vmap(functools.partial(_attention, qblock=qblock))(q, k, v)
    x = x + _mm(o, p["attn"]["wo"], fp8)
    h = _rms(x, p["ln2"], d["eps"])
    f = p["ffn"]
    a = jax.nn.silu(_mm(h, f["w_gate"], fp8)) * _mm(h, f["w_up"], fp8)
    return x + _mm(a, f["w_down"], fp8)


def _block_slice(blocks, i):
    return jax.tree_util.tree_map(lambda a: a[i], blocks)


@jax.jit
def _embed(params, tokens):
    return params["embed"][tokens].astype(F32)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _one_layer(x, blocks, i, c_items, fp8, qblock):
    with jax.default_matmul_precision("highest"):
        return layer(x, _block_slice(blocks, i), dict(c_items), fp8, qblock)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_rows(params, x, rows, c_items, fp8):
    d = dims(dict(c_items))
    with jax.default_matmul_precision("highest"):
        xr = _rms(x[0][rows], params["final_norm"], d["eps"])
        return _mm(xr, params["lm_head"], fp8)


def logits_at(params, c: dict, tokens, rows, *, fp8: bool = False,
              qblock: int = 512):
    """Logits (len(rows), V) of one sequence at positions ``rows``,
    computed layer by layer.  ``tokens`` is padded to a multiple of
    ``qblock``; causality keeps the padding out of every earlier row."""
    ci = _static(c)
    tokens = jnp.asarray(tokens, jnp.int32)[None]
    x = _embed(params, tokens)
    for i in range(dims(c)["L"]):
        x = _one_layer(x, params["blocks"], jnp.int32(i), ci, fp8, qblock)
    return _head_rows(params, x, jnp.asarray(rows, jnp.int32), ci, fp8)


# ---------------------------------------------------------------------------
# Training: loss, gradients and AdamW, for the first steps.
# ---------------------------------------------------------------------------
def loss(params, c: dict, tokens, labels, *, fp8: bool = False,
         qblock: int = 256, ce_rows: int = 2048):
    """Mean next-token cross entropy over every position of the batch."""
    d = dims(c)
    x = params["embed"].astype(F32)[tokens]

    def body(x, p):
        return jax.checkpoint(lambda x, p: layer(x, p, c, fp8, qblock))(
            x, p), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = _rms(x, params["final_norm"], d["eps"])
    rows = x.reshape(-1, d["D"])
    labs = labels.reshape(-1)
    n = rows.shape[0]
    chunk = min(ce_rows, n)

    @jax.checkpoint
    def nll(i):
        xr = jax.lax.dynamic_slice_in_dim(rows, i * chunk, chunk, 0)
        lr = jax.lax.dynamic_slice_in_dim(labs, i * chunk, chunk, 0)
        z = _mm(xr, params["lm_head"], fp8)
        lse = jax.nn.logsumexp(z, -1)
        return jnp.sum(lse - jnp.take_along_axis(z, lr[:, None], -1)[:, 0])

    return jnp.sum(jax.lax.map(nll, jnp.arange(n // chunk))) / n


def adamw_schedule(o: dict, count):
    step = count.astype(F32)
    warm = jnp.minimum(1.0, (step + 1) / max(1, o["warmup_steps"]))
    prog = jnp.clip((step - o["warmup_steps"])
                    / max(1, o["total_steps"] - o["warmup_steps"]), 0.0, 1.0)
    cos = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return o["lr"] * warm * cos


def adamw(o: dict, params, grads, mu, nu, count):
    """Decoupled weight decay on matrices, global-norm clipping, bias
    correction; ``count`` is the number of updates made before this one."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree_util.tree_leaves(grads)))
    clip = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    lr = adamw_schedule(o, count)
    t = (count + 1).astype(F32)
    b1c, b2c = 1 - o["b1"] ** t, 1 - o["b2"] ** t

    def upd(p, g, m, v):
        g = g * clip
        m = o["b1"] * m + (1 - o["b1"]) * g
        v = o["b2"] * v + (1 - o["b2"]) * g * g
        decay = o["weight_decay"] if p.ndim >= 2 else 0.0
        p = p * (1 - lr * decay) - lr * (m / b1c) / (jnp.sqrt(v / b2c)
                                                     + o["eps"])
        return p, m, v

    tree = jax.tree_util.tree_map(upd, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(
        lambda _, t: t[i], params, tree,
        is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), gnorm
