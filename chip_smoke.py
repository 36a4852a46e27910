#!/usr/bin/env python3
"""Bring-up check on a TPU: kernels, serving and training at Llama-3-8B widths.

    python chip_smoke.py              # one chip: kernels, train, serve
    python chip_smoke.py --chips 4    # four chips: the mesh paths only

Runs in one process the code the launchers run (``ServeEngine`` as
``repro.launch.serve`` builds it, ``make_train_step`` as
``repro.launch.train`` builds it) on random weights drawn from ``--seed``,
and checks every result against a reference:

* flash, kvq, pack, ssd: each Pallas kernel on the compiled backend
  against its ``ref`` oracle (run at ``highest`` matmul precision) —
  flash forward and gradients (causal and windowed), split-K int8 decode
  on a ragged batch, the pack codec round trip, and the SSD scan;
* train: planned-remat steps with the flash custom_vjp; losses and grad
  norms finite, the first step's loss, grad norm and per-leaf gradients
  (read back from its AdamW first moment) match the jnp attention path,
  and the allocator's peak is printed beside the planner's prediction;
* serve: a seeded trace through the engine with the platform-chosen
  kernels (flash prefill, split-K decode); every request finishes, no
  program recompiles after warmup, no slot leaks; prefill and
  first-decode logits with every slot live match the jnp/``ref`` paths,
  and every served token is checked against an f32 forward pass over
  its prompt and the tokens served before it.

``--chips 4`` runs only the mesh paths, each against one device: the
kv-head-sharded engine (f32 tokens equal to one device's; the default
bf16 engine's served tokens against the f32 forward pass), and the
(data, model) train step's loss, grad norm and gradients in f32 and bf16.

Model depth (and, for training, the vocabulary) is cut to what one 16 GB
chip holds; every cut is printed.  Each phase prints its compile and
steady seconds, and every comparison its error beside its tolerance; a
phase with a miss fails after running all its comparisons.  Exits
non-zero, without the result line, when JAX finds no TPU, when
``src/repro`` is not beside this script, or when any phase fails.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

ARCH = "llama3-8b"
# One v5e chip has 16 GB of HBM.  Serving keeps f32 weights: 0.87 GB per
# layer plus 4.2 GB for embedding and LM head at the full 128256 vocab.
SERVE_LAYERS = 6
SERVE_SLOTS, SERVE_MAX_LEN, SERVE_REQUESTS = 8, 1024, 8
SERVE_BUCKETS = (128, 256, 512)
SERVE_SPLITS = 2                     # 1024 slots / 512-slot decode tiles
# Training keeps f32 weights + AdamW moments: ~10.5 GB per layer-GB of
# weights; the full vocabulary alone (1.05 B params) would need 16.8 GB.
TRAIN_LAYERS, TRAIN_VOCAB = 2, 32768
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 2048, 3
# Kernel phase shapes: Llama-3-8B heads (batch, q heads, kv heads, seq,
# head_dim); the decode batch's ragged cache lengths; a CIFAR-shaped image
# batch; Mamba2-130M's SSD (batch, seq, heads, head channels, state).
FLASH_SHAPE = (1, 32, 8, 2048, 128)
FLASH_WINDOW = 512
DECODE_LENGTHS = (1, 100, 511, 512, 513, 1000, 1537, 2048)
IMAGES_SHAPE = (1024, 32, 32, 3)
SSD_SHAPE = (2, 2048, 24, 64, 128)

# Stated tolerances: max |got - want| / max |want|.
TOL_FWD = 2e-2          # kernel outputs (bf16 inputs, f32 accumulation)
TOL_GRAD = 5e-2         # flash gradients; train-step gradients, per leaf
                        # by ||got - want|| / ||want||
TOL_LOGITS = 5e-2       # engine logits: kernels vs jnp/ref paths, bf16
# A served token's shortfall: (max z - z[token]) / max |z| of the f32
# reference logits z at its position.  A bf16 near-tie may flip the
# argmax by a little; a wrong cache row or length gives a random token,
# about a whole max |z| short.
TOL_SERVED = 5e-2
TOL_LOSS = 1e-4         # |loss - loss_ref| / |loss_ref|
TOL_NORM = 1e-2         # |grad_norm - ref| / ref
# The f32 mesh checks compare sharded against one-device runs at
# ``highest`` matmul precision, so that only a sharding fault, not bf16
# rounding in another reduction order, can move the result; every
# request's greedy tokens must then match exactly.
TOL_MESH_LOSS = 1e-4    # sharded vs one-device loss and grad norm
TOL_MESH_GRAD = 1e-3    # sharded vs one-device gradients, per leaf


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def rel_l2(got, want) -> float:
    """||got - want|| / ||want||: a gradient leaf's error, which bf16
    rounding in another reduction order moves less than its max."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


# Comparisons that missed their tolerance in the running phase: each is
# printed and recorded, so one run reports every miss; the phase then fails.
MISSES: list[str] = []


def check(name: str, err: float, tol: float) -> None:
    ok = err <= tol
    print(f"  {name}: rel err {err:.3e} (tol {tol:g}){'' if ok else ' MISS'}",
          flush=True)
    if not ok:
        MISSES.append(name)


def timed(fn, *args):
    """(result, seconds) of ``fn(*args)`` once its outputs are ready."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def first_and_steady(name: str, fn, *args):
    """Call twice: the first call compiles, the second is steady."""
    out, t_first = timed(fn, *args)
    _, t_steady = timed(fn, *args)
    print(f"  {name}: compile+first {t_first:.3f} s, steady "
          f"{t_steady:.6f} s", flush=True)
    return out


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------
def phase_flash(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash import ops as flash_ops, ref as flash_ref

    rng = np.random.default_rng(seed)
    highest = jax.default_matmul_precision("highest")
    b, h, hkv, s, d = FLASH_SHAPE
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    for window in (0, FLASH_WINDOW):
        fwd = jax.jit(lambda q, k, v, win=window: flash_ops.flash_attention(
            q, k, v, window=win, backend="pallas"))
        out = first_and_steady(f"flash fwd window={window}", fwd, q, k, v)
        with highest:
            want = jax.jit(lambda q, k, v, win=window: flash_ref.flash_ref(
                q, k, v, window=win))(q, k, v)
        check(f"flash fwd window={window}", rel_err(out, want), TOL_FWD)

        def loss(q, k, v, fn, win=window):
            return jnp.sum(fn(q, k, v, win).astype(jnp.float32) * w)

        grad = jax.jit(jax.grad(functools.partial(
            loss, fn=lambda q, k, v, win: flash_ops.flash_attention(
                q, k, v, window=win, backend="pallas")), argnums=(0, 1, 2)))
        got = first_and_steady(f"flash grads window={window}", grad, q, k, v)
        with highest:
            want = jax.jit(jax.grad(functools.partial(
                loss, fn=lambda q, k, v, win: flash_ref.flash_ref(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), window=win)),
                argnums=(0, 1, 2)))(q, k, v)
        for name, g, r in zip("qkv", got, want):
            check(f"flash d{name} window={window}", rel_err(g, r), TOL_GRAD)


def phase_kvq(seed: int) -> None:
    """Split-K int8 decode over a ragged batch: the cache lengths
    straddle, fill and skip the 512-slot tiles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.kvq import ops as kvq_ops

    rng = np.random.default_rng(seed)
    highest = jax.default_matmul_precision("highest")
    _, h, hkv, _, d = FLASH_SHAPE
    bsz, s_kv = len(DECODE_LENGTHS), max(DECODE_LENGTHS)
    lengths = jnp.asarray(DECODE_LENGTHS, jnp.int32)
    qd = jnp.asarray(rng.normal(size=(bsz, h, d)), jnp.bfloat16)
    kq, ks = kvq_ops.quantize_kv(jnp.asarray(
        rng.normal(size=(bsz, hkv, s_kv, d)), jnp.float32))
    vq, vs = kvq_ops.quantize_kv(jnp.asarray(
        rng.normal(size=(bsz, hkv, s_kv, d)), jnp.float32))
    with highest:
        want = jax.jit(lambda *a: kvq_ops.decode_attention(
            *a[:5], lengths=a[5], backend="ref"))(qd, kq, ks, vq, vs, lengths)
    for splits in (1, 4):
        dec = jax.jit(lambda *a, sp=splits: kvq_ops.decode_attention(
            *a[:5], lengths=a[5], backend="pallas", splits=sp))
        out = first_and_steady(f"kvq decode splits={splits}", dec,
                               qd, kq, ks, vq, vs, lengths)
        check(f"kvq decode splits={splits}", rel_err(out, want), TOL_FWD)


def phase_pack(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.pack import ops as pack_ops

    rng = np.random.default_rng(seed)
    images = jnp.asarray(rng.integers(0, 256, IMAGES_SHAPE), jnp.uint8)
    enc = jax.jit(lambda x: pack_ops.encode(x, backend="pallas"))
    packed = first_and_steady("pack encode", enc, images)
    want = pack_ops.encode(images, backend="ref")
    if not np.array_equal(np.asarray(packed), np.asarray(want)):
        MISSES.append("pack encode differs from ref")
    dec = jax.jit(lambda x: pack_ops.decode(x, backend="pallas"))
    pixels = first_and_steady("pack decode", dec, packed)
    check("pack decode", rel_err(pixels, pack_ops.decode(packed,
                                                         backend="ref")),
          1e-6)
    exact = np.array_equal(
        np.rint(np.asarray(pixels) * 255).astype(np.uint8), np.asarray(images))
    print(f"  pack round trip: {'exact' if exact else 'MISS'}", flush=True)
    if not exact:
        MISSES.append("pack round trip does not return the images")


def phase_ssd(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ssd import ops as ssd_ops

    rng = np.random.default_rng(seed)
    highest = jax.default_matmul_precision("highest")
    bs, L, sh, sp, sn = SSD_SHAPE
    x = jnp.asarray(rng.normal(size=(bs, L, sh, sp)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (bs, L, sh)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 1.5, (sh,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(bs, L, sn)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(bs, L, sn)), jnp.float32)
    dd = jnp.asarray(rng.normal(size=(sh,)), jnp.float32)
    ssd = jax.jit(lambda *z: ssd_ops.ssd(*z, backend="pallas"))
    out = first_and_steady("ssd", ssd, x, dt, a, bm, cm, dd)
    with highest:
        want = jax.jit(lambda *z: ssd_ops.ssd(*z, backend="ref"))(
            x, dt, a, bm, cm, dd)
    check("ssd", rel_err(out, want), TOL_FWD)


def serve_config():
    from repro import configs
    full = configs.get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=SERVE_LAYERS)
    print(f"serve cut: {ARCH} n_layers {full.n_layers} -> {cfg.n_layers} "
          f"(widths and the {cfg.vocab}-token vocabulary unchanged)",
          flush=True)
    return cfg


def serve_trace(cfg, seed: int):
    from repro.serve import synthetic_trace
    return synthetic_trace(
        SERVE_REQUESTS, seed=seed, vocab=cfg.vocab, mean_prompt=200,
        max_prompt=SERVE_BUCKETS[-1], min_prompt=16, mean_gen=16,
        max_gen=32, arrival_rate=1.0)


def build_engine(cfg, params, *, mesh=None, kv_backend="auto",
                 policy="bf16"):
    """The engine as ``repro.launch.serve`` builds it (greedy)."""
    from repro.serve import ServeEngine
    return ServeEngine(
        params, cfg, max_slots=SERVE_SLOTS, max_len=SERVE_MAX_LEN,
        prompt_buckets=SERVE_BUCKETS, policy_name=policy, quantized=True,
        kv_backend=kv_backend, kv_splits=SERVE_SPLITS, temperature=0.0,
        seed=0, mesh=mesh)


def serve_run(engine, trace, label: str) -> dict:
    """Warm up, serve ``trace``, and check the engine's contracts: every
    request DONE, frozen programs, no leaked slots.  Returns rid->tokens."""
    t0 = time.perf_counter()
    counts = engine.warmup()
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    summary = engine.run(trace)
    t_steady = time.perf_counter() - t0
    print(f"  {label}: warmup (compile) {t_compile:.1f} s, trace "
          f"{t_steady:.2f} s for {summary['total_tokens']} tokens in "
          f"{summary['n_steps']} steps; programs {counts}", flush=True)
    if summary["stalled"] or summary["n_done"] != len(trace):
        raise AssertionError(f"{label}: {summary['n_done']}/{len(trace)} "
                             f"requests DONE ({summary['diagnostics']})")
    if engine.compile_counts() != counts:
        raise AssertionError(f"{label}: recompiled after warmup: "
                             f"{counts} -> {engine.compile_counts()}")
    pool = engine.pool
    pool.audit()
    if pool.occupancy or pool.allocs != pool.frees:
        raise AssertionError(f"{label}: slot leak (occupancy "
                             f"{pool.occupancy}, {pool.allocs} allocs, "
                             f"{pool.frees} frees)")
    states = engine.request_states()
    # the engine numbers requests in submission order: arrival order
    order = sorted(trace, key=lambda r: r.arrival_step)
    return [(req.prompt, list(states[rid]["tokens"]))
            for rid, req in enumerate(order)]


def probe_logits(cfg, params, prompts, kv_backend: str, tokens=None):
    """Prefill and first-decode logits of ``prompts``, one per slot, with
    every slot live at its own length, built as ``ServeEngine`` builds
    them: a bucketed prefill grown to ``max_len``, a scatter into the slot
    pool, one decode round fed ``tokens`` (default: each prefill argmax).
    Returns two host f32 ``(len(prompts), vocab)`` arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.mixed_precision import get_policy
    from repro.models import transformer
    from repro.serve.cache_pool import SlotPool, scatter_request

    policy = get_policy("bf16")
    bucket = SERVE_BUCKETS[-1]

    @jax.jit
    def prefill(params, tokens, true_len):
        logits, aux = transformer.forward(
            params, cfg, {"tokens": tokens}, policy=policy,
            build_cache=True, cache_quantized=True)
        last = jax.lax.dynamic_index_in_dim(logits[0], true_len - 1,
                                            keepdims=False)
        return (last[:cfg.vocab].astype(jnp.float32),
                transformer.grow_cache(aux["cache"], SERVE_MAX_LEN))

    @jax.jit
    def decode(params, cache, tokens):
        logits, _ = transformer.decode_step(
            params, cfg, cache, tokens, policy=policy, quantized=True,
            kvq_backend=kv_backend, kvq_splits=SERVE_SPLITS,
            active=jnp.ones(tokens.shape, bool))
        return logits[:, :cfg.vocab].astype(jnp.float32)

    scatter = jax.jit(scatter_request, donate_argnums=(0,))
    cache = SlotPool(cfg, len(prompts), SERVE_MAX_LEN).cache
    first = []
    for slot, prompt in enumerate(prompts):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = prompt
        logits, req = prefill(params, padded, np.int32(len(prompt)))
        cache = scatter(cache, req, np.int32(slot), np.int32(len(prompt)))
        first.append(np.asarray(logits))
    first = np.stack(first)
    if tokens is None:
        tokens = first.argmax(-1)
    return first, np.asarray(decode(params, cache,
                                    jnp.asarray(tokens, jnp.int32)))


def check_served(cfg, params, served, label: str) -> None:
    """Check every served greedy token against f32 reference logits: a
    forward pass (jnp attention, float weights, ``highest`` precision)
    over the prompt and the tokens served before it.  ``params`` live on
    one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer

    ref_cfg = dataclasses.replace(cfg, attn_backend="jnp")
    gen = max(len(toks) for _, toks in served)

    @jax.jit
    def rows(params, tokens, start):
        logits, _ = transformer.forward(params, ref_cfg, {"tokens": tokens})
        return jax.lax.dynamic_slice_in_dim(
            logits[0], start, gen)[:, :cfg.vocab].astype(jnp.float32)

    worst, flips, n = 0.0, 0, 0
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        for prompt, toks in served:
            seq = np.zeros((1, SERVE_MAX_LEN), np.int32)
            ctx = list(prompt) + toks[:-1]
            seq[0, :len(ctx)] = ctx
            # row i predicts toks[i]: it sits at position len(prompt)-1+i
            z = np.asarray(rows(params, seq, np.int32(len(prompt) - 1)))
            for zi, t in zip(z, toks):
                worst = max(worst, float((zi.max() - zi[t])
                                         / np.abs(zi).max()))
                flips += int(t != zi.argmax())
                n += 1
    print(f"  {label}: {n} served tokens vs the f32 forward pass, {flips} "
          f"not its argmax ({time.perf_counter() - t0:.1f} s with compile)",
          flush=True)
    check(f"{label}: worst served-token shortfall", worst, TOL_SERVED)


def phase_serve(seed: int) -> None:
    import gc

    import jax

    from repro.distributed.sharding import init_sharded_params

    cfg = serve_config()
    if cfg.attn_impl() != "pallas":
        raise AssertionError(f"attention resolved to {cfg.attn_impl()}")
    params = init_sharded_params(cfg, jax.random.PRNGKey(seed))
    engine = build_engine(cfg, params)
    if engine.kv_backend != "pallas":
        raise AssertionError(f"kv decode resolved to {engine.kv_backend}")
    trace = serve_trace(cfg, seed)
    served = serve_run(engine, trace,
                       "engine (flash prefill, kvq pallas decode)")
    del engine
    gc.collect()
    check_served(cfg, params, served, "engine")

    prompts = [r.prompt for r in trace]
    t0 = time.perf_counter()
    want = probe_logits(dataclasses.replace(cfg, attn_backend="jnp"),
                        params, prompts, "ref")
    got = probe_logits(cfg, params, prompts, "pallas",
                       tokens=want[0].argmax(-1))
    print(f"  logits probe: {len(prompts)} slots live at lengths "
          f"{[len(p) for p in prompts]}, flash + kvq pallas vs jnp + ref "
          f"({time.perf_counter() - t0:.1f} s with compile)", flush=True)
    for name, g, w in zip(("prefill", "first-decode"), got, want):
        check(f"{name} logits vs jnp/ref paths (worst slot)",
              max(rel_err(gs, ws) for gs, ws in zip(g, w)), TOL_LOGITS)
    del params
    gc.collect()


def train_config():
    from repro import configs
    full = configs.get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=TRAIN_LAYERS, vocab=TRAIN_VOCAB)
    print(f"train cut: {ARCH} n_layers {full.n_layers} -> {cfg.n_layers}, "
          f"vocab {full.vocab} -> {cfg.vocab} (slice; widths unchanged)",
          flush=True)
    return cfg


def train_args(policy: str):
    """The launcher's defaults for ``--remat auto`` (no budget)."""
    return argparse.Namespace(remat_policy="full", policy=policy, accum=1,
                              mem_budget_mb=0)


def build_train(cfg, mesh, seed: int, policy: str = "bf16"):
    """Step, state and batches as ``repro.launch.train.run`` builds them."""
    import jax
    import jax.numpy as jnp

    from repro.launch.train import auto_remat, init_state, \
        synthetic_lm_batches
    from repro.optim import adamw
    from repro.train.train_step import TrainConfig, make_train_step

    batch_sds = {
        "tokens": jax.ShapeDtypeStruct((TRAIN_BATCH, TRAIN_SEQ), jnp.int32),
        "labels": jax.ShapeDtypeStruct((TRAIN_BATCH, TRAIN_SEQ), jnp.int32),
    }
    remat, plan_bytes = auto_remat(cfg, train_args(policy), mesh, batch_sds)
    tc = TrainConfig(policy=policy, remat=remat,
                     opt=adamw.AdamWConfig(
                         total_steps=TRAIN_STEPS,
                         warmup_steps=min(100, TRAIN_STEPS // 10 + 1)))
    step_fn, shards = make_train_step(cfg, mesh, tc, batch_sds)
    params, opt = init_state(cfg, seed, mesh, shards)
    data = synthetic_lm_batches(cfg, TRAIN_BATCH, TRAIN_SEQ, seed=seed)
    return step_fn, shards, params, opt, data, tc, plan_bytes


def step_grads(opt, metrics, tc):
    """The gradients a first train step computed, read back from its
    AdamW state on the host: one update from zero moments leaves
    ``mu = (1 - b1) * clip * grads``."""
    import jax
    import numpy as np
    c = tc.opt
    norm = float(metrics["grad_norm"])
    clip = min(1.0, c.grad_clip / max(norm, 1e-9)) if c.grad_clip > 0 \
        else 1.0
    scale = (1 - c.b1) * clip
    return jax.tree_util.tree_map(lambda m: np.asarray(m) / np.float32(scale),
                                  jax.device_get(opt.mu))


def check_grads(label: str, got: tuple, want: tuple, tol_loss: float,
                tol_norm: float, tol_grad: float) -> None:
    """Compare two (loss, grad_norm, grads) triples; each gradient leaf
    by ``rel_l2``, and the worst leaf is named."""
    import jax
    (loss, norm, g), (loss_w, norm_w, g_w) = got, want
    check(f"{label} loss", abs(loss - loss_w) / abs(loss_w), tol_loss)
    check(f"{label} grad norm", abs(norm - norm_w) / abs(norm_w), tol_norm)
    errs = jax.tree_util.tree_leaves_with_path(
        jax.tree_util.tree_map(rel_l2, g, g_w))
    path, worst = max(errs, key=lambda e: e[1])
    check(f"{label} grads, worst leaf {jax.tree_util.keystr(path)}", worst,
          tol_grad)


def phase_train(seed: int) -> None:
    import gc

    import jax
    import numpy as np

    from repro.core.mixed_precision import LossScale, get_policy, \
        scaled_value_and_grad
    from repro.distributed.sharding import init_sharded_params
    from repro.launch.mesh import make_mesh_for
    from repro.models import transformer
    from repro.optim import adamw

    cfg = train_config()
    if cfg.attn_impl() != "pallas":
        raise AssertionError(f"attention resolved to {cfg.attn_impl()}")
    # the allocator's peak is kept for the process's life: this phase runs
    # before serving, and its starting value is printed beside its own
    peak_before = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    mesh = make_mesh_for(max_model=16)
    step_fn, shards, params, opt, data, tc, plan_bytes = build_train(
        cfg, mesh, seed)
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (params, opt)))
    ls = jax.device_put(LossScale.noop(), shards["loss_scale"])
    losses, norms, times = [], [], []
    for i in range(TRAIN_STEPS):
        _, batch = next(data)
        if i == 0:
            batch0 = batch
        (params, opt, ls, metrics), dt = timed(step_fn, params, opt, ls,
                                                batch)
        losses.append(float(metrics["loss"]))
        norms.append(float(metrics["grad_norm"]))
        times.append(dt)
        if i == 0:
            grads0 = step_grads(opt, metrics, tc)
        print(f"  step {i}: loss {losses[-1]:.6f} grad_norm {norms[-1]:.4f} "
              f"({dt:.3f} s)", flush=True)
    print(f"  train step: compile+first {times[0]:.1f} s, steady "
          f"{np.mean(times[1:]):.3f} s/step", flush=True)
    if not (np.isfinite(losses).all() and np.isfinite(norms).all()):
        raise AssertionError(f"non-finite losses {losses} / norms {norms}")
    stats = jax.devices()[0].memory_stats()
    print(f"  memory: peak_bytes_in_use {stats['peak_bytes_in_use']} B "
          f"(before this phase {peak_before} B); "
          f"planner activation peak {plan_bytes} B + params/AdamW state "
          f"{state_bytes} B = {plan_bytes + state_bytes} B", flush=True)
    del params, opt, step_fn
    gc.collect()

    # the reference: the first step's loss and gradients, taken as the
    # step takes them, with jnp attention in place of the flash kernels
    jnp_cfg = dataclasses.replace(cfg, attn_backend="jnp")
    policy = get_policy(tc.policy)
    vg = jax.jit(scaled_value_and_grad(
        lambda p, b: transformer.loss_fn(p, jnp_cfg, b, policy=policy,
                                         remat=tc.remat, mesh=mesh),
        policy))
    params = init_sharded_params(cfg, jax.random.PRNGKey(seed), mesh)
    ((loss, _), grads, _), dt = timed(vg, params, batch0)
    print(f"  jnp-attention reference: loss {float(loss):.6f} "
          f"({dt:.1f} s with compile)", flush=True)
    want = (float(loss), float(adamw.global_norm(grads)),
            jax.device_get(grads))
    check_grads("first step vs jnp attention:",
                (losses[0], norms[0], grads0), want, TOL_LOSS, TOL_NORM,
                TOL_GRAD)
    del params, grads
    gc.collect()


def phase_mesh_serve(seed: int, devices) -> None:
    import gc

    import jax

    from repro.distributed import sharding as shd
    from repro.distributed.sharding import init_sharded_params
    from repro.launch.mesh import describe, make_mesh_for

    cfg = serve_config()
    mesh = make_mesh_for(len(devices), max_model=16)
    kv_shard = shd.serve_kv_shard(mesh, cfg.n_kv, SERVE_MAX_LEN)
    print(f"  mesh {describe(mesh)}; kv cache sharded over '{kv_shard}'",
          flush=True)
    if kv_shard != "heads":
        raise AssertionError(f"expected kv-head sharding, got {kv_shard}")
    key = jax.random.PRNGKey(seed)
    trace = serve_trace(cfg, seed)
    runs = []
    with jax.default_matmul_precision("highest"):
        for label, m in (("sharded engine f32", mesh),
                         ("one-device engine f32", None)):
            engine = build_engine(cfg, init_sharded_params(cfg, key, m),
                                  mesh=m, policy="full")
            runs.append([toks for _, toks in serve_run(engine, trace,
                                                       label)])
            del engine
            gc.collect()
    same = sum(a == b for a, b in zip(*runs))
    print(f"  tokens: {same}/{len(trace)} requests equal token for token",
          flush=True)
    if same != len(trace):
        MISSES.append("sharded engine tokens diverge from one device")

    # the launcher's default: bf16 with the platform-chosen kernels
    engine = build_engine(cfg, init_sharded_params(cfg, key, mesh),
                          mesh=mesh)
    if engine.kv_backend != "pallas":
        raise AssertionError(f"kv decode resolved to {engine.kv_backend}")
    served = serve_run(engine, trace, "sharded engine bf16")
    del engine
    gc.collect()
    params = init_sharded_params(cfg, key)
    check_served(cfg, params, served, "sharded engine bf16")
    del params
    gc.collect()


def phase_mesh_train(seed: int, devices) -> None:
    import contextlib
    import gc

    import jax

    from repro.core.mixed_precision import LossScale
    from repro.launch.mesh import describe, make_mesh, make_mesh_for

    cfg = train_config()
    mesh = make_mesh_for(len(devices), max_model=16)
    one = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    print(f"  mesh {describe(mesh)} vs {describe(one)}", flush=True)

    def first_step(m, policy: str):
        """(loss, grad_norm, grads) of the launcher's first train step."""
        precision = jax.default_matmul_precision("highest") \
            if policy == "full" else contextlib.nullcontext()
        with precision:
            step_fn, shards, params, opt, data, tc, _ = build_train(
                cfg, m, seed, policy)
            _, batch = next(data)
            ls = jax.device_put(LossScale.noop(), shards["loss_scale"])
            (params, opt, _, metrics), dt = timed(step_fn, params, opt, ls,
                                                  batch)
        out = (float(metrics["loss"]), float(metrics["grad_norm"]),
               step_grads(opt, metrics, tc))
        print(f"  {policy} step on {describe(m)}: loss {out[0]:.6f} "
              f"grad_norm {out[1]:.4f} ({dt:.1f} s with compile)",
              flush=True)
        del params, opt, step_fn
        gc.collect()
        return out

    for policy, tol_loss, tol_grad in (
            ("full", TOL_MESH_LOSS, TOL_MESH_GRAD),
            ("bf16", TOL_LOSS, TOL_GRAD)):
        got = first_step(mesh, policy)
        want = first_step(one, policy)
        check_grads(f"{policy} mesh step vs one device:", got, want,
                    tol_loss, tol_loss if policy == "full" else TOL_NORM,
                    tol_grad)


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the mesh serve and train paths")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no src/repro beside {__file__}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    from repro.launch import compile_cache

    cache = compile_cache.enable()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this check runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    print(f"device: {dev.device_kind} x{len(devices)} (jax {jax.__version__}"
          f"); compile cache {cache}", flush=True)

    if args.chips == 1:
        phases = [("flash", phase_flash), ("kvq", phase_kvq),
                  ("pack", phase_pack), ("ssd", phase_ssd),
                  ("train", phase_train), ("serve", phase_serve)]
    else:
        phases = [("mesh_serve", functools.partial(phase_mesh_serve,
                                                   devices=devices)),
                  ("mesh_train", functools.partial(phase_mesh_train,
                                                   devices=devices))]
    failed = []
    for name, run in phases:
        print(f"phase {name}", flush=True)
        t0 = time.perf_counter()
        MISSES.clear()
        try:
            run(args.seed)
            if MISSES:
                raise AssertionError(f"out of tolerance: {MISSES}")
        except Exception:  # noqa: BLE001 - report, run the rest, then fail
            traceback.print_exc()
            failed.append(name)
            print(f"phase {name}: FAILED after "
                  f"{time.perf_counter() - t0:.1f} s", flush=True)
            continue
        print(f"phase {name}: ok in {time.perf_counter() - t0:.1f} s",
              flush=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
