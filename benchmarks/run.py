"""Benchmark harness — one benchmark per paper table/figure.

  fig8_memory     GPU-memory-in-1-iteration analogue (paper Fig. 8):
                  compiled temp bytes for ResNet-18, standard vs S-C.
  fig9_time_acc   time+accuracy parity for 10-epoch CIFAR runs (paper
                  Fig. 9), reduced to CPU scale: baseline vs E-D vs S-C
                  vs E-D+S-C vs +M-P on synthetic CIFAR.
  fig10_pipelines memory across pipelines B / E-D / M-P / S-C /
                  S-C + M-P for ResNet and an LM (paper Fig. 10).
  tbl_codec       encode/decode throughput + compression ratios for
                  Algorithms 1/3/4 and the u32 codec (paper II.A claims:
                  16x passage saving, >=20% time saving).
  tbl_pipeline    parallel E-D loader: epoch time with/without the
                  background encode thread (paper Fig. 1).
  tbl_compression gradient-compression payload bytes vs fp32 (framework
                  distributed-optimization feature).
  plan_vs_uniform profile-driven RematPlan vs uniform even-split remat at
                  the same checkpoint count (repro.plan acceptance table;
                  writes BENCH_plan.json).
  flash_fwd_bwd   trainable flash attention: fwd / fwd+bwd residual bytes
                  (pallas custom_vjp vs jnp S^2 path) across S, and wall
                  time in interpret mode (writes BENCH_flash.json).
  flash_decode    split-K int8 KV decode: sequential vs split-K wall time
                  (interpret mode), dense-vs-visited tile claw-back on a
                  ragged S=2048 batch, and the planner's serve-side
                  reports (writes BENCH_decode.json).
  serve_trace     continuous batching vs the lockstep driver on the same
                  ragged request trace: useful tokens/s, TTFT (steps),
                  slot occupancy and wasted slot-steps (writes
                  BENCH_serve.json).
  mesh_shard      sharded hot paths on 8 emulated devices: flash train
                  grads and engine token streams vs single device, plus
                  per-device slot capacity (subprocess — the device grid
                  must be set before jax initializes; writes
                  BENCH_shard.json).

Prints ``name,us_per_call,derived`` CSV rows (plus derived metrics).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _rows(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}", flush=True)


def _timeit(fn, *args, warmup=1, iters=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6, out


def _temp_bytes(fn, *sds):
    c = jax.jit(fn).lower(*sds).compile()
    m = c.memory_analysis()
    return int(getattr(m, "temp_size_in_bytes", 0))


def _residual_mb(loss_of_params, params, *rest):
    """Bytes saved between forward and backward (the paper's 'extra memory
    to back-propagate'): size of the vjp residual pytree, via eval_shape
    (no allocation).  Unlike XLA temp bytes on CPU, this directly reflects
    what S-C changes."""
    out = jax.eval_shape(
        lambda p, *r: jax.vjp(lambda pp: loss_of_params(pp, *r), p),
        params, *rest)
    leaves = jax.tree_util.tree_leaves(out)
    return sum(x.size * x.dtype.itemsize for x in leaves) / 2 ** 20


# ---------------------------------------------------------------------------
def fig8_memory():
    """ResNet-18 activation memory, standard vs sequential checkpoints."""
    from repro.core.checkpoint import CheckpointConfig
    from repro.models import cnn
    from repro.plan import RematPlan
    cfg = cnn.resnet18(stem_stride=2)
    params = cnn.init_params(cfg, jax.random.PRNGKey(0))
    imgs = jax.ShapeDtypeStruct((16, 512, 512, 3), jnp.float32)
    labels = jax.ShapeDtypeStruct((16,), jnp.int32)
    n = cnn.num_layer_fns(cfg)

    for name, seg in [("fig8_resnet18_standard", 0),
                      ("fig8_resnet18_sc2", 2),
                      ("fig8_resnet18_sc4", 4),
                      ("fig8_resnet18_sc8", 8)]:
        remat = CheckpointConfig(plan=RematPlan.uniform(n, seg)) if seg \
            else None
        def loss(p, im, lb, _r=remat):
            return cnn.loss_fn(p, cfg, im, lb, remat=_r)[0]
        mb = _residual_mb(loss, params, imgs, labels)
        _rows(name, 0.0, f"residual_mb={mb:.0f}")


def fig10_pipelines():
    """Memory across optimization pipelines for ResNet-50 and a small LM."""
    from repro.models import cnn
    from repro import configs
    from repro.models import transformer
    from repro.core.checkpoint import CheckpointConfig
    from repro.core.mixed_precision import get_policy

    from repro.plan import RematPlan
    cfg = cnn.resnet50(stem_stride=2)
    params = cnn.init_params(cfg, jax.random.PRNGKey(0))
    imgs_f = jax.ShapeDtypeStruct((16, 512, 512, 3), jnp.float32)
    imgs_p = jax.ShapeDtypeStruct((4, 512, 512, 3), jnp.uint32)
    labels = jax.ShapeDtypeStruct((16,), jnp.int32)
    sc8 = CheckpointConfig(plan=RematPlan.uniform(cnn.num_layer_fns(cfg), 8))

    cases = [
        ("fig10_resnet50_B", dict(remat=None), imgs_f),
        ("fig10_resnet50_ED", dict(remat=None, decode_backend="ref"),
         imgs_p),
        ("fig10_resnet50_SC", dict(remat=sc8), imgs_f),
        ("fig10_resnet50_ED_SC", dict(remat=sc8, decode_backend="ref"),
         imgs_p),
    ]
    for name, kw, im_sds in cases:
        def loss(p, im, lb, _kw=kw):
            return cnn.loss_fn(p, cfg, im, lb, **_kw)[0]
        mb = _residual_mb(loss, params, im_sds, labels)
        # E-D also cuts the host->device stream 4x (u32 vs f32 input bytes)
        inp_mb = np.prod(im_sds.shape) * im_sds.dtype.itemsize / 2 ** 20
        _rows(name, 0.0, f"residual_mb={mb:.0f},input_mb={inp_mb:.0f}")

    # LM variant: remat on/off x M-P on/off (smoke-sized llama)
    lcfg = configs.smoke_config("llama3-8b")
    lp = transformer.init_params(lcfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((8, 256), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 256), jnp.int32)}
    for name, remat, pol in [
            ("fig10_lm_B", False, "full"), ("fig10_lm_MP", False, "bf16"),
            ("fig10_lm_SC", True, "full"), ("fig10_lm_SC_MP", True, "bf16")]:
        def loss(p, b, _r=remat, _p=pol):
            return transformer.loss_fn(
                p, lcfg, b, policy=get_policy(_p),
                remat=CheckpointConfig(enabled=_r))[0]
        mb = _residual_mb(loss, lp, batch)
        _rows(name, 0.0, f"residual_mb={mb:.0f}")


def fig9_time_acc():
    """Accuracy/time parity across pipelines (reduced CIFAR run)."""
    from repro.data.synthetic import make_cifar_like
    from repro.data.pipeline import ParallelEncodedLoader
    from repro.models import cnn
    from repro.optim import adamw

    imgs, labels = make_cifar_like(n=1024, seed=0)
    cfg = cnn.resnet18()
    steps = 60

    def run(num_segments, codec, policy="full"):
        from repro.core.checkpoint import CheckpointConfig
        from repro.plan import RematPlan
        params = cnn.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw.init(params)
        ocfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=steps,
                                 weight_decay=0.0)
        remat = CheckpointConfig(plan=RematPlan.uniform(
            cnn.num_layer_fns(cfg), num_segments)) if num_segments else None

        @jax.jit
        def step(params, opt, im, lb):
            decode = "ref" if codec == "u32" else None

            def lossp(p):
                if policy == "bf16":
                    p = jax.tree_util.tree_map(
                        lambda x: x.astype(jnp.bfloat16)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, p)
                return cnn.loss_fn(p, cfg, im, lb, remat=remat,
                                   decode_backend=decode)

            (l, aux), g = jax.value_and_grad(lossp, has_aux=True)(params)
            g = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), g)
            params2, opt2, _ = adamw.update(ocfg, g, opt, params)
            return params2, opt2, l, aux["acc"]

        t0 = time.perf_counter()
        accs = []
        with ParallelEncodedLoader(imgs, labels, 32, codec=codec,
                                   prefetch=2) as dl:
            for _ in range(steps):
                enc, lb = next(dl)
                im = jnp.asarray(enc)
                params, opt, l, acc = step(params, opt, im, jnp.asarray(lb))
                accs.append(float(acc))
        dt = time.perf_counter() - t0
        return dt, float(np.mean(accs[-10:]))

    for name, seg, codec, pol in [
            ("fig9_baseline", 0, "none", "full"),
            ("fig9_ED", 0, "u32", "full"),
            ("fig9_SC", 6, "none", "full"),
            ("fig9_ED_SC", 6, "u32", "full"),
            ("fig9_ED_SC_MP", 6, "u32", "bf16")]:
        dt, acc = run(seg, codec, pol)
        _rows(name, dt * 1e6 / steps, f"acc={acc:.3f},total_s={dt:.1f}")


def plan_vs_uniform():
    """Profile-driven RematPlan vs uniform even-split remat at the same
    requested checkpoint count (acceptance benchmark for ``repro.plan``;
    paper Fig. 11 automated).  Writes BENCH_plan.json next to the repo root
    so the perf trajectory is tracked.

      * ResNet-18 (pyramid byte profile): the DP puts checkpoints at the
        narrow late activations -> strictly fewer stored residual bytes
        than the even split with the SAME number of checkpoints.
      * transformer, 14-layer smoke config: a uniform ``segment_size`` can
        only realize divisors of L (requesting ~4 segments of 14 layers
        degrades to 7 segments = 7 stored carries); the plan realizes
        exactly 4 non-uniform segments -> fewer stored carries.
    """
    import dataclasses
    import json
    import os
    import warnings

    from repro import configs, plan as plan_mod
    from repro.core.checkpoint import CheckpointConfig
    from repro.models import cnn, transformer

    out: dict = {}

    # ---- ResNet-18 ------------------------------------------------------
    cfg = cnn.resnet18(stem_stride=2)
    params = cnn.init_params(cfg, jax.random.PRNGKey(0))
    imgs_sds = jax.ShapeDtypeStruct((8, 256, 256, 3), jnp.float32)
    labels_sds = jax.ShapeDtypeStruct((8,), jnp.int32)
    prof = plan_mod.profile_resnet(params, cfg, imgs_sds)
    k = 5
    planned = plan_mod.plan_min_peak(prof, k)
    uniform = plan_mod.RematPlan.uniform(prof.n_layers, k + 1)
    assert len(planned.boundaries) == len(uniform.boundaries) == k

    im_t = jnp.asarray(np.random.default_rng(0).normal(
        size=(8, 64, 64, 3)).astype(np.float32))
    lb_t = jnp.asarray(np.arange(8) % 10)

    res_entry = {"checkpoints": k, "shape": list(imgs_sds.shape)}
    for name, plan in (("uniform", uniform), ("planned", planned)):
        remat = CheckpointConfig(plan=plan)

        def loss(p, im, lb, _r=remat):
            return cnn.loss_fn(p, cfg, im, lb, remat=_r)[0]

        mb = _residual_mb(loss, params, imgs_sds, labels_sds)
        step = jax.jit(jax.grad(
            lambda p: cnn.loss_fn(p, cfg, im_t, lb_t, remat=remat)[0]))
        us, _ = _timeit(lambda: step(params), iters=3)
        res_entry[name] = {
            "boundaries": list(plan.boundaries),
            "residual_mb": round(mb, 2),
            "us_per_step_64px": round(us, 1),
        }
        _rows(f"plan_vs_uniform_resnet18_{name}", us,
              f"residual_mb={mb:.0f},boundaries={list(plan.boundaries)}")
    assert res_entry["planned"]["residual_mb"] < \
        res_entry["uniform"]["residual_mb"], "planner must beat even split"
    out["resnet18"] = res_entry

    # ---- transformer (smoke config deepened to 14 layers) ---------------
    lcfg = dataclasses.replace(configs.smoke_config("llama3-8b"), n_layers=14)
    lp = transformer.init_params(lcfg, jax.random.PRNGKey(0))
    batch_sds = {"tokens": jax.ShapeDtypeStruct((4, 128), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((4, 128), jnp.int32)}
    lprof = plan_mod.profile_transformer(lcfg, batch_sds)
    req_segments = 4                       # what the user asks for
    tplan = plan_mod.plan_min_peak(lprof, req_segments - 1)
    # legacy knob: ~L/4 blocks per segment; 14 % 4 != 0 -> divisor fallback
    from repro.core.checkpoint import _largest_divisor_leq
    seg_size = -(-lcfg.n_layers // req_segments)
    seg_size_executed = _largest_divisor_leq(lcfg.n_layers, seg_size)
    rng = np.random.default_rng(1)
    batch = {"tokens": jnp.asarray(rng.integers(0, 255, (4, 128), np.int32)),
             "labels": jnp.asarray(rng.integers(0, 255, (4, 128), np.int32))}

    tf_entry = {"requested_segments": req_segments, "n_layers": lcfg.n_layers,
                "shape": [4, 128]}
    cases = (("uniform", CheckpointConfig(segment_size=seg_size)),
             ("planned", CheckpointConfig(plan=tplan)))
    for name, remat in cases:
        def loss(p, b, _r=remat):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")  # divisor fallback, expected
                return transformer.loss_fn(p, lcfg, b, remat=_r)[0]

        mb = _residual_mb(loss, lp, batch)
        step = jax.jit(jax.grad(lambda p: loss(p, batch)))
        us, _ = _timeit(lambda: step(lp), iters=3)
        tf_entry[name] = {
            # record what actually EXECUTES: the uniform knob degrades to
            # the largest divisor of L, not the requested size
            "segment_sizes": (tplan.segment_sizes() if name == "planned"
                              else [seg_size_executed]
                              * (lcfg.n_layers // seg_size_executed)),
            "residual_mb": round(mb, 2),
            "us_per_step": round(us, 1),
        }
        _rows(f"plan_vs_uniform_transformer_{name}", us,
              f"residual_mb={mb:.0f}")
    assert tf_entry["planned"]["residual_mb"] < \
        tf_entry["uniform"]["residual_mb"], \
        "plan must beat the degraded uniform split"
    out["transformer_smoke14"] = tf_entry

    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_plan.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(f"# wrote {os.path.normpath(path)}", flush=True)


def flash_fwd_bwd():
    """Trainable flash attention (ISSUE 2 + 3 acceptance): fwd-only vs
    fwd+bwd, pallas custom_vjp vs the jnp O(S^2) path — residual ("peak
    between fwd and bwd") bytes across S, wall time where the kernels
    execute on CPU (interpret mode), and the sparse-grid tile/FLOP
    claw-back (visited vs dense KV tile-steps, measured via the kernels'
    debug counters at a CPU-feasible size and analytic across S).
    Writes BENCH_flash.json.

    The pallas rows use ``backend="pallas"`` under ``jax.eval_shape`` (the
    custom_vjp residual structure is backend-independent; abstract eval
    never lowers to Mosaic), so the recorded bytes are exactly what a TPU
    run would save between forward and backward.
    """
    import json
    import os

    from repro.kernels.flash import kernel as flash_kernel, \
        ops as flash_ops, ref as flash_ref

    b, h, hkv, d = 1, 4, 2, 64
    out: dict = {"shape": {"batch": b, "heads": h, "kv_heads": hkv,
                           "head_dim": d}, "cases": {}}

    def fwd_bytes(fn, *sds):
        o = jax.eval_shape(fn, *sds)
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(o))

    def fwd_bwd_bytes(fn, *sds):
        # output + vjp residuals: everything alive between fwd and bwd
        o = jax.eval_shape(lambda *a: jax.vjp(fn, *a), *sds)
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(o))

    for s in (512, 1024, 2048):
        sds = (jax.ShapeDtypeStruct((b, h, s, d), jnp.float32),
               jax.ShapeDtypeStruct((b, hkv, s, d), jnp.float32),
               jax.ShapeDtypeStruct((b, hkv, s, d), jnp.float32))
        fns = {
            "jnp": lambda q, k, v: flash_ref.flash_ref(q, k, v),
            "pallas": lambda q, k, v: flash_ops.flash_attention(
                q, k, v, backend="pallas"),
        }
        entry = {}
        for name, fn in fns.items():
            entry[name] = {
                "fwd_bytes": fwd_bytes(fn, *sds),
                "fwd_bwd_peak_bytes": fwd_bwd_bytes(fn, *sds),
            }
            _rows(f"flash_fwd_bwd_s{s}_{name}", 0.0,
                  f"fwd_mb={entry[name]['fwd_bytes']/2**20:.1f},"
                  f"fwd_bwd_mb={entry[name]['fwd_bwd_peak_bytes']/2**20:.1f}")
        if s >= 1024:
            assert entry["pallas"]["fwd_bwd_peak_bytes"] < \
                entry["jnp"]["fwd_bwd_peak_bytes"], \
                "flash custom_vjp must beat the jnp S^2 residuals"
        out["cases"][f"s{s}"] = entry

    # ---- sparse grids (ISSUE 3): visited vs dense tile-steps ----------
    # analytic counts across S for the two schedules that matter, plus a
    # measured interpret-mode run (debug counters) to prove the kernels
    # execute exactly the analytic schedule.
    sparsity: dict = {}
    for s in (512, 1024, 2048):
        for name, w in (("causal", 0), ("window256", 256)):
            if w >= s:
                continue
            c = flash_kernel.tile_step_counts(s, causal=True, window=w)
            steps = {g: c[g] for g in ("fwd", "dq", "dkv")}
            visited = sum(steps.values())
            dense = 3 * c["dense"]
            sparsity[f"{name}_s{s}"] = {
                **steps, "dense_per_grid": c["dense"],
                "skipped_frac": round(1 - visited / dense, 4),
            }
            _rows(f"flash_sparse_{name}_s{s}", 0.0,
                  f"visited={visited},dense={dense},"
                  f"skipped={1 - visited/dense:.3f}")
    # measured counters at S=512 on 128 x 128 tiles (cheap in interpret
    # mode): must equal the analytic schedule tile-for-tile
    s_m, h_m = 512, 2
    qm = jnp.asarray(np.random.default_rng(5).normal(
        size=(h_m, s_m, d)).astype(np.float32))
    o_m, m_m, l_m, cnt = flash_kernel.flash_attention_fwd_pallas(
        qm, qm, qm, causal=True, bq=128, bk=128, interpret=True,
        debug_counts=True)
    *_, dqc, dkvc = flash_kernel.flash_attention_bwd_pallas(
        qm, qm, qm, o_m, m_m, l_m, jnp.ones_like(o_m), causal=True,
        bq=128, bk=128, interpret=True, debug_counts=True)
    c = flash_kernel.tile_step_counts(s_m, causal=True, window=0)
    measured = {"fwd": int(cnt[0].sum()), "dq": int(dqc[0].sum()),
                "dkv": int(dkvc[0].sum())}
    assert measured == {g: c[g] for g in ("fwd", "dq", "dkv")}, \
        (measured, c)
    sparsity["measured_causal_s512"] = measured
    out["sparsity"] = sparsity

    # FLOP claw-back the planner budgets (causal smoke config @ 2048): on
    # 128 x 128 grids, and at the tiles the kernels run, which trade
    # masked area on the diagonal for fewer grid steps
    import dataclasses as dc_mod

    from repro import configs, plan as plan_mod
    cfg_cb = dc_mod.replace(configs.smoke_config("llama3-8b"),
                            attn_backend="pallas", head_dim=64)
    for key, tiles, floor in (
            ("flop_clawback_s2048", (128, 128), 0.45),
            ("flop_clawback_s2048_running_tiles", None, 0.2)):
        rep = plan_mod.flash_attn_flop_report(cfg_cb, 1, 2048, tiles=tiles)
        assert rep["eligible"] and rep["skip_frac"] >= floor, (key, rep)
        out[key] = {
            "dense_gflops": round(rep["dense_flops"] / 1e9, 2),
            "visited_gflops": round(rep["visited_flops"] / 1e9, 2),
            "clawback_x": round(rep["dense_flops"] / rep["visited_flops"],
                                3),
            "tile_skip_frac": round(rep["skip_frac"], 4),
        }
        _rows(f"flash_{key}", 0.0,
              f"dense_gflops={rep['dense_flops']/1e9:.1f},"
              f"visited_gflops={rep['visited_flops']/1e9:.1f},"
              f"clawback={rep['dense_flops']/rep['visited_flops']:.2f}x")

    # wall time at a CPU-executable size: interpret-mode kernels vs jnp
    s = 256
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)).astype(np.float32))
    timing = {}
    for name, backend in (("jnp", "ref"), ("interpret", "interpret")):
        fwd = jax.jit(lambda q, k, v, _b=backend: flash_ops.flash_attention(
            q, k, v, backend=_b))
        grad = jax.jit(jax.grad(
            lambda q, k, v, _b=backend: jnp.sum(flash_ops.flash_attention(
                q, k, v, backend=_b) ** 2), argnums=(0, 1, 2)))
        us_f, _ = _timeit(fwd, q, k, v)
        us_g, _ = _timeit(grad, q, k, v)
        timing[name] = {"fwd_us": round(us_f, 1),
                        "fwd_bwd_us": round(us_g, 1)}
        _rows(f"flash_wall_s{s}_{name}", us_g, f"fwd_us={us_f:.0f}")
    out["wall_s256"] = timing

    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_flash.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(f"# wrote {os.path.normpath(path)}", flush=True)


def flash_decode():
    """Split-K int8 flash decode (ISSUE 4 acceptance): sequential vs
    split-K wall time where the kernels execute on CPU (interpret mode),
    and the dense-vs-visited tile claw-back of length-aware skipping on a
    ragged S=2048 batch (mean length S/4) — measured via the kernel's
    debug counters and asserted against the analytic twin.  Writes
    BENCH_decode.json.
    """
    import json
    import os

    from repro import configs, plan as plan_mod
    from repro.kernels import tiling
    from repro.kernels.kvq import ops as kvq_ops, ref as kvq_ref

    b, h, hkv, d, s, bs = 4, 8, 2, 64, 2048, 256
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)).astype(np.float32))
    kq, ks = kvq_ref.quantize_kv(k)
    vq, vs = kvq_ref.quantize_kv(v)
    lengths = jnp.asarray([256, 512, 512, 768], jnp.int32)  # mean S/4
    out: dict = {"shape": {"batch": b, "heads": h, "kv_heads": hkv,
                           "head_dim": d, "seq": s, "block_s": bs,
                           "lengths": [int(x) for x in lengths]}}

    # ---- tile claw-back: measured counters == analytic, >= 70% skipped
    o_cnt, cnt = kvq_ops.decode_attention(
        q, kq, ks, vq, vs, lengths=lengths, backend="interpret", splits=4,
        block_s=bs, debug_counts=True)
    executed = int(np.asarray(cnt)[:, 0].sum())          # per kv head
    dense = b * (s // bs)
    c = tiling.decode_tile_step_counts(s, [int(x) for x in lengths],
                                       block_s=bs, splits=4)
    assert executed == c["visited"], (executed, c)
    skip = 1 - executed / dense
    assert skip >= 0.70, skip
    out["tile_clawback_s2048_ragged"] = {
        "visited": executed, "dense": dense, "skip_frac": round(skip, 4)}
    _rows("flash_decode_tiles_s2048_ragged", 0.0,
          f"visited={executed},dense={dense},skipped={skip:.3f}")

    # ---- sequential vs split-K wall time (interpret mode; the schedule
    # restructuring, not TPU latency — that needs hardware)
    timing = {}
    for name, splits in (("sequential", 1), ("splitk4", 4)):
        fn = jax.jit(lambda q, kq, ks, vq, vs, _s=splits:
                     kvq_ops.decode_attention(
                         q, kq, ks, vq, vs, lengths=lengths,
                         backend="interpret", splits=_s, block_s=bs))
        us, o = _timeit(fn, q, kq, ks, vq, vs)
        timing[name] = round(us, 1)
        _rows(f"flash_decode_wall_s2048_{name}", us, f"splits={splits}")
    o_seq = jax.jit(lambda *a: kvq_ops.decode_attention(
        *a, lengths=lengths, backend="ref"))(q, kq, ks, vq, vs)
    assert float(jnp.abs(o_cnt - o_seq).max()) < 1e-3
    out["wall_us_interpret"] = timing

    # ---- planner decode report at a serving shape (llama3 @ decode_32k
    # geometry, reduced batch): visited-vs-dense tiles + int8 cache bytes
    cfg = configs.get_config("llama3-8b")
    rep = plan_mod.decode_tile_report(cfg, 4, 32768,
                                      lengths=[8192] * 4, splits=8)
    cache_rep = plan_mod.kv_cache_report(cfg, 4, 32768)
    out["planner_llama3_32k_quarter"] = {
        "visited_tile_steps": rep["visited_tile_steps"],
        "dense_tile_steps": rep["dense_tile_steps"],
        "skip_frac": round(rep["skip_frac"], 4),
        "visited_kv_gbytes": round(rep["visited_kv_bytes"] / 1e9, 3),
        "dense_kv_gbytes": round(rep["dense_kv_bytes"] / 1e9, 3),
        "kv_cache_int8_gbytes": round(cache_rep["int8_bytes"] / 1e9, 3),
        "kv_cache_f32_gbytes": round(cache_rep["f32_bytes"] / 1e9, 3),
    }
    _rows("flash_decode_planner_llama3_32k", 0.0,
          f"skip={rep['skip_frac']:.3f},"
          f"kv_int8_gb={cache_rep['int8_bytes']/1e9:.2f},"
          f"kv_f32_gb={cache_rep['f32_bytes']/1e9:.2f}")

    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_decode.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(f"# wrote {os.path.normpath(path)}", flush=True)


def serve_trace():
    """Continuous batching vs lockstep on one ragged trace (ISSUE 5
    acceptance): the engine joins requests mid-flight and retires them at
    their own length, so no slot pays for the slowest request; lockstep
    groups the same requests into fixed batches, pads every prompt to the
    group max, and decodes the group's max generation length for
    everyone.  Useful tokens (each request's own gen budget) per wall
    second is the headline; wasted slot-steps make the padding cost
    explicit.  Writes BENCH_serve.json.
    """
    import json
    import os

    from repro import configs
    from repro.models import transformer
    from repro.serve import ServeEngine, synthetic_trace
    from repro.train.serve_step import build_decode_step, build_prefill_step

    cfg = configs.smoke_config("llama3-8b")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    slots, max_len, bucket = 4, 96, 16
    trace = synthetic_trace(12, seed=7, vocab=cfg.vocab, mean_prompt=10,
                            max_prompt=bucket, mean_gen=16, max_gen=48,
                            arrival_rate=1.0)
    useful = sum(r.max_new_tokens for r in trace)

    # ---- continuous batching vs lockstep, interleaved best-of-5: both
    # sides are ~50 ms walls on CPU, so OS/allocator noise between two
    # separately-timed blocks can swing the ratio by 20%+ (observed while
    # re-basing for ISSUE 8).  Alternating one engine pass with one
    # lockstep pass inside the SAME loop makes any machine-state drift
    # hit both sides equally; min-of-5 then compares steady-state floors.
    eng = ServeEngine(params, cfg, max_slots=slots, max_len=max_len,
                      prompt_buckets=(bucket,), seed=0)
    compiles = eng.warmup()

    prefill = jax.jit(build_prefill_step(cfg, quantized=True,
                                         s_max=max_len))
    decode = jax.jit(build_decode_step(cfg, quantized=True))
    groups = [trace[i:i + slots] for i in range(0, len(trace), slots)]

    def run_lockstep():
        slot_steps = ttfts = 0
        step_clock = 0
        for g in groups:
            toks = np.zeros((slots, bucket), np.int32)
            for j, r in enumerate(g):
                toks[j, :len(r.prompt)] = r.prompt      # pad to the bucket
            # the whole group must have arrived before a lockstep batch
            # can prefill, and it holds all slots for the group max
            step_clock = max(step_clock, max(r.arrival_step for r in g))
            logits, cache = prefill(params, {"tokens": jnp.asarray(toks)})
            tok = jnp.asarray(logits.argmax(-1), jnp.int32)
            np.asarray(tok)          # serving streams every token out
            ttfts += sum(step_clock + 1 - r.arrival_step for r in g)
            g_steps = max(r.max_new_tokens for r in g)
            for _ in range(g_steps - 1):
                lg, cache = decode(params, cache, tok)
                tok = jnp.asarray(lg.argmax(-1), jnp.int32)
                np.asarray(tok)      # same per-step delivery the engine pays
            step_clock += g_steps
            slot_steps += g_steps * slots
        return slot_steps, ttfts / len(trace)

    run_lockstep()                                      # compile warmup
    wall_e = wall_l = float("inf")
    for _ in range(5):
        eng.reset()
        t0 = time.perf_counter()
        summary = eng.run(trace)
        wall_e = min(wall_e, time.perf_counter() - t0)
        t0 = time.perf_counter()
        slot_steps, ttft_lock = run_lockstep()
        wall_l = min(wall_l, time.perf_counter() - t0)
    assert eng.compile_counts() == compiles, "engine re-jitted mid-trace"
    assert summary["total_tokens"] == useful

    # ---- same trace under seeded faults (ISSUE 7): the canonical
    # detect -> quarantine -> replay run.  Victims and steps are pinned to
    # this seeded trace (replay prompts must fit the 16-token bucket; a
    # drop_scatter victim must land on a first-use slot for the pos>0
    # sentinel); the injected-count asserts catch any drift.
    from repro.serve import FaultInjector, FaultPlan
    wall_f = float("inf")
    for _ in range(3):
        eng.reset()
        plan = (FaultPlan().drop_scatter(3, rid=3).nan_logits(5, rid=0)
                .corrupt_row(15, rid=6))
        inj = FaultInjector(eng, plan)
        t0 = time.perf_counter()
        fsum = eng.run(trace)
        wall_f = min(wall_f, time.perf_counter() - t0)
        inj.uninstall()
        assert dict(inj.injected) == {"drop_scatter": 1, "nan_logits": 1,
                                      "corrupt_row": 1}, inj.injected
    assert eng.compile_counts() == compiles, "fault injection re-jitted"
    assert fsum["n_failed"] == 0 and fsum["n_done"] == len(trace)
    leaks = eng.pool.allocs - eng.pool.frees + eng.pool.occupancy
    goodput_f = fsum["goodput_tokens"] / wall_f

    # ---- replica fleet (ISSUE 8): 2 engines behind the router, same
    # trace, one replica killed mid-trace.  The canonical seeded failover
    # run: every request still completes (migrated ones replay from
    # prompt + emitted tokens on the survivor), and the ratchet floors
    # failover_replay_success and the goodput ratio vs the fault-free
    # fleet.
    from repro.serve import FleetFaultInjector, Router

    # a mid-trace failover replays prompt + emitted tokens, so fleet
    # replicas carry a second prefill bucket big enough for any replay
    # (max_prompt 16 + max_gen 48 = 64); single-engine runs above pin
    # faults early enough to fit one bucket, a killed replica can't
    fleet_eng = [ServeEngine(params, cfg, max_slots=slots, max_len=max_len,
                             prompt_buckets=(bucket, 64), seed=0,
                             sampler_keys="request")
                 for _ in range(2)]
    fleet_compiles = [e.warmup() for e in fleet_eng]

    wall_ff = float("inf")
    for _ in range(3):
        for e in fleet_eng:
            e.reset()
        router = Router(fleet_eng)
        t0 = time.perf_counter()
        ffsum = router.run(trace)
        wall_ff = min(wall_ff, time.perf_counter() - t0)
    assert ffsum["fleet"]["n_done"] == len(trace)

    wall_k = float("inf")
    for _ in range(3):
        for e in fleet_eng:
            e.reset()
        router = Router(fleet_eng)
        kplan = FaultPlan().replica_crash(4, 1)
        kinj = FleetFaultInjector(router, kplan)
        t0 = time.perf_counter()
        ksum = router.run(trace)
        wall_k = min(wall_k, time.perf_counter() - t0)
        assert kinj.crashed == {1}, kinj.injected
    for e, c in zip(fleet_eng, fleet_compiles):
        assert e.compile_counts() == c, "fleet replica re-jitted"
    fleet_leaks = sum(e.pool.allocs - e.pool.frees + e.pool.occupancy
                      for e in fleet_eng)
    assert ksum["fleet"]["n_done"] == len(trace), ksum["fleet"]
    assert ksum["reconcile"]["ok"], ksum["reconcile"]
    goodput_ff = ffsum["fleet"]["goodput_tokens"] / wall_ff
    goodput_k = ksum["fleet"]["goodput_tokens"] / wall_k

    # ---- durable serving (ISSUE 9): the canonical seeded router-crash
    # run.  (a) journaled-but-uncrashed fleet run on the same trace —
    # the fsync'd WAL must cost < 20% goodput vs the unjournaled fleet
    # (the ratchet floors the ratio); (b) the run is killed -9 after a
    # fixed step budget (router abandoned, engine-side requests vanish),
    # then a FRESH router reopens the journal, recovers every live
    # request, and drives the fleet dry — the ratchet floors
    # recovery_replay_success.
    import tempfile

    from repro.serve import RequestJournal, TERMINAL

    def _force_drain():
        # kill -9 semantics: engine-side state vanishes, jit cache stays
        for e in fleet_eng:
            for rid, st in list(e.request_states().items()):
                if st["state"] not in TERMINAL:
                    e.evict_request(rid)
            e.reset()

    wal_dir = tempfile.mkdtemp(prefix="bench_wal_")
    # interleaved like continuous-vs-lockstep above: the journal's cost
    # is a few ms on a ~50 ms wall, well inside machine noise between
    # separately-timed blocks, so each iteration times one unjournaled
    # pass and one journaled pass back to back and the ratio compares
    # min-of-3 floors
    wall_j = wall_ffi = float("inf")
    for it in range(3):
        for e in fleet_eng:
            e.reset()
        router = Router(fleet_eng)
        t0 = time.perf_counter()
        ffisum = router.run(trace)
        wall_ffi = min(wall_ffi, time.perf_counter() - t0)
        for e in fleet_eng:
            e.reset()
        jp = os.path.join(wal_dir, f"journaled_{it}.jsonl")
        # group commit (flush_every=16): one fsync amortizes a batch of
        # appends.  The fsync-lag window this opens is exactly what
        # recovery tolerates — lost tail records are regenerated
        # deterministically — so the serving price of durability is the
        # batched write, not an fsync per token
        with RequestJournal(jp, snapshot_every=64,
                            flush_every=16) as jrn:
            router = Router(fleet_eng, journal=jrn,
                            journal_tokens_every=4)
            t0 = time.perf_counter()
            jsum = router.run(trace)
            wall_j = min(wall_j, time.perf_counter() - t0)
            assert router.reconcile()["ok"]
            j_appends = jrn.appends
    assert jsum["fleet"]["n_done"] == len(trace)
    assert ffisum["fleet"]["n_done"] == len(trace)
    goodput_j = jsum["fleet"]["goodput_tokens"] / wall_j
    journal_overhead_ratio = goodput_j / (
        ffisum["fleet"]["goodput_tokens"] / wall_ffi)

    crash_step = 12
    jp = os.path.join(wal_dir, "crash.jsonl")
    jrn = RequestJournal(jp, snapshot_every=64, flush_every=16)
    router = Router(fleet_eng, journal=jrn, journal_tokens_every=4)
    t0 = time.perf_counter()
    router.run(trace, max_steps=crash_step)      # stalled = "crashed"
    n_live_at_crash = router.live_requests()
    del router                                   # kill -9
    _force_drain()
    jrn.close()

    j2 = RequestJournal(jp)
    router = Router(fleet_eng, journal=j2)
    rinfo = router.recover()
    guard = 2000
    while router.live_requests() > 0 and guard:
        router.step()
        guard -= 1
    wall_r = time.perf_counter() - t0
    rsum = router.summary()
    rrec = router.reconcile()
    j2.close()
    assert guard, "recovered fleet failed to drain"
    assert rrec["ok"], rrec
    assert rrec["checks"]["journal_accounted"]
    for e, c in zip(fleet_eng, fleet_compiles):
        assert e.compile_counts() == c, "recovery re-jitted"
    recovery_leaks = sum(e.pool.allocs - e.pool.frees + e.pool.occupancy
                         for e in fleet_eng)

    tps_e = useful / wall_e
    tps_l = useful / wall_l
    out = {
        "trace": {"requests": len(trace), "useful_tokens": useful,
                  "slots": slots, "max_len": max_len,
                  "gen_lengths": [r.max_new_tokens for r in trace]},
        "continuous": {
            "tokens_per_s": round(tps_e, 1), "wall_s": round(wall_e, 3),
            "ttft_mean_steps": round(summary["ttft_mean_steps"], 2),
            "occupancy_mean": round(summary["occupancy_mean"], 2),
            "engine_steps": summary["n_steps"],
            "wasted_slot_steps": summary["n_steps"] * slots - useful,
            # deterministic packing quality on the seeded trace (no wall
            # clock involved): useful tokens per slot-step the engine
            # actually ran — the structural win continuous batching
            # ratchets regardless of machine noise
            "slot_step_efficiency":
                round(useful / (summary["n_steps"] * slots), 3),
        },
        "lockstep": {
            "tokens_per_s": round(tps_l, 1), "wall_s": round(wall_l, 3),
            "ttft_mean_steps": round(ttft_lock, 2),
            "decode_slot_steps": slot_steps,
            "wasted_slot_steps": slot_steps - useful,
        },
        "speedup_tokens_per_s": round(tps_e / tps_l, 2),
        "fault_trace": {
            "injected": dict(inj.injected),
            "n_faults": fsum["n_faults"], "n_retried": fsum["n_retried"],
            "n_done": fsum["n_done"], "n_failed": fsum["n_failed"],
            "retry_success_rate": fsum["retry_success_rate"],
            "goodput_tokens": fsum["goodput_tokens"],
            "goodput_tokens_per_s": round(goodput_f, 1),
            "goodput_frac_of_fault_free": round(goodput_f / tps_e, 3),
            "quarantines": eng.pool.quarantines,
            "zero_slot_leaks": leaks == 0,
            "engine_steps": fsum["n_steps"],
        },
        "fleet": {
            "replicas": 2,
            "fault_free": {
                "wall_s": round(wall_ff, 3),
                "router_steps": ffsum["step_no"],
                "goodput_tokens": ffsum["fleet"]["goodput_tokens"],
                "goodput_tokens_per_s": round(goodput_ff, 1),
            },
            "replica_kill": {
                "kill_step": 4, "replica": 1,
                "wall_s": round(wall_k, 3),
                "router_steps": ksum["step_no"],
                "failovers": ksum["fleet"]["failovers"],
                "n_migrations": ksum["fleet"]["n_migrations"],
                "failover_replay_success":
                    ksum["fleet"]["replay_success_rate"],
                "n_done": ksum["fleet"]["n_done"],
                "goodput_tokens": ksum["fleet"]["goodput_tokens"],
                "goodput_tokens_per_s": round(goodput_k, 1),
                "goodput_frac_of_fault_free":
                    round(goodput_k / goodput_ff, 3),
                "zero_slot_leaks": fleet_leaks == 0,
            },
        },
        "recovery": {
            "journaled": {
                "wall_s": round(wall_j, 3),
                "goodput_tokens": jsum["fleet"]["goodput_tokens"],
                "goodput_tokens_per_s": round(goodput_j, 1),
                "appends": j_appends,
            },
            "journaled_goodput_frac_of_unjournaled":
                round(journal_overhead_ratio, 3),
            "router_crash": {
                "crash_step": crash_step,
                "n_live_at_crash": n_live_at_crash,
                "n_recovered": rinfo["n_recovered"],
                "n_placed": rinfo["n_placed"],
                "n_done_from_disk": rinfo["n_done"],
                "wall_s_end_to_end": round(wall_r, 3),
                "n_done": rsum["fleet"]["n_done"],
                "terminal_counts":
                    dict(j2.state.terminal_counts),
                "one_terminal_per_submit":
                    rrec["checks"]["journal_accounted"],
                "zero_slot_leaks": recovery_leaks == 0,
            },
            "recovery_replay_success":
                rsum["fleet"]["recovery_replay_success"],
        },
    }
    _rows("serve_trace_faulted", wall_f * 1e6,
          f"goodput_tok_s={goodput_f:.1f},faults={fsum['n_faults']}")
    _rows("serve_fleet_fault_free", wall_ff * 1e6,
          f"goodput_tok_s={goodput_ff:.1f},replicas=2")
    _rows("serve_fleet_replica_kill", wall_k * 1e6,
          f"goodput_tok_s={goodput_k:.1f},"
          f"failovers={ksum['fleet']['failovers']}")
    _rows("serve_fleet_journaled", wall_j * 1e6,
          f"goodput_tok_s={goodput_j:.1f},"
          f"frac_of_unjournaled={journal_overhead_ratio:.3f}")
    _rows("serve_router_crash_recover", wall_r * 1e6,
          f"recovered={rinfo['n_recovered']},"
          f"replay_success={rsum['fleet']['recovery_replay_success']:.2f}")
    _rows("serve_trace_continuous", wall_e * 1e6,
          f"tok_s={tps_e:.1f},occ={summary['occupancy_mean']:.2f}")
    _rows("serve_trace_lockstep", wall_l * 1e6, f"tok_s={tps_l:.1f}")
    _rows("serve_trace_speedup", 0.0, f"{tps_e/tps_l:.2f}x")
    assert tps_e > tps_l, (
        f"continuous batching ({tps_e:.1f} tok/s) must beat lockstep "
        f"({tps_l:.1f} tok/s) on a ragged trace")

    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(f"# wrote {os.path.normpath(path)}", flush=True)


def tbl_codec():
    """Codec throughput + ratios (paper claims up-to 16x passage saving)."""
    from repro.core import encoding
    rng = np.random.default_rng(0)
    batch = rng.integers(0, 256, (16, 512, 512, 3), dtype=np.uint8)

    us, _ = _timeit(lambda: encoding.pack_u8_to_u32(batch), iters=5)
    _rows("codec_u32_pack_16x512x512x3", us,
          f"ratio_vs_f32={encoding.compression_ratio(4, 'u32'):.0f}x")
    packed = np.asarray(encoding.pack_u8_to_u32(batch))
    us, _ = _timeit(lambda: encoding.unpack_u32_to_u8(packed), iters=5)
    _rows("codec_u32_unpack", us, "exact=True")

    sub = batch[:6]
    us, _ = _timeit(lambda: encoding.encode_base256(sub), iters=3)
    _rows("codec_base256_encode_6imgs", us, "ratio=3x,f64")
    enc = encoding.encode_base256(sub)
    us, _ = _timeit(lambda: encoding.decode_base256(enc, 6), iters=3)
    _rows("codec_base256_decode", us, "exact=True")

    sub7 = batch[:7]
    us, _ = _timeit(lambda: encoding.encode_lossless(sub7), iters=3)
    _rows("codec_lossless_encode_7imgs", us, "alg4,f64+offsets")

    # jit'd fused decode layer (the network's first layer)
    from repro.kernels.pack import ops as pack_ops
    pj = jnp.asarray(packed)
    us, _ = _timeit(lambda: pack_ops.decode(pj, backend="ref"), iters=5)
    _rows("codec_decode_layer_jit", us, "fused_normalize=True")


def tbl_pipeline():
    """Parallel E-D: background-thread encoding vs inline (paper Fig. 1)."""
    from repro.data.synthetic import make_cifar_like
    from repro.data.pipeline import ParallelEncodedLoader
    from repro.core import encoding

    imgs, labels = make_cifar_like(n=2048, seed=0)
    bs, steps = 32, 64
    train_ms = 3.0  # simulated device step time

    def consume_parallel():
        with ParallelEncodedLoader(imgs, labels, bs, codec="u32",
                                   prefetch=4) as dl:
            t0 = time.perf_counter()
            for _ in range(steps):
                next(dl)
                time.sleep(train_ms / 1e3)
            return time.perf_counter() - t0

    def consume_inline():
        rng = np.random.default_rng(0)
        t0 = time.perf_counter()
        for _ in range(steps):
            idx = rng.integers(0, len(imgs), bs)
            encoding.pack_u8_to_u32(imgs[idx])
            time.sleep(train_ms / 1e3)
        return time.perf_counter() - t0

    tp = consume_parallel()
    ti = consume_inline()
    _rows("pipeline_parallel_ED", tp / steps * 1e6,
          f"speedup_vs_inline={ti/tp:.2f}x")
    _rows("pipeline_inline_ED", ti / steps * 1e6, "")


def tbl_compression():
    from repro.optim import compression
    g = {"w": jnp.asarray(np.random.default_rng(0)
                          .normal(size=(1 << 20,)).astype(np.float32))}
    us, (payload, _) = _timeit(
        lambda: compression.compress_with_feedback(
            g, None, jax.random.PRNGKey(0), codec="int8"), iters=3)
    raw = 4 * (1 << 20)
    _rows("grad_compress_int8_1M", us,
          f"payload_ratio={raw/compression.payload_bytes(payload):.1f}x")
    us, (payload, _) = _timeit(
        lambda: compression.compress_with_feedback(
            g, None, jax.random.PRNGKey(0), codec="topk", topk_frac=0.01),
        iters=3)
    _rows("grad_compress_topk1pct_1M", us,
          f"payload_ratio={raw/compression.payload_bytes(payload):.1f}x")


def mesh_shard():
    """Mesh-sharding parity + capacity (ISSUE 6 acceptance), via
    subprocess: this process already initialized jax with however many
    devices exist, and the 8-device emulated grid can only be requested
    through XLA_FLAGS before backend init — so bench_shard.py runs in a
    fresh interpreter and this wrapper just relays its result."""
    import os
    import subprocess
    import sys as _sys

    script = os.path.join(os.path.dirname(__file__), "bench_shard.py")
    t0 = time.perf_counter()
    proc = subprocess.run([_sys.executable, script], text=True,
                          capture_output=True)
    _sys.stdout.write(proc.stdout)
    if proc.returncode:
        _sys.stderr.write(proc.stderr)
        raise SystemExit(f"bench_shard failed ({proc.returncode})")
    _rows("mesh_shard_total", (time.perf_counter() - t0) * 1e6,
          "devices=8,see=BENCH_shard.json")


BENCHES = [tbl_codec, tbl_pipeline, tbl_compression, fig8_memory,
           fig10_pipelines, plan_vs_uniform, flash_fwd_bwd, flash_decode,
           serve_trace, mesh_shard, fig9_time_acc]


def main() -> None:
    import sys
    wanted = set(sys.argv[1:])
    benches = [b for b in BENCHES if not wanted or b.__name__ in wanted]
    if wanted and not benches:
        raise SystemExit(f"unknown benchmark(s) {sorted(wanted)}; "
                         f"known: {[b.__name__ for b in BENCHES]}")
    print("name,us_per_call,derived")
    for b in benches:
        t0 = time.time()
        b()
        print(f"# {b.__name__} done in {time.time()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
