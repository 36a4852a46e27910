"""Training: the program's jitted train step under its planned remat, fed
seeded token rows, for ``--seconds``.

Set-up builds one step and its state, as the launcher does, and drives
them through their first three steps with the same feed and call the
window uses; the window then continues the same object.  In the window
the host keeps ``trainer.ahead_steps`` steps dispatched beyond the one
whose loss it reads, as a trainer that logs each loss a few steps late
does, so that a stall of the host does not leave the chip idle.  When
the window's time is up nothing more is dispatched; every step sent is
awaited, and the clock is read after that wait.

End-to-end: ``train_tokens_per_s``, tokens of every step dispatched in
the window over the time from its start to the end of the last of them;
``setup_s``.
Correct: the first three steps against the float32 reference (its own
loss, gradients and AdamW, from the same weights and rows): each step's
loss; each leaf's first gradient norm, read back from the program's AdamW
first moment after step 1; each leaf's change after step 3.
"""
from __future__ import annotations

import argparse
import collections
import gc
import sys

import numpy as np

import traffic

CHECK_STEPS = 3


def leaf_gap(got: dict, want: dict, keep=None) -> tuple[str, float]:
    """Worst leaf by ``|got - want| / max(want, median of want)``."""
    names = [k for k in want if keep is None or keep(k)]
    med = float(np.median([want[k] for k in want]))
    gaps = {k: abs(got[k] - want[k]) / max(want[k], med, 1e-30)
            for k in names}
    worst = max(gaps, key=gaps.get)
    return worst, gaps[worst]


def run(run):
    import jax
    import jax.numpy as jnp

    from repro.core.mixed_precision import LossScale
    from repro.launch.mesh import make_mesh_for
    from repro.launch.train import auto_remat
    from repro.optim import adamw
    from repro.train.train_step import TrainConfig, make_train_step

    wl, ref = run.workload, run.reference
    trn, opt_cfg = wl["trainer"], wl["optimizer"]
    b, s = wl["traffic"]["batch"], wl["traffic"]["seq"]
    d = ref.dims(run.config)
    cfg = run.program_config()
    mesh = make_mesh_for(max_model=16)
    sds = {k: jax.ShapeDtypeStruct((b, s), jnp.int32)
           for k in ("tokens", "labels")}
    remat, plan_bytes = auto_remat(cfg, argparse.Namespace(
        remat_policy=trn["remat_policy"], policy=trn["policy"], accum=1,
        mem_budget_mb=trn["mem_budget_mb"]), mesh, sds)
    tc = TrainConfig(policy=trn["policy"], remat=remat,
                     opt=adamw.AdamWConfig(**opt_cfg))
    step_fn, shards = make_train_step(cfg, mesh, tc, sds)
    params = ref.make_params(run.config, run.seed, jnp.float32,
                             out_shardings=shards["params"])
    opt = jax.jit(adamw.init, out_shardings=shards["opt"])(params)
    ls = jax.device_put(LossScale.noop(), shards["loss_scale"])
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (params, opt)))

    def feed(k):
        with run.annotate("next_batch"):
            tok, lab = traffic.token_rows(b, s, d["V"], run.seed, k)
            return jax.device_put({"tokens": tok, "labels": lab},
                                  shards["batch"])

    def dispatch(k, params, opt, ls):
        batch = feed(k)
        with run.annotate("train_step"):
            return step_fn(params, opt, ls, batch)

    def await_loss(m) -> float:
        with run.annotate("wait"):
            return float(m["loss"])

    losses = []
    for k in range(CHECK_STEPS):
        params, opt, ls, m = dispatch(k, params, opt, ls)
        losses.append(await_loss(m))
        if k == 0:
            gnorm = float(m["grad_norm"])
            clip = min(1.0, opt_cfg["grad_clip"] / max(gnorm, 1e-9))
            scale = (1 - opt_cfg["b1"]) * clip
            grads = {k2: v / scale for k2, v in
                     ref.leaf_norms(opt.mu, run.config).items()}
    change = ref.change_norms(params, run.config, run.seed)
    setup_s = run.setup_done()

    lo, hi = wl["trace_steps"]
    ahead = int(trn["ahead_steps"])
    pending = collections.deque()   # metrics of the steps in flight
    window_losses = []

    def settle(keep: int) -> None:
        while len(pending) > keep:
            window_losses.append(await_loss(pending.popleft()))

    t0 = run.clock()
    k, sent, stretch = CHECK_STEPS, 0, None
    while run.clock() - t0 < run.seconds:
        if run.trace and sent == lo:
            # the traced stretch holds exactly its own steps, each awaited
            settle(0)
            run.start_trace()
            stretch = [sent, None]
        params, opt, ls, m = dispatch(k, params, opt, ls)
        pending.append(m)
        k += 1
        sent += 1
        if stretch is not None and stretch[1] is None and sent == hi:
            settle(0)
            run.stop_trace()
            stretch[1] = sent
        settle(ahead)
    settle(0)
    t_end = run.clock()
    loss = window_losses[-1]
    if stretch is not None and stretch[1] is None:
        run.stop_trace()
        stretch[1] = sent
    memory = run.memory_peak_bytes()
    elapsed = t_end - t0
    e2e = {"train_tokens_per_s": sent * b * s / elapsed,
           "setup_s": setup_s}
    print(f"train: {sent} steps of {b}x{s} tokens in {elapsed:.3f} s "
          f"({elapsed / sent:.4f} s/step, {ahead} dispatched ahead, last "
          f"loss {loss:.6f}); "
          f"set-up {setup_s:.3f} s; remat {remat.plan.segment_sizes()}; "
          f"peak {memory} B vs plan {plan_bytes} B + state {state_bytes} B",
          file=sys.stderr, flush=True)
    if run.trace:
        run.record.update(traced_steps=stretch[1] - stretch[0], batch=b,
                          seq=s, plan_bytes=plan_bytes,
                          state_bytes=state_bytes)
    del params, opt, ls, m, step_fn
    gc.collect()

    t_ref = run.clock()
    want = reference_steps(run, b, s)
    print(f"check: reference losses {want['losses']} vs program {losses} "
          f"({run.clock() - t_ref:.1f} s)", file=sys.stderr, flush=True)
    got = {"losses": losses, "grads": grads, "change": change}
    run.record.update(got=got, want=want)
    return {"e2e": e2e, "attempted": sent + CHECK_STEPS,
            "failed": int(sum(not np.isfinite(x) for x in window_losses)),
            "memory_peak_bytes": memory,
            "checks": compare(got, want, wl["check"]["limits"])}


def compare(got: dict, want: dict, limits: dict) -> list:
    """The three numbers compared, each with its limit.  Leaves whose
    reference first gradient is under a thousandth of the median leaf's
    move under Adam by rounding alone; their change is not compared."""
    loss_gap = max(abs(a - w) / abs(w)
                   for a, w in zip(got["losses"], want["losses"]))
    g_leaf, grad_gap = leaf_gap(got["grads"], want["grads"])
    med = float(np.median(list(want["grads"].values())))
    c_leaf, change_gap = leaf_gap(
        got["change"], want["change"],
        keep=lambda name: want["grads"][name] >= 1e-3 * med)
    print(f"check: worst first-gradient leaf {g_leaf}, worst change leaf "
          f"{c_leaf}", file=sys.stderr, flush=True)
    return [{"name": "loss_gap", "value": loss_gap,
             "limit": limits["loss_gap"]},
            {"name": "grad_gap", "value": grad_gap,
             "limit": limits["grad_gap"]},
            {"name": "change_gap", "value": change_gap,
             "limit": limits["change_gap"]}]


def reference_steps(run, b: int, s: int, *, fp8: bool = False,
                    rows: int | None = None) -> dict:
    """The reference's first three steps from the seeded weights and rows:
    losses, per-leaf first-gradient norms, per-leaf change after three.
    ``rows`` keeps only that many rows of each batch (a fault: part of the
    batch left out, the mean taken over the rest)."""
    import functools

    import jax
    import jax.numpy as jnp

    ref, c, o = run.reference, run.config, run.workload["optimizer"]
    d = ref.dims(c)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def ref_step(p, mu, nu, count, tok, lab):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(
                lambda p: ref.loss(p, d, tok, lab, fp8=fp8))(p)
            p, mu, nu, _ = ref.adamw(o, p, g, mu, nu, count)
        # the gradients' norms, not the gradients: they would hold a
        # fourth copy of the parameters at the step's peak
        return p, mu, nu, loss, ref.leaf_norm_vector(g, c)

    p = ref.make_params(c, run.seed, jnp.float32)
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)  # noqa: E731
    mu, nu = zeros(p), zeros(p)
    losses = []
    for k in range(CHECK_STEPS):
        tok, lab = traffic.token_rows(b, s, d["V"], run.seed, k)
        if rows is not None:
            tok, lab = tok[:rows], lab[:rows]
        p, mu, nu, loss, norms = ref_step(p, mu, nu, jnp.int32(k), tok, lab)
        losses.append(float(loss))
        if k == 0:
            grads = ref.named_norms(norms, c)
    return {"losses": losses, "grads": grads,
            "change": ref.change_norms(p, c, run.seed)}
