"""Production training driver.

Fault-tolerance features wired here:
  * resume from the latest atomic checkpoint (params + optimizer + loss
    scale + data-iterator state) — restart-safe;
  * SIGTERM/SIGINT -> save-and-exit (preemption handling);
  * periodic + final checkpointing (keep-last GC), with the config
    fingerprint verified on restore (a checkpoint from a different arch
    fails loudly, never silently);
  * step watchdog: a daemon thread logs (and would page, in production) if
    a step exceeds ``watchdog_factor`` x the trailing-median step time —
    straggler/hang mitigation;
  * ``--guard``: NaN/Inf-grad steps apply no update (in-jit skip via
    ``TrainConfig.skip_nonfinite``) and a rolling-median loss-spike
    detector (``train/guards.py``) escalates consecutive bad steps to a
    rollback that restores the last good checkpoint and replays;
  * elastic restarts: the mesh is built from however many devices exist
    (launch.mesh.make_mesh_for) and restore reshards into it.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/run1
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import statistics
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpointing.ckpt import CheckpointManager
from repro.core.checkpoint import CheckpointConfig
from repro.core.mixed_precision import LossScale
from repro.data.synthetic import token_stream
from repro.events import EventSink
from repro.launch import compile_cache
from repro.launch.mesh import describe, make_mesh_for
from repro.obs import MemStat, MetricsRegistry, Tracer, maybe_span
from repro.optim import adamw
from repro.train.guards import GuardConfig, TrainGuard
from repro.train.train_step import TrainConfig, make_train_step


class Watchdog:
    """Logs when the current step runs long (straggler/hang detection).

    Thread-safe by a lock + step generation counter: the old
    implementation's monitor thread cleared ``self._started`` (its "one
    alert per step" latch) while ``step_end`` was reading it on the main
    thread — an alert racing a step boundary could drop that step's
    duration sample or re-arm against the wrong step.  Now every field
    is read/written under ``_lock``, the alert latch is "alerted at
    generation N" (so an alerted step still records its duration at
    ``step_end``), and with a ``sink`` each alert is also emitted as a
    ``watchdog_alert`` event to the JSONL stream (``--events``) instead
    of being print-only."""

    def __init__(self, factor: float = 5.0, min_history: int = 5,
                 *, sink: EventSink | None = None):
        self.factor, self.min_history = factor, min_history
        self.times: list[float] = []
        self._started: float | None = None
        self._gen = 0                 # step generation (monotonic)
        self._alerted_gen = -1        # last generation already alerted
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.alerts = 0
        self.sink = sink
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def step_start(self):
        with self._lock:
            self._gen += 1
            self._started = time.time()

    def step_end(self):
        with self._lock:
            if self._started is not None:
                self.times.append(time.time() - self._started)
                self.times = self.times[-100:]
            self._started = None

    def _run(self):
        while not self._stop.wait(0.5):
            with self._lock:
                if (self._started is None
                        or self._gen == self._alerted_gen
                        or len(self.times) < self.min_history):
                    continue
                med = statistics.median(self.times)
                running = time.time() - self._started
                if running <= self.factor * med:
                    continue
                self.alerts += 1
                self._alerted_gen = self._gen    # one alert per step
            print(f"[watchdog] step running {running:.1f}s"
                  f" > {self.factor:.0f}x median {med:.2f}s — straggler?")
            if self.sink is not None:
                self.sink.emit("watchdog_alert", running_s=running,
                               median_s=med, factor=self.factor)

    def close(self):
        self._stop.set()


def synthetic_lm_batches(cfg, batch: int, seq: int, *, seed=0, state=None):
    """Deterministic, resumable synthetic LM stream (batch index = state)."""
    start = state or 0
    corpus = token_stream(max(200_000, batch * (seq + 1) * 4), cfg.vocab,
                          seed=seed)
    i = start
    while True:
        rng = np.random.default_rng((seed, i))
        offs = rng.integers(0, len(corpus) - seq - 1, size=batch)
        toks = np.stack([corpus[o:o + seq] for o in offs])
        labs = np.stack([corpus[o + 1:o + seq + 1] for o in offs])
        yield i, {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labs)}
        i += 1


def auto_remat(cfg, args, mesh, batch_sds) -> tuple[CheckpointConfig, int]:
    """Planner-driven remat: budget-constrained when ``--mem-budget-mb``
    is given (delegating to ``train_step.resolve_remat`` — the same path
    ``TrainConfig.mem_budget_mb`` takes programmatically), else Chen-style
    sqrt(L) checkpoints at the byte-optimal sites.  Either way the profile
    is the per-device microbatch in the policy's compute dtype
    (``train_step.plan_profile``)."""
    import math

    from repro import plan as plan_mod
    from repro.train.train_step import plan_profile, resolve_remat

    base = CheckpointConfig(enabled=True, policy=args.remat_policy)
    tc0 = TrainConfig(policy=args.policy, remat=base, accum=args.accum,
                      mem_budget_mb=args.mem_budget_mb)
    prof = plan_profile(cfg, tc0, batch_sds, mesh=mesh)
    if args.mem_budget_mb > 0:
        remat = resolve_remat(cfg, tc0, batch_sds, mesh=mesh).remat
    else:
        rp = plan_mod.plan_min_peak(prof, math.isqrt(cfg.n_layers) or 1,
                                    policy=args.remat_policy)
        remat = dataclasses.replace(base, plan=rp)
    rep = plan_mod.plan_report(prof, remat.plan)
    print(f"remat plan [{remat.plan.source}]: "
          f"segments {remat.plan.segment_sizes()} "
          f"peak {rep['peak_bytes']/2**20:.1f} MiB/device "
          f"(no-remat {rep['no_remat_bytes']/2**20:.1f} MiB, "
          f"recompute >= {rep['recompute_frac']*100:.0f}% of fwd FLOPs)")
    return remat, int(rep["peak_bytes"])


def init_state(cfg, seed: int, mesh, shards):
    """Fresh (params, AdamW state), each born in its shardings: no
    device ever materializes the whole model first."""
    from repro.distributed.sharding import init_sharded_params
    params = init_sharded_params(cfg, jax.random.PRNGKey(seed), mesh)
    return params, jax.jit(adamw.init, out_shardings=shards["opt"])(params)


def run(args):
    compile_cache.enable()
    mesh = make_mesh_for(max_model=args.max_model)
    print(f"mesh: {describe(mesh)} ({mesh.size} devices)")
    if args.mem_budget_mb > 0:
        from repro.distributed import sharding as shd
        print(f"mem budget: {args.mem_budget_mb} MiB PER DEVICE "
              f"(microbatch = batch / {shd.dp_size(mesh)} dp shards; "
              f"attention residuals / {mesh.shape['model']} model shards)")
    cfg = configs.smoke_config(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    if args.attn_backend is not None:
        cfg = dataclasses.replace(cfg, attn_backend=args.attn_backend)
    if cfg.attn_impl() == "jnp":
        print("attn backend: jnp")
    else:
        from repro.plan import flash_attn_flop_report, \
            flash_training_eligible
        eligible = flash_training_eligible(cfg)
        print(f"attn backend: {cfg.attn_impl()}"
              + (" (flash custom_vjp: O(S*D) attention residuals)"
                 if eligible else
                 " — flash INELIGIBLE for this arch/shape, jnp path "
                 "(O(S^2) residuals) will run"))
        if eligible:
            rep = flash_attn_flop_report(cfg, args.batch, args.seq)
            print(f"  sparse flash grids: {rep['skip_frac']*100:.0f}% of KV "
                  f"tile-steps skipped "
                  f"({rep['visited_flops']/1e9:.1f} GFLOPs visited vs "
                  f"{rep['dense_flops']/1e9:.1f} dense per step)")

    batch_sds = {
        "tokens": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),
    }
    remat_mode = "off" if args.no_remat else args.remat
    if remat_mode == "off" and args.mem_budget_mb > 0:
        print("[warn] --mem-budget-mb ignored with remat off")
    plan_bytes = None                     # activation budget (MemStat score)
    if remat_mode == "auto" or (remat_mode == "on" and args.mem_budget_mb > 0):
        # a budget implies the planner even without an explicit --remat auto
        remat, plan_bytes = auto_remat(cfg, args, mesh, batch_sds)
    else:
        remat = CheckpointConfig(enabled=remat_mode != "off",
                                 policy=args.remat_policy)
    tc = TrainConfig(
        policy=args.policy,
        remat=remat,
        accum=args.accum,
        use_loss_scale=(args.policy == "fp16"),
        skip_nonfinite=args.guard,
        opt=adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=min(100, args.steps // 10 + 1)),
    )
    step_fn, shards = make_train_step(cfg, mesh, tc, batch_sds)

    mgr = CheckpointManager(args.ckpt_dir, keep_last=args.keep_last)
    if tc.remat.plan is not None:
        import os
        os.makedirs(args.ckpt_dir, exist_ok=True)
        tc.remat.plan.save(os.path.join(args.ckpt_dir, "remat_plan.json"))
    params, opt = init_state(cfg, args.seed, mesh, shards)
    ls = LossScale.init() if tc.use_loss_scale else LossScale.noop()
    start_step, data_state = 0, 0

    latest = mgr.latest_intact_step()
    if latest is not None and not args.fresh:
        state_like = {"params": params, "opt": opt}
        (restored, extra) = mgr.restore(
            latest, state_like,
            shardings={"params": shards["params"], "opt": shards["opt"]},
            config=cfg.arch_id)
        params, opt = restored["params"], restored["opt"]
        start_step = extra.get("step", latest)
        data_state = extra.get("data_state", 0)
        if tc.use_loss_scale and "loss_scale" in extra:
            ls = dataclasses.replace(ls, scale=jnp.float32(extra["loss_scale"]))
        print(f"resumed from step {start_step} (data batch {data_state})")
    # the step's outputs live on the mesh: a loss scale that does not
    # would key a second compile of the step
    ls = jax.device_put(ls, shards["loss_scale"])

    stop = {"now": False}

    def _sig(_s, _f):
        print("[signal] preemption notice — checkpoint and exit")
        stop["now"] = True

    old_handlers = [signal.signal(s, _sig) for s in (signal.SIGTERM,
                                                     signal.SIGINT)]

    def save(step):
        # `step` here = number of completed steps; resume continues there
        with maybe_span(tracer, "checkpoint", step=step, op="save"):
            mgr.save(step, {"params": params, "opt": opt},
                     extra={"step": step, "data_state": data_state,
                            "loss_scale": float(ls.scale),
                            "arch": cfg.arch_id},
                     config=cfg.arch_id)

    sink = EventSink(args.events) if args.events else None
    if args.trace and sink is None:
        print("[warn] --trace requires --events; tracing disabled")
    registry = MetricsRegistry()
    tracer = Tracer(sink, pid="train") if args.trace and sink is not None \
        else None
    memstat = MemStat(sink=sink, registry=registry, plan_bytes=plan_bytes)
    guard = None
    if args.guard:
        guard = TrainGuard(GuardConfig(
            window=args.guard_window,
            spike_factor=args.guard_spike_factor,
            rollback_after=args.guard_rollback_after), sink=sink,
            registry=registry)
        print(f"guard: skip non-finite steps in-jit; loss spike > "
              f"{args.guard_spike_factor}x rolling median; "
              f"{args.guard_rollback_after} consecutive bad steps -> "
              f"rollback (costs one loss sync per step)")
    wd = Watchdog(sink=sink)
    data = synthetic_lm_batches(cfg, args.batch, args.seq, seed=args.seed,
                                state=data_state)
    t0 = time.time()
    step = start_step
    try:
        while step < args.steps:
            with maybe_span(tracer, "data", step=step):
                data_state, batch = next(data)
            wd.step_start()
            with maybe_span(tracer, "train_step", step=step):
                params, opt, ls, metrics = step_fn(params, opt, ls, batch)
                verdict = TrainGuard.OK
                if guard is not None:
                    # the loss sync closes the step: the span measures
                    # dispatch + device time, not just dispatch
                    with maybe_span(tracer, "guard", step=step):
                        verdict = guard.observe(
                            float(metrics["loss"]),  # sync
                            bool(metrics["grads_finite"]),
                            grad_norm=float(metrics["grad_norm"]))
            if verdict == TrainGuard.ROLLBACK:
                wd.step_end()
                if guard.rollbacks > args.guard_max_rollbacks:
                    print(f"[guard] {guard.rollbacks} rollbacks exceed "
                          f"--guard-max-rollbacks="
                          f"{args.guard_max_rollbacks} — persistent "
                          f"fault, aborting ({guard.counters()})")
                    return 1
                # never roll back onto a torn/corrupt checkpoint — fall
                # back to the newest one whose shard checksums verify
                latest = mgr.latest_intact_step()
                if latest is None:
                    print("[guard] rollback with no checkpoint on disk — "
                          "restarting from init")
                    params, opt = init_state(cfg, args.seed, mesh, shards)
                    step, data_state = 0, 0
                else:
                    with maybe_span(tracer, "checkpoint", step=latest,
                                    op="restore"):
                        restored, extra = mgr.restore(
                            latest, {"params": params, "opt": opt},
                            shardings={"params": shards["params"],
                                       "opt": shards["opt"]},
                            config=cfg.arch_id)
                    params, opt = restored["params"], restored["opt"]
                    step = extra.get("step", latest)
                    data_state = extra.get("data_state", 0)
                    if tc.use_loss_scale and "loss_scale" in extra:
                        ls = jax.device_put(dataclasses.replace(
                            ls, scale=jnp.float32(extra["loss_scale"])),
                            shards["loss_scale"])
                guard.reset_history()
                data = synthetic_lm_batches(cfg, args.batch, args.seq,
                                            seed=args.seed,
                                            state=data_state)
                print(f"[guard] rolled back to step {step} "
                      f"(data batch {data_state}; {guard.counters()})")
                continue
            if verdict == TrainGuard.SKIP:
                print(f"[guard] step {step}: bad step "
                      f"({guard.counters()}) — update "
                      f"{'suppressed in-jit' if not bool(metrics['grads_finite']) else 'applied; loss quarantined'}")
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])  # sync point
                print(f"step {step:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f} "
                      f"({(time.time()-t0):.1f}s)")
            wd.step_end()
            data_state += 1
            step += 1
            if args.metrics_every and step % args.metrics_every == 0:
                # host-side only: live-array walk + registry snapshot,
                # never a device sync
                memstat.sample(step)
                if sink is not None:
                    registry.emit(sink, step=step)
            healthy = guard is None or guard.bad_streak == 0
            if step % args.ckpt_every == 0 and healthy:
                # never checkpoint mid-bad-streak: the rollback target
                # must be a GOOD state
                save(step)
            if stop["now"]:
                if healthy:
                    save(step)
                return 0
        save(args.steps)
    finally:
        wd.close()
        if sink is not None:
            sink.close()
        for s, h in zip((signal.SIGTERM, signal.SIGINT), old_handlers):
            signal.signal(s, h)
    if guard is not None:
        print(f"guard: {guard.counters()}")
    if memstat.samples:
        print(memstat.banner())
    print("done")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--policy", default="bf16",
                    choices=["full", "bf16", "fp16", "bf16_params",
                             "resid_bf16"],
                    help="mixed-precision policy; resid_bf16 = f32 compute "
                         "with the flash custom_vjp's saved (q,k,v,o) "
                         "residuals stored in bf16 (stats stay f32)")
    ap.add_argument("--attn-backend", default=None,
                    choices=["jnp", "interpret", "pallas"],
                    help="attention kernel override (default: the arch "
                         "config's, compiled flash on a TPU, jnp "
                         "elsewhere): jnp (O(S^2) residuals), or the "
                         "flash kernel via the Pallas interpreter / "
                         "compiled Mosaic (trainable custom_vjp, O(S*D) "
                         "residuals)")
    ap.add_argument("--remat", default="on", choices=["on", "off", "auto"],
                    help="auto: profile-driven RematPlan (see repro.plan)")
    ap.add_argument("--mem-budget-mb", type=int, default=0,
                    help="per-device activation-byte budget; > 0 engages "
                         "the remat planner (with --remat auto, 0 means "
                         "sqrt(L) checkpoints instead)")
    ap.add_argument("--remat-policy", default="full")
    ap.add_argument("--no-remat", action="store_true",
                    help="deprecated alias for --remat off")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--keep-last", type=int, default=3)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--max-model", type=int, default=16)
    ap.add_argument("--fresh", action="store_true")
    ap.add_argument("--guard", action="store_true",
                    help="enable train/guards.py: skip NaN/Inf-grad "
                         "updates in-jit, detect loss spikes against a "
                         "rolling median, roll back to the last good "
                         "checkpoint after consecutive bad steps")
    ap.add_argument("--guard-window", type=int, default=32,
                    help="guard: healthy-loss history for the median")
    ap.add_argument("--guard-spike-factor", type=float, default=4.0,
                    help="guard: loss > factor x median => spike")
    ap.add_argument("--guard-rollback-after", type=int, default=3,
                    help="guard: consecutive bad steps before rollback")
    ap.add_argument("--guard-max-rollbacks", type=int, default=5,
                    help="guard: abort (exit 1) past this many rollbacks "
                         "— a persistent fault, not a transient")
    ap.add_argument("--events", default=None,
                    help="append-only JSONL event log (repro.events): "
                         "guard verdicts stream here for post-mortems")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="every N steps: sample live-array bytes "
                         "(mem_sample) and emit a metrics_snapshot of "
                         "the obs registry to --events (0 = off)")
    ap.add_argument("--trace", action="store_true",
                    help="emit span_begin/span_end records (data / "
                         "train_step / guard / checkpoint) to --events; "
                         "tools/tracelens.py renders the timeline")
    return run(ap.parse_args())


if __name__ == "__main__":
    raise SystemExit(main())
