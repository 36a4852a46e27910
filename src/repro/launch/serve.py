"""Production serving driver: continuous-batching engine or lockstep demo.

Engine mode (``--engine``) drives ``repro.serve.ServeEngine`` over a
seeded synthetic request trace — slot-pooled int8 KV cache, FCFS
admission, mid-flight joins/retirements with zero re-jits after warmup:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --engine --requests 16 --max-slots 4 --max-len 128

The default (lockstep) mode keeps the original demo: one fixed batch
prefills once and decodes ``--gen`` steps in unison — every slot pays for
the slowest request.  Both modes share the seeded sampler
(``--temperature`` / ``--top-k``; greedy stays the default).

Fleet mode (``--engine --replicas N``) fronts N engine replicas with the
health-routing ``repro.serve.Router`` — least-loaded admission, an
error-budget circuit breaker per replica, and cross-replica request
migration.  ``--chaos-seed`` runs the seeded chaos harness (replica
crash/sick/slow events) against the fleet:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --engine --replicas 2 --requests 16 --chaos-seed 11

Serving-side fault tolerance: the decode loop is stateless beyond the
cache, so a restart re-prefills in one step; the watchdog flags stuck
steps (straggler chips in production); ``--events out.jsonl`` streams
fault/health/failover events to an append-only JSONL sink.

Durability (``--journal wal.jsonl``): every fleet request transition is
written ahead to an fsync'd journal; after a whole-process crash,
re-running with ``--recover`` rebuilds the fleet from the journal and
finishes every in-flight request from its durable prompt + token
prefix.  ``--workers`` runs each replica as a REAL subprocess behind
the pipe RPC (``repro.serve.worker``) — crashes become SIGKILLs and the
breaker is exercised across a process boundary:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --engine --replicas 2 --workers --journal wal.jsonl --recover
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.distributed.sharding import init_sharded_params
from repro.launch import compile_cache
from repro.launch.mesh import describe, make_mesh_for
from repro.launch.train import Watchdog
from repro.obs import MemStat, Tracer
from repro.serve import sampling
from repro.train.serve_step import build_decode_step, build_prefill_step


def _kv_banner(cfg, args, s_total: int, backend: str):
    """Honest banner: name what decode will ACTUALLY run — the int8 kvq
    kernel only dispatches on a quantized GQA cache (MLA latents and SSM
    states take their own decode paths), and the split count is clamped
    to the KV tile count of the preallocated cache.  ``backend`` is the
    resolved kvq backend."""
    quant = not args.no_quantize
    kvq_eligible = cfg.mixer in ("attn", "hybrid") and cfg.mla is None
    if not kvq_eligible:
        kv_backend, kv_splits = "n/a (no kvq-layout attention cache)", 1
    elif quant:
        from repro.kernels.kvq import ops as kvq_ops
        kv_backend = backend
        kv_splits = kvq_ops.resolve_splits(s_total, args.kv_splits)
    else:
        kv_backend, kv_splits = "jnp (cache not quantized)", 1
    print(f"kv decode: backend={kv_backend} splits={kv_splits} "
          f"(requested {args.kv_splits}, cache {s_total} slots)")


def _fleet_buckets(max_len: int) -> tuple:
    """Fleet prefill buckets: the defaults plus a max_len bucket, so a
    migration or crash-recovery replay (prompt + emitted tokens, up to
    max_len) always fits some bucket instead of going FAILED."""
    from repro.serve import default_buckets
    base = default_buckets(max_len)
    return base if base[-1] >= max_len else base + (max_len,)


def _build_engine(args, cfg, params, mesh=None, *, sink=None,
                  sampler_keys: str = "step", replay_buckets: bool = False):
    from repro.serve import ServeEngine
    quant = not args.no_quantize
    budget = (int(args.mem_budget_mb * 2**20)
              if args.mem_budget_mb else None)
    return ServeEngine(
        params, cfg, max_slots=args.max_slots, max_len=args.max_len,
        prompt_buckets=(_fleet_buckets(args.max_len)
                        if replay_buckets else None),
        policy_name=args.policy, quantized=quant,
        kv_backend=args.kv_backend, kv_splits=args.kv_splits,
        temperature=args.temperature, top_k=args.top_k, seed=args.seed,
        max_prefill_per_step=args.max_prefill_per_step,
        mem_budget_bytes=budget, mesh=mesh,
        max_queue=args.max_queue or None,
        deadline_steps=(args.deadline_steps
                        if args.deadline_steps >= 0 else None),
        max_retries=args.max_retries, sampler_keys=sampler_keys,
        sink=sink)


def _worker_kwargs(args) -> dict:
    """The ``engine_factory`` spec for subprocess replicas — mirrors
    ``_build_engine`` for the knobs a worker child builds itself (each
    worker initializes its own params from ``--seed``; meshes stay
    in-process)."""
    budget = (int(args.mem_budget_mb * 2**20)
              if args.mem_budget_mb else None)
    return dict(
        arch=args.arch, smoke=args.smoke, init_seed=args.seed,
        max_slots=args.max_slots, max_len=args.max_len,
        prompt_buckets=_fleet_buckets(args.max_len),
        policy_name=args.policy, quantized=not args.no_quantize,
        kv_backend=args.kv_backend, kv_splits=args.kv_splits,
        temperature=args.temperature, top_k=args.top_k, seed=args.seed,
        max_prefill_per_step=args.max_prefill_per_step,
        mem_budget_bytes=budget, max_queue=args.max_queue or None,
        deadline_steps=(args.deadline_steps
                        if args.deadline_steps >= 0 else None),
        max_retries=args.max_retries, sampler_keys="request")


def _make_trace(args, cfg, engine):
    from repro.serve import synthetic_trace
    # size the trace to what the engine can admit: prompts within the
    # largest bucket, prompt+gen within max_len
    max_prompt = min(engine.buckets[-1], max(4, args.max_len // 2))
    return synthetic_trace(
        args.requests, seed=args.seed, vocab=cfg.vocab,
        mean_prompt=args.mean_prompt, max_prompt=max_prompt,
        mean_gen=args.mean_gen, max_gen=max(1, args.max_len - max_prompt),
        arrival_rate=args.arrival_rate, min_prompt=min(4, max_prompt))


def _open_sink(args):
    if not args.events:
        return None
    from repro.events import EventSink
    print(f"events: streaming to {args.events}")
    return EventSink(args.events)


def _want_trace(args, sink) -> bool:
    if args.trace and sink is None:
        print("[warn] --trace requires --events; tracing disabled")
        return False
    return bool(args.trace)


def _install_obs_hook(obj, sink, memstat, every: int, snapshot_fn) -> None:
    """Chain a periodic metrics/memory emitter onto ``pre_step`` —
    AFTER any fault injector, so neither hook clobbers the other."""
    prev = obj.hooks.get("pre_step")

    def _hook(o, _prev=prev):
        if _prev is not None:
            _prev(o)
        if o.step_no and o.step_no % every == 0:
            memstat.sample(o.step_no)
            if sink is not None:
                sink.emit("metrics_snapshot", snapshot=snapshot_fn(),
                          step=o.step_no)

    obj.hooks["pre_step"] = _hook


def run_fleet(args, cfg, params, mesh=None) -> int:
    """N engine replicas behind the health-routing Router, optionally
    under the seeded chaos harness.  With ``--workers`` this process never
    touches JAX (``params`` is None): each worker builds its own weights.
    On a TPU host one worker holds the chips: libtpu lets one process at a
    time use them, so a second worker could not start."""
    from repro.serve import (BreakerConfig, FleetFaultInjector, Router,
                             chaos_plan, supports)
    if not supports(cfg):
        print(f"fleet: {cfg.arch_id} is not engine-eligible")
        return 2
    if args.workers and args.replicas > 1:
        from repro.serve.worker import host_chips
        chips = host_chips()
        if chips is not None:
            print(f"fleet: --workers on a TPU host runs one worker process: "
                  f"libtpu lets one process at a time hold this host's "
                  f"{chips} chip(s), so {args.replicas} workers would "
                  f"contend for them (use --replicas 1, or drop --workers "
                  f"to run the replicas in one process)")
            return 2
    sink = _open_sink(args)
    journal = None
    if args.journal:
        from repro.serve import RequestJournal
        journal = RequestJournal(args.journal, snapshot_every=64)
        print(f"journal: write-ahead log at {args.journal} "
              f"({journal.state.n_live} live requests on open)")
    t0 = time.time()
    if args.workers:
        from repro.serve import spawn_workers
        _kv_banner(cfg, args, args.max_len,
                   f"{args.kv_backend} (resolved in each worker)")
        engines = spawn_workers(args.replicas, kwargs=_worker_kwargs(args))
        for i, w in enumerate(engines):
            w.metrics.replica = i
        print(f"fleet: {args.replicas} subprocess workers "
              f"(pids {[w.pid for w in engines]}, devices "
              f"{[w.devices for w in engines]}) warmed in "
              f"{time.time()-t0:.1f}s "
              f"({engines[0].pool.max_slots} slots each)")
    else:
        engines = []
        for i in range(args.replicas):
            e = _build_engine(args, cfg, params, mesh, sink=sink,
                              sampler_keys="request",
                              replay_buckets=True)
            e.metrics.replica = i
            e.warmup()
            engines.append(e)
        _kv_banner(cfg, args, args.max_len, engines[0].kv_backend)
        print(f"fleet: {args.replicas} replicas warmed in "
              f"{time.time()-t0:.1f}s "
              f"({engines[0].pool.max_slots} slots each)")
    breaker = BreakerConfig(
        window_steps=args.breaker_window,
        degrade_faults=args.breaker_degrade,
        quarantine_faults=args.breaker_quarantine,
        cooldown_steps=args.breaker_cooldown,
        stall_steps=args.breaker_stall)
    router = Router(engines, policy=args.route, breaker=breaker,
                    max_migrations=args.max_migrations, sink=sink,
                    journal=journal,
                    journal_tokens_every=args.journal_tokens_every)
    if _want_trace(args, sink):
        # tracers attach POST-warmup (the warmup probe must not trace)
        # and BEFORE recover() so recovery replay gets root spans
        for i, e in enumerate(engines):
            e.tracer = Tracer(sink, pid=f"r{i}")
        router.tracer = Tracer(sink, pid="router")
        if journal is not None:
            journal.tracer = Tracer(sink, pid="journal")
        print("trace: span records -> events "
              "(render with tools/tracelens.py)")
    if args.recover:
        if journal is None:
            print("--recover needs --journal")
            return 2
        info = router.recover()
        print(f"recover: {info['n_recovered']} requests rebuilt from the "
              f"journal ({info['n_done']} already complete on disk, "
              f"{info['n_placed']} re-placed, {info['n_pending']} pending, "
              f"{info['n_failed']} failed)")
    if args.chaos_seed >= 0:
        plan = chaos_plan(args.chaos_seed, steps=max(8, args.requests),
                          replicas=args.replicas,
                          n_events=args.chaos_events)
        FleetFaultInjector(router, plan)
        print(f"chaos: seed {args.chaos_seed} -> "
              f"{dict(plan.counts())}")
    memstat = None
    if args.metrics_every and args.workers:
        print("[warn] --metrics-every samples this process's arrays; with "
              "--workers they live in the workers — memory sampling off")
    elif args.metrics_every:
        memstat = MemStat(sink=sink,
                          plan_bytes=(int(args.mem_budget_mb * 2**20)
                                      or None))
        _install_obs_hook(router, sink, memstat, args.metrics_every,
                          router.registry_snapshot)
    trace = _make_trace(args, cfg, engines[0])
    t0 = time.time()
    summary = router.run(trace)
    wall = time.time() - t0
    fleet = summary["fleet"]
    print(f"fleet trace: {args.requests} requests in {wall:.2f}s; "
          f"health={summary['health']}")
    print(f"throughput: {summary['tokens_per_s']:.1f} tok/s, goodput "
          f"{summary['goodput_tokens_per_s']:.1f} tok/s "
          f"({summary['total_tokens']} tokens)")
    print(f"failover: {fleet['failovers']} failovers, "
          f"{fleet['n_migrations']} migrations, replay success "
          f"{fleet['replay_success_rate']:.2f}, quarantine steps "
          f"{summary['time_in_quarantine']}")
    print(f"outcomes: done {fleet['n_done']} dropped {fleet['n_dropped']} "
          f"cancelled {fleet['n_cancelled']} failed {fleet['n_failed']} "
          f"rejected {fleet['n_rejected']}")
    if fleet["n_recovered"]:
        print(f"recovery: {fleet['n_recovered']} recovered, replay "
              f"success {fleet['recovery_replay_success']:.2f}")
    if memstat is not None and memstat.samples:
        print(memstat.banner())
    if journal is not None:
        st = journal.state
        print(f"journal: {journal.appends} appends, "
              f"{journal.snapshots} snapshots, {st.n_submits} submits -> "
              f"{st.n_terminals} terminals (+{st.n_live} live)")
        journal.close()
    if args.workers:
        for w in engines:
            w.shutdown()
    if sink is not None:
        sink.close()
    if summary["stalled"]:
        print("STALLED fleet run")
        return 1
    rec = summary["reconcile"]
    assert rec["ok"], f"fleet ledger does not reconcile: {rec}"
    for e in engines:
        assert e.pool.occupancy == 0 and e.pool.allocs == e.pool.frees, \
            "slot leak"
    return 0


def run_engine(args, cfg, params, mesh=None) -> int:
    from repro.serve import supports

    if not supports(cfg):
        print(f"engine: {cfg.arch_id} is not engine-eligible (needs a "
              f"uniform-window GQA attention cache — MLA/SSM/encoder/"
              f"global-layer archs serve through the lockstep driver)")
        return 2
    sink = _open_sink(args)
    budget = (int(args.mem_budget_mb * 2**20)
              if args.mem_budget_mb else None)
    engine = _build_engine(args, cfg, params, mesh, sink=sink)
    _kv_banner(cfg, args, args.max_len, engine.kv_backend)
    # one source of truth for capacity: the engine's own clamp/accounting
    if mesh is not None:
        from repro.distributed import sharding as shd
        print(f"mesh: {describe(mesh)}, kv cache sharded over "
              f"'{shd.serve_kv_shard(mesh, cfg.n_kv, args.max_len)}', "
              f"{engine.pool.bytes_per_slot_per_device()/2**20:.2f} "
              f"MB/slot PER DEVICE")
    print(f"capacity: {engine.pool.bytes_per_slot_per_device()/2**20:.2f} "
          f"MB/slot{'/device' if mesh is not None else ''} at "
          f"max_len={args.max_len}"
          + (f" -> budget {args.mem_budget_mb} MB"
             f"{' per device' if mesh is not None else ''} admits "
             f"{engine.pool.max_slots} of "
             f"{args.max_slots} requested slots" if budget else ""))
    t0 = time.time()
    compiles = engine.warmup()
    print(f"warmup: {time.time()-t0:.1f}s, programs={compiles}")
    if _want_trace(args, sink):
        engine.tracer = Tracer(sink, pid="r0")   # post-warmup attach
        print("trace: span records -> events "
              "(render with tools/tracelens.py)")
    memstat = None
    if args.metrics_every:
        memstat = MemStat(sink=sink,
                          plan_bytes=budget,
                          registry=engine.metrics.registry)
        _install_obs_hook(engine, sink, memstat, args.metrics_every,
                          engine.metrics.registry_snapshot)

    trace = _make_trace(args, cfg, engine)
    t0 = time.time()
    summary = engine.run(trace)
    wall = time.time() - t0
    assert engine.compile_counts() == compiles, \
        "recompile during serving (static-shape contract broken)"
    print(f"trace: {args.requests} requests in {wall:.2f}s "
          f"({summary['n_steps']} engine steps)")
    print(f"throughput: {summary['tokens_per_s']:.1f} tok/s "
          f"({summary['total_tokens']} tokens)")
    print(f"ttft: mean {summary['ttft_mean_s']*1e3:.1f} ms "
          f"(p95 {summary['ttft_p95_s']*1e3:.1f} ms, "
          f"{summary['ttft_mean_steps']:.1f} steps); "
          f"itl: {summary['itl_mean_s']*1e3:.1f} ms")
    print(f"occupancy: {summary['occupancy_mean']:.2f}/"
          f"{engine.pool.max_slots} slots "
          f"(queue depth mean {summary['queue_depth_mean']:.2f}, "
          f"max {summary['queue_depth_max']})")
    failures = (summary["n_cancelled"] + summary["n_dropped"]
                + summary["n_failed"])
    if failures or summary["n_rejected"] or summary["n_faults"]:
        print(f"failure paths: dropped {summary['n_dropped']} "
              f"cancelled {summary['n_cancelled']} "
              f"failed {summary['n_failed']} "
              f"rejected {summary['n_rejected']} "
              f"(faults {summary['n_faults']}, "
              f"retries {summary['n_retried']}); "
              f"goodput {summary['goodput_tokens_per_s']:.1f} tok/s "
              f"of {summary['tokens_per_s']:.1f}")
    if memstat is not None and memstat.samples:
        print(memstat.banner())
    if sink is not None:
        sink.close()
    if summary["stalled"]:
        print(f"STALLED: {summary['diagnostics']}")
        return 1
    # every trace request must be accounted for: finished, shed, or
    # rejected at the door — nothing silently lost
    assert summary["n_done"] + failures + summary["n_rejected"] \
        == args.requests
    assert engine.pool.occupancy == 0 and \
        engine.pool.allocs == engine.pool.frees, "slot leak"
    return 0


def run_lockstep(args, cfg, params) -> int:
    quant = not args.no_quantize
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)

    s_total = args.prompt_len + args.gen
    kv_backend = args.kv_backend
    if kv_backend == "auto":
        from repro.kernels import platform_backend
        kv_backend = platform_backend()
    _kv_banner(cfg, args, s_total, kv_backend)

    # the decode cache is preallocated at prompt_len + gen INSIDE the
    # compiled prefill (grow_cache) — no post-hoc host-side pad
    prefill = jax.jit(build_prefill_step(cfg, policy_name=args.policy,
                                         quantized=quant, s_max=s_total))
    decode = jax.jit(build_decode_step(cfg, policy_name=args.policy,
                                       quantized=quant,
                                       kvq_backend=kv_backend,
                                       kvq_splits=args.kv_splits))
    sampler = sampling.make_sampler(temperature=args.temperature,
                                    top_k=args.top_k)
    key = jax.random.PRNGKey(args.seed)

    t0 = time.time()
    batch = {"tokens": prompts}
    if cfg.encoder is not None:
        batch["frames"] = jnp.zeros(
            (args.batch, cfg.encoder.n_frames, cfg.d_model), jnp.float32)
    last_logits, cache = prefill(params, batch)
    tok = sampler(last_logits, jax.random.fold_in(key, 0))
    t_prefill = time.time() - t0

    wd = Watchdog()
    out_tokens = [np.asarray(tok)]
    dec_kw = {}
    if cfg.encoder is not None:
        dec_kw["enc_out"] = batch["frames"]
    t0 = time.time()
    try:
        for i in range(args.gen - 1):
            wd.step_start()
            logits, cache = decode(params, cache, tok, **dec_kw)
            tok = sampler(logits, jax.random.fold_in(key, i + 1))
            out_tokens.append(np.asarray(tok))
            wd.step_end()
    finally:
        wd.close()
    t_decode = time.time() - t0

    gen = np.stack(out_tokens, 1)
    print(f"prefill {args.batch}x{args.prompt_len}: {t_prefill*1e3:.0f} ms")
    print(f"decode {args.gen} tok: {t_decode*1e3:.0f} ms "
          f"({t_decode/max(1, args.gen-1)*1e3:.1f} ms/tok, "
          f"{args.batch*(args.gen-1)/max(t_decode,1e-9):.1f} tok/s)")
    print(f"sample: {gen[0][:12].tolist()}")
    assert np.isfinite(gen).all()
    return 0


def run(args):
    cfg = configs.smoke_config(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    if args.engine and args.workers:
        # this parent stays off JAX, so the chips are free for the
        # workers, which build their own weights
        return run_fleet(args, cfg, None)
    compile_cache.enable()
    mesh = make_mesh_for(max_model=args.max_model)
    print(f"mesh: {describe(mesh)} ({mesh.size} devices)")
    key = jax.random.PRNGKey(args.seed)
    if args.engine:
        # single-device mesh adds nothing but sharding plumbing — keep the
        # engine on the exact unsharded path there
        eng_mesh = mesh if mesh.size > 1 else None
        params = init_sharded_params(cfg, key, eng_mesh)
        if args.replicas > 1 or args.journal:
            # journal mode always goes through the router — a single
            # replica is just a fleet of one
            return run_fleet(args, cfg, params, mesh=eng_mesh)
        return run_engine(args, cfg, params, mesh=eng_mesh)
    return run_lockstep(args, cfg, init_sharded_params(cfg, key))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--policy", default="bf16")
    ap.add_argument("--kv-backend", default="auto",
                    choices=["auto", "ref", "interpret", "pallas"],
                    help="int8 KV decode-attention backend (kernels/kvq); "
                         "auto = the compiled kernel on a TPU, ref "
                         "elsewhere")
    ap.add_argument("--kv-splits", type=int, default=1,
                    help="split-K fan-out of the decode grid (clamped to "
                         "the cache's KV tile count)")
    ap.add_argument("--no-quantize", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, the default)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the top-k logits (0 = all)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-model", type=int, default=16)
    # -- continuous-batching engine mode ----------------------------------
    ap.add_argument("--engine", action="store_true",
                    help="serve a synthetic request trace through the "
                         "continuous-batching engine (repro.serve)")
    ap.add_argument("--requests", type=int, default=16,
                    help="engine: number of trace requests")
    ap.add_argument("--max-slots", type=int, default=4,
                    help="engine: resident request slots in the KV pool")
    ap.add_argument("--max-len", type=int, default=128,
                    help="engine: per-slot cache length (prompt + gen cap)")
    ap.add_argument("--mean-prompt", type=int, default=24,
                    help="engine: mean trace prompt length")
    ap.add_argument("--mean-gen", type=int, default=12,
                    help="engine: mean trace generation length")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="engine: trace arrivals per engine step")
    ap.add_argument("--max-prefill-per-step", type=int, default=1,
                    help="engine: prefill-vs-decode interleave quota")
    ap.add_argument("--mem-budget-mb", type=float, default=0.0,
                    help="engine: clamp resident slots to this KV-pool "
                         "budget (plan.serve_capacity_report)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="engine: bounded queue depth — submits beyond it "
                         "are rejected (0 = unbounded)")
    ap.add_argument("--deadline-steps", type=int, default=-1,
                    help="engine: queue TTL in engine steps — requests "
                         "still queued past it are DROPPED (-1 = none)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="engine: replay budget per request after a "
                         "detected decode fault")
    ap.add_argument("--events", default="",
                    help="append fault/health/failover events to this "
                         "JSONL file (repro.events.EventSink)")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="every N steps: sample live-array bytes "
                         "(mem_sample) and emit a metrics_snapshot of "
                         "the obs registry to --events (0 = off)")
    ap.add_argument("--trace", action="store_true",
                    help="emit span_begin/span_end records (queue / "
                         "prefill / decode / migrate / journal / rpc) "
                         "to --events; tools/tracelens.py renders "
                         "per-request timelines and Perfetto JSON")
    # -- replica fleet (router) --------------------------------------------
    ap.add_argument("--replicas", type=int, default=1,
                    help="fleet: engine replicas behind the router "
                         "(1 = plain single-engine mode)")
    ap.add_argument("--route", default="least_loaded",
                    choices=["least_loaded", "round_robin"],
                    help="fleet: admission routing policy")
    ap.add_argument("--max-migrations", type=int, default=2,
                    help="fleet: cross-replica moves per request before "
                         "it FAILs at fleet level")
    ap.add_argument("--breaker-window", type=int, default=32,
                    help="fleet: circuit-breaker fault window (steps)")
    ap.add_argument("--breaker-degrade", type=int, default=1,
                    help="fleet: faults in window -> DEGRADED")
    ap.add_argument("--breaker-quarantine", type=int, default=3,
                    help="fleet: faults in window -> QUARANTINED")
    ap.add_argument("--breaker-cooldown", type=int, default=16,
                    help="fleet: quarantine steps before probation rejoin")
    ap.add_argument("--breaker-stall", type=int, default=8,
                    help="fleet: no-progress steps -> QUARANTINED")
    ap.add_argument("--chaos-seed", type=int, default=-1,
                    help="fleet: run the seeded chaos harness (replica "
                         "crash/sick/slow; -1 = off)")
    ap.add_argument("--chaos-events", type=int, default=3,
                    help="fleet: chaos events to schedule")
    # -- durability (write-ahead journal + subprocess workers) -------------
    ap.add_argument("--journal", default="",
                    help="fleet: write-ahead request journal (JSONL; "
                         "fsync'd).  Reopening an existing journal "
                         "replays it")
    ap.add_argument("--workers", action="store_true",
                    help="fleet: run each replica as a real subprocess "
                         "behind the pipe RPC (repro.serve.worker); on a "
                         "TPU host, one worker (--replicas 1)")
    ap.add_argument("--recover", action="store_true",
                    help="fleet: rebuild in-flight requests from the "
                         "--journal before serving the trace "
                         "(whole-router crash recovery)")
    ap.add_argument("--journal-tokens-every", type=int, default=1,
                    help="fleet: journal token deltas every N router "
                         "steps (group-commit cadence; lost tail tokens "
                         "are regenerated deterministically on recovery)")
    return run(ap.parse_args())


if __name__ == "__main__":
    raise SystemExit(main())
