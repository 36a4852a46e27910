#!/usr/bin/env python
"""Failure-count + benchmark ratchet for the tier-1 suite.

Parses a pytest junit XML report and fails the build when the suite does
worse than the committed baseline.  The baseline below locks in the current
tree's state; the seed repo was 7 failed / 106 passed with 2 modules
uncollectable without hypothesis — only ever move these numbers in the
good direction.

With ``--bench-dir``, also ratchets the committed BENCH_*.json results:
serve-engine throughput speedup, the flash/decode kernels' tile-skip
fractions, and the mesh-sharding parity/capacity flags.  A perf
optimization that quietly re-densifies a kernel grid or melts engine
throughput then fails CI even though every correctness test still passes.

Usage: python tools/ci_ratchet.py report.xml [--max-failed N]
           [--min-passed M] [--bench-dir DIR]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import xml.etree.ElementTree as ET

# Ratchet baseline (update when the suite legitimately improves/grows).
# Seed repo: 7 failed / 106 passed; PR 1: 0 failed / 160 passed;
# PR 2 (trainable flash attention: kernel-gradient + planner-residual
# tests): 0 failed / 185 passed; PR 3 (sparse flash grids: tile-bound
# sweep, counter-vs-analytic, skip-ratio acceptance, resid policy, kvq
# no-bias): 0 failed / 239 passed; PR 4 (split-K int8 flash decode:
# ragged-length parity, split/merge oracle, decode counters, skip-ratio
# floor, no-bias jaxprs, planner decode reports, serve CLI): 0 failed /
# 275 passed; PR 5 (continuous-batching serve engine: slot pool
# alloc/free + scatter, scheduler admission, token-exact parity vs
# isolated decode across staggered joins/retirements, zero-recompile
# counters, slot-leak drain, sampler, capacity report, trace driver):
# 0 failed / 304 passed; PR 6 (mesh-parallel hot paths: rule tables on
# 1/2/8-device meshes, 8-device flash train grad parity, token-exact
# mesh serving heads+seq with no-all-gather HLO assertion, int8 decode
# collective vs oracle, compressed psum-grad parity/unbiasedness,
# per-device planner budgets): 0 failed / 420 passed on one device;
# PR 7 (fault tolerance: scheduler terminal states + bounded queue +
# deadlines, decode health sentinel + quarantine/replay under seeded
# fault injection, train guards with NaN-skip + rollback, checkpoint
# fingerprint/config identity + conflicting-resave rejection):
# 0 failed / 451 passed on one device — the 8-device CI grid unskips 8
# more (7 mesh + the cross-mesh checkpoint round-trip); the lock stays
# at the 1-device floor so the suite passes anywhere.
MAX_FAILED = 0
# PR 9 (durable serving: write-ahead request journal append/snapshot/
# torn-tail + crash-at-every-append harness, subprocess worker RPC +
# SIGKILL failover, whole-router kill -9 recovery token-exact with one
# terminal per journaled SUBMIT, watchdog race regression): 0 failed /
# 531 passed on the CI 8-device grid (523 pass on one device; the same
# 8 mesh/checkpoint tests as before skip without the emulated grid).
# PR 10 (observability: metrics registry + merge laws, span tracing
# with crash-visible open spans + fleet crash/recovery timeline
# acceptance, tracelens, memstat, event-schema closed world,
# fleet_summary/read_events edge cases): 0 failed / 559 passed on the
# CI grid (551 on one device).
MIN_PASSED = 559

# Benchmark floors (path into the committed BENCH json, minimum value or
# required flag).  Floors sit safely under the committed results so normal
# run-to-run noise passes, but a structural regression (a kernel grid
# re-densifying, the engine losing its continuous-batching win, mesh
# sharding losing parity) trips them.
BENCH_FLOORS = [
    # serve engine: continuous batching must keep a real throughput win
    # over lockstep.  PR 7 re-based 1.55x -> 1.1 (single-shot timing had
    # charged lockstep its cold start); PR 8 re-based again to 1.0: both
    # walls are ~50 ms on CPU and lockstep's is bimodal ACROSS processes
    # (observed 1.03x-1.34x over repeated interleaved best-of-5 runs), so
    # any floor above parity flakes on regeneration.  The structural win
    # is ratcheted deterministically below via slot_step_efficiency
    # (useful tokens per executed slot-step on the seeded trace, arrival
    # gaps included: engine 0.764 vs lockstep's 0.57 — no wall clock
    # involved, exact on the seeded trace).
    ("BENCH_serve.json", ("speedup_tokens_per_s",), 1.0),
    ("BENCH_serve.json", ("continuous", "slot_step_efficiency"), 0.75),
    # fault tolerance (ISSUE 7): under the canonical seeded fault plan
    # (NaN logits + corrupt cache row + dropped scatter) the engine must
    # recover every victim (no slot leaks, every retry reaches DONE) and
    # keep real goodput (committed: 4116 tok/s, 0.78x fault-free)
    ("BENCH_serve.json", ("fault_trace", "zero_slot_leaks"), True),
    ("BENCH_serve.json", ("fault_trace", "retry_success_rate"), 0.99),
    ("BENCH_serve.json", ("fault_trace", "goodput_tokens_per_s"), 3000),
    ("BENCH_serve.json", ("fault_trace", "goodput_frac_of_fault_free"),
     0.55),
    # replica fleet (ISSUE 8): under the canonical seeded replica-kill
    # (2 replicas, replica 1 crashed at router step 4) every migrated
    # request must replay to DONE on the survivor, neither pool may leak,
    # and fleet goodput must hold at least half the fault-free fleet's
    # (committed: replay 1.0, ratio ~1.0 — the survivor's steps cost less
    # than stepping two engines on CPU)
    ("BENCH_serve.json", ("fleet", "replica_kill", "zero_slot_leaks"),
     True),
    ("BENCH_serve.json",
     ("fleet", "replica_kill", "failover_replay_success"), 0.99),
    ("BENCH_serve.json",
     ("fleet", "replica_kill", "goodput_frac_of_fault_free"), 0.5),
    # durable serving (ISSUE 9): the canonical seeded router-crash run
    # (kill -9 after 12 router steps, fresh router recovers from the
    # write-ahead journal) must finish every recovered request
    # (committed: replay 1.0, one terminal per journaled SUBMIT, zero
    # leaks), and the fsync'd journal — group commit flush_every=16,
    # token cadence 4 — must keep >= 0.8 of unjournaled fleet goodput
    # on the interleaved min-of-3 comparison (committed: ~0.9)
    ("BENCH_serve.json", ("recovery", "recovery_replay_success"), 0.99),
    ("BENCH_serve.json",
     ("recovery", "journaled_goodput_frac_of_unjournaled"), 0.8),
    ("BENCH_serve.json",
     ("recovery", "router_crash", "one_terminal_per_submit"), True),
    ("BENCH_serve.json",
     ("recovery", "router_crash", "zero_slot_leaks"), True),
    # split-K int8 decode: ragged-batch tile claw-back (committed: 0.75)
    ("BENCH_decode.json", ("tile_clawback_s2048_ragged", "skip_frac"), 0.70),
    # sparse flash grids (committed: 0.47 causal, 0.82 windowed)
    ("BENCH_flash.json", ("flop_clawback_s2048", "tile_skip_frac"), 0.45),
    ("BENCH_flash.json", ("sparsity", "causal_s2048", "skipped_frac"), 0.45),
    ("BENCH_flash.json", ("sparsity", "window256_s2048", "skipped_frac"),
     0.80),
    # mesh sharding: single-device parity and per-device capacity scaling
    ("BENCH_shard.json", ("train", "parity"), True),
    ("BENCH_shard.json", ("serve", "token_parity"), True),
    ("BENCH_shard.json", ("capacity", "slots_times_devices_ge_single"),
     True),
]


def check_event_schema(repo_root: str) -> int:
    """Closed-world event schema: every ``sink.emit("kind", ...)`` call
    site under src/ must name a kind declared in ``repro.obs.schema``.
    An undeclared kind means a producer was added without extending the
    schema — tracelens and downstream consumers would silently drop it."""
    sys.path.insert(0, os.path.join(repo_root, "src"))
    try:
        from repro.obs.schema import undeclared_kinds_in_source
    except ImportError as e:
        print(f"SCHEMA CHECK SKIPPED: repro.obs unimportable ({e})")
        return 1
    undeclared = undeclared_kinds_in_source(os.path.join(repo_root, "src"))
    if undeclared:
        for kind, sites in sorted(undeclared.items()):
            print(f"SCHEMA VIOLATION: event kind {kind!r} emitted at "
                  f"{sites} but not declared in repro/obs/schema.py")
        return len(undeclared)
    print("schema: every emitted event kind is declared in "
          "repro/obs/schema.py")
    return 0


def check_bench(bench_dir: str) -> int:
    bad = 0
    for fname, path, floor in BENCH_FLOORS:
        fpath = os.path.join(bench_dir, fname)
        label = f"{fname}:{'.'.join(path)}"
        try:
            with open(fpath) as f:
                val = json.load(f)
            for key in path:
                val = val[key]
        except (OSError, KeyError, TypeError) as e:
            print(f"BENCH RATCHET VIOLATION: {label} unreadable ({e})")
            bad += 1
            continue
        if floor is True:
            ok = val is True
            print(f"bench: {label} = {val} (required: true)"
                  + ("" if ok else "  <-- VIOLATION"))
        else:
            ok = isinstance(val, (int, float)) and val >= floor
            print(f"bench: {label} = {val} (floor: {floor})"
                  + ("" if ok else "  <-- VIOLATION"))
        bad += 0 if ok else 1
    return bad


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("report")
    ap.add_argument("--max-failed", type=int, default=MAX_FAILED)
    ap.add_argument("--min-passed", type=int, default=MIN_PASSED)
    ap.add_argument("--bench-dir", default=None,
                    help="also ratchet the committed BENCH_*.json results "
                         "in this directory")
    args = ap.parse_args()

    root = ET.parse(args.report).getroot()
    suites = root.iter("testsuite")
    tests = failures = errors = skipped = 0
    for s in suites:
        tests += int(s.get("tests", 0))
        failures += int(s.get("failures", 0))
        errors += int(s.get("errors", 0))
        skipped += int(s.get("skipped", 0))
    failed = failures + errors
    passed = tests - failed - skipped
    print(f"tier-1: {passed} passed, {failed} failed/errored, "
          f"{skipped} skipped (ratchet: <= {args.max_failed} failed, "
          f">= {args.min_passed} passed)")
    if failed > args.max_failed:
        print(f"RATCHET VIOLATION: {failed} > {args.max_failed} failures")
        return 1
    if passed < args.min_passed:
        print(f"RATCHET VIOLATION: {passed} < {args.min_passed} passes "
              f"(tests deleted or newly skipped?)")
        return 1
    if args.bench_dir is not None:
        if check_bench(args.bench_dir):
            return 1
        if check_event_schema(args.bench_dir):
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
