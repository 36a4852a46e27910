"""Continuous-batching serve engine (ISSUE 5): slot pool lifetimes, FCFS
scheduling, token-exact parity of continuous batching vs isolated decode
across staggered joins/retirements, the zero-recompile contract, the
seeded sampler, and the planner's serve capacity report."""
from __future__ import annotations

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, plan
from repro.models import transformer
from repro.serve import (Request, Scheduler, ServeEngine, SlotPool,
                         sample_tokens, synthetic_trace)
from repro.serve.trace import TraceRequest
from repro.train.serve_step import build_prefill_step


def _smoke_cfg():
    return configs.smoke_config("llama3-8b")


@pytest.fixture(scope="module")
def llama():
    cfg = _smoke_cfg()
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(0))


def _isolated_greedy(params, cfg, prompt, n_new, s_max):
    """Single-request reference: batch-1 prefill + scalar-pos decode."""
    logits, aux = transformer.forward(
        params, cfg, {"tokens": jnp.asarray(prompt)[None]},
        build_cache=True, cache_quantized=True)
    cache = transformer.grow_cache(aux["cache"], s_max)
    cache["pos"] = jnp.int32(len(prompt))
    toks = [int(logits[0, -1].argmax(-1))]
    tok = jnp.asarray([toks[-1]], jnp.int32)
    for _ in range(n_new - 1):
        lg, cache = transformer.decode_step(params, cfg, cache, tok,
                                            quantized=True)
        tok = jnp.asarray(lg.argmax(-1), jnp.int32)
        toks.append(int(tok[0]))
    return toks


# ---------------------------------------------------------------------------
class TestSlotPool:
    def test_alloc_free_cycle(self):
        pool = SlotPool(_smoke_cfg(), 3, 32)
        slots = [pool.alloc() for _ in range(3)]
        assert sorted(slots) == [0, 1, 2] and pool.occupancy == 3
        assert pool.alloc() is None            # exhausted, not an error
        pool.free(slots[1])
        assert pool.occupancy == 2 and pool.alloc() == slots[1]
        for s in (slots[0], slots[1], slots[2]):
            pool.free(s)
        assert pool.occupancy == 0 and pool.allocs == pool.frees == 4

    def test_double_free_raises(self):
        pool = SlotPool(_smoke_cfg(), 2, 32)
        s = pool.alloc()
        pool.free(s)
        with pytest.raises(ValueError, match="not live"):
            pool.free(s)

    def test_per_slot_lengths_and_bytes(self):
        cfg = _smoke_cfg()
        pool = SlotPool(cfg, 4, 32)
        assert pool.cache["pos"].shape == (4,)     # occupancy is data
        assert pool.cache["k"].dtype == jnp.int8
        # exact accounting: batch axis is the slot axis on every leaf
        assert pool.bytes_per_slot() * 4 == sum(
            x.size * x.dtype.itemsize
            for k, x in pool.cache.items() if k != "pos")


# ---------------------------------------------------------------------------
def _req(rid, plen=4, gen=4, arrival=0):
    return Request(rid=rid, prompt=np.ones((plen,), np.int32),
                   max_new_tokens=gen, arrival_step=arrival)


class TestScheduler:
    def test_fcfs_order_and_quota(self):
        sch = Scheduler(4, max_prefill_per_step=2)
        for i in range(4):
            sch.submit(_req(i))
        got = sch.pop_admissible(free_slots=4, now_step=0)
        assert [r.rid for r in got] == [0, 1]      # quota caps per step
        got = sch.pop_admissible(free_slots=2, now_step=0)
        assert [r.rid for r in got] == [2, 3]
        assert sch.queue_depth == 0 and sch.resident == 4

    def test_head_of_line_blocks_on_slots_and_arrival(self):
        sch = Scheduler(2, max_prefill_per_step=4)
        sch.submit(_req(0, arrival=5))
        sch.submit(_req(1, arrival=0))            # behind a later arrival
        assert sch.pop_admissible(free_slots=2, now_step=0) == []
        assert sch.pop_admissible(free_slots=0, now_step=5) == []
        assert [r.rid for r in sch.pop_admissible(2, 5)] == [0, 1]

    def test_byte_budget_bounds_residency(self):
        sch = Scheduler(8, bytes_per_slot=100, byte_budget=250,
                        max_prefill_per_step=8)
        for i in range(4):
            sch.submit(_req(i))
        got = sch.pop_admissible(free_slots=8, now_step=0)
        assert len(got) == 2                       # 3 slots would be 300 B
        sch.retire(got[0])
        assert len(sch.pop_admissible(8, 0)) == 1

    def test_retire_accounting(self):
        sch = Scheduler(2)
        sch.submit(_req(0))
        (r,) = sch.pop_admissible(2, 0)
        sch.retire(r)
        assert sch.resident == 0 and not sch.has_work()
        with pytest.raises(ValueError, match="DONE"):
            sch.retire(r)


# ---------------------------------------------------------------------------
class TestSampling:
    def test_greedy_is_argmax(self):
        logits = jnp.asarray(np.random.default_rng(0).normal(size=(5, 33)),
                             jnp.float32)
        got = sample_tokens(logits, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(logits.argmax(-1)))

    def test_temperature_needs_key(self):
        with pytest.raises(ValueError, match="PRNG key"):
            sample_tokens(jnp.zeros((1, 8)), temperature=0.5)

    def test_seeded_and_topk_support(self):
        logits = jnp.asarray(np.random.default_rng(1).normal(size=(64, 50)),
                             jnp.float32)
        key = jax.random.PRNGKey(7)
        a = sample_tokens(logits, key, temperature=0.8, top_k=5)
        b = sample_tokens(logits, key, temperature=0.8, top_k=5)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # every sampled token must come from its row's top-5 set
        top5 = np.asarray(jax.lax.top_k(logits, 5)[1])
        assert all(int(a[i]) in top5[i] for i in range(logits.shape[0]))
        # high temperature over the full vocab should leave the argmax
        # sometimes (sanity that it's not greedy in disguise)
        c = sample_tokens(logits, key, temperature=5.0)
        assert (np.asarray(c) != np.asarray(logits.argmax(-1))).any()


# ---------------------------------------------------------------------------
class TestTrace:
    def test_deterministic_and_bounded(self):
        t1 = synthetic_trace(20, seed=5, vocab=100, mean_prompt=8,
                             max_prompt=16, mean_gen=4, max_gen=8)
        t2 = synthetic_trace(20, seed=5, vocab=100, mean_prompt=8,
                             max_prompt=16, mean_gen=4, max_gen=8)
        assert len(t1) == 20
        for a, b in zip(t1, t2):
            assert a.arrival_step == b.arrival_step
            assert a.max_new_tokens == b.max_new_tokens
            np.testing.assert_array_equal(a.prompt, b.prompt)
        steps = [r.arrival_step for r in t1]
        assert steps == sorted(steps)
        assert all(4 <= len(r.prompt) <= 16 and 1 <= r.max_new_tokens <= 8
                   and r.prompt.max() < 100 for r in t1)


# ---------------------------------------------------------------------------
class TestPerSlotDecode:
    """Model-layer contract the engine builds on: vector cache['pos']."""

    def test_vector_pos_matches_scalar(self, llama):
        cfg, params = llama
        tok = jnp.asarray([3, 5], jnp.int32)
        c_s = transformer.init_cache(cfg, 2, 16, quantized=True)
        c_s["pos"] = jnp.int32(4)
        c_v = transformer.init_cache(cfg, 2, 16, quantized=True)
        c_v["pos"] = jnp.asarray([4, 4], jnp.int32)
        lg_s, nc_s = transformer.decode_step(params, cfg, c_s, tok,
                                             quantized=True)
        lg_v, nc_v = transformer.decode_step(params, cfg, c_v, tok,
                                             quantized=True)
        np.testing.assert_allclose(np.asarray(lg_s), np.asarray(lg_v),
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(nc_v["pos"]), [5, 5])

    def test_active_mask_freezes_slots(self, llama):
        cfg, params = llama
        cache = transformer.init_cache(cfg, 3, 16, quantized=True)
        cache["pos"] = jnp.asarray([4, 7, 2], jnp.int32)
        active = jnp.asarray([True, False, True])
        k_before = np.asarray(cache["k"])
        _, nc = transformer.decode_step(params, cfg, cache,
                                        jnp.zeros((3,), jnp.int32),
                                        quantized=True, active=active)
        np.testing.assert_array_equal(np.asarray(nc["pos"]), [5, 7, 3])

    def test_active_without_vector_pos_raises(self, llama):
        cfg, params = llama
        cache = transformer.init_cache(cfg, 2, 16, quantized=True)
        with pytest.raises(ValueError, match="active"):
            transformer.decode_step(params, cfg, cache,
                                    jnp.zeros((2,), jnp.int32),
                                    active=jnp.asarray([True, True]))

    def test_per_slot_needs_kvq_layout(self):
        cfg = configs.smoke_config("mamba2-130m")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        cache = transformer.init_cache(cfg, 2, 16)
        cache["pos"] = jnp.zeros((2,), jnp.int32)
        with pytest.raises(NotImplementedError, match="kvq"):
            transformer.decode_step(params, cfg, cache,
                                    jnp.zeros((2,), jnp.int32))

    def test_grow_cache(self, llama):
        cfg, _ = llama
        cache = transformer.init_cache(cfg, 2, 8, quantized=True)
        grown = transformer.grow_cache(cache, 32)
        assert grown["k"].shape[3] == 32 and grown["v_scale"].shape[3] == 32
        with pytest.raises(ValueError, match="grow_cache"):
            transformer.grow_cache(grown, 8)


# ---------------------------------------------------------------------------
class TestPrefillPrealloc:
    def test_prefill_emits_final_length_cache(self, llama):
        cfg, params = llama
        prompts = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab, (2, 12)), jnp.int32)
        step = jax.jit(build_prefill_step(cfg, quantized=True, s_max=40))
        logits, cache = step(params, {"tokens": prompts})
        assert logits.shape == (2, cfg.vocab)
        assert cache["k"].shape[3] == 40 and cache["k_scale"].shape[3] == 40
        assert int(cache["pos"]) == 12
        # the grown tail is zeros — nothing stale can leak into decode
        assert not np.asarray(cache["k"])[:, :, :, 12:].any()


# ---------------------------------------------------------------------------
class TestEngineParity:
    """Continuous-batched greedy == isolated single-request decode,
    token for token, across staggered joins and retirements."""

    def _trace(self, cfg):
        rng = np.random.default_rng(11)
        specs = [(0, 7, 6), (0, 13, 3), (2, 5, 8), (4, 16, 2), (7, 9, 5)]
        return [TraceRequest(arrival_step=a,
                             prompt=rng.integers(0, cfg.vocab, (p,),
                                                 dtype=np.int32),
                             max_new_tokens=g)
                for (a, p, g) in specs]

    def test_tokens_match_isolated(self, llama):
        cfg, params = llama
        trace = self._trace(cfg)
        eng = ServeEngine(params, cfg, max_slots=2, max_len=48,
                          prompt_buckets=(8, 16), seed=0)
        eng.warmup()
        eng.run(trace)
        assert len(eng._requests_done) == len(trace)
        for t in trace:
            req = next(r for r in eng._requests_done
                       if r.prompt_len == len(t.prompt)
                       and r.max_new_tokens == t.max_new_tokens)
            ref = _isolated_greedy(params, cfg, t.prompt,
                                   t.max_new_tokens, 48)
            assert req.tokens == ref, (req.rid, req.tokens, ref)

    def test_interpret_backend_matches_ref(self, llama):
        cfg, params = llama
        trace = self._trace(cfg)[:3]
        toks = {}
        for backend in ("ref", "interpret"):
            eng = ServeEngine(params, cfg, max_slots=2, max_len=48,
                              prompt_buckets=(8, 16), seed=0,
                              kv_backend=backend, kv_splits=2)
            eng.warmup()
            eng.run(trace)
            toks[backend] = sorted(tuple(r.tokens)
                                   for r in eng._requests_done)
        assert toks["ref"] == toks["interpret"]

    def test_auto_backends_follow_platform(self, llama):
        """By default the engine and the model pick kernels by platform:
        compiled Pallas on a TPU, the jnp/ref paths elsewhere."""
        cfg, params = llama
        tpu = jax.default_backend() == "tpu"
        assert cfg.attn_backend == "auto"
        assert cfg.attn_impl() == ("pallas" if tpu else "jnp")
        eng = ServeEngine(params, cfg, max_slots=2, max_len=48,
                          prompt_buckets=(8, 16))
        assert eng.kv_backend == ("pallas" if tpu else "ref")


class TestEngineInvariants:
    def _engine(self, llama, **kw):
        cfg, params = llama
        kw.setdefault("max_slots", 3)
        kw.setdefault("max_len", 48)
        kw.setdefault("prompt_buckets", (8, 16))
        return ServeEngine(params, cfg, **kw)

    def test_no_recompile_after_warmup(self, llama):
        cfg, params = llama
        eng = self._engine(llama)
        baseline = eng.warmup()
        trace = synthetic_trace(9, seed=2, vocab=cfg.vocab, mean_prompt=8,
                                max_prompt=16, mean_gen=6, max_gen=12,
                                arrival_rate=0.8)
        eng.run(trace)
        assert eng.compile_counts() == baseline, \
            "mid-flight join/evict re-jitted a program"

    def test_slot_leak_invariant(self, llama):
        eng = self._engine(llama)
        eng.warmup()
        trace = synthetic_trace(8, seed=4, vocab=_smoke_cfg().vocab,
                                mean_prompt=8, max_prompt=16, mean_gen=5,
                                max_gen=10, arrival_rate=0.6)
        summary = eng.run(trace)
        assert summary["n_done"] == 8
        assert eng.pool.allocs == eng.pool.frees        # every alloc freed
        assert eng.pool.occupancy == 0                  # pool drained
        assert eng.scheduler.resident == 0
        assert summary["total_tokens"] == sum(
            len(r.tokens) for r in eng._requests_done)
        assert 0 < summary["occupancy_mean"] <= 3

    def test_eos_retires_early(self, llama):
        cfg, params = llama
        prompt = np.random.default_rng(3).integers(0, cfg.vocab, (9,),
                                                   dtype=np.int32)
        eng = self._engine(llama)
        eng.warmup()
        eng.run([TraceRequest(0, prompt, 8)])
        (ref,) = eng._requests_done
        assert len(ref.tokens) == 8
        eos = ref.tokens[2]
        eng2 = self._engine(llama, eos_id=eos)
        eng2.warmup()
        eng2.run([TraceRequest(0, prompt, 8)])
        (got,) = eng2._requests_done
        assert got.tokens == ref.tokens[:3]             # stopped AT the eos
        assert eng2.pool.occupancy == 0

    def test_mem_budget_clamps_slots(self, llama):
        cfg, params = llama
        per_slot = SlotPool(cfg, 1, 48).bytes_per_slot()
        eng = self._engine(llama, max_slots=8,
                           mem_budget_bytes=3 * per_slot + 1)
        assert eng.pool.max_slots == 3
        assert eng.capacity_report["max_slots"] == 3
        with pytest.raises(ValueError, match="0 slots"):
            self._engine(llama, mem_budget_bytes=per_slot - 1)

    def test_unsupported_arch_raises(self):
        cfg = configs.smoke_config("mamba2-130m")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(NotImplementedError, match="lockstep"):
            ServeEngine(params, cfg, max_slots=2, max_len=32)

    def test_oversize_request_rejected(self, llama):
        eng = self._engine(llama)
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(np.zeros((16,), np.int32), 64)
        with pytest.raises(ValueError, match="bucket"):
            eng.submit(np.zeros((17,), np.int32), 1)


# ---------------------------------------------------------------------------
class TestServeCapacityReport:
    def test_matches_pool_accounting(self):
        cfg = _smoke_cfg()
        rep = plan.serve_capacity_report(cfg, 64, 10 * 2**20)
        assert rep["eligible"]
        assert rep["bytes_per_slot"] == SlotPool(cfg, 1, 64).bytes_per_slot()
        assert rep["max_slots"] == (10 * 2**20) // rep["bytes_per_slot"]
        # full-causal GQA arch: the exact accounting IS the kv_cache_report
        assert rep["bytes_per_slot"] == rep["kv_int8_bytes_per_slot"]

    def test_params_bytes_and_budget(self):
        cfg = _smoke_cfg()
        rep = plan.serve_capacity_report(cfg, 64, 2**20,
                                         params_bytes=2**20)
        assert rep["max_slots"] == 0
        full = plan.serve_capacity_report(cfg, 64, 2**30)
        half = plan.serve_capacity_report(cfg, 32, 2**30)
        assert 0 < full["max_slots"] < half["max_slots"]

    def test_unquantized_slots_cost_more(self):
        cfg = _smoke_cfg()
        q = plan.serve_capacity_report(cfg, 64, 2**30, quantized=True)
        f = plan.serve_capacity_report(cfg, 64, 2**30, quantized=False)
        assert q["max_slots"] > f["max_slots"]


# ---------------------------------------------------------------------------
class TestEngineCLI:
    def test_engine_mode_banner_and_metrics(self):
        env = {**os.environ, "PYTHONPATH": "src", "PYTHONUNBUFFERED": "1",
               "XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.serve", "--arch",
             "llama3-8b", "--smoke", "--engine", "--requests", "4",
             "--max-slots", "2", "--max-len", "64", "--mean-prompt", "8",
             "--mean-gen", "4"],
            env=env, capture_output=True, text=True, timeout=480)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "capacity:" in out.stdout
        assert "throughput:" in out.stdout
        assert "ttft:" in out.stdout
        assert "occupancy:" in out.stdout
