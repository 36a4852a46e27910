"""Latent attention and held experts at a small size on the CPU, against
plain references: the per-slot MLA decode of the slot pool (with a dense
prologue and a held-expert MoE, slots joining and retiring) against the
full forward pass and the benchmark's float32 reference; the latent
decode kernel in the Pallas interpreter against its jnp reference; the
``noaux_tc`` router against a plain top-k; the disjoint expert shares
summing to the uncut layer; the softmax router as it was."""
from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.mixed_precision import Policy
from repro.kernels.mla import ops as mla_ops
from repro.models import moe as moe_mod
from repro.models import transformer
from repro.models.config import MoEConfig
from repro.serve.cache_pool import SlotPool, scatter_request

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "tpu", "configs"))
import glm4moe_lite as ref  # noqa: E402

#: the benchmark reference's keys for a tiny GLM-4.7-Flash: one dense
#: layer, two MoE layers holding 4 (2-5) of 8 experts
TINY = {"num_hidden_layers": 3, "first_k_dense_replace": 1,
        "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 24,
        "qk_rope_head_dim": 8, "v_head_dim": 32, "n_routed_experts": 4,
        "held_first_expert": 2, "published": {"n_routed_experts": 8},
        "num_experts_per_tok": 2, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "vocab_size": 256,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0}
PROGRAM = {"n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv": 4,
           "d_ff": 96, "vocab": 256, "head_dim": 32, "norm_eps": 1e-5,
           "rope_theta": 10000.0,
           "mla": {"q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_dim": 24,
                   "qk_rope_dim": 8, "v_head_dim": 32},
           "moe": {"num_experts": 8, "top_k": 2, "d_expert": 32,
                   "num_shared": 1, "d_shared": 32, "capacity_factor": 0.0,
                   "scoring": "sigmoid", "routed_scale": 1.8,
                   "held": [2, 4]}}


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(configs.get_config("glm47-flash"), **PROGRAM)
    return cfg, ref.make_params(TINY, 7, jnp.float32)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


def test_forward_matches_reference(tiny):
    cfg, params = tiny
    toks = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = transformer.forward(params, cfg,
                                     {"tokens": jnp.asarray(toks)[None]})
    pad = np.zeros(64, np.int32)
    pad[:40] = toks
    want = ref.logits_at(params, TINY, pad, np.arange(40), qblock=32)
    assert _rel(got[0], want) < 1e-4


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_pool_decode_matches_forward_and_reference(tiny, backend):
    """Requests join the pool at steps 0, 3 and 6 (prefill, scatter into
    a slot), every round decodes all live slots at their own positions,
    one retires at step 9 (its slot freezes): each live slot's logits
    match the full forward pass over its sequence so far, and the
    reference's.  The latent cache is bf16, so 2e-2."""
    cfg, params = tiny
    pool = SlotPool(cfg, 3, 64)
    cache = pool.cache
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (5, 17, 9)]
    seqs = [list(p) for p in prompts]
    joins = {0: 0, 3: 1, 6: 2}              # step -> request (= slot)
    active = np.zeros(3, bool)
    tokens = np.zeros(3, np.int32)
    worst_fwd = worst_ref = 0.0
    for step in range(12):
        if step in joins:
            r = joins[step]
            lg, aux = transformer.forward(
                params, cfg, {"tokens": jnp.asarray(prompts[r])[None]},
                build_cache=True)
            cache = scatter_request(
                cache, transformer.grow_cache(aux["cache"], 64), r,
                len(prompts[r]))
            seqs[r].append(int(lg[0, -1].argmax()))
            active[r], tokens[r] = True, seqs[r][-1]
        if step == 9:
            active[0] = False
        frozen = int(cache["pos"][0])
        lg, cache = transformer.decode_step(
            params, cfg, cache, jnp.asarray(tokens),
            active=jnp.asarray(active), kvq_backend=backend)
        if not active[0]:
            assert int(cache["pos"][0]) == frozen
        for r in np.nonzero(active)[0]:
            full, _ = transformer.forward(
                params, cfg, {"tokens": jnp.asarray(seqs[r])[None]})
            worst_fwd = max(worst_fwd, _rel(lg[r], full[0, -1]))
            pad = np.zeros(64, np.int32)
            pad[:len(seqs[r])] = seqs[r]
            want = ref.logits_at(params, TINY, pad, [len(seqs[r]) - 1],
                                 qblock=32)[0]
            worst_ref = max(worst_ref, _rel(lg[r], want))
            seqs[r].append(int(lg[r].argmax()))
            tokens[r] = seqs[r][-1]
    assert [len(s) for s in seqs] == [15, 27, 16]
    assert worst_fwd < 2e-2 and worst_ref < 2e-2, (worst_fwd, worst_ref)


@pytest.mark.parametrize("s,lengths,layer", [
    (256, [1, 256, 100, 129], 0),
    (512, [512, 1, 511, 3], 2),
])
def test_mla_decode_kernel_matches_ref(s, lengths, layer):
    """Ragged slot lengths, including one position and the whole cache,
    on a layer of the stacked cache; tiles of 128 so that a slot ends
    inside, at the edge of, and before the last tile."""
    b, h, c, r, n_layers = len(lengths), 20, 64, 16, 3
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q_abs = jax.random.normal(ks[0], (b, h, c)).astype(jnp.bfloat16)
    q_rope = jax.random.normal(ks[1], (b, h, r)).astype(jnp.bfloat16)
    lat = jax.random.normal(ks[2], (n_layers, b, s, c)).astype(jnp.bfloat16)
    rope = jax.random.normal(ks[3], (n_layers, b, r, s)).astype(jnp.bfloat16)
    lens = jnp.asarray(lengths, jnp.int32)
    kw = dict(sm_scale=(c + r) ** -0.5)
    want = mla_ops.mla_decode_attention(q_abs, q_rope, lat, rope, lens,
                                        layer, backend="ref", **kw)
    got = mla_ops.mla_decode_attention(q_abs, q_rope, lat, rope, lens,
                                       layer, backend="interpret",
                                       block_s=128, **kw)
    assert got.shape == (b, h, c) and got.dtype == jnp.float32
    assert _rel(got, want) < 2e-2
    # the oracle itself: one position attends to itself alone
    one = int(np.argmin(lengths))
    np.testing.assert_allclose(np.asarray(want[one]),
                               np.broadcast_to(np.asarray(
                                   lat[layer, one, 0], np.float32),
                                   (h, c)), rtol=1e-6)


def test_noaux_tc_router_is_plain_topk():
    """Sigmoid scores; the top k of score + bias; the chosen scores
    normalised and scaled.  The bias chooses, it never weighs."""
    rng = np.random.default_rng(4)
    x = rng.normal(size=(32, 16)).astype(np.float32)
    w = rng.normal(size=(16, 8)).astype(np.float32)
    bias = rng.normal(size=(8,)).astype(np.float32)
    weights, idx, aux = moe_mod.router_topk(
        jnp.asarray(x), jnp.asarray(w), 3, scoring="sigmoid",
        bias=jnp.asarray(bias), scale=1.8)
    scores = 1 / (1 + np.exp(-(x.astype(np.float64) @ w)))
    for t in range(32):
        top = np.argsort(-(scores[t] + bias), kind="stable")[:3]
        assert set(np.asarray(idx[t])) == set(top)
        chosen = scores[t][np.asarray(idx[t])]
        np.testing.assert_allclose(np.asarray(weights[t]),
                                   chosen / chosen.sum() * 1.8, rtol=1e-5)
    assert float(aux) == 0.0
    assert not np.array_equal(
        np.sort(np.asarray(idx), -1),
        np.sort(np.argsort(-scores, -1)[:, :3], -1))  # the bias mattered


def _moe_cfg(held=None):
    m = MoEConfig(num_experts=16, top_k=4, d_expert=24, num_shared=1,
                  d_shared=40, capacity_factor=0.0, scoring="sigmoid",
                  routed_scale=1.8, held=held)
    return dataclasses.replace(configs.smoke_config("deepseek-moe-16b"),
                               moe=m)


def test_expert_shares_sum_to_the_uncut_layer():
    """Eight chips holding two experts each: the parts the shares compute,
    the shared expert counted once, add up to the whole layer, in the
    program and in the benchmark's reference."""
    cfg = _moe_cfg()
    p = transformer._init_ffn(cfg, jax.random.PRNGKey(5))
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 12, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_mod.moe_ffn(p, x, cfg)
        shared = moe_mod.swiglu(x, p["shared_gate"], p["shared_up"],
                                p["shared_down"])
        parts = []
        for first in range(0, 16, 2):
            share = dict(p, **{k: p[k][first:first + 2]
                               for k in ("w_gate", "w_up", "w_down")})
            out, _ = moe_mod.moe_ffn(share, x, _moe_cfg((first, 2)))
            parts.append(out - shared)
        summed = shared + sum(parts)
        d = dict(ref.dims(TINY), E=16, first=0, held=16, k=4, scale=1.8)
        want = jax.vmap(lambda r: ref.routed(r, p, d, False))(x)
    assert _rel(summed, whole) < 1e-5
    assert _rel(whole, want) < 1e-5


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "granite-moe-3b-a800m"])
@pytest.mark.parametrize("capacity", [0.0, 8.0])
def test_softmax_router_configs_route_as_before(arch, capacity):
    """The softmax router's top-k renormalised, its Switch load-balance
    loss, and the layer's sum: written out here as the MoE layer had
    them before the sigmoid router and held experts came."""
    cfg = configs.smoke_config(arch)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=capacity))
    assert cfg.moe.scoring == "softmax" and cfg.moe.held is None
    p = transformer._init_ffn(cfg, jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 8, cfg.d_model))
    out, aux = moe_mod.moe_ffn(p, x, cfg)
    m = cfg.moe
    xf = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    logits = xf @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, -1, kind="stable")[:, :m.top_k]
    tp = np.take_along_axis(probs, top, -1)
    wts = tp / tp.sum(-1, keepdims=True)
    f = np.bincount(top.reshape(-1), minlength=m.num_experts)
    aux_want = m.num_experts * np.sum(f / f.sum() * probs.mean(0))
    silu = lambda v: v / (1 + np.exp(-v))  # noqa: E731
    want = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        for j in range(m.top_k):
            e = top[t, j]
            g = xf[t] @ np.asarray(p["w_gate"][e], np.float64)
            u = xf[t] @ np.asarray(p["w_up"][e], np.float64)
            want[t] += wts[t, j] * (silu(g) * u) @ np.asarray(
                p["w_down"][e], np.float64)
    if m.num_shared:
        want += (silu(xf @ np.asarray(p["shared_gate"], np.float64))
                 * (xf @ np.asarray(p["shared_up"], np.float64))) \
            @ np.asarray(p["shared_down"], np.float64)
    assert _rel(np.asarray(out).reshape(xf.shape), want) < 1e-4
    np.testing.assert_allclose(float(aux), aux_want, rtol=1e-5)


def _isolated_greedy(params, cfg, prompt, n_new, s_max):
    """One request alone: prefill, then per-slot decode in a one-slot
    pool."""
    lg, aux = transformer.forward(params, cfg,
                                  {"tokens": jnp.asarray(prompt)[None]},
                                  build_cache=True)
    cache = scatter_request(SlotPool(cfg, 1, s_max).cache,
                            transformer.grow_cache(aux["cache"], s_max), 0,
                            len(prompt))
    toks = [int(lg[0, -1].argmax())]
    for _ in range(n_new - 1):
        lg, cache = transformer.decode_step(
            params, cfg, cache, jnp.asarray(toks[-1:], jnp.int32),
            active=jnp.asarray([True]))
        toks.append(int(lg[0].argmax()))
    return toks


def test_engine_serves_mla_with_prologue_and_held_experts(tiny):
    """The engine admits the architecture and runs requests through the
    pool with joins and retirements and no recompile; each request's
    greedy tokens are those it gets alone.  (Against the forward pass a
    served token may differ where two experts' routing scores nearly
    tie, as the bf16 latent cache rounds: the logits tests above bound
    the numbers.)"""
    from repro.serve import ServeEngine
    cfg, params = tiny
    eng = ServeEngine(params, cfg, max_slots=2, max_len=64,
                      prompt_buckets=(16, 32), policy_name="full")
    counts = eng.warmup()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (7, 20, 12)]
    rids = [eng.submit(p, n) for p, n in zip(prompts, (6, 9, 4))]
    for _ in range(200):
        if not eng.scheduler.has_work():
            break
        eng.step()
    assert eng.compile_counts() == counts
    states = eng.request_states()
    for p, rid, n in zip(prompts, rids, (6, 9, 4)):
        assert states[rid]["state"] == "DONE"
        assert states[rid]["tokens"] == _isolated_greedy(params, cfg, p, n,
                                                         64)
    assert eng.pool.allocs == eng.pool.frees == 3


def test_step_span_counts_latent_positions(tiny):
    """Traced, each decode round's ``step`` span ends with the cached
    positions its live slots read: each one's prompt and tokens so far
    (its newest token included)."""
    from repro.obs.trace import Tracer
    from repro.serve import ServeEngine

    class Sink:
        def __init__(self):
            self.events = []

        def emit(self, kind, **f):
            self.events.append((kind, f))

    cfg, params = tiny
    eng = ServeEngine(params, cfg, max_slots=2, max_len=64,
                      prompt_buckets=(16,), policy_name="full",
                      max_prefill_per_step=2)
    eng.warmup()
    sink = Sink()
    eng.tracer = Tracer(sink, pid="engine")
    eng.submit(np.arange(5, dtype=np.int32), 4)
    eng.submit(np.arange(9, dtype=np.int32), 4)
    while eng.scheduler.has_work():
        eng.step()
    begins = {f["sid"]: f for k, f in sink.events
              if k == "span_begin" and f["name"] == "step"}
    got = [f.get("latent_positions") for k, f in sink.events
           if k == "span_end" and f["sid"] in begins]
    # the admitting step decodes too: rounds 1-3 read (5 + r) + (9 + r)
    assert got == [16, 18, 20]
