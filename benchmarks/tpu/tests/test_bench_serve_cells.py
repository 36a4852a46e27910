"""Tiny serving cells end to end on the CPU, with the look for a chip
skipped: sound runs are correct, the fp8 control and faults planted in
the timed path are not."""
from __future__ import annotations

import pytest

from tinycells import run_tiny, tiny_bench  # noqa: F401


@pytest.mark.parametrize("cell,trace,e2e,per_layer", [
    ("tiny.chat", False, {"ttft_p95_ms", "itl_p99_ms", "setup_s"}, set()),
    ("tiny.chat", True, set(), {"queue_wait_p95_ms", "decode_step_p50_ms",
                                "mfu.chat", "idle_share.chat"}),
])
def test_sound_run_is_correct(tiny_bench, cell, trace, e2e, per_layer):
    d, bench = tiny_bench
    out = run_tiny(d, bench, cell, trace=trace)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    # the CPU trace has no Pallas kernels: their rooflines stay silent
    assert set(out["metrics"]) == (e2e | per_layer)
    assert list(out)[-1] == "checks"
    if trace:
        assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
        assert out["breakdown"]["device_ops"]
        assert 0 < out["metrics"]["mfu.chat"]["value"] <= 100
        assert out["missing"] == ["kvq_decode_roofline"]


def _alter_tokens(monkeypatch):
    """A token altered where it is produced: the sampler's choice + 1."""
    from repro.serve import sampling
    orig = sampling.sample_tokens

    def altered(logits, key=None, **kw):
        return (orig(logits, key, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(sampling, "sample_tokens", altered)


def _state_unchanged(monkeypatch):
    """A decode step that returns the cache it was given."""
    from repro.models import transformer
    orig = transformer.decode_step

    def frozen(params, cfg, cache, tokens_t, **kw):
        logits, _ = orig(params, cfg, cache, tokens_t, **kw)
        return logits, cache

    monkeypatch.setattr(transformer, "decode_step", frozen)


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_fault_makes_run_incorrect(tiny_bench, monkeypatch, fault):
    d, bench = tiny_bench
    fault(monkeypatch)
    out = run_tiny(d, bench, "tiny.chat")
    assert not out["correct"]
    assert out["checks"]["served_gap"]["value"] > \
        out["checks"]["served_gap"]["limit"]


def test_fp8_control_fails_the_limit(tiny_bench):
    """The reference in fp8, at the same positions of served tokens."""
    import json
    import os

    import jax.numpy as jnp

    import bench as harness
    import serving
    import traffic

    d, _ = tiny_bench
    with open(os.path.join(d, "workloads", "tiny.chat.json")) as f:
        wl = json.load(f)
    with open(os.path.join(d, "configs", "tiny.json")) as f:
        cfg = json.load(f)
    worst = []
    for seed in (1, 2, 3):
        run = harness.Run(wl, cfg, seed=seed, seconds=1.0, trace=False,
                          t_process=0.0, bench_dir=d)
        params = run.reference.make_params(cfg, seed, jnp.bfloat16)
        reqs = traffic.requests(wl["traffic"], 512, seed, 1.0)[:3]
        served = [(r.prompt, [int(t) for t in r.prompt[:8]]) for r in reqs]
        worst.append(serving.served_gaps(run, params, served,
                                         fp8=True)["worst_gap"])
    assert min(worst) > wl["check"]["limits"]["served_gap"], worst


def test_engine_serves_bf16_params_unchanged(tiny_bench):
    """The served weights are made in bf16 and the engine keeps them as
    given: the bf16 policy's cast of them is a no-op."""
    import json
    import os

    import jax
    import numpy as np

    import bench as harness
    import serving

    d, _ = tiny_bench
    with open(os.path.join(d, "workloads", "tiny.chat.json")) as f:
        wl = json.load(f)
    with open(os.path.join(d, "configs", "tiny.json")) as f:
        cfg = json.load(f)
    run = harness.Run(wl, cfg, seed=5, seconds=1.0, trace=False,
                      t_process=0.0, bench_dir=d)
    engine, params = serving.build_engine(run)
    given = jax.tree_util.tree_leaves(params)
    served = jax.tree_util.tree_leaves(engine.params)
    assert len(given) == len(served)
    for a, b in zip(given, served):
        assert a.dtype == b.dtype == jax.numpy.bfloat16
        assert np.array_equal(np.asarray(a), np.asarray(b))
