"""Per-arch smoke tests (reduced configs): one forward/train step on CPU,
shape + finiteness asserts; decode consistency vs full forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.checkpoint import CheckpointConfig
from repro.core.mixed_precision import Policy
from repro.models import transformer

ARCHS = configs.list_archs()
KEY = jax.random.PRNGKey(0)


def _batch_for(cfg, b=2, s=32):
    rng = np.random.default_rng(1)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
    }
    if cfg.encoder is not None:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(b, cfg.encoder.n_frames, cfg.d_model))
            .astype(np.float32))
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.normal(size=(b, 8, cfg.d_model)).astype(np.float32))
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(s)[None, None], (3, b, s)).astype(jnp.int32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_shapes_and_finite(arch):
    cfg = configs.smoke_config(arch)
    params = transformer.init_params(cfg, KEY)
    batch = _batch_for(cfg)
    logits, aux = transformer.forward(params, cfg, batch)
    assert logits.shape == (2, 32, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step_decreases_loss(arch):
    from repro.optim import adamw
    from repro.train.train_step import TrainConfig, build_train_step
    from repro.core.mixed_precision import LossScale

    cfg = configs.smoke_config(arch)
    params = transformer.init_params(cfg, KEY)
    batch = _batch_for(cfg, b=4, s=16)
    tc = TrainConfig(policy="full",
                     opt=adamw.AdamWConfig(lr=1e-2, warmup_steps=0,
                                           total_steps=100))
    step = jax.jit(build_train_step(cfg, tc))
    opt = adamw.init(params)
    ls = LossScale.noop()
    losses = []
    for _ in range(4):
        params, opt, ls, m = step(params, opt, ls, batch)
        losses.append(float(m["loss"]))
        assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_remat_equals_standard(arch):
    """OpTorch S-C must not change the math (paper: 'same accuracy')."""
    cfg = configs.smoke_config(arch)
    params = transformer.init_params(cfg, KEY)
    batch = _batch_for(cfg)
    l1, _ = transformer.loss_fn(params, cfg, batch,
                                remat=CheckpointConfig(enabled=False))
    l2, _ = transformer.loss_fn(params, cfg, batch,
                                remat=CheckpointConfig(enabled=True))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    if arch not in ("llama3-8b", "deepseek-moe-16b", "mamba2-130m",
                    "hymba-1.5b"):
        return  # grad equality: one arch per family (compile-time budget)
    g1 = jax.grad(lambda p: transformer.loss_fn(
        p, cfg, batch, remat=CheckpointConfig(enabled=False))[0])(params)
    g2 = jax.grad(lambda p: transformer.loss_fn(
        p, cfg, batch, remat=CheckpointConfig(enabled=True))[0])(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("arch,s", [
    *[pytest.param(a, 12, id=a)
      for a in ("llama3-8b", "glm4-9b", "qwen2-vl-2b", "mamba2-130m",
                "hymba-1.5b", "minicpm3-4b", "whisper-base")],
    # 24 tokens against the smoke window of 16: the band must hold once
    # the window has filled (windowed layers decode on the bias path)
    pytest.param("hymba-1.5b", 24, id="hymba-1.5b-past-window"),
])
def test_decode_matches_forward(arch, s):
    """Greedy decode logits must match teacher-forced forward logits."""
    cfg = configs.smoke_config(arch)
    params = transformer.init_params(cfg, KEY)
    b = 2
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    kw = {}
    if cfg.encoder is not None:
        frames = jnp.asarray(rng.normal(
            size=(b, cfg.encoder.n_frames, cfg.d_model)).astype(np.float32))
        batch["frames"] = frames
        kw["enc_out"] = transformer._run_encoder(params, cfg, frames,
                                                 Policy.full())
    if cfg.family == "vlm":
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(s)[None, None], (3, b, s)).astype(jnp.int32)
    full_logits, _ = transformer.forward(params, cfg, batch)

    cache = transformer.init_cache(cfg, b, s, quantized=False,
                                   dtype=jnp.float32)
    step_logits = []
    for t in range(s):
        lg, cache = transformer.decode_step(params, cfg, cache, toks[:, t],
                                            quantized=False, **kw)
        step_logits.append(lg)
    dec = np.stack([np.asarray(l) for l in step_logits], 1)
    np.testing.assert_allclose(dec, np.asarray(full_logits),
                               atol=2e-3, rtol=2e-2)


def test_quantized_cache_close_to_exact():
    cfg = configs.smoke_config("llama3-8b")
    params = transformer.init_params(cfg, KEY)
    b, s = 2, 10
    toks = jnp.asarray(np.random.default_rng(5).integers(0, cfg.vocab, (b, s)),
                       jnp.int32)
    cache_q = transformer.init_cache(cfg, b, s, quantized=True)
    cache_f = transformer.init_cache(cfg, b, s, quantized=False,
                                     dtype=jnp.float32)
    for t in range(s):
        lq, cache_q = transformer.decode_step(params, cfg, cache_q,
                                              toks[:, t], quantized=True)
        lf, cache_f = transformer.decode_step(params, cfg, cache_f,
                                              toks[:, t], quantized=False)
    # int8 cache must preserve the argmax token and be close in value
    assert (np.asarray(lq).argmax(-1) == np.asarray(lf).argmax(-1)).all()
    np.testing.assert_allclose(np.asarray(lq), np.asarray(lf), atol=0.05,
                               rtol=0.05)


def test_prefill_cache_matches_incremental():
    cfg = configs.smoke_config("llama3-8b")
    params = transformer.init_params(cfg, KEY)
    b, s = 2, 8
    toks = jnp.asarray(np.random.default_rng(6).integers(0, cfg.vocab, (b, s)),
                       jnp.int32)
    logits, aux = transformer.forward(params, cfg, {"tokens": toks},
                                      build_cache=True, cache_quantized=True)
    cache_pf = aux["cache"]
    # continue decoding one token; compare against incremental-built cache
    cache_inc = transformer.init_cache(cfg, b, s + 4, quantized=True)
    for t in range(s):
        lg_inc, cache_inc = transformer.decode_step(params, cfg, cache_inc,
                                                    toks[:, t])
    np.testing.assert_allclose(np.asarray(lg_inc), np.asarray(logits[:, -1]),
                               atol=0.05, rtol=0.05)
    # prefill cache continues correctly
    nxt = jnp.asarray(logits[:, -1].argmax(-1), jnp.int32)
    # pad prefill cache to the incremental cache length for the next step
    assert int(cache_pf["pos"]) == s
