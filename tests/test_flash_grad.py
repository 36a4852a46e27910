"""Trainable flash attention: interpret-mode gradient parity vs the jnp
oracle, dispatch guards, and end-to-end differentiability of the
``attn_backend="pallas"`` training path (custom_vjp, O(S*D) residuals)."""
import dataclasses as dc
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels.flash import kernel as flash_kernel, ops as flash_ops, \
    ref as flash_ref
from repro.models import transformer

RNG = np.random.default_rng(11)


def _qkv(b, h, hkv, s, d, dtype=np.float32):
    q = jnp.asarray(RNG.normal(size=(b, h, s, d)).astype(dtype))
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(dtype))
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(dtype))
    return q, k, v


class TestFlashGradParity:
    """jax.grad of the Pallas backward (interpret mode) vs jnp autodiff of
    the exact reference — the ISSUE 2 acceptance bar (<= 1e-3 max abs)."""

    @pytest.mark.parametrize("b,h,hkv,s,d,window,causal", [
        (1, 4, 4, 256, 64, 0, True),     # MHA causal
        (2, 8, 2, 256, 64, 0, True),     # GQA 4:1
        (2, 8, 1, 256, 64, 0, True),     # MQA
        (1, 4, 2, 200, 32, 0, True),     # padding path (pads 200 -> 256)
        (1, 4, 4, 256, 64, 64, True),    # sliding window
        (1, 4, 4, 200, 64, 100, True),   # window + padding
        (1, 2, 2, 200, 64, 0, False),    # non-causal + padded KV masking
        (1, 2, 2, 256, 64, 0, False),    # non-causal
    ])
    def test_grads_match_ref(self, b, h, hkv, s, d, window, causal):
        q, k, v = _qkv(b, h, hkv, s, d)
        t = jnp.asarray(RNG.normal(size=(b, h, s, d)).astype(np.float32))

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) * t)

        g_int = jax.grad(loss(lambda q, k, v: flash_ops.flash_attention(
            q, k, v, causal=causal, window=window, backend="interpret")),
            argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss(lambda q, k, v: flash_ref.flash_ref(
            q, k, v, causal=causal, window=window)),
            argnums=(0, 1, 2))(q, k, v)
        for name, a, b_ in zip("qkv", g_int, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=1e-3,
                err_msg=f"d{name} mismatch")

    def test_sm_scale_override(self):
        q, k, v = _qkv(1, 2, 2, 256, 64)
        scale = 0.05
        f_int = lambda q, k, v: jnp.sum(flash_ops.flash_attention(
            q, k, v, sm_scale=scale, backend="interpret") ** 2)
        f_ref = lambda q, k, v: jnp.sum(flash_ref.flash_ref(
            q, k, v, sm_scale=scale) ** 2)
        g_int = jax.grad(f_int, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_int, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=1e-3)

    def test_fwd_stats_consistent_with_output(self):
        """o == (exp-weighted V) / l with the saved (m, l): the backward's
        recompute contract."""
        b, h, s, d = 1, 2, 256, 64
        q, k, v = _qkv(b, h, h, s, d)
        o, m, l = flash_kernel.flash_attention_fwd_pallas(
            q.reshape(b * h, s, d), k.reshape(b * h, s, d),
            v.reshape(b * h, s, d), interpret=True)
        lse = np.asarray(m) + np.log(np.maximum(np.asarray(l), 1e-30))
        logits = np.einsum("hqd,hkd->hqk", np.asarray(q[0]),
                           np.asarray(k[0])) * d ** -0.5
        mask = np.tril(np.ones((s, s), bool))
        p = np.where(mask, np.exp(logits - lse[:, :, None]), 0.0)
        o_rec = np.einsum("hqk,hkd->hqd", p, np.asarray(v[0]))
        np.testing.assert_allclose(np.asarray(o), o_rec, atol=2e-5)


class TestDispatchGuards:
    """``backend="pallas"`` runs the compiled kernel or raises: it never
    hands back the ref result in its place."""

    def test_pallas_head_dim_falls_back_with_warning(self):
        # head_dim 32 lowers for the chip (tests/test_tpu_compile.py); on
        # a host without one the compiled backend must fail loudly
        # instead of quietly returning the ref output
        q, k, v = _qkv(1, 2, 2, 256, 32)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            with pytest.raises(ValueError, match="interpret"):
                flash_ops.flash_attention(q, k, v, backend="pallas")
        assert not [x for x in w if "falling back" in str(x.message)]

    def test_pallas_short_seq_falls_back_with_warning(self):
        q, k, v = _qkv(1, 2, 2, 40, 64)           # s=40 < one 128 block
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            with pytest.raises(ValueError, match="interpret"):
                flash_ops.flash_attention(q, k, v, backend="pallas")
        assert not [x for x in w if "falling back" in str(x.message)]

    def test_interpret_not_restricted(self):
        """The interpreter runs the shapes the old guard refused (head_dim
        32, a sub-block sequence) and agrees with ref."""
        for s in (256, 40):
            q, k, v = _qkv(1, 2, 2, s, 32)
            out = flash_ops.flash_attention(q, k, v, backend="interpret")
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(flash_ref.flash_ref(q, k, v)),
                atol=2e-5)

    def test_gqa_indivisible_raises_on_every_backend(self):
        """n_heads % n_kv != 0 is an invalid GQA input everywhere (even
        the ref path groups query heads over KV heads) — a clear error
        naming the shapes, not an opaque reshape crash."""
        q, k, v = _qkv(1, 6, 4, 256, 64)
        for backend in ("ref", "interpret", "pallas"):
            with pytest.raises(ValueError, match="n_heads=6"):
                flash_ops.flash_attention(q, k, v, backend=backend)

    def test_unknown_backend_raises(self):
        q, k, v = _qkv(1, 2, 2, 256, 64)
        with pytest.raises(ValueError, match="unknown backend"):
            flash_ops.flash_attention(q, k, v, backend="mosaic")


class TestEndToEnd:
    def test_block_grads_match_jnp_backend(self):
        """One transformer stack: grads through attn_backend='interpret'
        (Pallas custom_vjp backward) vs 'jnp' (autodiff)."""
        cfg = configs.smoke_config("llama3-8b")
        cfg_flash = dc.replace(cfg, attn_backend="interpret")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        batch = {
            "tokens": jnp.asarray(RNG.integers(0, cfg.vocab, (2, 32)),
                                  jnp.int32),
            "labels": jnp.asarray(RNG.integers(0, cfg.vocab, (2, 32)),
                                  jnp.int32),
        }
        g_jnp = jax.grad(lambda p: transformer.loss_fn(
            p, cfg, batch)[0])(params)
        g_fla = jax.grad(lambda p: transformer.loss_fn(
            p, cfg_flash, batch)[0])(params)
        for a, b in zip(jax.tree_util.tree_leaves(g_jnp),
                        jax.tree_util.tree_leaves(g_fla)):
            scale = float(jnp.abs(a).max()) + 1e-9
            assert float(jnp.abs(a - b).max()) / scale < 1e-3

    def test_pallas_backend_differentiable_abstractly(self):
        """Regression: jax.grad through attn_backend='pallas' must trace
        (the custom_vjp covers the backward; before ISSUE 2 this raised).
        eval_shape never lowers to Mosaic, so it runs on any host.
        head_dim is pinned to 64, the width of the production heads' MXU
        tiles."""
        cfg = dc.replace(configs.smoke_config("llama3-8b"),
                         attn_backend="pallas", n_layers=1, head_dim=64)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        batch = {
            "tokens": jax.ShapeDtypeStruct((1, 128), jnp.int32),
            "labels": jax.ShapeDtypeStruct((1, 128), jnp.int32),
        }
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            grads = jax.eval_shape(
                jax.grad(lambda p, b: transformer.loss_fn(p, cfg, b)[0]),
                params, batch)
        assert not [x for x in w if "falling back" in str(x.message)], \
            "pallas path fell back to ref — the custom_vjp was not traced"
        leaves = jax.tree_util.tree_leaves(grads)
        assert leaves and all(x.shape is not None for x in leaves)

    def test_flash_residuals_are_subquadratic(self):
        """vjp residual bytes: custom_vjp path must beat jnp autodiff of
        the reference (which stores the S^2 probability matrix)."""
        b, h, s, d = 1, 4, 1024, 64
        sds = [jax.ShapeDtypeStruct((b, h, s, d), jnp.float32)] * 3

        def resid_bytes(fn):
            out = jax.eval_shape(lambda q, k, v: jax.vjp(fn, q, k, v), *sds)
            return sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(out))

        flash = resid_bytes(lambda q, k, v: flash_ops.flash_attention(
            q, k, v, backend="interpret"))
        jnp_path = resid_bytes(lambda q, k, v: flash_ref.flash_ref(q, k, v))
        assert flash < jnp_path / 2, (flash, jnp_path)
