"""Sharding rules, collectives, optimizer, compression — distributed layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed import sharding as shd
from repro.distributed.collectives import sp_decode_attention
from repro.models import transformer
from repro.optim import adamw, compression


def _mesh11():
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"))


class TestParamSpecs:
    @pytest.mark.parametrize("arch", configs.list_archs())
    def test_specs_cover_tree_and_axes_valid(self, arch):
        cfg = configs.get_config(arch)
        sds = jax.eval_shape(
            lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
        specs = shd.param_specs(cfg, sds)
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        flat_p = jax.tree_util.tree_leaves(sds)
        assert len(flat_s) == len(flat_p)
        for spec, leaf in zip(flat_s, flat_p):
            assert len(spec) <= len(leaf.shape)
            # any model-sharded dim must divide by 16 (production TP width)
            for ax, name in zip(range(len(spec)), spec):
                if name == "model":
                    assert leaf.shape[ax] % 16 == 0, (spec, leaf.shape)

    def test_ssm_params_replicated(self):
        cfg = configs.get_config("mamba2-130m")
        sds = jax.eval_shape(
            lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
        specs = shd.param_specs(cfg, sds)
        for s in jax.tree_util.tree_leaves(
                specs["blocks"]["ssm"], is_leaf=lambda x: isinstance(x, P)):
            assert s == P()


class TestAdamW:
    def test_converges_on_quadratic(self):
        cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                                total_steps=200, grad_clip=0)
        params = {"w": jnp.array([5.0, -3.0])}
        state = adamw.init(params)
        for _ in range(150):
            g = {"w": 2 * params["w"]}
            params, state, _ = adamw.update(cfg, g, state, params)
        assert float(jnp.abs(params["w"]).max()) < 0.3

    def test_skip_freezes_state(self):
        cfg = adamw.AdamWConfig(lr=0.1)
        params = {"w": jnp.ones((2,))}
        state = adamw.init(params)
        p2, s2, _ = adamw.update(cfg, {"w": jnp.ones((2,))}, state, params,
                                 skip=jnp.bool_(True))
        np.testing.assert_array_equal(np.asarray(p2["w"]),
                                      np.asarray(params["w"]))
        assert int(s2.count) == 0

    def test_grad_clip(self):
        cfg = adamw.AdamWConfig(lr=1e-3, grad_clip=1.0)
        params = {"w": jnp.ones((4,))}
        _, _, m = adamw.update(cfg, {"w": jnp.full((4,), 100.0)},
                               adamw.init(params), params)
        assert float(m["grad_norm"]) == pytest.approx(200.0)


class TestCompression:
    def test_int8_unbiased_roundtrip(self):
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (512,)) * 3
        outs = []
        for i in range(50):
            q, s = compression.quantize_int8(x, jax.random.PRNGKey(i))
            outs.append(compression.dequantize_int8(q, s))
        err = np.abs(np.mean(outs, axis=0) - np.asarray(x))
        assert err.max() < 0.05  # stochastic rounding -> unbiased mean

    def test_error_feedback_reduces_bias(self):
        grads = {"w": jnp.linspace(-1, 1, 256)}
        res = None
        recon_sum = jnp.zeros((256,))
        for i in range(20):
            payload, res = compression.compress_with_feedback(
                grads, res, jax.random.PRNGKey(i), codec="int8")
            recon_sum += compression.dequantize_int8(*payload["w"])
        # cumulative reconstruction tracks cumulative true grads
        np.testing.assert_allclose(np.asarray(recon_sum) / 20,
                                   np.asarray(grads["w"]), atol=0.02)

    def test_topk_payload_smaller(self):
        grads = {"w": jnp.ones((1000,))}
        payload, _ = compression.compress_with_feedback(
            grads, None, jax.random.PRNGKey(0), codec="topk", topk_frac=0.01)
        assert compression.payload_bytes(payload) < 1000 * 4 * 0.05


class TestSPDecodeAttention:
    def test_matches_plain_softmax(self):
        mesh = _mesh11()
        b, h, s, d = 2, 4, 64, 16
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
        bias = jnp.where(jnp.arange(s)[None] < 40, 0.0, -1e30)
        bias = jnp.broadcast_to(bias, (b, s)).astype(jnp.float32)
        out = sp_decode_attention(q, k, v, bias, mesh, sm_scale=d ** -0.5)
        logits = jnp.einsum("bhd,bhsd->bhs", q, k) * d ** -0.5 + bias[:, None]
        p = jax.nn.softmax(logits, -1)
        ref = jnp.einsum("bhs,bhsd->bhd", p, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


class TestCacheSpecs:
    def test_decode32k_shards_batch_and_sequence(self):
        cfg = configs.get_config("llama3-8b")
        cache = jax.eval_shape(
            lambda: transformer.init_cache(cfg, 128, 1024, quantized=True))
        from jax.sharding import AbstractMesh
        mesh = AbstractMesh((16, 16), ("data", "model"))
        specs = shd.cache_specs(cfg, cache, mesh)
        assert specs["k"][1] == "data"     # batch over DP
        # llama3 kv=8 heads don't divide model=16 -> sequence over model
        assert specs["k"][3] == "model"

    def test_long500k_shards_sequence(self):
        cfg = configs.get_config("hymba-1.5b")
        cache = jax.eval_shape(
            lambda: transformer.init_cache(cfg, 1, 2048, quantized=True))
        from jax.sharding import AbstractMesh
        mesh = AbstractMesh((16, 16), ("data", "model"))
        specs = shd.cache_specs(cfg, cache, mesh)
        assert specs["k"][3] == ("data", "model")  # sequence sharded
