"""Mesh-parallel hot paths: sharding rules, per-device budgets, and
multi-device parity.

Rule tests run on abstract meshes (any device count).  Parity tests need 8
real devices — CI provides them via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; on a bare 1-device
checkout they skip.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import configs
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.models import transformer

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")

MESH_SHAPES = [(1, 1), (2, 1), (1, 2), (2, 4), (1, 8)]


def _mesh8(shape=(1, 8)):
    return make_mesh(shape, ("data", "model"))


# -- sharding rules (abstract meshes, run everywhere) ----------------------
class TestParamSpecsOnMesh:
    @pytest.mark.parametrize("arch", configs.list_archs())
    @pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
    def test_every_config_resolves_to_valid_specs(self, arch, mesh_shape):
        """The divisibility fallback makes the production rule table legal
        on ANY mesh: every 'model'-sharded dim divides the model axis."""
        cfg = configs.get_config(arch)
        mesh = AbstractMesh(mesh_shape, ("data", "model"))
        sds = jax.eval_shape(
            lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
        specs = shd.param_specs(cfg, sds, mesh=mesh)
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        flat_p = jax.tree_util.tree_leaves(sds)
        assert len(flat_s) == len(flat_p)
        n_model = mesh.shape["model"]
        for spec, leaf in zip(flat_s, flat_p):
            for ax, name in enumerate(spec):
                if name == "model":
                    assert leaf.shape[ax] % n_model == 0, (spec, leaf.shape)

    def test_fallback_replicates_non_dividing_dims(self):
        """llama3 kv projection: n_kv * head_dim = 1024 divides 2 but the
        smoke config's 64 does not divide e.g. 48 — pick a width that
        forces the fallback and check the raw rule still shards."""
        cfg = configs.get_config("llama3-8b")
        sds = jax.eval_shape(
            lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
        raw = shd.param_specs(cfg, sds)
        # production rules shard wk's last dim
        assert raw["blocks"]["attn"]["wk"][-1] == "model"
        odd = AbstractMesh((1, 3), ("data", "model"))
        fitted = shd.param_specs(cfg, sds, mesh=odd)
        wk_dim = sds["blocks"]["attn"]["wk"].shape[-1]
        expect = "model" if wk_dim % 3 == 0 else None
        assert fitted["blocks"]["attn"]["wk"][-1] == expect

    @pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
    def test_ssm_replicated_on_every_mesh(self, mesh_shape):
        cfg = configs.get_config("mamba2-130m")
        mesh = AbstractMesh(mesh_shape, ("data", "model"))
        sds = jax.eval_shape(
            lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
        specs = shd.param_specs(cfg, sds, mesh=mesh)
        for s in jax.tree_util.tree_leaves(
                specs["blocks"]["ssm"], is_leaf=lambda x: isinstance(x, P)):
            assert s == P()


class TestFlashShardSpecs:
    def test_trivial_mesh_opts_out(self):
        assert shd.flash_shard_specs(None, 8, 8, 8) is None
        mesh = AbstractMesh((1, 1), ("data", "model"))
        assert shd.flash_shard_specs(mesh, 8, 8, 8) is None

    def test_heads_and_batch_shard_when_divisible(self):
        mesh = AbstractMesh((2, 4), ("data", "model"))
        spec = shd.flash_shard_specs(mesh, batch=8, heads=8, kv_heads=4)
        assert spec == P("data", "model", None, None)

    def test_gqa_misaligned_heads_fall_back_to_batch(self):
        # kv_heads=2 doesn't divide model=4: head sharding would split a
        # GQA group across shards, so only the batch axis shards
        mesh = AbstractMesh((2, 4), ("data", "model"))
        spec = shd.flash_shard_specs(mesh, batch=8, heads=8, kv_heads=2)
        assert spec == P("data", None, None, None)

    def test_nothing_divides_means_none(self):
        mesh = AbstractMesh((2, 4), ("data", "model"))
        assert shd.flash_shard_specs(mesh, batch=3, heads=6, kv_heads=3) \
            is None


class TestServeKvShard:
    def test_mode_table(self):
        mesh = AbstractMesh((1, 8), ("data", "model"))
        assert shd.serve_kv_shard(None, 8, 64) == "none"
        assert shd.serve_kv_shard(
            AbstractMesh((8, 1), ("data", "model")), 8, 64) == "none"
        assert shd.serve_kv_shard(mesh, 8, 64) == "heads"
        assert shd.serve_kv_shard(mesh, 2, 64) == "seq"   # hkv fallback
        assert shd.serve_kv_shard(mesh, 2, 63) == "none"  # nothing divides

    @pytest.mark.parametrize("arch", configs.list_archs())
    @pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
    def test_cache_specs_follow_the_rule(self, arch, mesh_shape):
        """serve_cache_specs must agree with serve_kv_shard for every
        config, and the slot axis must never shard."""
        cfg = configs.get_config(arch)
        mesh = AbstractMesh(mesh_shape, ("data", "model"))
        cache = jax.eval_shape(
            lambda: transformer.init_cache(cfg, 4, 128, quantized=True))
        specs = shd.serve_cache_specs(cfg, cache, mesh)
        for name, spec in specs.items():
            leaf = cache[name]
            # slot (batch) axis is never sharded
            if len(leaf.shape) >= 2:
                assert len(spec) < 2 or spec[1] is None, (name, spec)
            if name in ("k", "v") and len(leaf.shape) == 5:
                mode = shd.serve_kv_shard(mesh, leaf.shape[2], leaf.shape[3])
                want = {"heads": P(None, None, "model", None, None),
                        "seq": P(None, None, None, "model", None),
                        "none": P()}[mode]
                assert spec == want, (name, mode, spec)
            elif name not in ("k_scale", "v_scale"):
                assert spec == P(), (name, spec)

    def test_spec_shards_counts_devices(self):
        mesh = AbstractMesh((2, 4), ("data", "model"))
        assert shd.spec_shards(mesh, P()) == 1
        assert shd.spec_shards(mesh, P(None, "model")) == 4
        assert shd.spec_shards(mesh, P("data", "model")) == 8
        assert shd.spec_shards(mesh, P(("data", "model"))) == 8


# -- per-device planner budgets (abstract, run everywhere) -----------------
class TestPerDeviceBudgets:
    def test_attn_residuals_divide_by_model_shards(self):
        from repro.plan import profile_transformer
        cfg = configs.smoke_config("llama3-8b")
        sds = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        p1 = profile_transformer(cfg, sds, dtype_bytes=4)
        p2 = profile_transformer(cfg, sds, dtype_bytes=4, model_shards=2)
        # smoke llama3: heads=4, kv=2 — both divide 2, residuals halve
        assert all(b2 * 2 == b1 for b1, b2 in
                   zip(p1.resid_bytes, p2.resid_bytes))
        # the (B, S, D) carry is replicated over model: NOT divided
        assert p1.act_bytes == p2.act_bytes

    def test_non_dividing_heads_keep_whole_residuals(self):
        from repro.plan import profile_transformer
        cfg = configs.smoke_config("llama3-8b")   # kv=2 doesn't divide 8
        sds = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        p1 = profile_transformer(cfg, sds, dtype_bytes=4)
        p8 = profile_transformer(cfg, sds, dtype_bytes=4, model_shards=8)
        assert p1.resid_bytes == p8.resid_bytes

    def test_serve_capacity_scales_with_devices(self):
        """Acceptance: per-device slot capacity x devices >= single-device
        capacity — sharding the cache can only admit MORE total slots."""
        from repro.plan import serve_capacity_report
        cfg = configs.get_config("llama3-8b")
        budget = 8 * 2 ** 30
        r1 = serve_capacity_report(cfg, 4096, budget)
        mesh = AbstractMesh((1, 8), ("data", "model"))
        r8 = serve_capacity_report(cfg, 4096, budget, mesh=mesh)
        assert r8["kv_shard"] == "heads" and r8["model_shards"] == 8
        assert r8["bytes_per_slot_per_device"] * 8 >= r8["bytes_per_slot"]
        assert r8["max_slots"] >= r1["max_slots"]
        # same per-chip budget, 1/8th the bytes pinned per chip per slot
        assert r8["bytes_per_slot_per_device"] <= r1["bytes_per_slot"] // 4

    def test_plan_profile_threads_model_shards(self):
        from repro.train.train_step import TrainConfig, plan_profile
        cfg = configs.smoke_config("llama3-8b")
        sds = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        tc = TrainConfig(policy="full")
        mesh = AbstractMesh((4, 2), ("data", "model"))
        p1 = plan_profile(cfg, tc, sds)
        p2 = plan_profile(cfg, tc, sds, mesh=mesh)
        # microbatch 8/4 dp + residuals /2 model: strictly smaller profile
        assert p2.total_resid_bytes() < p1.total_resid_bytes()


# -- multi-device parity (8 emulated devices) ------------------------------
@multidevice
class TestTrainParity:
    def test_flash_train_grads_match_single_device(self):
        """Loss and grads on a (4, 2) mesh match the (1, 1) mesh — the
        shard_map'd flash path under remat + scan + grad is exact."""
        from repro.core.mixed_precision import get_policy
        from repro.train import train_step as ts
        cfg = dataclasses.replace(configs.smoke_config("llama3-8b"),
                                  attn_backend="interpret")
        b, s = 8, 64
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(
                     rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
                 "labels": jnp.asarray(
                     rng.integers(0, cfg.vocab, (b, s)), jnp.int32)}
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        tc = ts.TrainConfig(policy="full")
        pol = get_policy("full")

        def grads_for(mesh):
            def loss(p, mb):
                return transformer.loss_fn(p, cfg, mb, policy=pol,
                                           remat=tc.remat, mesh=mesh)[0]
            p_shard = shd.to_shardings(
                mesh, shd.param_specs(cfg, params, mesh=mesh))
            b_shard = shd.to_shardings(mesh, shd.batch_specs(cfg, batch,
                                                             mesh))
            pp = jax.device_put(params, p_shard)
            bb = jax.device_put(batch, b_shard)
            return jax.jit(jax.value_and_grad(loss),
                           in_shardings=(p_shard, b_shard))(pp, bb)

        l1, g1 = grads_for(make_mesh((1, 1), ("data", "model")))
        l8, g8 = grads_for(make_mesh((4, 2), ("data", "model")))
        assert abs(float(l1) - float(l8)) < 1e-4
        g1, g8 = jax.device_get(g1), jax.device_get(g8)
        diffs = jax.tree_util.tree_map(
            lambda a, b_: float(np.abs(a - b_).max()), g1, g8)
        assert max(jax.tree_util.tree_leaves(diffs)) < 1e-3


@multidevice
class TestServeParity:
    def _trace(self):
        from repro.serve.trace import TraceRequest
        rng = np.random.default_rng(0)
        lens = [(5, 0), (9, 0), (13, 2), (3, 4), (7, 5)]
        return [TraceRequest(prompt=list(rng.integers(1, 200, (pl,))),
                             max_new_tokens=6, arrival_step=st)
                for pl, st in lens]

    def _run(self, cfg, mesh):
        from repro.serve.engine import ServeEngine
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(params, cfg, max_slots=4, max_len=64,
                          prompt_buckets=(8, 16), policy_name="full",
                          mesh=mesh)
        compiles = eng.warmup()
        eng.run(self._trace())
        assert eng.compile_counts() == compiles, "recompile during serving"
        return eng, {r.rid: list(r.tokens) for r in eng._requests_done}

    def _assert_no_cache_gather(self, eng):
        import re
        hlo = eng.decode_hlo()
        k = eng.pool.cache["k"]
        # the smallest gather that could materialize a whole per-layer
        # K slice
        thresh = k.shape[1] * k.shape[2] * k.shape[3] * k.shape[4]
        sizes = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2,
                 "s8": 1, "u8": 1, "pred": 1}
        bad = []
        for m in re.finditer(r"(\w+)\[([\d,]*)\][^=]*= \S*all-gather", hlo):
            dims = m.group(2)
            n = int(np.prod([int(x) for x in dims.split(",") if x])) \
                if dims else 1
            if sizes.get(m.group(1), 4) * n >= thresh:
                bad.append(m.group(0)[:120])
        assert not bad, bad

    def test_heads_sharded_engine_token_exact(self):
        cfg = dataclasses.replace(configs.smoke_config("llama3-8b"),
                                  n_heads=8, n_kv=8, window=0)
        mesh = _mesh8()
        assert shd.serve_kv_shard(mesh, cfg.n_kv, 64) == "heads"
        _, t1 = self._run(cfg, None)
        eng, t8 = self._run(cfg, mesh)
        assert t1 == t8
        self._assert_no_cache_gather(eng)

    def test_seq_sharded_engine_token_exact(self):
        cfg = dataclasses.replace(configs.smoke_config("llama3-8b"),
                                  window=0)
        mesh = _mesh8()
        assert shd.serve_kv_shard(mesh, cfg.n_kv, 64) == "seq"
        _, t1 = self._run(cfg, None)
        eng, t8 = self._run(cfg, mesh)
        assert t1 == t8
        self._assert_no_cache_gather(eng)


@multidevice
class TestSeqShardedDecodeCollective:
    def _setup(self):
        from repro.kernels.kvq import ref as kvq_ref
        b, h, hkv, s, d = 3, 4, 2, 64, 16
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
        kq, ks = kvq_ref.quantize_kv(k)
        vq, vs = kvq_ref.quantize_kv(v)
        kn = jnp.asarray(rng.normal(size=(b, hkv, d)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(b, hkv, d)), jnp.float32)
        kqn, ksn = kvq_ref.quantize_kv(kn)
        vqn, vsn = kvq_ref.quantize_kv(vn)
        write_at = jnp.asarray([5, 17, 40], jnp.int32)

        def wr(c, n, at):
            return jax.vmap(lambda cc, nn, a: jax.lax.dynamic_update_slice(
                cc, nn[:, None], (0, a, 0)[:cc.ndim]))(c, n, at)

        ck, csk = wr(kq, kqn, write_at), wr(ks, ksn, write_at)
        cv, csv = wr(vq, vqn, write_at), wr(vs, vsn, write_at)
        ref = kvq_ref.decode_attention_ref(
            q.reshape(b, hkv, h // hkv, d), ck, csk, cv, csv, None,
            d ** -0.5, lengths=write_at + 1).reshape(b, h, d)
        return (q, kq, ks, vq, vs, (kqn, ksn, vqn, vsn), write_at, d,
                (ck, csk, cv, csv), ref)

    def test_lengths_path_matches_oracle(self):
        from repro.distributed import collectives
        (q, kq, ks, vq, vs, new, at, d, written, ref) = self._setup()
        out, ck, csk, cv, csv = collectives.sp_decode_attention_int8(
            q, kq, ks, vq, vs, new, at, _mesh8(), sm_scale=d ** -0.5,
            lengths=at + 1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        # the sharded in-place write produced the same cache
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(written[0]))
        np.testing.assert_array_equal(np.asarray(csv),
                                      np.asarray(written[3]))

    def test_bias_path_matches_oracle(self):
        from repro.distributed import collectives
        (q, kq, ks, vq, vs, new, at, d, _w, ref) = self._setup()
        s = kq.shape[2]
        bias = jnp.where(jnp.arange(s)[None, :] < (at + 1)[:, None],
                         0.0, -1e30).astype(jnp.float32)
        out, *_ = collectives.sp_decode_attention_int8(
            q, kq, ks, vq, vs, new, at, _mesh8(), sm_scale=d ** -0.5,
            bias=bias)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


@multidevice
class TestCompressedPsumGrads:
    def _grads(self):
        rng = np.random.default_rng(3)
        # realistic post-backward magnitudes: int8 quantization noise on
        # N(0,1)-scale grads would swamp the 1e-2 parity bound
        return {"w": jnp.asarray(rng.normal(size=(8, 32, 16)) * 0.4,
                                 jnp.float32),
                "b": jnp.asarray(rng.normal(size=(8, 16)) * 0.4,
                                 jnp.float32)}

    def test_matches_plain_psum_mean(self):
        from repro.distributed import collectives
        g = self._grads()
        mesh = make_mesh((8, 1), ("data", "model"))
        out = jax.device_get(collectives.compressed_psum_grads(
            g, mesh, "data", jax.random.PRNGKey(0)))
        plain = jax.device_get(
            jax.tree_util.tree_map(lambda x: x.mean(0), g))
        diffs = jax.tree_util.tree_map(
            lambda a, b: float(np.abs(a - b).max()), out, plain)
        assert max(jax.tree_util.tree_leaves(diffs)) < 1e-2

    def test_unbiased_over_seeds(self):
        from repro.distributed import collectives
        g = self._grads()
        mesh = make_mesh((8, 1), ("data", "model"))
        plain = jax.device_get(
            jax.tree_util.tree_map(lambda x: x.mean(0), g))
        acc = None
        n = 30
        for i in range(n):
            o = jax.device_get(collectives.compressed_psum_grads(
                g, mesh, "data", jax.random.PRNGKey(i)))
            acc = o if acc is None else \
                jax.tree_util.tree_map(np.add, acc, o)
        errs = jax.tree_util.tree_map(
            lambda a, p: float(np.abs(a / n - p).max()), acc, plain)
        assert max(jax.tree_util.tree_leaves(errs)) < 2e-3
