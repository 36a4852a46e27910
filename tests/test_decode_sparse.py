"""Split-K flash decode over the int8 KV cache (ISSUE 4): parity vs the
ref oracle across ragged lengths / GQA / MQA / window-sized caches and
split counts, the split-K merge oracle, measured-vs-analytic tile-step
counters, the >=70% ragged skip-ratio acceptance, the no-bias jaxpr
contract on every backend, decode_step integration, the planner's decode
report vs measured counts, and the serve CLI flags."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, plan
from repro.kernels import tiling
from repro.kernels.kvq import kernel as DK, ops as DO, ref as DR
from repro.models import transformer

RNG = np.random.default_rng(11)


def _cache(b, hkv, s, d):
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(np.float32))
    kq, ks = DR.quantize_kv(k)
    vq, vs = DR.quantize_kv(v)
    return kq, ks, vq, vs


def _q(b, h, d):
    return jnp.asarray(RNG.normal(size=(b, h, d)).astype(np.float32))


# (b, h, hkv, s, d, splits, block_s) — MHA/GQA/MQA, ragged tile counts,
# split counts that don't divide the tile count, splits > tiles (clamped),
# and a window-sized cache (s == W, the planner's windowed geometry)
CASES = [
    (1, 4, 4, 512, 64, 1, 512),       # MHA, sequential baseline
    (2, 8, 2, 1024, 64, 4, 256),      # GQA 4:1, even split
    (2, 8, 1, 512, 128, 2, 128),      # MQA
    (3, 6, 2, 768, 32, 3, 256),       # odd batch, ns == splits
    (2, 8, 2, 768, 64, 2, 256),       # splits don't divide ns (3 tiles)
    (2, 4, 2, 256, 16, 8, 64),        # splits > ns -> clamped
    (2, 4, 2, 256, 64, 2, 128),       # a cache as long as the window
    (1, 4, 2, 2048, 64, 3, 512),      # ns=4, splits=3 -> empty last shard
]


class TestSplitKParity:
    @pytest.mark.parametrize("b,h,hkv,s,d,splits,bs", CASES)
    def test_ragged_lengths_match_ref(self, b, h, hkv, s, d, splits, bs):
        q = _q(b, h, d)
        kq, ks, vq, vs = _cache(b, hkv, s, d)
        lengths = jnp.asarray(RNG.integers(1, s + 1, (b,)), jnp.int32)
        o_ref = DO.decode_attention(q, kq, ks, vq, vs, lengths=lengths,
                                    backend="ref")
        o_int = DO.decode_attention(q, kq, ks, vq, vs, lengths=lengths,
                                    backend="interpret", splits=splits,
                                    block_s=bs)
        np.testing.assert_allclose(np.asarray(o_int), np.asarray(o_ref),
                                   atol=1e-3)

    @pytest.mark.parametrize("b,h,hkv,s,d,splits,bs", CASES)
    def test_splitk_oracle_matches_ref(self, b, h, hkv, s, d, splits, bs):
        """The pure-jnp split/merge oracle must agree with the one-shot
        softmax — the merge arithmetic has its own ground truth."""
        q = _q(b, h, d)
        kq, ks, vq, vs = _cache(b, hkv, s, d)
        lengths = jnp.asarray(RNG.integers(1, s + 1, (b,)), jnp.int32)
        g = h // hkv
        qg = q.astype(jnp.float32).reshape(b, hkv, g, d)
        o_ref = DR.decode_attention_ref(qg, kq, ks, vq, vs, None, d ** -0.5,
                                        lengths=lengths)
        o_sk = DR.decode_attention_splitk_ref(qg, kq, ks, vq, vs, d ** -0.5,
                                              lengths=lengths, block_s=bs,
                                              splits=splits)
        np.testing.assert_allclose(np.asarray(o_sk), np.asarray(o_ref),
                                   atol=1e-4)

    def test_no_mask_and_bias_paths_with_splits(self):
        b, h, hkv, s, d = 2, 4, 2, 512, 64
        q = _q(b, h, d)
        kq, ks, vq, vs = _cache(b, hkv, s, d)
        o_ref = DO.decode_attention(q, kq, ks, vq, vs, backend="ref")
        o_int = DO.decode_attention(q, kq, ks, vq, vs, backend="interpret",
                                    splits=4, block_s=128)
        np.testing.assert_allclose(np.asarray(o_int), np.asarray(o_ref),
                                   atol=1e-3)
        # dense-bias fallback (masks lengths can't express) on the split grid
        bias = jnp.where(jnp.arange(s)[None, :] % 3 != 0, 0.0, -1e30
                         ).astype(jnp.float32)
        bias = jnp.broadcast_to(bias, (b, s))
        o_ref = DO.decode_attention(q, kq, ks, vq, vs, bias=bias,
                                    backend="ref")
        o_int = DO.decode_attention(q, kq, ks, vq, vs, bias=bias,
                                    backend="interpret", splits=2,
                                    block_s=256)
        np.testing.assert_allclose(np.asarray(o_int), np.asarray(o_ref),
                                   atol=1e-3)

    def test_lengths_and_bias_are_exclusive(self):
        b, h, hkv, s, d = 1, 2, 2, 256, 16
        q = _q(b, h, d)
        kq, ks, vq, vs = _cache(b, hkv, s, d)
        with pytest.raises(ValueError, match="exclusive"):
            DO.decode_attention(q, kq, ks, vq, vs,
                                lengths=jnp.ones((b,), jnp.int32),
                                bias=jnp.zeros((b, s)))
        with pytest.raises(ValueError, match="debug_counts"):
            DO.decode_attention(q, kq, ks, vq, vs, backend="ref",
                                debug_counts=True)


def _stacked_cache(n_layers, b, hkv, s, d):
    """An int8 cache of ``n_layers`` layers, each with data of its own."""
    leaves = [_cache(b, hkv, s, d) for _ in range(n_layers)]
    return tuple(jnp.stack(xs) for xs in zip(*leaves))


class TestStackedCache:
    """The kernel reads one layer of a stacked (L, B, Hkv, S, D) cache
    where it lies: the layer it is given, and no other."""

    @pytest.mark.parametrize("layer", [0, 2])
    @pytest.mark.parametrize("splits", [1, 4])
    def test_layer_matches_ref(self, layer, splits):
        b, h, hkv, s, d, bs = 3, 8, 2, 1024, 64, 128
        q = _q(b, h, d)
        kq, ks, vq, vs = _stacked_cache(3, b, hkv, s, d)
        lengths = jnp.asarray([1, s, int(RNG.integers(2, s))], jnp.int32)
        o_layer = DO.decode_attention(
            q, kq[layer], ks[layer], vq[layer], vs[layer], lengths=lengths,
            backend="ref")
        o_ref = DO.decode_attention(q, kq, ks, vq, vs, layer=layer,
                                    lengths=lengths, backend="ref")
        np.testing.assert_array_equal(np.asarray(o_ref), np.asarray(o_layer))
        o_int = DO.decode_attention(q, kq, ks, vq, vs, layer=layer,
                                    lengths=lengths, backend="interpret",
                                    splits=splits, block_s=bs)
        np.testing.assert_allclose(np.asarray(o_int), np.asarray(o_layer),
                                   atol=1e-3)
        other = DO.decode_attention(
            q, kq[1], ks[1], vq[1], vs[1], lengths=lengths, backend="ref")
        assert np.abs(np.asarray(o_int) - np.asarray(other)).max() > 1e-2

    @pytest.mark.parametrize("layer", [0, 2])
    @pytest.mark.parametrize("splits", [1, 4])
    def test_counters_match_analytic(self, layer, splits):
        s, bs, lengths = 1024, 128, [1, 1024, 300]
        b, hkv, d = len(lengths), 2, 32
        kq, ks, vq, vs = _stacked_cache(3, b, hkv, s, d)
        _, cnt = DO.decode_attention(
            _q(b, 2 * hkv, d), kq, ks, vq, vs, layer=layer,
            lengths=jnp.asarray(lengths), backend="interpret",
            splits=splits, block_s=bs, debug_counts=True)
        c = tiling.decode_tile_step_counts(s, lengths, block_s=bs,
                                           splits=splits)
        for j in range(hkv):
            np.testing.assert_array_equal(np.asarray(cnt)[:, j],
                                          np.asarray(c["counts"]))

    @pytest.mark.parametrize("mask", ["lengths", "bias", "none"])
    def test_unit_layer_axis_is_the_per_layer_form(self, mask):
        """A per-layer cache reads the same through ``decode_attention``
        as its ``cache[None]`` at layer 0 does through the kernel."""
        b, h, hkv, s, d = 2, 4, 2, 512, 64
        q = _q(b, h, d)
        cache = _cache(b, hkv, s, d)
        kw = {}
        if mask == "lengths":
            kw["lengths"] = jnp.asarray([7, s], jnp.int32)
        elif mask == "bias":
            kw["bias"] = jnp.broadcast_to(jnp.where(
                jnp.arange(s) % 3 != 0, 0.0, -1e30).astype(jnp.float32),
                (b, s))
        o_layer = DO.decode_attention(q, *cache, backend="interpret",
                                      splits=2, block_s=128, **kw)
        qg = q.reshape(b, hkv, h // hkv, d)
        o_unit = DK.flash_decode_pallas(
            qg, *(c[None] for c in cache), kw.get("bias"), kw.get("lengths"),
            0, sm_scale=d ** -0.5, splits=2, block_s=128, interpret=True)
        np.testing.assert_array_equal(np.asarray(o_layer),
                                      np.asarray(o_unit).reshape(b, h, d))


class TestDecodeCounters:
    """Measured ``debug_counts`` == ``tiling.decode_tile_step_counts``,
    tile-for-tile per (batch row, split), identical across KV heads."""

    def _measure(self, s, lengths, *, splits, bs, b=None, hkv=2, d=32):
        b = len(lengths) if b is None else b
        q = _q(b, 2 * hkv, d)
        kq, ks, vq, vs = _cache(b, hkv, s, d)
        _, cnt = DO.decode_attention(
            q, kq, ks, vq, vs,
            lengths=None if lengths is None else jnp.asarray(lengths),
            backend="interpret", splits=splits, block_s=bs,
            debug_counts=True)
        return np.asarray(cnt)                         # (B, Hkv, splits)

    @pytest.mark.parametrize("s,lengths,splits,bs", [
        (512, [1, 512], 1, 512),
        (512, [100, 300, 512], 2, 128),
        (1024, [1000, 17], 4, 256),
        (768, [768, 700, 5], 3, 256),
        (512, None, 4, 128),                 # no lengths: every tile visited
        (256, [64, 256], 8, 64),             # splits clamped to ns=4
    ])
    def test_counters_match_analytic(self, s, lengths, splits, bs):
        b = 2 if lengths is None else len(lengths)
        cnt = self._measure(s, lengths, splits=splits, bs=bs, b=b)
        c = tiling.decode_tile_step_counts(s, lengths, block_s=bs,
                                           splits=splits)
        ana = np.asarray(c["counts"]) if lengths is not None else \
            np.broadcast_to(np.asarray(c["counts"]), (b, c["splits"]))
        assert cnt.shape == (b, cnt.shape[1], c["splits"])
        for i in range(b):
            for j in range(cnt.shape[1]):              # every KV head alike
                np.testing.assert_array_equal(cnt[i, j], ana[i])
        if lengths is None:
            assert int(cnt[0, 0].sum()) == c["ns"]     # dense sweep

    def test_ragged_mean_quarter_skips_70pct(self):
        """Acceptance: a ragged batch with mean length S/4 at S=2048 must
        execute <= 30% of the dense tile-steps."""
        s, bs = 2048, 256
        lengths = [256, 512, 512, 768]                 # mean 512 == S/4
        assert sum(lengths) * 4 == s * len(lengths)
        cnt = self._measure(s, lengths, splits=4, bs=bs)
        executed = int(cnt[:, 0].sum())                # per kv head
        dense = len(lengths) * (s // bs)
        assert executed / dense <= 0.30, (executed, dense)
        c = tiling.decode_tile_step_counts(s, lengths, block_s=bs, splits=4)
        assert executed == c["visited"]


class TestNoBiasMaterialization:
    """With ``lengths`` the decode path must never build a (B, S) f32
    tensor — on the ref backend, the kernel backends, and the
    non-quantized inline path alike (satellite: ALL backends)."""

    def test_ref_backend_jaxpr(self):
        b, h, hkv, s, d = 2, 4, 2, 256, 64
        q = jax.ShapeDtypeStruct((b, h, d), jnp.float32)
        kq = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.int8)
        sc = jax.ShapeDtypeStruct((b, hkv, s), jnp.float32)
        ln = jax.ShapeDtypeStruct((b,), jnp.int32)
        jaxpr = str(jax.make_jaxpr(
            lambda q, kq, ks, vq, vs, ln: DO.decode_attention(
                q, kq, ks, vq, vs, lengths=ln, backend="ref"))(
            q, kq, sc, kq, sc, ln))
        assert f"f32[{b},{s}]" not in jaxpr

    def test_interpret_backend_jaxpr(self):
        # b chosen != the kernel's per-tile group dim so the (B, S) pattern
        # can only match a genuinely materialized dense bias
        b, h, hkv, s, d = 3, 4, 2, 256, 64
        q = jax.ShapeDtypeStruct((b, h, d), jnp.float32)
        kq = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.int8)
        sc = jax.ShapeDtypeStruct((b, hkv, s), jnp.float32)
        ln = jax.ShapeDtypeStruct((b,), jnp.int32)
        jaxpr = str(jax.make_jaxpr(
            lambda q, kq, ks, vq, vs, ln: DO.decode_attention(
                q, kq, ks, vq, vs, lengths=ln, backend="interpret",
                splits=2))(q, kq, sc, kq, sc, ln))
        assert f"f32[{b},{s}]" not in jaxpr

    def test_attn_decode_unquantized_jaxpr(self):
        from repro.models import attention as attn
        cfg = configs.smoke_config("llama3-8b")
        b, s = 2, 96                 # s != d_model: no benign collisions
        d_model = cfg.d_model
        hkv, hd = cfg.n_kv, cfg.head_dim
        p = {k: jnp.zeros(sh) for k, sh in (
            ("wq", (d_model, cfg.n_heads * hd)),
            ("wk", (d_model, hkv * hd)), ("wv", (d_model, hkv * hd)),
            ("wo", (cfg.n_heads * hd, d_model)))}
        x = jax.ShapeDtypeStruct((b, d_model), jnp.float32)
        ck = jax.ShapeDtypeStruct((b, hkv, s, hd), jnp.bfloat16)
        cs = jax.ShapeDtypeStruct((b, hkv, s), jnp.float32)
        jaxpr = str(jax.make_jaxpr(
            lambda x, ck, cs, cv, csv: attn.attn_decode(
                p, x, cfg, ck, cs, cv, csv, jnp.int32(5), window=0,
                quantized=False))(x, ck, cs, ck, cs))
        assert f"f32[{b},{s}]" not in jaxpr


class TestDecodeStepIntegration:
    """The serve path end-to-end: decode_step on interpret split-K matches
    the ref backend, for a uniform schedule (static window -> lengths
    path) and a hybrid stack with per-layer windows (traced window ->
    bias path)."""

    def _run(self, cfg, step_fn, cache, steps=3, **kw):
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (2,)), jnp.int32)
        outs = []
        for _ in range(steps):
            logits, cache = step_fn(cache, toks, **kw)
            toks = jnp.asarray(logits.argmax(-1), jnp.int32)
            outs.append(np.asarray(logits))
        return outs

    @pytest.mark.parametrize("arch", ["llama3-8b", "hymba-1.5b"])
    def test_decode_step_splitk_matches_ref(self, arch):
        cfg = configs.smoke_config(arch)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        outs = {}
        for backend, splits in (("ref", 1), ("interpret", 2)):
            cache = transformer.init_cache(cfg, 2, 64, quantized=True)
            step = lambda c, t, _b=backend, _s=splits: transformer.decode_step(
                params, cfg, c, t, quantized=True, kvq_backend=_b,
                kvq_splits=_s)
            outs[backend] = self._run(cfg, step, cache)
        for a, b_ in zip(outs["ref"], outs["interpret"]):
            np.testing.assert_allclose(a, b_, atol=1e-3)


class TestPlannerDecodeHonesty:
    """plan.decode_tile_report's visited counts == the kernel's measured
    counters, within one tile per layer (ISSUE 4 acceptance); cache-byte
    report sanity."""

    def _measured_layer_tiles(self, s_l, lens_l, *, splits, hkv=2, d=32):
        b = len(lens_l)
        q = _q(b, 2 * hkv, d)
        kq, ks, vq, vs = _cache(b, hkv, s_l, d)
        _, cnt = DO.decode_attention(
            q, kq, ks, vq, vs, lengths=jnp.asarray(lens_l),
            backend="interpret", splits=splits, debug_counts=True)
        return int(np.asarray(cnt).sum()) // hkv

    def test_report_within_one_tile_of_measured(self):
        cfg = configs.smoke_config("llama3-8b")       # uniform window 0
        b, s, splits = 3, 1024, 4
        lengths = [100, 700, 1024]
        rep = plan.decode_tile_report(cfg, b, s, lengths=lengths,
                                      splits=splits)
        assert rep["eligible"] and len(rep["per_layer"]) == cfg.n_layers
        for layer in rep["per_layer"]:
            s_l = layer["cache_len"]
            meas = self._measured_layer_tiles(
                s_l, [min(ln, s_l) for ln in lengths], splits=splits)
            assert abs(layer["visited"] - meas) <= 1, (layer, meas)

    def test_windowed_layers_shrink_statically(self):
        cfg = configs.get_config("hymba-1.5b")
        rep = plan.decode_tile_report(cfg, 2, 32768)
        win_layers = [l for l in rep["per_layer"] if l["window"] > 0]
        assert win_layers and all(
            l["cache_len"] == min(l["window"], 32768) for l in win_layers)
        # windowed layers sized at min(W, S): most pay ~W/S of the sweep
        assert rep["skip_frac"] > 0.8
        assert rep["visited_flops"] < rep["dense_flops"]

    def test_lengths_batch_mismatch_raises(self):
        cfg = configs.smoke_config("llama3-8b")
        with pytest.raises(ValueError, match="lengths"):
            plan.decode_tile_report(cfg, 8, 1024, lengths=[512] * 4)

    def test_ineligible_archs_report_zeros(self):
        for arch in ("mamba2-130m", "minicpm3-4b"):   # SSM / MLA caches
            rep = plan.decode_tile_report(configs.get_config(arch), 2, 1024)
            assert not rep["eligible"] and rep["visited_tile_steps"] == 0

    def test_kv_cache_report_int8_vs_f32(self):
        cfg = configs.get_config("llama3-8b")
        rep = plan.kv_cache_report(cfg, 4, 32768)
        assert rep["eligible"] and rep["int8_bytes"] < rep["f32_bytes"]
        assert rep["ratio"] > 3.0                     # ~3.76x at head_dim 128
        # min(W, S) windowed layers shrink it on top of quantization
        hy = plan.kv_cache_report(configs.get_config("hymba-1.5b"), 4, 32768)
        full = 4 * configs.get_config("hymba-1.5b").n_layers
        assert hy["int8_bytes"] < hy["f32_bytes"]


class TestServeCLI:
    def test_kv_backend_and_splits_flags(self, tmp_path):
        """--kv-backend/--kv-splits plumb through to decode_attention and
        the banner names the resolved backend + clamped split count."""
        env = {**os.environ, "PYTHONPATH": "src", "PYTHONUNBUFFERED": "1",
               "XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.serve", "--arch",
             "llama3-8b", "--smoke", "--batch", "2", "--prompt-len", "16",
             "--gen", "4", "--kv-backend", "interpret", "--kv-splits", "2"],
            env=env, capture_output=True, text=True, timeout=480)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "kv decode: backend=interpret splits=" in out.stdout
        assert "prefill" in out.stdout and "decode" in out.stdout
