"""Sparsity-aware flash grids (ISSUE 3): tile-bound math vs the mask's
support, measured interpret-mode visit counters vs the analytic counts,
the skip-ratio acceptance bars, grad parity on the sparse grids (incl.
the ragged last tile), planner-honest FLOP budgets, the bf16 residual
policy, and the kvq no-bias passthrough."""
import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import tiling as T
from repro.kernels.flash import kernel as K, ops as O, ref as R
from repro.models import transformer

RNG = np.random.default_rng(7)


def _qkv(b, h, hkv, s, d, dtype=np.float32):
    q = jnp.asarray(RNG.normal(size=(b, h, s, d)).astype(dtype))
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(dtype))
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(dtype))
    return q, k, v


def _flat(h, hkv, s, d):
    q = jnp.asarray(RNG.normal(size=(h, s, d)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(hkv, s, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(hkv, s, d)).astype(np.float32))
    return q, k, v


def _dense_mask(s_len, *, causal, window, kv_len):
    """Position-level ground truth of ``_position_mask``'s geometry."""
    q = np.arange(s_len)[:, None]
    k = np.arange(s_len)[None, :]
    ok = np.broadcast_to(k < kv_len, (s_len, s_len)).copy()
    if causal:
        ok &= q >= k
        if window > 0:
            ok &= (q - k) < window
    return ok


# schedule sweep: (bq, bk, s_len, kv_len, window, causal) — mixed tile
# sizes, ragged kv tails, windows that don't divide tiles
SWEEP = [
    (128, 128, 512, 512, 0, True),
    (128, 128, 512, 512, 128, True),
    (128, 128, 512, 512, 100, True),      # window not tile-aligned
    (128, 128, 512, 300, 64, True),       # ragged kv + window
    (128, 128, 512, 300, 0, True),        # ragged kv, full causal
    (128, 128, 512, 200, 0, False),       # non-causal, dead last tile
    (64, 128, 512, 512, 96, True),        # bq != bk
    (128, 64, 512, 400, 96, True),        # bq != bk, ragged
    (64, 64, 256, 256, 1, True),          # degenerate window = 1
    (128, 128, 2048, 2048, 256, True),
    (8, 8, 40, 40, 0, True),              # sub-block path (ops pads to 8)
    # the tiles flash_tiles chooses: seq4k fwd/dQ and dKV, a windowed
    # layer, a ragged padded bucket
    (1024, 1024, 4096, 4096, 0, True),
    (512, 1024, 4096, 4096, 0, True),
    (512, 512, 2048, 2048, 600, True),
    (640, 640, 2560, 2500, 0, True),
]


class TestTileBounds:
    """The wedge bounds must EXACTLY cover ``_position_mask``'s support:
    every tile holding a live position is inside [lo, hi], and (when any
    live tile exists) lo/hi are the min/max live tiles — no overshoot."""

    @pytest.mark.parametrize("bq,bk,s,kv_len,window,causal", SWEEP)
    def test_kv_bounds_cover_support_exactly(self, bq, bk, s, kv_len,
                                             window, causal):
        ok = _dense_mask(s, causal=causal, window=window, kv_len=kv_len)
        n_q, n_k = s // bq, s // bk
        for i in range(n_q):
            lo, hi = K.kv_tile_bounds(i, bq=bq, bk=bk, causal=causal,
                                      window=window, kv_len=kv_len)
            live = [t for t in range(n_k)
                    if ok[i * bq:(i + 1) * bq, t * bk:(t + 1) * bk].any()]
            if live:
                assert (lo, hi) == (min(live), max(live)), \
                    (i, lo, hi, live)
            else:  # fully-masked q tile: any one-step range is legal
                assert 0 <= lo <= hi < n_k

    @pytest.mark.parametrize("bq,bk,s,kv_len,window,causal", SWEEP)
    def test_q_bounds_cover_support_exactly(self, bq, bk, s, kv_len,
                                            window, causal):
        ok = _dense_mask(s, causal=causal, window=window, kv_len=kv_len)
        n_q, n_k = s // bq, s // bk
        for t in range(n_k):
            lo, hi = K.q_tile_bounds(t, bq=bq, bk=bk, causal=causal,
                                     window=window, n_q=n_q, kv_len=kv_len)
            live = [i for i in range(n_q)
                    if ok[i * bq:(i + 1) * bq, t * bk:(t + 1) * bk].any()]
            if live:
                assert (lo, hi) == (min(live), max(live)), \
                    (t, lo, hi, live)
            else:  # dead KV tile: visited via a one-step range, early-out
                assert 0 <= lo <= hi < n_q

    @pytest.mark.parametrize("bq,bk,s,kv_len,window,causal", SWEEP[:6])
    def test_traced_bounds_agree_with_static(self, bq, bk, s, kv_len,
                                             window, causal):
        """The same formulas run on traced grid indices inside index maps
        and kernel bodies — the jnp arithmetic must agree with the Python
        ints used for grid sizing."""
        for i in range(s // bq):
            lo_s, hi_s = K.kv_tile_bounds(i, bq=bq, bk=bk, causal=causal,
                                          window=window, kv_len=kv_len)
            lo_t, hi_t = K.kv_tile_bounds(jnp.int32(i), bq=bq, bk=bk,
                                          causal=causal, window=window,
                                          kv_len=kv_len)
            assert (int(lo_t), int(hi_t)) == (lo_s, hi_s)
        for t in range(s // bk):
            lo_s, hi_s = K.q_tile_bounds(t, bq=bq, bk=bk, causal=causal,
                                         window=window, n_q=s // bq,
                                         kv_len=kv_len)
            lo_t, hi_t = K.q_tile_bounds(jnp.int32(t), bq=bq, bk=bk,
                                         causal=causal, window=window,
                                         n_q=s // bq, kv_len=kv_len)
            assert (int(lo_t), int(hi_t)) == (lo_s, hi_s)

    def test_analytic_counts_match_mask_support(self):
        """tile_step_counts == the number of tiles with any live position
        (plus the clamped one-step rows for fully-masked q tiles)."""
        for bq, bk, s, kv_len, window, causal in SWEEP:
            ok = _dense_mask(s, causal=causal, window=window, kv_len=kv_len)
            c = K.tile_step_counts(s, bq=bq, bk=bk, causal=causal,
                                   window=window, kv_len=kv_len)
            n_q, n_k = s // bq, s // bk
            live_pairs = sum(
                ok[i * bq:(i + 1) * bq, t * bk:(t + 1) * bk].any()
                for i in range(n_q) for t in range(n_k))
            # fwd visits every live pair, plus 1 step per fully-dead q row
            dead_q = sum(not ok[i * bq:(i + 1) * bq].any()
                         for i in range(n_q))
            assert c["fwd"] == live_pairs + dead_q
            # dkv visits every live pair; dead KV tiles are early-outed
            assert c["dkv"] == live_pairs
            assert c["dense"] == n_q * n_k


class TestMeasuredCounters:
    """interpret-mode debug counters vs the analytic counts, and the
    ISSUE 3 acceptance ratios."""

    def _measure(self, s, *, window, causal, kv_len=None, h=2, hkv=1, d=64,
                 bq=128, bk=128):
        kvl = s if kv_len is None else kv_len
        q, k, v = _flat(h, hkv, s, d)
        o, m, l, cnt = K.flash_attention_fwd_pallas(
            q, k, v, causal=causal, window=window, kv_len=kvl, bq=bq, bk=bk,
            interpret=True, debug_counts=True)
        do = jnp.ones_like(o)
        _, _, _, dqc, dkvc = K.flash_attention_bwd_pallas(
            q, k, v, o, m, l, do, causal=causal, window=window, kv_len=kvl,
            bq=bq, bk=bk, interpret=True, debug_counts=True)
        group = h // hkv
        return {"fwd": int(cnt[0].sum()), "dq": int(dqc[0].sum()),
                "dkv": int(dkvc[0].sum()) // group}

    @pytest.mark.parametrize("s,window,causal,kv_len,bq,bk", [
        pytest.param(512, 0, True, None, 128, 128, id="512-0-True-None"),
        pytest.param(512, 128, True, None, 128, 128, id="512-128-True-None"),
        pytest.param(512, 100, True, 400, 128, 128, id="512-100-True-400"),
        pytest.param(256, 0, False, 200, 128, 128, id="256-0-False-200"),
        pytest.param(256, 64, True, None, 128, 128, id="256-64-True-None"),
        # tiles flash_tiles chooses: fwd/dQ at 4096, dKV at 4096,
        # windowed, a ragged bucket (1200 pads to 1280 -> 640)
        (4096, 0, True, None, 1024, 1024),
        (4096, 0, True, None, 512, 1024),
        (2048, 600, True, None, 512, 512),
        (1280, 0, True, 1200, 640, 640),
        (1280, 0, False, 1200, 640, 640),
    ])
    def test_counters_match_analytic(self, s, window, causal, kv_len, bq,
                                     bk):
        kvl = s if kv_len is None else kv_len
        meas = self._measure(s, window=window, causal=causal, kv_len=kv_len,
                             bq=bq, bk=bk)
        c = K.tile_step_counts(s, bq=bq, bk=bk, causal=causal,
                               window=window, kv_len=kvl)
        assert meas == {k_: c[k_] for k_ in ("fwd", "dq", "dkv")}

    @pytest.mark.parametrize("s,window", [(4096, 0), (2048, 0), (2048, 512),
                                          (1280, 0)])
    def test_counters_match_analytic_at_chosen_tiles(self, s, window):
        """Given no tiles, each of the fwd, dQ and dKV grids runs the
        tiles flash_tiles chooses for it (dKV its own)."""
        d = 64
        meas = self._measure(s, window=window, causal=True, bq=None,
                             bk=None, d=d)
        want = {}
        for kn in T.FLASH_KERNELS:
            bq, bk = T.flash_tiles(s, d, window=window, kernel=kn)
            want[kn] = K.tile_step_counts(s, bq=bq, bk=bk, causal=True,
                                          window=window)[kn]
        assert meas == want

    def test_causal_s2048_skips_at_least_45pct(self):
        """Acceptance: causal S=2048 on 128 x 128 tiles must skip >= 45%
        of KV tile-steps on all three grids (the dense rectangle is
        16x16=256; the wedge visits the 136-step lower triangle)."""
        meas = self._measure(2048, window=0, causal=True, bq=128, bk=128)
        dense = K.tile_step_counts(2048, bq=128, bk=128, causal=True,
                                   window=0)["dense"]
        for grid in ("fwd", "dq", "dkv"):
            skipped = 1 - meas[grid] / dense
            assert skipped >= 0.45, (grid, skipped)

    def test_window256_s2048_skips_band_complement(self):
        """Acceptance: W=256 at S=2048 must skip >= 1 - W/S - eps where
        eps = (BQ + BK)/S covers tile-granularity overhang (a band of
        width W can straddle at most W/BK + 1 tiles per q tile), and on
        these 128 x 128 tiles at least 80% (the band visits at most 3 of
        each q row's 16 tiles)."""
        s, w = 2048, 256
        meas = self._measure(s, window=w, causal=True, bq=128, bk=128)
        c = K.tile_step_counts(s, bq=128, bk=128, causal=True, window=w)
        eps = (c["bq"] + c["bk"]) / s
        for grid in ("fwd", "dq", "dkv"):
            skipped = 1 - meas[grid] / c["dense"]
            assert skipped >= 1 - w / s - eps, (grid, skipped)
            assert skipped >= 0.80, (grid, skipped)

    def test_counts_via_public_op_shapes(self):
        """The wedge grid + counters also run where ops.py pads (ragged
        last tile): S=300 pads to 384, kv_len=300 masks the tail."""
        s_pad = O.padded_seq_len(300)
        assert s_pad == 384
        meas = self._measure(s_pad, window=0, causal=True, kv_len=300,
                             bq=128, bk=128)
        c = K.tile_step_counts(s_pad, bq=128, bk=128, causal=True, window=0,
                               kv_len=300)
        assert meas == {k_: c[k_] for k_ in ("fwd", "dq", "dkv")}


class TestFlashTiles:
    """The tiles each flash kernel runs when its caller names none."""

    @pytest.mark.parametrize("s,d,kernel,want", [
        # the seq4k cell: (4, 32, 4096, 128), 2 KV heads
        (4096, 128, "fwd", (1024, 1024)),
        (4096, 128, "dq", (1024, 1024)),
        (4096, 128, "dkv", (512, 1024)),
        # the chat cell's prefill buckets, 128...2048
        (128, 128, "fwd", (128, 128)),
        (256, 128, "fwd", (256, 256)),
        (512, 128, "fwd", (512, 512)),
        (1024, 128, "fwd", (1024, 1024)),
        (2048, 128, "fwd", (1024, 1024)),
        (2048, 128, "dkv", (512, 1024)),
        # below one block: one tile over the 8-padded sequence
        (40, 64, "fwd", (40, 40)),
    ])
    def test_choice_for_the_cells_shapes(self, s, d, kernel, want):
        assert T.flash_tiles(s, d, kernel=kernel) == want

    @pytest.mark.parametrize("s,want", [(384, (384, 384)),
                                        (2560, (640, 640)),
                                        (1408, (128, 128))])
    def test_padded_lengths_divide(self, s, want):
        """Tiles are multiples of 128 that divide S: 384 is one tile,
        2560 = 4 x 640, and 1408 = 11 x 128 leaves only 128."""
        assert T.flash_tiles(s, 128, kernel="fwd") == want

    @pytest.mark.parametrize("window,want", [(1, (128, 128)),
                                             (100, (128, 128)),
                                             (256, (256, 256)),
                                             (600, (512, 512)),
                                             (4096, (1024, 1024))])
    def test_window_caps_both_sides(self, window, want):
        assert T.flash_tiles(4096, 128, window=window, kernel="fwd") == want

    @pytest.mark.parametrize("kernel", T.FLASH_KERNELS)
    @pytest.mark.parametrize("d", [32, 64, 128, 256])
    def test_every_choice_fits_v5e_scoped_vmem(self, kernel, d):
        for s in (128, 256, 384, 512, 1024, 1280, 2048, 2560, 4096, 16384):
            for window in (0, 256, 1000):
                bq, bk = T.flash_tiles(s, d, window=window, kernel=kernel)
                assert s % bq == 0 and s % bk == 0, (s, bq, bk)
                assert bq % 128 == 0 and bk % 128 == 0, (s, bq, bk)
                assert max(bq, bk) <= T.FLASH_TILE_CAP
                if window:
                    assert max(bq, bk) <= max(128, window // 128 * 128)
                assert T.flash_vmem_bytes(bq, bk, d, kernel=kernel) \
                    <= T.V5E_SCOPED_VMEM, (s, window, bq, bk)

    def test_rejects_what_no_kernel_runs(self):
        with pytest.raises(ValueError, match="multiple of 128"):
            T.flash_tiles(1000, 128)
        with pytest.raises(ValueError, match="unknown kernel"):
            T.flash_tiles(4096, 128, kernel="delta")


class TestStoredOperandDtype:
    """bf16-stored q, k, v and dO meet the MXU as stored, a mixed pair is
    widened, and nothing the kernels compute in f32 (P, dS) is rounded to
    bf16 on its way into a matmul: against the f32 oracle on the same
    values, only the order of accumulation differs, far inside bf16's
    rounding of P or dS (~4e-3)."""

    @pytest.mark.parametrize("do_dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("window", [0, 512])
    def test_bf16_operands_match_f32_oracle(self, window, do_dtype):
        """An f32 dO against bf16 q, k, v is the ``resid_bf16`` policy's
        backward: f32 compute with bf16-saved residuals."""
        h, s, d = 4, 1024, 64
        q, k, v = (jnp.asarray(RNG.normal(size=(n, s, d)), jnp.bfloat16)
                   for n in (h, 1, 1))
        do = jnp.asarray(RNG.normal(size=(h, s, d)), do_dtype)
        q32, k32, v32, do32 = (x.astype(jnp.float32) for x in (q, k, v, do))
        kw = dict(causal=True, window=window, interpret=True, bq=512,
                  bk=1024)

        def oracle(q, k, v):
            return R.flash_ref(q[None], k[None], v[None], causal=True,
                               window=window)[0]

        o_ref, vjp = jax.vjp(oracle, q32, k32, v32)

        def rel(a, b_):
            return float(jnp.abs(a - b_).max() / jnp.abs(b_).max())

        # QK^T from the bf16 tiles as stored: the softmax stats
        _, m, l = K.flash_attention_fwd_pallas(q, k, v, **kw)
        _, m32, l32 = K.flash_attention_fwd_pallas(q32, k32, v32, **kw)
        assert rel(m, m32) < 1e-6 and rel(l, l32) < 1e-6
        # P.V with bf16 v: an f32 q makes the output f32, so it shows
        o, m, l = K.flash_attention_fwd_pallas(q32, k, v, **kw)
        assert rel(o, o_ref) < 1e-4
        # dP = dO V^T from bf16 tiles; P^T dO, dS K and dS^T Q keep P, dS
        grads = K.flash_attention_bwd_pallas(
            q, k, v, o, m, l, do, grad_dtypes=("float32",) * 3, **kw)
        for name, a, b_ in zip(("dq", "dk", "dv"), grads, vjp(do32)):
            assert rel(a, b_) < 1e-4, name


class TestSparseGridGradParity:
    """Grad parity (<= 1e-3 vs the jnp oracle) re-run on the SPARSE grids,
    including the ragged last tile, window + ragged, GQA and non-causal
    padded-KV cases."""

    @pytest.mark.parametrize("b,h,hkv,s,d,window,causal", [
        (1, 4, 4, 256, 64, 0, True),      # causal wedge
        (2, 8, 2, 256, 64, 0, True),      # GQA 4:1 on the wedge dKV grid
        (1, 4, 2, 200, 64, 0, True),      # ragged last tile (pads to 256)
        (1, 4, 4, 200, 64, 100, True),    # window + ragged
        (1, 4, 4, 512, 64, 128, True),    # statically shrunk window grid
        (1, 2, 2, 200, 64, 0, False),     # non-causal padded KV
        (1, 2, 1, 384, 64, 96, True),     # MQA, window not tile-aligned
        # GQA 16:1 at the chosen, non-128 tiles: 1536 -> 768 x 768;
        # window 512 -> 512 x 512; 1200 pads to 1280 -> 640 x 640 ragged
        (1, 16, 1, 1536, 64, 0, True),
        (1, 16, 1, 1536, 64, 512, True),
        (1, 16, 1, 1200, 64, 0, True),
    ])
    def test_grads_match_ref(self, b, h, hkv, s, d, window, causal):
        q, k, v = _qkv(b, h, hkv, s, d)
        t = jnp.asarray(RNG.normal(size=(b, h, s, d)).astype(np.float32))

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) * t)

        g_int = jax.grad(loss(lambda q, k, v: O.flash_attention(
            q, k, v, causal=causal, window=window, backend="interpret")),
            argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss(lambda q, k, v: R.flash_ref(
            q, k, v, causal=causal, window=window)),
            argnums=(0, 1, 2))(q, k, v)
        for name, a, b_ in zip("qkv", g_int, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=1e-3,
                err_msg=f"d{name} mismatch")


class TestPlannerHonesty:
    """profile/flash_bwd_recompute_flops budgets == the measured visited
    positions, exactly, at the tiles the kernels run, on grids of several
    steps, where a short budget shows."""

    D = 64

    def _cfg(self, **kw):
        return dc.replace(configs.smoke_config("llama3-8b"),
                          attn_backend="interpret", **kw)

    def _fwd(self, s, window):
        """Layer 0's forward, per head: (budgeted, measured) positions."""
        b, d = 1, self.D
        cfg = self._cfg(head_dim=d, window=window)
        h, hkv = cfg.n_heads, cfg.n_kv
        from repro.plan import profile_transformer
        batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        prof = profile_transformer(cfg, batch)

        # measured: one layer's forward on the padded flash grid, at the
        # tiles the kernel chooses
        s_pad = O.padded_seq_len(s)
        q, k, v = _flat(b * h, hkv, s_pad, d)
        *_, cnt = K.flash_attention_fwd_pallas(
            q, k, v, causal=True, window=window, kv_len=s, interpret=True,
            debug_counts=True)
        bq, bk = T.flash_tiles(s_pad, d, window=window, kernel="fwd")
        measured = int(cnt.sum()) // (b * h) * bq * bk

        # budgeted: back out the per-head positions from the profile's
        # attention term (total layer flops - matmul term)
        params_sds = jax.eval_shape(
            lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
        block_elems = sum(x.size for x in jax.tree_util.tree_leaves(
            params_sds["blocks"]))
        matmul = 2.0 * b * s * (block_elems / cfg.n_layers)
        return (prof.flops[0] - matmul) / (4.0 * b * h * d), measured

    def _bwd(self, s, window):
        """Layer 0's dQ + dKV recompute, per head: (budgeted, measured)
        positions and each grid's visited steps."""
        b, d = 1, self.D
        cfg = self._cfg(head_dim=d, window=window)
        h, hkv = cfg.n_heads, cfg.n_kv
        from repro.plan import flash_bwd_recompute_flops
        per_layer = flash_bwd_recompute_flops(cfg, b, s)

        s_pad = O.padded_seq_len(s)
        q, k, v = _flat(b * h, hkv, s_pad, d)
        o, m, l = K.flash_attention_fwd_pallas(
            q, k, v, causal=True, window=window, kv_len=s, interpret=True)
        *_, dqc, dkvc = K.flash_attention_bwd_pallas(
            q, k, v, o, m, l, jnp.ones_like(o), causal=True, window=window,
            kv_len=s, interpret=True, debug_counts=True)
        group = h // hkv
        steps = {"dq": int(dqc.sum()) // (b * h),
                 "dkv": int(dkvc.sum()) // (b * group * hkv)}
        measured = 0
        for kn, n in steps.items():
            bq, bk = T.flash_tiles(s_pad, d, window=window, kernel=kn)
            measured += n * bq * bk
        return per_layer[0] / (2.0 * b * h * d), measured, steps

    def test_profile_budget_matches_measured_tiles(self):
        # S=2000 pads to 2048: 1024^2 tiles, 3 of the 4 steps visited
        budget, measured = self._fwd(2000, 0)
        assert measured == 3 * 1024 * 1024
        assert abs(budget - measured) < 0.5, (budget, measured)

    def test_bwd_budget_matches_measured_tiles(self):
        # dQ on 1024^2 tiles (3 of 4 steps), dKV on 512 x 1024 (6 of 8)
        budget, measured, steps = self._bwd(2000, 0)
        assert steps == {"dq": 3, "dkv": 6}
        assert abs(budget - measured) < 0.5, (budget, measured)

    def test_windowed_budgets_match_measured_tiles(self):
        # window 256, S=1000 padded to 1024: every grid on 256^2 tiles,
        # 7 of the 16 steps visited
        budget, measured = self._fwd(1000, 256)
        assert measured == 7 * 256 * 256
        assert abs(budget - measured) < 0.5, (budget, measured)
        budget, measured, steps = self._bwd(1000, 256)
        assert steps == {"dq": 7, "dkv": 7}
        assert abs(budget - measured) < 0.5, (budget, measured)

    def test_flop_report_claws_back_causal(self):
        from repro.plan import flash_attn_flop_report
        cfg = self._cfg(head_dim=64)
        # on 128 x 128 grids the sparse grids claw back over 40%
        rep = flash_attn_flop_report(cfg, 1, 2048, tiles=(128, 128))
        assert rep["eligible"]
        assert rep["visited_flops"] < 0.6 * rep["dense_flops"]
        assert 0.45 <= rep["skip_frac"] < 1.0
        # by default the report counts each grid at the tiles it runs
        rep = flash_attn_flop_report(cfg, 1, 2048)
        visited = dense = 0.0
        steps = {"visited": 0, "dense": 0}
        for kn, per_pos in (("fwd", 4.0), ("dq", 6.0), ("dkv", 8.0)):
            bq, bk = T.flash_tiles(2048, 64, kernel=kn)
            c = K.tile_step_counts(2048, bq=bq, bk=bk, causal=True)
            visited += per_pos * c[kn] * bq * bk
            dense += per_pos * c["dense"] * bq * bk
            steps["visited"] += c[kn]
            steps["dense"] += c["dense"]
        bhd = cfg.n_heads * cfg.head_dim * cfg.n_layers
        assert rep["visited_flops"] == pytest.approx(bhd * visited)
        assert rep["dense_flops"] == pytest.approx(bhd * dense)
        assert rep["skip_frac"] == 1 - steps["visited"] / steps["dense"]
        assert rep["visited_flops"] < rep["dense_flops"]
        # ineligible config reports zeros, not a phantom claw-back
        rep_jnp = flash_attn_flop_report(dc.replace(cfg, attn_backend="jnp"),
                                         1, 2048)
        assert not rep_jnp["eligible"] and rep_jnp["dense_flops"] == 0.0

    def test_sparse_budget_shifts_checkpoint_boundaries(self):
        """The point of honesty: a hybrid window/global schedule prices
        windowed flash layers FAR cheaper to recompute than global ones,
        so the budget DP's recompute objective must see heterogeneous
        flops (the dense model priced every layer's scores ~equally)."""
        from repro.plan import profile_transformer
        cfg = dc.replace(
            configs.smoke_config("llama3-8b"), attn_backend="interpret",
            head_dim=64, n_layers=8, window=128, global_layers=())
        batch = {"tokens": jax.ShapeDtypeStruct((1, 2048), jnp.int32)}
        prof_w = profile_transformer(cfg, batch)
        prof_g = profile_transformer(dc.replace(cfg, window=0), batch)
        # windowed flash layers must be budgeted well under causal-full
        assert sum(prof_w.flops) < 0.6 * sum(prof_g.flops)


class TestFlashResidPolicy:
    """bf16 policy on the saved (q, k, v, o) residual tuple; (m, l) stats
    stay f32; planner resid_bytes follow the policy dtype."""

    def _resid_structure(self, resid_dtype):
        b, h, s, d = 1, 2, 256, 64
        sds = [jax.ShapeDtypeStruct((b, h, s, d), jnp.float32)] * 3
        out = jax.eval_shape(
            lambda q, k, v: jax.vjp(lambda *a: O.flash_attention(
                *a, backend="interpret", resid_dtype=resid_dtype), q, k, v),
            *sds)
        return jax.tree_util.tree_leaves(out)

    def test_qkvo_cast_stats_stay_f32(self):
        leaves = self._resid_structure("bfloat16")
        dtypes = sorted(str(x.dtype) for x in leaves)
        # output stays f32; saved q,k,v,o are bf16; m,l stay f32
        assert dtypes.count("bfloat16") == 4
        assert dtypes.count("float32") == 3
        f32 = sum(x.size * x.dtype.itemsize for x in leaves)
        plain = sum(x.size * x.dtype.itemsize
                    for x in self._resid_structure(None))
        assert f32 < plain

    def test_grads_f32_and_close(self):
        q, k, v = _qkv(1, 2, 2, 256, 64)
        g16 = jax.grad(lambda q, k, v: jnp.sum(O.flash_attention(
            q, k, v, backend="interpret", resid_dtype="bfloat16") ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(R.flash_ref(q, k, v) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g16, gr):
            assert a.dtype == jnp.float32        # cotangents match primals
            scale = float(jnp.abs(b_).max()) + 1e-9
            assert float(jnp.abs(a - b_).max()) / scale < 2e-2  # bf16 trade

    def test_policy_threads_through_transformer(self):
        from repro.core.mixed_precision import get_policy
        pol = get_policy("resid_bf16")
        assert pol.flash_resid_dtype == jnp.bfloat16
        assert pol.compute_dtype == jnp.float32
        cfg = dc.replace(configs.smoke_config("llama3-8b"),
                         attn_backend="interpret")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jnp.asarray(RNG.integers(0, cfg.vocab, (2, 32)),
                                       jnp.int32),
                 "labels": jnp.asarray(RNG.integers(0, cfg.vocab, (2, 32)),
                                       jnp.int32)}
        g = jax.grad(lambda p: transformer.loss_fn(
            p, cfg, batch, policy=pol)[0])(params)
        assert all(bool(jnp.all(jnp.isfinite(x)))
                   for x in jax.tree_util.tree_leaves(g))

    def test_planner_resid_bytes_follow_policy(self):
        from repro.plan import profile_transformer
        cfg = dc.replace(configs.smoke_config("llama3-8b"),
                         attn_backend="interpret")
        batch = {"tokens": jax.ShapeDtypeStruct((2, 512), jnp.int32)}
        p4 = profile_transformer(cfg, batch, dtype_bytes=4)
        p2 = profile_transformer(cfg, batch, dtype_bytes=4,
                                 flash_resid_bytes=2)
        # the O(S*D) qkvo term halves; the f32 (m, l) rows do not move
        stats = 2 * 4 * 2 * cfg.n_heads * 512
        qo_kv4 = (2 * cfg.n_heads + 2 * cfg.n_kv) * 2 * 512 \
            * cfg.head_dim * 4
        assert p4.resid_bytes[0] == qo_kv4 + stats
        assert p2.resid_bytes[0] == qo_kv4 // 2 + stats


class TestKvqNoBiasPassthrough:
    def test_no_mask_matches_zero_bias(self):
        from repro.kernels.kvq import ops as KO
        b, h, hkv, s, d = 2, 8, 4, 512, 64
        q = jnp.asarray(RNG.normal(size=(b, h, d)).astype(np.float32))
        k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(np.float32))
        v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(np.float32))
        kq, ks = KO.quantize_kv(k)
        vq, vs = KO.quantize_kv(v)
        zeros = jnp.zeros((b, s), jnp.float32)
        for backend in ("ref", "interpret"):
            o_none = KO.decode_attention(q, kq, ks, vq, vs, backend=backend)
            o_zero = KO.decode_attention(q, kq, ks, vq, vs, bias=zeros,
                                         backend=backend)
            np.testing.assert_allclose(np.asarray(o_none),
                                       np.asarray(o_zero), atol=1e-6)

    def test_no_bias_tensor_materialized(self):
        """The no-mask jaxpr must contain NO (B, S) f32 tensor at all —
        previously a dense zero bias was built and broadcast-added."""
        from repro.kernels.kvq import ops as KO
        b, h, hkv, s, d = 2, 4, 2, 256, 64
        q = jax.ShapeDtypeStruct((b, h, d), jnp.float32)
        kq = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.int8)
        sc = jax.ShapeDtypeStruct((b, hkv, s), jnp.float32)
        jaxpr = str(jax.make_jaxpr(
            lambda q, kq, ks, vq, vs: KO.decode_attention(
                q, kq, ks, vq, vs, backend="ref"))(q, kq, sc, kq, sc))
        assert f"f32[{b},{s}]" not in jaxpr
