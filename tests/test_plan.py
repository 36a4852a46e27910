"""Memory planner: profiling, placement DP, budget solver, serialization."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.checkpoint import CheckpointConfig
from repro.plan import (ChainProfile, RematPlan, budget_boundaries,
                        min_peak_boundaries, plan_for_budget, plan_metrics,
                        plan_min_peak, plan_report, profile_resnet,
                        profile_sequential, profile_transformer)

UNET = [100, 60, 8, 4, 8, 60, 100]  # bytes: bottleneck in the middle


class TestSolver:
    def test_picks_unet_bottleneck(self):
        """Fig. 11: checkpoints land on the narrow middle activations."""
        b = min_peak_boundaries(UNET, 2)
        assert set(b) <= {3, 4, 5}, b  # sites storing the 4/8-byte acts
        assert plan_metrics(UNET, [1.0] * 7, b)["stored_bytes"] <= 12

    def test_peak_bounded_by_no_remat(self):
        # (peak is NOT monotone in k — storing an extra forced checkpoint
        # can cost more than it saves — but it never exceeds no-remat)
        no_remat = sum(UNET)
        peaks = []
        for k in range(1, 6):
            b = min_peak_boundaries(UNET, k)
            peaks.append(plan_metrics(UNET, [1.0] * 7, b)["peak_bytes"])
            assert peaks[-1] <= no_remat
        assert min(peaks) < no_remat  # checkpointing actually helps

    def test_budget_monotonicity(self):
        """Looser budget -> less (or equal) recompute FLOPs."""
        flops = [10.0, 20.0, 5.0, 5.0, 5.0, 20.0, 10.0]
        prev = float("inf")
        for budget in (50, 120, 180, 250, 340, 1000):
            b, _ = budget_boundaries(UNET, flops, budget)
            rec = plan_metrics(UNET, flops, b)["recompute_flops"]
            assert rec <= prev, (budget, b)
            prev = rec

    def test_budget_respected_when_feasible(self):
        b, feasible = budget_boundaries(UNET, [1.0] * 7, 250)
        assert feasible
        assert plan_metrics(UNET, [1.0] * 7, b)["peak_bytes"] <= 250

    def test_loose_budget_means_no_remat(self):
        b, feasible = budget_boundaries(UNET, [1.0] * 7, 10_000)
        assert feasible and b == []

    def test_infeasible_budget_falls_back_to_min_peak(self):
        b, feasible = budget_boundaries(UNET, [1.0] * 7, 1)
        assert not feasible and len(b) >= 1

    def test_recompute_is_prefix_of_last_boundary(self):
        flops = [float(10 ** i) for i in range(1, 8)]
        m = plan_metrics(UNET, flops, [2, 5])
        assert m["recompute_flops"] == sum(flops[:5])


class TestRematPlan:
    def test_json_round_trip(self):
        p = RematPlan(12, (3, 7, 9), policy=("full", "dots", "none", "full"),
                      source="budget:1234")
        assert RematPlan.from_json(p.to_json()) == p
        q = RematPlan(5, (2,))
        assert RematPlan.from_json(q.to_json()) == q

    def test_file_round_trip(self, tmp_path):
        p = plan_for_budget(ChainProfile(tuple(UNET), (1.0,) * 7), 250)
        f = str(tmp_path / "plan.json")
        p.save(f)
        assert RematPlan.load(f) == p

    def test_uniform_matches_even_split(self):
        p = RematPlan.uniform(12, 4)
        assert p.segment_sizes() == [3, 3, 3, 3]
        assert RematPlan.uniform(7, 3).n_segments == 3

    def test_validation(self):
        with pytest.raises(ValueError):
            RematPlan(4, (0,))            # boundary at chain start
        with pytest.raises(ValueError):
            RematPlan(4, (4,))            # boundary at chain end
        with pytest.raises(ValueError):
            RematPlan(8, (2, 4), policy=("full",))  # wrong policy count


class TestProfiles:
    def test_sequential_profile_tracks_shapes(self):
        fns = [lambda x: jnp.tanh(x @ jnp.ones((8, 2))),   # narrow
               lambda x: jnp.tanh(x @ jnp.ones((2, 8))),   # wide again
               lambda x: x.sum(-1)]
        prof = profile_sequential(fns, jax.ShapeDtypeStruct((4, 8),
                                                            jnp.float32))
        assert prof.n_layers == 3
        assert prof.act_bytes[0] == 4 * 2 * 4      # (4, 2) f32
        assert prof.act_bytes[1] == 4 * 8 * 4
        assert all(f > 0 for f in prof.flops)
        assert ChainProfile.from_json(prof.to_json()) == prof

    def test_resnet_profile_is_heterogeneous(self):
        from repro.models import cnn
        cfg = cnn.resnet18(stem_stride=2)
        params = cnn.init_params(cfg, jax.random.PRNGKey(0))
        prof = profile_resnet(params, cfg,
                              jax.ShapeDtypeStruct((2, 64, 64, 3),
                                                   jnp.float32))
        assert prof.n_layers == cnn.num_layer_fns(cfg)
        # strided stages shrink activations: profile must not be flat
        assert max(prof.act_bytes) > 2 * min(prof.act_bytes[:-1])
        # the planner prefers the narrow late sites over an even split:
        # strictly fewer stored checkpoint bytes at the same count, and
        # never a worse peak
        for k in (3, 4, 5):
            planned = min_peak_boundaries(prof.act_bytes, k)
            uniform = RematPlan.uniform(prof.n_layers, k + 1).boundaries
            assert len(planned) == len(uniform)
            mp = plan_metrics(prof.act_bytes, prof.flops, planned)
            mu = plan_metrics(prof.act_bytes, prof.flops, uniform)
            assert mp["stored_bytes"] < mu["stored_bytes"]
            assert mp["peak_bytes"] <= mu["peak_bytes"]

    def test_transformer_profile_window_aware(self):
        from repro import configs
        import dataclasses
        cfg = dataclasses.replace(configs.smoke_config("hymba-1.5b"),
                                  n_layers=4, global_layers=(0,), window=16)
        prof = profile_transformer(
            cfg, {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32)})
        assert prof.n_layers == 4
        # global layer 0 attends full context -> more recompute FLOPs
        assert prof.flops[0] > prof.flops[1]
        assert len(set(prof.act_bytes)) == 1  # carry bytes are uniform


class TestBackendAwareResiduals:
    """ISSUE 2: flash layers carry O(S*D) residuals, not S^2 scores."""

    def _profiles(self, s=512):
        import dataclasses
        from repro import configs
        # head_dim pinned to 64, the production heads' MXU tile width
        cfg = dataclasses.replace(configs.smoke_config("llama3-8b"),
                                  head_dim=64, attn_backend="jnp")
        batch = {"tokens": jax.ShapeDtypeStruct((2, s), jnp.int32)}
        p_jnp = profile_transformer(cfg, batch)
        p_fla = profile_transformer(
            dataclasses.replace(cfg, attn_backend="pallas"), batch)
        return cfg, p_jnp, p_fla

    def test_flash_resid_subquadratic(self):
        cfg, p_jnp, p_fla = self._profiles()
        assert p_jnp.resid_bytes and p_fla.resid_bytes
        # jnp budgets the f32 (S x S) probability matrix; flash only the
        # O(S*D) stats -> the S^2 phantom is gone from every layer
        s2 = 4 * 2 * cfg.n_heads * 512 * 512
        for rj, rf in zip(p_jnp.resid_bytes, p_fla.resid_bytes):
            assert rj - rf == s2 - 2 * 4 * 2 * cfg.n_heads * 512
            assert rf < rj / 2

    def test_resid_widens_planned_peak(self):
        _, p_jnp, p_fla = self._profiles()
        plan = plan_min_peak(p_jnp, 3)
        rep_jnp = plan_report(p_jnp, plan)
        rep_fla = plan_report(p_fla, plan)
        assert rep_jnp["peak_bytes"] > rep_fla["peak_bytes"]
        assert rep_jnp["resid_bytes_total"] > rep_fla["resid_bytes_total"]
        # carries are identical; only the live-set term moved
        assert rep_jnp["stored_bytes"] == rep_fla["stored_bytes"]

    def test_solver_resid_shifts_boundaries(self):
        # two fat-residual layers at the end: the resid-aware DP must cut
        # them apart while the resid-blind one sees a flat chain
        act = [10] * 6
        resid = [0, 0, 0, 0, 100, 100]
        blind = min_peak_boundaries(act, 1)
        aware = min_peak_boundaries(act, 1, resid_bytes=resid)
        m_blind = plan_metrics(act, [1.0] * 6, blind, resid_bytes=resid)
        m_aware = plan_metrics(act, [1.0] * 6, aware, resid_bytes=resid)
        assert aware == [5]                       # splits the two fat layers
        assert m_aware["peak_bytes"] < m_blind["peak_bytes"]

    def test_budget_solver_accounts_resid(self):
        act = [10] * 6
        resid = [0, 0, 0, 0, 100, 100]
        # feasible without resid, infeasible live-set once resid counts
        b_blind, ok_blind = budget_boundaries(act, [1.0] * 6, 80)
        assert ok_blind and b_blind == []
        b_aware, ok_aware = budget_boundaries(act, [1.0] * 6, 80,
                                              resid_bytes=resid)
        assert not ok_aware or b_aware != []

    def test_flash_bwd_recompute_flops(self):
        import dataclasses
        from repro import configs
        from repro.kernels.flash.kernel import tile_step_counts
        from repro.plan import flash_bwd_recompute_flops
        cfg = dataclasses.replace(configs.smoke_config("llama3-8b"),
                                  attn_backend="pallas", head_dim=64)
        per_layer = flash_bwd_recompute_flops(cfg, 2, 512)
        assert len(per_layer) == cfg.n_layers
        # dQ and dKV each recompute scores, but only on the tiles their
        # sparse grids visit — NOT the dense (S x S) rectangle
        c = tile_step_counts(512, causal=True, window=0)
        expect = 2.0 * 2 * cfg.n_heads * cfg.head_dim * c["bq"] * c["bk"] \
            * (c["dq"] + c["dkv"])
        assert per_layer[0] == expect
        dense = 4.0 * 2 * 512 * 512 * cfg.n_heads * cfg.head_dim
        assert per_layer[0] < 0.7 * dense     # causal claws back ~2x
        cfg_jnp = dataclasses.replace(cfg, attn_backend="jnp")
        assert sum(flash_bwd_recompute_flops(cfg_jnp, 2, 512)) == 0.0

    def test_resid_follows_effective_dispatch_not_config_flag(self):
        """Asking for a flash backend is not enough: configs whose
        attention runs the jnp path (``auto`` off the chip, traced
        windows) must still be budgeted at O(S^2), or budget plans OOM."""
        import dataclasses
        from repro import configs
        from repro.plan import flash_training_eligible
        batch = {"tokens": jax.ShapeDtypeStruct((2, 512), jnp.int32)}
        # "auto" resolves to jnp on this CPU host -> S^2 budget
        tiny = configs.smoke_config("llama3-8b")
        assert tiny.attn_backend == "auto" and tiny.attn_impl() == "jnp"
        assert not flash_training_eligible(tiny)
        assert profile_transformer(tiny, batch).resid_bytes == \
            profile_transformer(dataclasses.replace(
                tiny, attn_backend="jnp"), batch).resid_bytes
        # the kernels lower every head_dim (smoke: 16) -> O(S*D) budget
        for backend in ("interpret", "pallas"):
            flash = dataclasses.replace(tiny, attn_backend=backend)
            assert flash_training_eligible(flash)
            assert profile_transformer(flash, batch).resid_bytes < \
                profile_transformer(tiny, batch).resid_bytes
        # global_layers force traced windows -> jnp path on any backend
        hyb = dataclasses.replace(configs.smoke_config("hymba-1.5b"),
                                  attn_backend="interpret")
        assert hyb.global_layers and not flash_training_eligible(hyb)


class TestPlannedExecution:
    def test_planned_resnet_grads_match(self):
        """A solved plan through cnn.forward reproduces plain grads."""
        from repro.models import cnn
        cfg = cnn.resnet18()
        params = cnn.init_params(cfg, jax.random.PRNGKey(0))
        imgs = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 16, 16, 3)).astype(np.float32))
        labels = jnp.asarray([1, 3])
        prof = profile_resnet(params, cfg, imgs)
        plan = plan_min_peak(prof, 4)
        assert plan.boundaries  # the DP actually placed checkpoints

        def loss(p, remat):
            return cnn.loss_fn(p, cfg, imgs, labels, remat=remat)[0]

        g_plain = jax.grad(loss)(params, None)
        g_plan = jax.grad(loss)(params, CheckpointConfig(plan=plan))
        for a, b in zip(jax.tree_util.tree_leaves(g_plain),
                        jax.tree_util.tree_leaves(g_plan)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_planned_transformer_loss_matches(self):
        from repro import configs
        from repro.models import transformer
        import dataclasses
        cfg = dataclasses.replace(configs.smoke_config("llama3-8b"),
                                  n_layers=6)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((2, 16), jnp.int32),
                 "labels": jnp.ones((2, 16), jnp.int32)}
        prof = profile_transformer(cfg, batch)
        with pytest.warns(UserWarning, match="infeasible"):
            # budget below any achievable peak: warned, best-effort plan
            plan = plan_for_budget(prof, 2 * prof.act_bytes[0] + 1)
        assert plan.boundaries  # tight budget forces checkpoints

        l_plain = transformer.loss_fn(
            params, cfg, batch, remat=CheckpointConfig(enabled=False))[0]
        l_plan = transformer.loss_fn(
            params, cfg, batch, remat=CheckpointConfig(plan=plan))[0]
        np.testing.assert_allclose(np.asarray(l_plain), np.asarray(l_plan),
                                   rtol=1e-5)

    def test_plan_policy_wins_in_both_paths(self):
        """A plan carries its policy: identical precedence for the scan
        path (CheckpointConfig.segment_policy) and the sequential path."""
        from repro.core.checkpoint import POLICIES
        cfgr = CheckpointConfig(policy="dots",
                                plan=RematPlan(4, (2,), policy="none"))
        assert cfgr.segment_policy(0) is POLICIES["none"]  # plan, not "dots"
        assert CheckpointConfig(policy="dots").segment_policy(0) \
            is POLICIES["dots"]

    def test_microbatch_specs_shard_and_dtype(self):
        """The planner budgets the PER-DEVICE microbatch in the policy's
        compute dtype (regression: global batch + hardcoded bf16)."""
        from jax.sharding import AbstractMesh
        from repro.train.train_step import microbatch_specs
        sds = {"tokens": jax.ShapeDtypeStruct((64, 32), jnp.int32)}
        mesh = AbstractMesh((16, 1), ("data", "model"))
        assert microbatch_specs(sds, accum=2,
                                mesh=mesh)["tokens"].shape == (2, 32)
        assert microbatch_specs(sds, accum=2)["tokens"].shape == (32, 32)
        from repro import configs
        cfg = configs.smoke_config("llama3-8b")
        mb = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
        p16 = profile_transformer(cfg, mb, dtype_bytes=2)
        p32 = profile_transformer(cfg, mb, dtype_bytes=4)
        assert p32.act_bytes[0] == 2 * p16.act_bytes[0]

    def test_report_fields(self):
        prof = ChainProfile(tuple(UNET), tuple(float(i + 1) for i in range(7)))
        rep = plan_report(prof, plan_min_peak(prof, 2))
        for key in ("peak_bytes", "stored_bytes", "recompute_flops",
                    "segment_sizes", "recompute_frac", "no_remat_bytes"):
            assert key in rep
        assert 0 <= rep["recompute_frac"] <= 1
