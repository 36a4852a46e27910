"""A tiny training cell end to end on the CPU, with the look for a chip
skipped: a sound run is correct, the fp8 control and faults planted in
the timed step are not."""
from __future__ import annotations

import pytest

from tinycells import run_tiny, tiny_bench  # noqa: F401


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.long"])
def test_sound_run_is_correct(tiny_bench, cell):
    d, bench = tiny_bench
    out = run_tiny(d, bench, cell, trace=True)
    assert out["correct"], out["checks"]
    # on the CPU the allocator keeps no peak and the trace no Pallas
    # kernel: those metrics stay silent
    assert set(out["metrics"]) == {"mfu.train", "idle_share.train"}
    assert 0 < out["metrics"]["mfu.train"]["value"] <= 100
    assert set(out["missing"]) == {"train_peak_over_plan",
                                   "flash_train_roofline"}
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "change_gap"}


def _wrap_step(monkeypatch, wrap):
    from repro.train import train_step
    orig = train_step.build_train_step

    def build(cfg, tc, mesh=None):
        return wrap(orig(cfg, tc, mesh=mesh))

    monkeypatch.setattr(train_step, "build_train_step", build)


def _state_unchanged(step):
    def frozen(params, opt, ls, batch):
        _, _, ls2, metrics = step(params, opt, ls, batch)
        return params, opt, ls2, metrics
    return frozen


def _half_batch(step):
    def half(params, opt, ls, batch):
        import jax
        return step(params, opt, ls, jax.tree_util.tree_map(
            lambda x: x[:x.shape[0] // 2], batch))
    return half


@pytest.mark.parametrize("cell,fault", [
    ("tiny.train", _state_unchanged), ("tiny.train", _half_batch),
    ("tiny.long", _state_unchanged)],
    ids=["state_unchanged", "half_batch", "long-state_unchanged"])
def test_fault_makes_run_incorrect(tiny_bench, monkeypatch, cell, fault):
    d, bench = tiny_bench
    _wrap_step(monkeypatch, fault)
    out = run_tiny(d, bench, cell, seconds=0.5)
    assert not out["correct"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_fp8_control_fails_a_limit(tiny_bench):
    """The reference in fp8 in the program's place, against the float32
    reference, on three seeds."""
    import json
    import os

    import bench as harness

    d, _ = tiny_bench
    with open(os.path.join(d, "workloads", "tiny.train.json")) as f:
        wl = json.load(f)
    with open(os.path.join(d, "configs", "tiny-train.json")) as f:
        cfg = json.load(f)
    for seed in (1, 2, 3):
        run = harness.Run(wl, cfg, seed=seed, seconds=1.0, trace=False,
                          t_process=0.0, bench_dir=d)
        driver = harness.load_module(os.path.join(d, "drivers",
                                                  "train_steps.py"))
        want = driver.reference_steps(run, 2, 64)
        got = driver.reference_steps(run, 2, 64, fp8=True)
        checks = driver.compare(got, want, wl["check"]["limits"])
        assert any(c["value"] > c["limit"] for c in checks), checks
