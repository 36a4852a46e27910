"""The tiny latent-attention, held-expert serving cell end to end on the
CPU, with the look for a chip skipped: a sound run is correct and reads
every metric of the cell but the two kernels' rooflines (a CPU trace
names no kernel: the tiny cell runs the prefill's flash kernel in the
Pallas interpreter, and the decode attention's jnp reference); the fp8
control and a planted wrong token fail."""
from __future__ import annotations

import json
import os

import pytest

from tinycells import run_tiny, tiny_bench  # noqa: F401

E2E = {"ttft_p95_ms", "setup_s"}
TRACED = {"queue_wait_p95_ms", "mfu.longctx", "idle_share.longctx"}


@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_sound_run_is_correct(tiny_bench, trace):
    d, bench = tiny_bench
    out = run_tiny(d, bench, "tiny.longctx", trace=trace)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == (TRACED if trace else E2E)
    if trace:
        assert out["missing"] == ["mla_decode_roofline",
                                  "mla_prefill_roofline"]
        assert 0 < out["metrics"]["mfu.longctx"]["value"] <= 100


def test_planted_wrong_token_fails(tiny_bench, monkeypatch):
    from repro.serve import sampling
    orig = sampling.sample_tokens

    def altered(logits, key=None, **kw):
        return (orig(logits, key, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(sampling, "sample_tokens", altered)
    d, bench = tiny_bench
    out = run_tiny(d, bench, "tiny.longctx")
    assert not out["correct"]
    assert out["checks"]["argmax_miss"]["value"] > \
        out["checks"]["argmax_miss"]["limit"]


def test_fp8_control_fails_the_limit(tiny_bench):
    """The reference in fp8, at the positions of served tokens."""
    import jax.numpy as jnp

    import bench as harness
    import serving
    import traffic

    d, _ = tiny_bench
    with open(os.path.join(d, "workloads", "tiny.longctx.json")) as f:
        wl = json.load(f)
    with open(os.path.join(d, "configs", "tiny-mla.json")) as f:
        cfg = json.load(f)
    miss = []
    for seed in (1, 2, 3):
        run = harness.Run(wl, cfg, seed=seed, seconds=1.0, trace=False,
                          t_process=0.0, bench_dir=d)
        params = run.reference.make_params(cfg, seed, jnp.bfloat16)
        reqs = traffic.requests(wl["traffic"], 512, seed, 1.0)[:3]
        served = [(r.prompt, [int(t) for t in r.prompt[:8]]) for r in reqs]
        g = serving.served_gaps(run, params, served, fp8=True)
        miss.append(g["not_argmax"] / g["tokens"])
    assert min(miss) > wl["check"]["limits"]["argmax_miss"], miss


def test_mla_decode_roofline_reads_the_rounds_of_the_stretch():
    """The latent kernel's roofline share from a synthetic record: the
    ``step`` spans inside the traced stretch carry ``latent_positions``;
    a span outside the stretch is not read, nor a program without the
    counter."""
    import importlib.util

    import work
    import work_mla
    from tinycells import BENCH, CPU_PEAKS
    from tinycells_longctx import TINY_MLA

    spec = importlib.util.spec_from_file_location(
        "mla_decode_roofline",
        os.path.join(BENCH, "metrics", "mla_decode_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    spec = importlib.util.spec_from_file_location(
        "glm4moe_lite", os.path.join(BENCH, "configs", "glm4moe_lite.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    d = ref.dims(TINY_MLA)

    def step(t, positions=None):
        attrs = {"admitted": 0, "occupancy": 2}
        if positions is not None:
            attrs["latent_positions"] = positions
        return {"name": "step", "start": t, "end": t + 0.5, "attrs": attrs}

    record = {
        "trace": {"n_devices": 1, "window_s": 2.0, "busy_s": 1.0,
                  "ops": {"mla": {"seconds": 1e-3, "count": 2,
                                  "names": ["mla_decode_pallas.3"]}}},
        "traced_steps": [{"t0": 10.0, "t1": 10.6, "prefill": [],
                          "decode": [30, 40]},
                         {"t0": 11.0, "t1": 11.6, "prefill": [],
                          "decode": [31, 41]}],
        "spans": [step(5.0, 500), step(10.05, 70), step(11.05, 72)],
        "dims": d, "peaks": CPU_PEAKS}
    need = sum(work.roofline_seconds(*work_mla.mla_decode(d, n, 2),
                                     CPU_PEAKS) for n in (70, 72))
    assert reader.read(record) == pytest.approx(100 * need / 1e-3)
    record["spans"] = [step(10.05), step(11.05)]
    assert reader.read(record) is None


def _newest_position_dropped(monkeypatch):
    """Each decoded token attends to every cached position but its own."""
    import jax.numpy as jnp

    from repro.models import attention
    orig = attention.mla_decode_slots

    def altered(p, x_t, cfg, lat, rope, layer, pos, lengths, **kw):
        return orig(p, x_t, cfg, lat, rope, layer, pos,
                    jnp.maximum(lengths - 1, 1), **kw)

    monkeypatch.setattr(attention, "mla_decode_slots", altered)


def _rope_one_position_late(monkeypatch):
    """The decoded token's query and rope key rotated one position on."""
    from repro.models import attention
    orig = attention._mla_decode_inputs

    def altered(p, x_t, cfg, pos_arr):
        return orig(p, x_t, cfg, pos_arr + 1)

    monkeypatch.setattr(attention, "_mla_decode_inputs", altered)


@pytest.mark.parametrize("plant", [_newest_position_dropped,
                                   _rope_one_position_late],
                         ids=lambda f: f.__name__.strip("_"))
def test_planted_decode_fault_fails(tiny_bench, monkeypatch, plant):
    """A fault in the latent decode alone, the prefill left sound."""
    plant(monkeypatch)
    d, bench = tiny_bench
    out = run_tiny(d, bench, "tiny.longctx")
    assert not out["correct"], out["checks"]
