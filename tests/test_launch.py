"""Launcher-layer units: collective parser, mesh construction, memory floor,
and an end-to-end preemption (SIGTERM) resume through the real driver."""
import json
import os
import signal
import subprocess
import sys
import time

import pytest


def test_collective_parser_counts_and_widening():
    from repro.launch.dryrun import collective_bytes
    hlo = "\n".join([
        "  %all-reduce.1 = f32[16,128]{1,0} all-reduce(%x), replica_groups={}",
        "  %ar2 = (f32[4,4]{1,0}, f32[2]{0}) all-reduce(%a, %b)",
        "  %ag = bf16[8,256]{1,0} all-gather(%c), dimensions={0}",
        "  %w = f32[4,128]{1,0} all-reduce(%convert_fusion.3)",  # widened bf16
        "  %rs-start = f32[64]{0} reduce-scatter-start(%d)",
        "  %done = f32[64]{0} all-reduce-done(%rs)",             # skip -done
        "  %notacoll = f32[2]{0} add(%e, %f)",
    ])
    out = collective_bytes(hlo)
    expected_ar = 16 * 128 * 4 + (16 + 2) * 4 + 4 * 128 * 4 // 2
    assert out["all-reduce"] == expected_ar
    assert out["all-gather"] == 8 * 256 * 2
    assert out["all-reduce_widened"] == 4 * 128 * 2
    assert out["total"] == sum(v for k, v in out.items()
                               if k not in ("total",) and
                               not k.endswith("_widened"))


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX's own JAX_COMPILATION_CACHE_DIR wins untouched; without it the
    cache goes to .jax_cache/ at the repository root."""
    import jax
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")


def _refused(monkeypatch, capsys, chips: int, replicas: int) -> bool:
    import argparse
    from repro import configs
    from repro.launch import serve
    from repro.serve import worker
    monkeypatch.setattr(worker, "host_chips", lambda: chips)
    args = argparse.Namespace(workers=True, replicas=replicas)
    rc = serve.run_fleet(args, configs.smoke_config("llama3-8b"), None)
    return rc == 2 and "runs one worker process" in capsys.readouterr().out


def test_workers_refuse_fewer_chips_than_workers(monkeypatch, capsys):
    """On a TPU host with fewer chips than workers the fleet refuses
    before spawning any worker."""
    from repro.serve import worker
    assert worker.host_chips() is None         # this host has no TPU
    assert _refused(monkeypatch, capsys, chips=1, replicas=2)


def test_workers_refuse_many_on_tpu(monkeypatch, capsys):
    """libtpu lets one process at a time hold a host's chips: with a chip
    for each worker, more than one worker is refused all the same."""
    assert _refused(monkeypatch, capsys, chips=4, replicas=2)


def test_memory_floor_positive_all_cells():
    from repro import configs
    from repro.launch.dryrun import _memory_floor_bytes
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((16, 16), ("data", "model"))
    for arch in configs.list_archs():
        cfg = configs.get_config(arch)
        for shape in configs.applicable_shapes(cfg):
            fb = _memory_floor_bytes(cfg, shape, mesh, accum=4)
            assert fb > 0, (arch, shape)


def test_make_mesh_for_elastic_shapes():
    from repro.launch.mesh import make_mesh_for
    m = make_mesh_for(1)
    assert m.devices.size == 1


def test_train_attn_backend_flag(tmp_path):
    """--attn-backend plumbs through to the config and trains (ISSUE 2:
    the flash custom_vjp backward is exercised by real optimizer steps)."""
    env = {**os.environ, "PYTHONPATH": "src", "PYTHONUNBUFFERED": "1",
           "XLA_FLAGS": ""}
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "llama3-8b",
         "--smoke", "--steps", "2", "--batch", "2", "--seq", "32",
         "--attn-backend", "interpret", "--ckpt-dir", str(tmp_path),
         "--fresh", "--log-every", "1"],
        env=env, capture_output=True, text=True, timeout=480)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "attn backend: interpret" in out.stdout
    assert "step     1" in out.stdout


def test_preemption_sigterm_saves_and_resumes(tmp_path):
    env = {**os.environ, "PYTHONPATH": "src", "PYTHONUNBUFFERED": "1",
           "XLA_FLAGS": ""}  # don't inherit dryrun's 512 fake devices
    args = [sys.executable, "-m", "repro.launch.train", "--arch", "llama3-8b",
            "--smoke", "--steps", "500", "--batch", "2", "--seq", "32",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "1000",
            "--log-every", "1", "--fresh"]
    proc = subprocess.Popen(args, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    # wait until a couple of steps have logged, then preempt
    t0 = time.time()
    saw_step = False
    lines = []
    while time.time() - t0 < 480:
        line = proc.stdout.readline()
        if not line:                      # EOF: child died early
            break
        lines.append(line)
        if line.startswith("step     2"):
            saw_step = True
            break
    if not saw_step:
        proc.kill()
    assert saw_step, "trainer never reached step 2:\n" + "".join(lines[-20:])
    proc.send_signal(signal.SIGTERM)
    proc.wait(timeout=120)
    from repro.checkpointing.ckpt import CheckpointManager
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.latest_step() is not None, "no checkpoint after SIGTERM"
    # resume run picks it up and finishes quickly
    resume_args = [a if a != "500" else str(mgr.latest_step() + 2)
                   for a in args[:-1]]
    out = subprocess.run(resume_args, env=env, capture_output=True, text=True,
                         timeout=240)
    assert "resumed from step" in out.stdout


def test_watchdog_alert_emits_event_and_keeps_duration_sample(tmp_path):
    """ISSUE 9 satellite: the watchdog's monitor thread must not race
    ``step_end`` — an alerted step still records its duration (the old
    implementation cleared the shared latch mid-read and dropped the
    sample) — and each alert lands in the event stream, once per step."""
    from repro.events import EventSink, read_events
    from repro.launch.train import Watchdog

    ev = str(tmp_path / "events.jsonl")
    sink = EventSink(ev)
    wd = Watchdog(factor=5.0, min_history=3, sink=sink)
    try:
        wd.times = [0.01] * 5             # fast history
        wd.step_start()
        with wd._lock:                    # the step has "run" 30 s
            wd._started = time.time() - 30.0
        deadline = time.time() + 5.0
        while wd.alerts == 0 and time.time() < deadline:
            time.sleep(0.05)
        assert wd.alerts == 1, "watchdog never alerted"
        time.sleep(1.2)                   # > 2 monitor periods
        assert wd.alerts == 1             # one alert per step generation
        n = len(wd.times)
        wd.step_end()
        assert len(wd.times) == n + 1     # alerted step still sampled
        assert wd.times[-1] > 25.0
        wd.step_start()                   # new generation re-arms
        with wd._lock:
            wd._started = time.time() - 30.0
        deadline = time.time() + 5.0
        while wd.alerts < 2 and time.time() < deadline:
            time.sleep(0.05)
        assert wd.alerts == 2
        wd.step_end()
    finally:
        wd.close()
        sink.close()
    alerts = read_events(ev, kind="watchdog_alert")
    assert len(alerts) == 2
    assert alerts[0]["factor"] == 5.0 and alerts[0]["running_s"] > 25.0


def test_watchdog_step_boundary_race(tmp_path):
    """Hammer step boundaries from the main thread while the monitor
    polls: no sample may be lost and no crash may surface regardless of
    interleaving (lock + generation counter)."""
    from repro.launch.train import Watchdog

    wd = Watchdog(factor=1000.0, min_history=2)
    try:
        for _ in range(300):
            wd.step_start()
            wd.step_end()
        assert len(wd.times) == 100       # rolling window, none dropped
        assert wd.alerts == 0
    finally:
        wd.close()
