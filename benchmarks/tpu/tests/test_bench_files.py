"""The benchmark's data files: each configuration and cell states what it
is, ``BENCHMARK.json`` finds every file by name, and a new cell is a new
workload file and a new metric file with no existing file edited."""
from __future__ import annotations

import hashlib
import json
import os

import pytest

from tinycells import BENCH, ROOT, run_tiny, tiny_bench  # noqa: F401

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("entry", BENCHMARK["configs"],
                         ids=lambda e: e["name"])
def test_config_states_source_cuts_and_assumptions(entry):
    cfg = _load("configs", entry["name"] + ".json")
    assert entry["file"] == f"benchmarks/tpu/configs/{entry['name']}.json"
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert isinstance(cfg["assumed"], dict) and cfg["deployment"]
    # every key cut is stated with its published value, and differs
    for key in cfg["reduced"]:
        assert cfg["published"][key] != cfg[key]
    assert os.path.exists(os.path.join(BENCH, "configs",
                                       cfg["reference"] + ".py"))


WORKLOAD_FILES = sorted(f[:-len(".json")] for f in
                        os.listdir(os.path.join(BENCH, "workloads")))


@pytest.mark.parametrize("name", WORKLOAD_FILES)
def test_workload_states_why_and_finds_its_files(name):
    """Every workload file, listed in BENCHMARK.json or not yet, names its
    configuration, driver, why and limits; a listed one agrees with its
    entry and reports setup_s, another end-to-end metric and a per-layer
    metric."""
    wl = _load("workloads", name + ".json")
    assert wl["name"] == name and len(wl["why"]) <= 200
    assert os.path.exists(os.path.join(BENCH, "configs",
                                       wl["config"] + ".json"))
    assert os.path.exists(os.path.join(BENCH, "drivers",
                                       wl["driver"] + ".py"))
    assert wl["check"]["limits"]
    entry = {w["name"]: w for w in BENCHMARK["workloads"]}.get(name)
    if entry is None:
        return
    assert wl["config"] == entry["config"] and wl["chips"] == entry["chips"]
    assert wl["why"] == entry["why"]
    reported = [m["name"] for m in BENCHMARK["end_to_end"]
                if name in m.get("workloads", [name])]
    assert "setup_s" in reported and len(reported) >= 2
    assert any(name in m["workloads"] for m in BENCHMARK["per_layer"])


@pytest.mark.parametrize("entry", BENCHMARK["per_layer"],
                         ids=lambda e: e["name"])
def test_every_per_layer_metric_has_a_reader(entry):
    assert os.path.exists(os.path.join(BENCH, "metrics",
                                       entry["name"] + ".py"))
    moves = {m["name"]: m for m in BENCHMARK["end_to_end"]}[entry["moves"]]
    assert set(entry["workloads"]) <= set(moves["workloads"])


def test_peaks_are_keyed_by_device_kind():
    peaks = _load("peaks.json")
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16 * 2 ** 30
    assert "cpu" not in peaks["devices"]


def _digest(directory):
    out = {}
    for dirpath, _, files in os.walk(directory):
        if "__pycache__" in dirpath:
            continue
        for name in files:
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, directory)] = \
                    hashlib.sha256(f.read()).hexdigest()
    return out


def test_new_cell_from_new_files_only(tiny_bench):
    """A later change adds a workload file, a metric file and their
    entries: the harness finds and runs them, and no file that was there
    changes."""
    d, bench = tiny_bench
    before = _digest(d)
    with open(os.path.join(d, "workloads", "tiny.chat.json")) as f:
        wl = json.load(f)
    wl.update(name="tiny.bursty", why="a later cell")
    wl["traffic"] = dict(wl["traffic"], rate_per_s=20)
    with open(os.path.join(d, "workloads", "tiny.bursty.json"), "w") as f:
        json.dump(wl, f)
    with open(os.path.join(d, "metrics", "steps_per_request.py"), "w") as f:
        f.write("def read(record):\n"
                "    return len(record['traced_steps']) / max(1, sum(\n"
                "        len(s['prefill']) for s in record['traced_steps']))\n")
    bench = dict(bench)
    bench["end_to_end"] = [dict(m, workloads=m["workloads"] + ["tiny.bursty"])
                           if m["name"] in ("ttft_p95_ms", "itl_p99_ms")
                           else m for m in bench["end_to_end"]]
    bench["per_layer"] = bench["per_layer"] + [
        {"name": "steps_per_request", "unit": "steps", "moves": "ttft_p95_ms",
         "workloads": ["tiny.bursty"]}]
    out = run_tiny(d, bench, "tiny.bursty", trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["steps_per_request"]["value"] > 0
    after = _digest(d)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "workloads/tiny.bursty.json", "metrics/steps_per_request.py"}
