"""The per-layer metrics read from the program's own spans, counters and
named scopes (``program_metrics.json``): each reader on a synthetic
record, ``program_trace.reduce`` on a recorded CPU trace, and the tiny
cells end to end through ``tools/program_metrics.py``'s additions."""
from __future__ import annotations

import json
import os
import time

import pytest

from tinycells import BENCH, run_tiny, tiny_bench  # noqa: F401

import bench as harness


def _reader(name, bench_dir=BENCH):
    return harness.load_module(os.path.join(bench_dir, "metrics",
                                            name + ".py"))


def _tool():
    return harness.load_module(os.path.join(BENCH, "tools",
                                            "program_metrics.py"))


def _span(name, start, end, **attrs):
    return {"name": name, "start": start, "end": end, "attrs": attrs}


def _ops(**scoped):
    """A reduced trace whose operations ran under ``jit(f)/<scope>/op``."""
    return {"program_scopes": {f"jit(f)/{path}/op": sec
                               for path, sec in scoped.items()},
            "n_devices": 1, "busy_s": 2.0, "window_s": 4.0}


SPANS = [
    _span("step", 0.0, 1.0, prefill_tokens=300, prefill_padded=212),
    _span("admit", 0.0, 0.1), _span("prefill", 0.1, 0.4, plen=300),
    _span("step", 1.0, 1.1, prefill_tokens=0, prefill_padded=0),
    _span("prefill", 2.0, 2.5, plen=200),        # overlaps a pause
    _span("compile", 3.0, 3.2, source="backend"),
]


@pytest.mark.parametrize("name,record,want", [
    ("prefill_ms_per_ktok.chat",
     {"spans": SPANS, "pauses": [(2.2, 2.3)]}, 1000.0),
    ("prefill_ms_per_ktok.chat", {"spans": SPANS[:2]}, None),
    ("prefill_pad_share.chat", {"spans": SPANS}, 100.0 * 212 / 512),
    ("prefill_pad_share.chat",
     {"spans": [_span("step", 0, 1, admitted=0)]}, None),
    ("idle_host_share.chat",
     {"trace": {"program_spans": [_span("step", 0, 1)], "n_devices": 1,
                "window_s": 4.0,
                "program_gaps": [(0.2, "emit"), (0.1, "sync"),
                                 (0.1, "step"), (0.05, "(none)")]}}, 7.5),
    ("idle_host_share.chat",
     {"trace": {"program_spans": [], "program_gaps": [(0.2, "(none)")],
                "n_devices": 1, "window_s": 4.0}}, None),
    ("compiles_after_warmup.chat", {"spans": SPANS}, 1),
    ("compiles_after_warmup.chat", {"spans": SPANS[:1]}, None),
    ("sentinel_share.chat",
     {"trace": _ops(sentinel=0.1, lm_head=0.5)}, 5.0),
    ("sentinel_share.chat", {"trace": _ops(lm_head=0.5)}, 0.0),
    ("sentinel_share.chat", {"trace": _ops(layers=0.5)}, None),
    ("lm_head_loss_ms.train",
     {"trace": _ops(**{"lm_head_loss": 0.06,
                       "transpose(jvp(lm_head_loss))": 0.1,
                       "lm_head": 1.0}), "traced_steps": 4}, 40.0),
    ("optimizer_ms.train",
     {"trace": _ops(optimizer=0.2, lm_head_loss=0.1), "traced_steps": 4},
     50.0),
    ("optimizer_ms.train",
     {"trace": _ops(optimizer=0.2), "traced_steps": 0}, None),
])
def test_reader_on_synthetic_record(name, record, want):
    got = _reader(name).read(record)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_metric_entries_are_well_formed():
    with open(os.path.join(BENCH, "program_metrics.json")) as f:
        extra = json.load(f)["per_layer"]
    with open(os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                           "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    listed = {m["name"] for m in benchmark["per_layer"]}
    e2e = {m["name"]: m for m in benchmark["end_to_end"]}
    keys = {"name", "unit", "better", "source", "layer", "moves",
            "workloads"}
    for m in extra:
        assert set(m) == keys and m["name"] not in listed
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter")
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"])
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


def _proto(*fields) -> bytes:
    """A protobuf message from ``(field number, value)`` pairs: an int is
    a varint, bytes or str a length-delimited field, a list packed ints."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += varint(num << 3) + varint(value)
            continue
        if isinstance(value, list):
            value = b"".join(varint(v) for v in value)
        elif isinstance(value, str):
            value = value.encode()
        out += varint(num << 3 | 2) + varint(len(value)) + value
    return out


def test_unnamed_instructions_take_their_root_or_operand_name():
    """XLA leaves a multi-output fusion and an inserted copy without an
    ``op_name``: the fusion is named by its root (a tuple, named by its
    first operand), the copy by what it copies."""
    import program_trace

    def inst(i, name, op="", operands=(), called=()):
        fields = [(1, name), (35, i)]
        if op:
            fields.append((7, _proto((2, op))))
        if operands:
            fields.append((36, list(operands)))
        if called:
            fields.append((38, list(called)))
        return _proto(*fields)

    fused = _proto((1, "fused_computation"), (5, 1), (6, 11),
                   (2, inst(10, "reduce.1", "jit(f)/sentinel/reduce_and")),
                   (2, inst(12, "select.1", "jit(f)/lm_head/select_n")),
                   (2, inst(11, "tuple.1", operands=(10, 12))))
    entry = _proto((1, "main"), (5, 2), (6, 21),
                   (2, inst(22, "param.0", "cache")),
                   (2, inst(20, "fusion.71", called=(1,), operands=(22,))),
                   (2, inst(21, "copy.3", operands=(20,))))
    names = program_trace._module_op_names(_proto((3, fused), (3, entry)))
    assert names == {"reduce.1": "jit(f)/sentinel/reduce_and",
                     "select.1": "jit(f)/lm_head/select_n",
                     "tuple.1": "jit(f)/sentinel/reduce_and",
                     "param.0": "cache",
                     "fusion.71": "jit(f)/sentinel/reduce_and",
                     "copy.3": "jit(f)/sentinel/reduce_and"}


@pytest.fixture(scope="module")
def recorded_trace(tmp_path_factory):
    """A CPU profiler trace of four engine steps under a ``window``
    annotation, with a 60 ms host sleep planted in one step's ``emit``
    phase (the emit of a decoded token)."""
    import jax
    import numpy as np

    from repro import configs
    from repro.models import transformer
    from repro.obs import Tracer
    from repro.serve import ServeEngine

    import serving

    cfg = configs.smoke_config("llama3-8b")
    eng = ServeEngine(transformer.init_params(cfg, jax.random.PRNGKey(0)),
                      cfg, max_slots=2, max_len=32, prompt_buckets=(16,))
    eng.warmup()
    sink = serving.ListSink()
    eng.tracer = Tracer(sink, pid="engine")
    eng.submit(np.arange(1, 9, dtype=np.int32), 6)
    emit = eng._emit
    slept = []

    def slow_emit(req, tok):
        if len(req.tokens) == 2 and not slept:
            time.sleep(0.06)
            slept.append(True)
        emit(req, tok)

    eng._emit = slow_emit
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(4):
            eng.step()
    jax.profiler.stop_trace()
    assert slept
    import trace_reduce
    return trace_reduce.find_trace(d)


def test_program_trace_labels_planted_sleep(recorded_trace):
    import program_trace
    got = program_trace.reduce(recorded_trace, platform="cpu")
    names = [s["name"] for s in got["program_spans"]]
    assert names.count("step") == 4
    assert {"admit", "dispatch", "sync", "emit", "prefill"} <= set(names)
    assert {s["attrs"].get("what") for s in got["program_spans"]
            if s["name"] == "dispatch"} == {"prefill", "scatter", "decode"}
    seconds, label = got["program_gaps"][0]
    assert label == "emit" and seconds >= 0.05
    # device time by op_name, read through the trace's HLO modules
    scoped = {scope: program_trace.scope_seconds(got, scope)
              for scope in ("prefill", "scatter", "layers", "lm_head",
                            "sample", "sentinel")}
    assert all(sec > 0 for sec in scoped.values()), scoped
    assert sum(got["program_scopes"].values()) <= \
        trace_reduce_busy(recorded_trace) + 1e-9


def trace_reduce_busy(path):
    import trace_reduce
    ops = trace_reduce.reduce(path, platform="cpu")["ops"]
    return sum(rec["seconds"] for rec in ops.values())


def test_added_keys_leave_the_reduction_unchanged(recorded_trace):
    import trace_reduce
    plain = trace_reduce.reduce(recorded_trace, platform="cpu")
    both = _tool().reduce_with_program(recorded_trace, platform="cpu")
    assert set(both) == set(plain) | {"program_spans", "program_gaps",
                                      "program_scopes"}
    for key in ("ops", "gaps", "busy_s", "window_s", "n_devices"):
        assert both[key] == plain[key]
    assert trace_reduce.breakdown(both) == trace_reduce.breakdown(plain)


#: the tiny cells that stand for each cell of BENCHMARK.json
TINY = {"glm4-9b-serve.chat": ["tiny.chat"],
        "glm4-9b-train.seq4k": ["tiny.train", "tiny.long"]}


@pytest.mark.parametrize("cell,read,missing", [
    ("tiny.chat",
     {"queue_wait_p95_ms", "decode_step_p50_ms", "mfu.chat",
      "idle_share.chat", "prefill_ms_per_ktok.chat",
      "prefill_pad_share.chat", "idle_host_share.chat",
      "compiles_after_warmup.chat", "sentinel_share.chat"},
     {"kvq_decode_roofline"}),
    ("tiny.train", {"mfu.train", "idle_share.train",
                    "lm_head_loss_ms.train", "optimizer_ms.train"},
     {"train_peak_over_plan", "flash_train_roofline"}),
])
def test_tiny_cell_reads_program_metrics(tiny_bench, monkeypatch, cell,
                                         read, missing):
    """Every program metric reads on the CPU; the kernels' rooflines stay
    silent, as the CPU trace has no Pallas kernel."""
    import trace_reduce

    d, benchmark = tiny_bench
    tool = _tool()
    extra = tool.with_program_metrics({"per_layer": []}, d)["per_layer"]
    benchmark = dict(benchmark, per_layer=benchmark["per_layer"] + [
        dict(m, workloads=[t for w in m["workloads"] for t in TINY[w]])
        for m in extra])
    monkeypatch.setattr(trace_reduce, "reduce", tool.reduce_with_program)
    out = run_tiny(d, benchmark, cell, trace=True)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == read
    assert set(out["missing"]) == missing
    if cell == "tiny.chat":
        m = out["metrics"]
        assert m["compiles_after_warmup.chat"]["value"] == 0
        assert 0 < m["prefill_pad_share.chat"]["value"] < 100
        assert m["prefill_ms_per_ktok.chat"]["value"] > 0
        assert 0 <= m["idle_host_share.chat"]["value"] \
            <= m["idle_share.chat"]["value"]
        assert 0 < m["sentinel_share.chat"]["value"] < 100
    else:
        m = out["metrics"]
        assert m["lm_head_loss_ms.train"]["value"] > 0
        assert m["optimizer_ms.train"]["value"] > 0
