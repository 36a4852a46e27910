"""Observability plane (ISSUE 10): metrics registry, request tracing,
schema closure, memory sampling, and the tracelens timeline exporter.

The acceptance scenario lives in :class:`TestFleetTrace`: a traced,
journaled fleet run takes a replica SIGKILL (request migration) and a
whole-router crash + journal recovery, and ``tools/tracelens.py`` must
reconstruct a complete per-request timeline — segments summing exactly
to the end-to-end span — plus a valid Perfetto export, with compile
counts frozen throughout (all instrumentation is host-side).
"""
from __future__ import annotations

import importlib.util
import json
import math
import os

import jax
import numpy as np
import pytest

from repro import configs
from repro.events import EventSink, read_events
from repro.models import transformer
from repro.obs import (EVENT_KINDS, SPAN_NAMES, Histogram, MemStat,
                       MetricsRegistry, Tracer, hist_quantile, maybe_span)
from repro.obs.schema import (CALL_SPANS, undeclared_kinds_in_source,
                              validate_events)
from repro.serve import (DONE, TERMINAL, RequestJournal, Router,
                         ServeEngine)

_TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")
_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _load_tracelens():
    spec = importlib.util.spec_from_file_location(
        "tracelens", os.path.join(_TOOLS, "tracelens.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tracelens = _load_tracelens()


# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counters_and_gauges(self):
        r = MetricsRegistry()
        r.inc("a")
        r.inc("a", 4)
        r.set("g", 2.5)
        assert r.count("a") == 5
        assert r.count("missing") == 0
        snap = r.snapshot()
        assert snap["counters"]["a"] == 5
        assert snap["gauges"]["g"] == {"value": 2.5, "updates": 1}

    def test_histogram_exact_moments_bounded_buckets(self):
        h = Histogram()
        vals = [0.001 * (i % 97 + 1) for i in range(10_000)]
        for v in vals:
            h.observe(v)
        assert h.n == 10_000
        assert h.mean == pytest.approx(sum(vals) / len(vals))
        assert h.min == pytest.approx(min(vals))
        assert h.max == pytest.approx(max(vals))
        # bounded memory: log2 buckets, never per-sample storage
        assert len(h.counts) < 20
        # quantiles: monotone, clamped to [min, max], 2x relative error
        q50, q95 = h.quantile(0.5), h.quantile(0.95)
        assert h.min <= q50 <= q95 <= h.max
        exact = sorted(vals)[5000]
        assert q50 / exact < 2.0 and exact / q50 < 2.0

    def test_histogram_adversarial_values(self):
        h = Histogram()
        for v in (0.0, -1.0, math.inf, 1e-300, 1e300):
            h.observe(v)
        assert h.n == 5
        assert h.quantile(0.5) >= h.min
        snap = h.to_dict()
        assert json.loads(json.dumps(snap)) == snap   # JSON-safe keys

    def test_merge_commutative_associative(self):
        regs = []
        for seed in range(3):
            r = MetricsRegistry()
            rng = np.random.RandomState(seed)
            for _ in range(50):
                r.inc("n", int(rng.randint(1, 5)))
                r.observe("lat", float(rng.exponential(0.01)))
            r.set("last", float(seed))
            regs.append(r.snapshot())
        a, b, c = regs
        m = MetricsRegistry.merge
        assert m(a, b) == m(b, a)
        assert m(m(a, b), c) == m(a, m(b, c))
        fused = m(m(a, b), c)
        assert fused["counters"]["n"] == sum(
            r["counters"]["n"] for r in regs)
        assert fused["hists"]["lat"]["n"] == 150
        # gauge winner: most updates, deterministic either order
        assert fused["gauges"]["last"]["updates"] == 1

    def test_merge_empty_histogram_placeholders(self):
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        r1.histogram("h")                      # empty: min/max placeholders
        r2.observe("h", 5.0)
        for first, second in ((r1, r2), (r2, r1)):
            out = MetricsRegistry.merge(first.snapshot(), second.snapshot())
            assert out["hists"]["h"]["min"] == 5.0
            assert out["hists"]["h"]["max"] == 5.0
            assert out["hists"]["h"]["n"] == 1

    def test_hist_quantile_on_snapshot(self):
        r = MetricsRegistry()
        for v in (1.0, 2.0, 3.0, 4.0):
            r.observe("x", v)
        h = r.snapshot()["hists"]["x"]
        assert hist_quantile(h, 0.0) >= 1.0
        assert hist_quantile(h, 1.0) == 4.0
        assert hist_quantile({"n": 0, "counts": {}}, 0.5) == 0.0

    def test_emit_snapshot_event(self, tmp_path):
        p = str(tmp_path / "m.jsonl")
        sink = EventSink(p)
        r = MetricsRegistry()
        r.inc("c", 3)
        r.emit(sink, step=7)
        sink.close()
        (rec,) = read_events(p)
        assert rec["kind"] == "metrics_snapshot"
        assert rec["step"] == 7
        assert rec["snapshot"]["counters"]["c"] == 3


# ---------------------------------------------------------------------------
class TestTracer:
    def test_pairing_and_attrs(self, tmp_path):
        p = str(tmp_path / "t.jsonl")
        sink = EventSink(p)
        tr = Tracer(sink, pid="w")
        sid = tr.begin("req", trace=4, rid=4)
        with tr.span("queue", trace=4, parent=sid, reason="submit"):
            pass
        tr.end(sid, state="DONE")
        tr.end(None)                       # late-attach no-op
        sink.close()
        closed, open_ = tracelens.load_spans(p)
        assert open_ == []
        assert [s["name"] for s in closed] == ["queue", "req"]
        req = closed[1]
        assert req["pid"] == "w" and req["trace"] == 4
        assert req["attrs"]["state"] == "DONE"
        assert closed[0]["parent"] == req["sid"]
        assert req["dur"] >= closed[0]["dur"] >= 0.0

    def test_undeclared_span_name_rejected(self, tmp_path):
        sink = EventSink(str(tmp_path / "t.jsonl"))
        with pytest.raises(ValueError, match="undeclared span name"):
            Tracer(sink).begin("not_a_span")
        sink.close()

    def test_maybe_span_none_tracer(self):
        with maybe_span(None, "req"):
            pass                            # nullcontext, no error


# ---------------------------------------------------------------------------
class TestSchema:
    def test_source_tree_emits_only_declared_kinds(self):
        bad = undeclared_kinds_in_source(_SRC)
        assert bad == {}, f"undeclared event kinds: {bad}"

    def test_span_names_closed_world(self):
        assert set(SPAN_NAMES) >= {"req", "queue", "prefill", "decode",
                                   "fleet_req", "migrate", "recover",
                                   "rpc", "journal_append", "train_step"}
        assert {"span_begin", "span_end", "metrics_snapshot",
                "mem_sample"} <= set(EVENT_KINDS)


# ---------------------------------------------------------------------------
class TestMemStat:
    def test_sample_and_banner(self, tmp_path):
        p = str(tmp_path / "m.jsonl")
        sink = EventSink(p)
        reg = MetricsRegistry()
        ms = MemStat(sink=sink, registry=reg, plan_bytes=2**20)
        _keep = jax.numpy.zeros((128, 128))   # something must be live
        rec = ms.sample(3)
        sink.close()
        assert rec["step"] == 3
        assert rec["live_bytes"] > 0 and rec["n_arrays"] > 0
        assert rec["plan_bytes"] == 2**20
        assert rec["frac_of_plan"] == pytest.approx(
            rec["live_bytes"] / 2**20, abs=1e-3)
        (ev,) = read_events(p)
        assert ev["kind"] == "mem_sample"
        assert reg.snapshot()["gauges"]["mem.live_bytes"]["value"] > 0
        assert "plan" in ms.banner()
        del _keep


# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def llama():
    cfg = configs.smoke_config("llama3-8b")
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def engines_mod(llama):
    cfg, params = llama
    out = []
    for _ in range(2):
        e = ServeEngine(params, cfg, max_slots=2, max_len=32,
                        prompt_buckets=(16,), sampler_keys="request")
        e.warmup()
        out.append(e)
    return out


def _reset(engines):
    for e in engines:
        e.reset()
        e.hooks.clear()
        e.tracer = None
    return engines


def _prompts(n=6, seed=0):
    rng = np.random.RandomState(seed)
    vocab = configs.smoke_config("llama3-8b").vocab
    return [rng.randint(1, vocab, size=rng.randint(4, 9)).astype(np.int32)
            for _ in range(n)]


def _force_drain(engines):
    for e in engines:
        for rid, st in list(e.request_states().items()):
            if st["state"] not in TERMINAL:
                e.evict_request(rid)
        e.reset()


MAX_NEW = 8


class _ListSink:
    def __init__(self):
        self.events = []

    def emit(self, kind, **fields):
        self.events.append((kind, fields))


class TestEngineTrace:
    def test_traced_run_complete_chains_zero_recompiles(
            self, engines_mod, tmp_path):
        eng = _reset(engines_mod)[0]
        compiles = eng.compile_counts()
        p = str(tmp_path / "eng.jsonl")
        sink = EventSink(p)
        eng.tracer = Tracer(sink, pid="r0")
        rids = [eng.submit(pr, MAX_NEW) for pr in _prompts(4)]
        guard = 200
        while eng.scheduler.has_work() and guard:
            eng.step()
            guard -= 1
        assert guard
        eng.tracer = None
        sink.close()
        assert eng.compile_counts() == compiles   # host-side only
        assert validate_events(p) == set()
        closed, open_ = tracelens.load_spans(p)
        assert open_ == []
        groups = tracelens.by_trace(closed)
        for rid in rids:
            names = [s["name"] for s in groups[rid]]
            assert names.count("req") == 1
            assert names.count("queue") >= 1
            assert names.count("prefill") == 1
            assert names.count("decode") >= 1
            root = tracelens._root(groups[rid])
            assert root["attrs"]["state"] == "DONE"
            segs = tracelens.segments(groups[rid], root)
            assert sum(s["dur"] for s in segs) == \
                pytest.approx(root["dur"], rel=1e-9)

    def test_step_phases_in_order(self, engines_mod):
        """One step that admits and decodes: ``step`` holds its phases in
        order, each parented on it, and ends with the prefill counters."""
        eng = _reset(engines_mod)[0]
        sink = _ListSink()
        eng.tracer = Tracer(sink, pid="r0")
        eng.submit(_prompts(1)[0], MAX_NEW)
        eng.step()
        eng.tracer = None
        begins = [f for kind, f in sink.events if kind == "span_begin"]
        ends = {f["sid"]: f for kind, f in sink.events if kind == "span_end"}
        (step,) = [b for b in begins if b["name"] == "step"]
        phases = [(b["name"], b.get("what")) for b in begins
                  if b["parent"] == step["sid"]]
        assert phases == [("admit", None), ("dispatch", "prefill"),
                          ("dispatch", "scatter"), ("sync", "first_token"),
                          ("emit", None), ("dispatch", "decode"),
                          ("sync", "decode"), ("emit", None)]
        assert all(b["sid"] in ends for b in begins
                   if b["parent"] == step["sid"])
        plen = len(_prompts(1)[0])
        end = ends[step["sid"]]
        assert end["prefill_tokens"] == plen
        assert end["prefill_padded"] == 16 - plen
        assert end["admitted"] == 1
        # the phases tile the step, one after another
        ts = [(b["ts"], ends[b["sid"]]["ts"]) for b in begins
              if b["parent"] == step["sid"]]
        assert all(a[1] <= b[0] for a, b in zip(ts, ts[1:]))
        assert step["ts"] <= ts[0][0] and ts[-1][1] <= end["ts"]
        _force_drain([eng])

    def test_untraced_step_emits_and_annotates_nothing(self, engines_mod,
                                                       monkeypatch):
        from repro.obs import trace as trace_mod
        made = []

        class Spy:
            def __init__(self, *a, **kw):
                made.append(a)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return None

        monkeypatch.setattr(trace_mod, "TraceAnnotation", Spy)
        eng = _reset(engines_mod)[0]
        for pr in _prompts(2, seed=5):
            eng.submit(pr, 3)
        while eng.scheduler.has_work():
            eng.step()
        assert made == []
        # the same steps traced open one annotation per call span
        sink = _ListSink()
        eng.tracer = Tracer(sink, pid="r0")
        eng.submit(_prompts(1)[0], 2)
        while eng.scheduler.has_work():
            eng.step()
        eng.tracer = None
        calls = [f["name"] for kind, f in sink.events
                 if kind == "span_begin" and f["name"] in CALL_SPANS]
        assert sorted(a[0] for a in made) == \
            sorted("repro." + n for n in calls)

    def test_compiles_after_warmup_are_spans(self, engines_mod):
        eng = _reset(engines_mod)[0]
        compiles = eng.compile_counts()
        sink = _ListSink()
        eng.tracer = Tracer(sink, pid="r0")
        for pr in _prompts(2, seed=7):
            eng.submit(pr, MAX_NEW)
        while eng.scheduler.has_work():
            eng.step()
        assert eng.compile_counts() == compiles
        names = [f["name"] for kind, f in sink.events if kind == "span_begin"]
        assert "compile" not in names      # steady state compiles nothing
        # a shape the warm-up never saw compiles once, as one span
        eng._sampler(np.zeros((3, eng.cfg.padded_vocab), np.float32), eng._key)
        begins = [f for kind, f in sink.events
                  if kind == "span_begin" and f["name"] == "compile"]
        ends = [f for kind, f in sink.events
                if kind == "span_end" and f["sid"] in
                {b["sid"] for b in begins}]
        assert len(begins) == len(ends) == 1
        assert ends[0]["source"] == "backend" and ends[0]["seconds"] > 0
        # detached, later compiles leave no span
        eng.tracer = None
        n = len(sink.events)
        eng._sampler(np.zeros((5, eng.cfg.padded_vocab), np.float32), eng._key)
        assert len(sink.events) == n

    def test_metrics_state_is_o_live(self, engines_mod):
        eng = _reset(engines_mod)[0]
        for pr in _prompts(4, seed=3):
            eng.submit(pr, MAX_NEW)
        guard = 200
        while eng.scheduler.has_work() and guard:
            eng.step()
            guard -= 1
        assert guard
        assert eng.metrics._live == {}            # everything retired
        s = eng.metrics.summary()
        assert s["n_done"] == 4
        assert s["ttft_p95_s"] >= s["ttft_p50_s"] > 0


# ---------------------------------------------------------------------------
class TestFleetTrace:
    """The acceptance scenario: migration + journal recovery, traced."""

    @pytest.fixture(scope="class")
    def traced_run(self, engines_mod, tmp_path_factory):
        tmp = tmp_path_factory.mktemp("obs_fleet")
        ep, jp = str(tmp / "events.jsonl"), str(tmp / "wal.jsonl")
        sink = EventSink(ep)

        def wire(router, journal, engines):
            for i, e in enumerate(engines):
                e.tracer = Tracer(sink, pid=f"r{i}")
            router.tracer = Tracer(sink, pid="router")
            journal.tracer = Tracer(sink, pid="journal")

        compiles = [e.compile_counts() for e in engines_mod]
        # -- epoch 1: journaled run; replica 0 dies; router crashes ----
        j1 = RequestJournal(jp)
        r1 = Router(_reset(engines_mod), journal=j1)
        wire(r1, j1, engines_mod)
        gids = [r1.submit(pr, MAX_NEW) for pr in _prompts()]
        for _ in range(3):
            r1.step()
        assert r1.kill(0)                  # replica crash -> migrations
        migrated = [g for g in gids if r1.request(g).migrations > 0]
        assert migrated, "kill must migrate at least one live request"
        for _ in range(2):
            r1.step()
        assert r1.live_requests() > 0, "must crash mid-flight"
        snap1 = r1.registry_snapshot()
        del r1                             # kill -9: no goodbye
        _force_drain(engines_mod)
        j1.close()

        # -- epoch 2: fresh router recovers from the journal -----------
        j2 = RequestJournal(jp)
        r2 = Router(_reset(engines_mod), journal=j2)
        wire(r2, j2, engines_mod)
        info = r2.recover()
        assert info["n_recovered"] > 0
        guard = 600
        while r2.live_requests() > 0 and guard:
            r2.step()
            guard -= 1
        assert guard
        states = {g: r2.request(g).state for g in gids}
        snap = r2.registry_snapshot()
        rec = r2.reconcile()
        for e in engines_mod:
            e.tracer = None
        j2.close()
        sink.close()
        assert rec["ok"], rec
        assert [e.compile_counts() for e in engines_mod] == compiles
        return {"events": ep, "gids": gids, "migrated": migrated,
                "recovered": info["n_recovered"], "states": states,
                "registry": snap, "registry_precrash": snap1}

    def test_schema_clean(self, traced_run):
        assert validate_events(traced_run["events"]) == set()

    def test_every_done_request_has_one_complete_chain(self, traced_run):
        closed, _open = tracelens.load_spans(traced_run["events"])
        groups = tracelens.by_trace(closed)
        for g in traced_run["gids"]:
            if traced_run["states"][g] != DONE:
                continue
            roots = [s for s in groups[g] if s["name"] == "fleet_req"
                     and s["attrs"].get("state") == DONE]
            assert len(roots) == 1, \
                f"gid {g}: want exactly one closed DONE root"
            assert roots[0]["attrs"]["tokens"] == MAX_NEW

    def test_crash_leaves_open_spans_visible(self, traced_run):
        _closed, open_ = tracelens.load_spans(traced_run["events"])
        # the crashed router's fleet_req spans died open — the timeline
        # SHOWS the crash instead of losing it
        assert any(s["name"] == "fleet_req" for s in open_)

    def test_migrated_timeline_has_migrate_segment(self, traced_run):
        closed, _ = tracelens.load_spans(traced_run["events"])
        groups = tracelens.by_trace(closed)
        names = {n for g in traced_run["migrated"]
                 for n in (s["name"] for s in groups.get(g, []))}
        assert "migrate" in names

    def test_recovered_timeline_segments_sum_exact(self, traced_run):
        closed, _ = tracelens.load_spans(traced_run["events"])
        groups = tracelens.by_trace(closed)
        checked = 0
        for g, spans in groups.items():
            roots = [s for s in spans if s["name"] == "fleet_req"
                     and s["attrs"].get("replay")]
            for root in roots:
                segs = tracelens.segments(spans, root)
                assert sum(s["dur"] for s in segs) == \
                    pytest.approx(root["dur"], rel=1e-9)
                checked += 1
        assert checked > 0, "no recovered root spans found"

    def test_journal_and_rpc_lanes_present(self, traced_run):
        closed, _ = tracelens.load_spans(traced_run["events"])
        names = {s["name"] for s in closed}
        assert "journal_append" in names
        assert "queue" in names and "prefill" in names

    def test_perfetto_export_valid(self, traced_run, tmp_path):
        closed, open_ = tracelens.load_spans(traced_run["events"])
        doc = tracelens.perfetto(closed, open_)
        ev = doc["traceEvents"]
        assert len(ev) == len(closed) + len(open_) + \
            len({s["pid"] for s in closed + open_})
        for e in ev:
            assert e["ph"] in ("M", "B", "X")
            if e["ph"] == "X":
                assert e["dur"] >= 0 and e["ts"] >= 0
        json.dumps(doc)                    # serializable end to end
        lanes = {e["args"]["name"] for e in ev if e["ph"] == "M"}
        assert {"router", "journal", "r0", "r1"} <= lanes

    def test_fleet_registry_merges_replicas(self, traced_run):
        # the crashed router's registry held the kill's failover counts
        pre = traced_run["registry_precrash"]
        assert pre["counters"]["fleet.failovers"] >= 1
        assert pre["counters"]["fleet.migrations"] >= 1
        # recovery router: per-replica serve counters + streaming hists
        # folded in through the same order-independent merge
        snap = traced_run["registry"]
        assert snap["counters"]["serve.submitted"] > 0
        assert snap["hists"]["serve.ttft_s"]["n"] > 0
        # both sides merge cleanly into one whole-history view
        whole = MetricsRegistry.merge(pre, snap)
        assert whole["counters"]["fleet.failovers"] == \
            pre["counters"]["fleet.failovers"]

    def test_latency_table_and_gantt_render(self, traced_run):
        closed, open_ = tracelens.load_spans(traced_run["events"])
        table = tracelens.latency_table(closed)
        assert "p95 ms" in table and "fleet_req" in table
        g = tracelens.gantt(closed + open_)
        assert "requests" in g
