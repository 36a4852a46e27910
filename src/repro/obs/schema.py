"""The single registry of event kinds and span names.

Every ``kind`` that can appear in a ``repro.events`` JSONL stream is
declared here — CI scans the source tree for literal emit callsites and
fails on any kind that is not in :data:`EVENT_KINDS` (see
``tools/ci_ratchet.py``), so a new subsystem cannot quietly invent a
private vocabulary that ``tools/tracelens.py`` and downstream consumers
do not understand.  Span *names* get the same treatment via
:data:`SPAN_NAMES`: ``obs.trace.Tracer`` refuses names that are not
declared, which keeps the timeline exporter's segment classification
closed-world.
"""
from __future__ import annotations

import re

# kind -> one-line description (the contract tracelens + dashboards read)
EVENT_KINDS = {
    # --- serve metrics (ServeMetrics._event) -------------------------
    "terminal": "a request reached a terminal state (rid, state, tokens)",
    "reject": "admission rejected a submit (backpressure)",
    "fault": "decode sentinel tripped on a request (rid)",
    "retry": "a faulted request was requeued for replay (rid, attempt)",
    # --- train guards (TrainGuard._emit) -----------------------------
    "guard_skip": "guard skipped an update (reason, loss, streak)",
    "guard_rollback": "guard escalated to checkpoint rollback",
    "watchdog_alert": "a train step overran the watchdog budget",
    # --- router (Router._event) --------------------------------------
    "health": "replica health transition (replica, frm, to)",
    "place": "fleet request placed on a replica (gid, replica, rid)",
    "failover": "fleet request evacuated off a replica (gid, reason)",
    "fleet_terminal": "fleet request reached a terminal state (gid, state)",
    "fleet_reject": "every replica rejected a submit (gid)",
    "recover": "journal recovery re-submitted a live request (gid)",
    "pause": "chaos/operator paused a replica (replica, steps)",
    # --- write-ahead request journal (RequestJournal._append) --------
    "wal_submit": "WAL: request accepted by the fleet",
    "wal_place": "WAL: request placed on a replica",
    "wal_tokens": "WAL: durable token batch (gid, start, toks)",
    "wal_migrate": "WAL: request evacuated, will be re-placed",
    "wal_terminal": "WAL: request reached a terminal state",
    # --- observability plane (repro.obs) -----------------------------
    "span_begin": "trace span opened (name, sid, trace, parent, pid, ts)",
    "span_end": "trace span closed (sid, ts, + outcome attrs)",
    "metrics_snapshot": "periodic registry snapshot (counters/gauges/hists)",
    "mem_sample": "live-bytes sample scored against the plan budget",
}

# span name -> one-line description, in two groups.  Segment
# classification in tools/tracelens.py keys off these names, so they are
# closed-world too.

#: spans that cross host calls (a request's lifetime and its stages):
#: event records only
LIFETIME_SPANS = {
    # engine / scheduler (trace = rid, or gid when key_id is set)
    "req": "whole request: submit -> terminal (root span)",
    "queue": "QUEUED: waiting for a slot (reason=submit|replay)",
    "decode": "DECODE residency: first token -> retirement",
    # router (trace = gid)
    "fleet_req": "whole fleet request: fleet submit -> fleet terminal",
    "migrate": "evacuation -> successful re-placement elsewhere",
    "recover": "journal recovery replay of one live request",
}

#: spans that begin and end inside one host call: the tracer also writes
#: each into the profiler's own trace as ``repro.<name>``, so that it
#: lies on the device trace's clock
CALL_SPANS = {
    # engine
    "prefill": "prompt prefill + scatter + first token",
    "step": "one engine step (admissions + fused decode + harvest); "
            "ends with prefill_tokens and prefill_padded (bucket - length), "
            "summed over the step's prefills",
    "admit": "step phase: deadline shedding, pop_admissible, slot allocation",
    "dispatch": "step phase: enqueue device work (what=prefill|scatter|"
                "decode)",
    "sync": "step phase: blocking host read of device results "
            "(what=first_token|decode)",
    "emit": "step phase: hand sampled tokens to their requests, "
            "retirements, metrics.on_step",
    "compile": "one XLA backend compile, or its load from the persistent "
               "cache (source=backend|cache, fun=...)",
    # router (trace = gid)
    "place": "placement attempt on a replica",
    # infrastructure
    "rpc": "one worker RPC round-trip (op=...)",
    "journal_append": "one WAL append (+ group-commit fsync when due)",
    "journal_snapshot": "atomic .snap compaction",
    # train driver
    "data": "host data step: next(loader) + device put",
    "train_step": "jitted train step dispatch + loss sync",
    "guard": "guard verdict on the synced loss/grads",
    "checkpoint": "checkpoint save (or rollback restore)",
}

SPAN_NAMES = {**LIFETIME_SPANS, **CALL_SPANS}


# literal emit callsites: EventSink.emit / the private wrappers every
# subsystem routes through (ServeMetrics._event, Router._event,
# RequestJournal._append, TrainGuard._emit, Tracer's own emits)
_EMIT_RE = re.compile(
    r"(?:\.emit|self\._event|self\._append|self\._emit)\(\s*"
    r"[\"']([a-z_]+)[\"']")


def undeclared_kinds_in_source(src_root: str):
    """Scan ``src_root`` for literal event-kind emit callsites and return
    ``{kind: [file:line, ...]}`` for any kind not in EVENT_KINDS."""
    import os

    bad: dict = {}
    for dirpath, _dirnames, filenames in os.walk(src_root):
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    for m in _EMIT_RE.finditer(line):
                        kind = m.group(1)
                        if kind not in EVENT_KINDS:
                            bad.setdefault(kind, []).append(
                                f"{path}:{lineno}")
    return bad


def validate_events(path: str):
    """Return the set of undeclared kinds found in an events file."""
    from repro.events import read_events

    return {e["kind"] for e in read_events(path)} - set(EVENT_KINDS)
