"""Request-scoped tracing: span_begin/span_end records on the event
stream, reconstructed into timelines by ``tools/tracelens.py``.

A span is two events sharing an ``sid``:

    span_begin  name, sid, trace, parent, pid, ts, **attrs
    span_end    sid, ts, **attrs

``ts`` is ``time.perf_counter()`` — monotonic, comparable across every
tracer in one process (the fleet tests run replicas in-process for
exactly this reason).  ``trace`` is the request identity the span
belongs to: the engine uses ``key_id or rid``, the router uses ``gid``,
and because migrated/recovered requests keep their gid the whole
lifetime stitches together across replicas.  Both halves are emitted
(not one folded "complete" record) so a crash leaves the open spans
visible in the stream — an unclosed ``decode`` span after kill -9 is
the observation, not a bug.

A span that begins and ends inside one host call (``CALL_SPANS`` in
``repro.obs.schema``: the engine step and its phases, ``prefill``,
``compile``, ``rpc``, the trainer's spans) also opens a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, carrying the
span's begin attributes, and closes it at ``end``.  While a profiler
session runs, those host spans land in the profiler's own trace, on the
device trace's clock; otherwise the annotation records nothing.
Annotations may close out of order.  Request-lifetime spans
(``LIFETIME_SPANS``) stay event-only.

Every call site guards ``if tracer is not None`` so the traced-off path
costs nothing.
"""
from __future__ import annotations

import itertools
import os
import time
from contextlib import contextmanager, nullcontext

from jax.profiler import TraceAnnotation

from repro.obs.schema import CALL_SPANS, SPAN_NAMES

#: per-process tracer instance counter: two tracers with the same pid
#: label (e.g. a restarted "router" appending to the same event file)
#: must never reuse span ids, or the new run's span_end records would
#: pair against the crashed run's still-open begins
_INSTANCES = itertools.count()


class Tracer:
    """Emits span records for one process/component to an EventSink.

    ``pid`` namespaces the span ids (and becomes the Perfetto process
    lane), so multiple tracers can share one sink: the router traces as
    ``router``, replica ``i`` as ``r{i}``, the journal as ``journal``.
    """

    def __init__(self, sink, *, pid: str = "main",
                 clock=time.perf_counter) -> None:
        self.sink = sink
        self.pid = pid
        self.clock = clock
        self._ns = f"{os.getpid()}.{next(_INSTANCES)}"
        self._n = 0
        self._annotations: dict[str, TraceAnnotation] = {}   # sid -> open

    def begin(self, name: str, *, trace=None, parent=None, **attrs) -> str:
        if name not in SPAN_NAMES:
            raise ValueError(f"undeclared span name {name!r}; add it to "
                             f"repro.obs.schema.SPAN_NAMES")
        self._n += 1
        sid = f"{self.pid}:{self._ns}:{self._n}"
        self.sink.emit("span_begin", name=name, sid=sid, trace=trace,
                       parent=parent, pid=self.pid, ts=self.clock(),
                       **attrs)
        if name in CALL_SPANS:
            ann = self._annotations[sid] = TraceAnnotation("repro." + name,
                                                           **attrs)
            ann.__enter__()
        return sid

    def end(self, sid, **attrs) -> None:
        if sid is None:          # begin was skipped (tracer attached late)
            return
        self.sink.emit("span_end", sid=sid, ts=self.clock(), **attrs)
        ann = self._annotations.pop(sid, None)
        if ann is not None:
            ann.__exit__(None, None, None)

    def switch(self, sid, name: str, **kw) -> str:
        """End ``sid`` and begin the next span: consecutive phases."""
        self.end(sid)
        return self.begin(name, **kw)

    @contextmanager
    def span(self, name: str, *, trace=None, parent=None, **attrs):
        sid = self.begin(name, trace=trace, parent=parent, **attrs)
        try:
            yield sid
        finally:
            self.end(sid)


def maybe_span(tracer, name: str, **kw):
    """``with maybe_span(self.tracer, "step"):`` — a no-op context when
    tracing is off, so call sites stay one line."""
    if tracer is None:
        return nullcontext()
    return tracer.span(name, **kw)
