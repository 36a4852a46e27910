"""Compiles as ``compile`` spans.

XLA's backend compile, or its load from JAX's persistent compilation
cache, becomes a ``compile`` span on every attached
:class:`~repro.obs.trace.Tracer`: it opens when JAX starts the compile
(``jax.monitoring`` reports the start of
``/jax/core/compile/backend_compile_duration`` as a scalar) and closes
with its duration, ``source=cache`` when the persistent cache's
retrieval event fired inside it, else ``source=backend``, and ``fun``,
the compiled function's name.  A ``compile`` span seen after warm-up is a
compile the warm-up missed.

``jax.monitoring``'s listeners are process-wide and cannot be narrowed
to one engine, so the listener is too: it is registered once per
process, on the first :func:`attach`, and costs nothing before that.
"""
from __future__ import annotations

import threading
import weakref

from jax import monitoring

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

_tracers: "weakref.WeakSet" = weakref.WeakSet()
_open = threading.local()        # per thread: compiles in progress
_registered = False
_lock = threading.Lock()


def attach(tracer) -> None:
    """Record every later compile in this process on ``tracer``."""
    global _registered
    with _lock:
        if not _registered:
            monitoring.register_scalar_listener(_on_start)
            monitoring.register_event_duration_secs_listener(_on_duration)
            _registered = True
        _tracers.add(tracer)


def detach(tracer) -> None:
    _tracers.discard(tracer)


def _stack() -> list:
    if not hasattr(_open, "stack"):
        _open.stack = []
    return _open.stack


def _on_start(event: str, value: float, **kw) -> None:
    if event != BACKEND_COMPILE:
        return
    fun = str(kw.get("fun_name", ""))
    _stack().append({"cache": False, "spans": [
        (t, t.begin("compile", fun=fun)) for t in list(_tracers)]})


def _on_duration(event: str, duration: float, **kw) -> None:
    stack = _stack()
    if not stack:
        return
    if event == CACHE_RETRIEVAL:
        stack[-1]["cache"] = True
    elif event == BACKEND_COMPILE:
        rec = stack.pop()
        source = "cache" if rec["cache"] else "backend"
        for tracer, sid in rec["spans"]:
            tracer.end(sid, source=source, seconds=float(duration))
