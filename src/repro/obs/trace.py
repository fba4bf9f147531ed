"""Engine step tracing: Chrome-trace-event JSON spans
(docs/observability.md).

``span("decode", rows=4)`` wraps a host-side phase in a complete
("ph": "X") Chrome trace event; the emitted file loads directly in
Perfetto / chrome://tracing.  The engine wires spans around its step
phases (retire → swap-in → chunk → decode/verify) and the train loop
wraps its steps — always around the *jitted calls*, never inside a
traced function, so tracing can never change a jaxpr.

Gates and cost:

  - off (the default): ``span()`` returns a shared no-op context
    manager — one dict lookup and zero allocations per call;
  - ``REPRO_TRACE=path``: spans record into a RING BUFFER of 65536
    events so long serving runs keep the last N events instead of
    growing without bound, and the buffer is flushed to ``path`` at
    process exit (or explicitly via ``get_tracer().save()`` / the
    CLIs' ``--trace-out``).

An enabled span is also a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``: while the JAX profiler records
(``jax.profiler.start_trace``) it lands in the same trace as the
device's ops, on the profiler's clock (otherwise it costs a check).

Durations measure wall time of the wrapped block.  JAX dispatch is
asynchronous — a span around a step call measures dispatch unless the
caller synchronizes; the serving engine reads every step's outputs
back to host (sampling), which makes its spans end-to-end in
practice.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager

import jax

DEFAULT_BUFFER_EVENTS = 65536


class _NullSpan:
    """Shared do-nothing context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class Tracer:
    """Ring-buffered Chrome-trace-event recorder."""

    def __init__(self, capacity: int = DEFAULT_BUFFER_EVENTS):
        self.enabled = False
        self.path: str | None = None
        self._events: deque = deque(maxlen=max(1, int(capacity)))
        self._lock = threading.Lock()
        self._pid = os.getpid()

    # -- control -------------------------------------------------------
    def enable(self, path: str | None = None,
               capacity: int | None = None):
        """Turn tracing on; ``path`` is where ``save()`` (and the
        atexit flush) writes."""
        if capacity is not None and capacity != self._events.maxlen:
            self._events = deque(self._events,
                                 maxlen=max(1, int(capacity)))
        self.enabled = True
        if path is not None:
            self.path = path
        return self

    def disable(self):
        self.enabled = False
        return self

    def clear(self):
        with self._lock:
            self._events.clear()

    def __len__(self):
        return len(self._events)

    # -- recording -----------------------------------------------------
    def span(self, name: str, **args):
        """Context manager recording one complete ("X") event around
        the block.  No-op (shared null CM) when disabled."""
        if not self.enabled:
            return _NULL
        return self._span(name, args)

    @contextmanager
    def _span(self, name, args):
        t0 = time.perf_counter_ns()
        try:
            with jax.profiler.TraceAnnotation(f"repro.{name}"):
                yield None
        finally:
            t1 = time.perf_counter_ns()
            ev = {"name": name, "ph": "X", "ts": t0 // 1000,
                  "dur": max(0, (t1 - t0) // 1000), "pid": self._pid,
                  "tid": threading.get_ident() & 0xFFFFFFFF}
            if args:
                ev["args"] = args
            with self._lock:
                self._events.append(ev)

    def instant(self, name: str, **args):
        """One instant ("i") event — markers like preemptions."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "s": "t",
              "ts": time.perf_counter_ns() // 1000, "pid": self._pid,
              "tid": threading.get_ident() & 0xFFFFFFFF}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    # -- export --------------------------------------------------------
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def save(self, path: str | None = None) -> str | None:
        """Write the buffered events as a Chrome-trace JSON array.
        Returns the path written, or None when there is nowhere to
        write."""
        path = path or self.path
        if path is None:
            return None
        with open(path, "w") as f:
            json.dump(self.events(), f)
        return path


_TRACER: Tracer | None = None
_ATEXIT_REGISTERED = False


def get_tracer() -> Tracer:
    """The process-wide tracer.  First call reads ``REPRO_TRACE``: a
    non-empty value enables tracing with that output path and
    registers an atexit flush."""
    global _TRACER, _ATEXIT_REGISTERED
    if _TRACER is None:
        _TRACER = Tracer()
        env = os.environ.get("REPRO_TRACE", "").strip()
        if env:
            _TRACER.enable(path=env)
            if not _ATEXIT_REGISTERED:
                atexit.register(_flush_at_exit)
                _ATEXIT_REGISTERED = True
    return _TRACER


def _flush_at_exit():
    if _TRACER is not None and _TRACER.enabled and _TRACER.path:
        _TRACER.save()


def trace_enabled() -> bool:
    return get_tracer().enabled


def span(name: str, **args):
    """Module-level convenience: ``with span("decode", rows=4): ...``"""
    return get_tracer().span(name, **args)


def instant(name: str, **args):
    get_tracer().instant(name, **args)
