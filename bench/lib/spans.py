"""The benchmark's own spans around its calls into each layer.

``Spans.span(name)``, when ``tracing`` is on, writes a span into the
profiler trace through ``jax.profiler.TraceAnnotation``, so the trace
reduction can put device operations and host work on one clock.  Names
start ``bench.``.
"""

from __future__ import annotations

import contextlib


class Spans:
    def __init__(self, tracing: bool = False):
        self.tracing = tracing

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.tracing:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield

