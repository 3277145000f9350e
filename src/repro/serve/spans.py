"""Host spans of the serving engine: where a step's host time goes.

``ServeEngine(..., spans=SpanLog())`` records one ``(name, t0, t1, parent)``
tuple per span into a bounded log, timed on the engine's clock (the clock
``Request.t_submit`` / ``t_first`` / ``tok_times`` are stamped on), and
opens a ``jax.profiler.TraceAnnotation`` of the same name, so a profiler
trace carries the spans on its host plane beside the device timeline.
With ``spans=None`` (the default) the engine's span points are one shared
no-op context manager.

Span names (children indented under their parent):

    serve.init                 engine start-up
      serve.init.quantize      head weight quantization
      serve.init.census        protected-shape census traces
      serve.init.plans         protection plan compilation
      serve.init.params        in-model weight quantization
      serve.init.autotune      kernel block-size warm-up
    serve.step                 one ServeEngine.step
      serve.shed               lapsed-deadline shedding
      serve.plan               admission planning
      serve.pack               host build of the packed token block
      serve.prefill            prefill program dispatch
      serve.land               landing a finished admission batch
        serve.land.sync        blocking read of the first tokens
      serve.flush              zeroing recycled slot rows
      serve.decode             decode uploads and dispatch
      serve.decode.sync        blocking read of the decoded tokens
      serve.emit               per-slot token bookkeeping
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Callable

import jax

# the engine's span point when spans are off: one reusable no-op
OFF = contextlib.nullcontext()


class SpanLog:
    """Bounded in-memory log of nested host spans."""

    def __init__(self, maxlen: int = 65_536):
        self.records: collections.deque = collections.deque(maxlen=maxlen)
        # the engine binds its own clock at construction
        self.clock: Callable[[], float] = time.monotonic
        self._open: list[str] = []

    @contextlib.contextmanager
    def span(self, name: str):
        parent = self._open[-1] if self._open else None
        self._open.append(name)
        t0 = self.clock()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self._open.pop()
            self.records.append((name, t0, self.clock(), parent))

    def durations(self, name: str) -> list:
        return [t1 - t0 for n, t0, t1, _ in self.records if n == name]

    def step_host_s(self) -> list:
        """Host seconds of each recorded ``serve.step``: its duration less
        the blocking device reads (``*.sync`` spans) inside it. A span is
        logged when it closes, so a step's children precede it."""
        out, sync = [], 0.0
        for name, t0, t1, _ in self.records:
            if name.endswith(".sync"):
                sync += t1 - t0
            elif name == "serve.step":
                out.append(t1 - t0 - sync)
                sync = 0.0
        return out
