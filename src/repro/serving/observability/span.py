"""Host spans on the profiler's clock.

:class:`span` is the serving stack's one timing primitive for a stretch of
host work.  It opens a ``jax.profiler.TraceAnnotation`` named
``alise.<name>`` with the given stats, so that a profiler trace
(``jax.profiler.trace``) holds the span on the same clock as the device's
programs, and it times the stretch with ``time.perf_counter``.  Bus events
queued with :meth:`span.emit` are written to the :class:`EventBus` on exit,
each with the span's start and duration: a site keeps one timing for the
profiler, the bus and its own rings.  With no profiler recording the
annotation costs about a microsecond; with no bus attached nothing is
queued.

Spans of the serving path (nesting as shown):

- ``alise.gateway.submit``: admission and dispatch of one request;
- ``alise.gateway.deliver``: engine events handed to the request streams;
- ``alise.engine.step``: one engine iteration, holding
  ``alise.sched.plan``, ``alise.kv.offload`` / ``alise.kv.upload`` (swaps),
  ``alise.engine.prefill`` (``tokens``, ``start``),
  ``alise.engine.prefill_pack`` (``tokens``, ``members``),
  ``alise.engine.decode`` (``lanes``, ``ctx``, ``width``: active lanes, the
  context tokens they held before the step, and the positions per lane its
  attention gathers) and ``alise.predictor.feedback``;
- under ``alise.engine.decode``: ``alise.decode.stage`` (page tables, lane
  arrays, one packed host-to-device copy), ``alise.decode.dispatch``,
  ``alise.decode.sync`` (the wait for the sampled tokens) and
  ``alise.decode.accept`` (token acceptance and stream events);
- ``alise.gc`` (``generation``): one garbage collection, from
  ``gc.callbacks`` (installed by :func:`trace_gc`), while a profiler records.
"""
from __future__ import annotations

import gc
import threading
import time

from jax.profiler import TraceAnnotation

PREFIX = "alise."


class span:
    """``with span(name, bus, at, **stats) as sp:`` times its block.

    ``bus``: an attached :class:`EventBus` or None; ``at``: the bus clock
    minus ``time.perf_counter`` at the site, so that an event starts at
    ``at + t0`` on the bus.  After the block, ``t0`` (perf_counter) and
    ``dur`` (seconds) hold the timing."""

    __slots__ = ("_ann", "bus", "_at", "_events", "t0", "dur")

    def __init__(self, name: str, bus=None, at: float = 0.0, **stats):
        self._ann = TraceAnnotation(PREFIX + name, **stats)
        self.bus = bus
        self._at = at
        self._events = None

    def emit(self, kind: str, **data) -> None:
        """Queue a bus event of ``kind`` that spans this block."""
        if self.bus is not None:
            if self._events is None:
                self._events = []
            self._events.append((kind, data))

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.dur = time.perf_counter() - self.t0
        self._ann.__exit__(exc_type, exc, tb)
        if self._events and exc_type is None:
            t = self._at + self.t0
            for kind, data in self._events:
                self.bus.emit(kind, t=t, dur=self.dur, **data)
        return False


_gc_open = threading.local()


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        if TraceAnnotation.is_enabled():
            ann = TraceAnnotation(PREFIX + "gc",
                                  generation=info["generation"])
            ann.__enter__()
            _gc_open.ann = ann
        return
    ann = getattr(_gc_open, "ann", None)
    if ann is not None:
        _gc_open.ann = None
        ann.__exit__(None, None, None)


def trace_gc() -> None:
    """Put each garbage collection in an ``alise.gc`` span while a profiler
    records (idempotent; a collection costs one check otherwise)."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
