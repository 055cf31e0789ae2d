"""Count XLA backend compilations via jax's monitoring hooks.

The serve path is only fast (and its iteration cost only predictable —
the property ALISE's EWT estimates lean on) if every dispatch shape the
engine can emit was compiled during warmup.  ``CompileCounter`` listens
for the ``/jax/core/compile/backend_compile_duration`` monitoring event,
which fires once per program the backend has to produce: an XLA compile,
or a load from JAX's persistent compilation cache (also counted apart, in
``cache_hits``).  Reuse of an executable already in the process and pure
retraces don't count.  It is used three ways:

* ``tests/test_prefill_buckets.py`` warms an engine, replays a
  mixed-length trace, and asserts the serve-time count is zero;
* ``bench_e2e`` emits a ``compile_count`` row and raises on any
  serve-time recompile, which fails the ``--smoke`` gate;
* ``chip_smoke.py`` asserts zero compiles after warmup on the chip.

The hook is a jax-internal API (``jax._src.monitoring``).  If it moves,
construction fails at once: a gate that silently counted nothing would
pass every recompile.
"""
from __future__ import annotations

import threading
from typing import List, Optional

from jax._src import monitoring

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts XLA backend compiles observed while attached.

    Usage::

        with CompileCounter() as cc:          # attaches immediately
            with cc.expect_no_compiles():     # raises if any compile fires
                engine.step(t)
        # detached on exit

    or sample ``cc.count`` manually around a region and call
    :meth:`detach` when done.
    """

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0              # backend time of those programs
        self.cache_hits = 0
        self.events: List[str] = []
        # compiles fire on whichever thread traced (the gateway's pump
        # threads): the read-modify-writes below take a lock
        self._lock = threading.Lock()
        monitoring.register_event_duration_secs_listener(self._on_event)
        monitoring.register_event_listener(self._on_hit)
        self._attached = True

    # -- listeners ----------------------------------------------------
    def _on_event(self, name: str, secs: float, **kw) -> None:
        if name == _COMPILE_EVENT:
            with self._lock:
                self.count += 1
                self.seconds += secs
                self.events.append(f"{name}:{secs * 1e3:.1f}ms")

    def _on_hit(self, name: str, **kw) -> None:
        if name == _CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    # -- API ----------------------------------------------------------
    def reset(self) -> int:
        """Zero the counters, returning the compile count so far."""
        with self._lock:
            n = self.count
            self.count = 0
            self.seconds = 0.0
            self.cache_hits = 0
            self.events.clear()
        return n

    def detach(self) -> None:
        """Unregister the listeners (idempotent)."""
        if self._attached:
            monitoring.unregister_event_duration_listener(self._on_event)
            monitoring.unregister_event_listener(self._on_hit)
            self._attached = False

    def __enter__(self) -> "CompileCounter":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.detach()

    def expect_no_compiles(self, label: str = "") -> "_NoCompileGuard":
        return _NoCompileGuard(self, label)


class _NoCompileGuard:
    def __init__(self, counter: CompileCounter, label: str) -> None:
        self.counter = counter
        self.label = label
        self._start = 0

    def __enter__(self) -> "_NoCompileGuard":
        self._start = self.counter.count
        return self

    def __exit__(self, exc_type, exc, tb) -> Optional[bool]:
        if exc_type is not None:
            return None
        fresh = self.counter.count - self._start
        if fresh:
            tail = "; ".join(self.counter.events[-fresh:])
            raise AssertionError(
                f"{fresh} unexpected XLA compile(s)"
                + (f" during {self.label}" if self.label else "")
                + (f" [{tail}]" if tail else ""))
        return None
