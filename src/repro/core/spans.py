"""Host spans and counters of the pane loop.

``span(name, **ids)`` marks one stretch of host work in two places:

  * always in the profiler's own trace (``jax.profiler.TraceAnnotation``,
    on the device trace's clock, ``ids`` as event stats), so an idle gap of
    the device can be put down to the program span the host was in;
  * when a :class:`Recorder` is active on the calling thread, in that
    recorder's totals: per name a count, total seconds and self seconds
    (total less the time of the spans nested inside it), read through the
    recorder's injected clock.

``count(name, n)`` adds to a counter of the active recorder.  Both do
nothing beyond the profiler annotation when no recorder is active, so a
``StreamSession.step`` called without a runtime records nothing.

One process-wide ``jax.monitoring`` listener, registered when a recorder is
first activated, counts backend compiles (``compiles.<span>``: every
compile request, persistent-cache loads included) and persistent-cache loads
(``cache_loads.<span>``) under the innermost span open on the compiling
thread, which says which step compiled.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_local = threading.local()  # .recorder, .stack (open spans, innermost last)
_listener_lock = threading.Lock()
_listening = False


class Recorder:
    """Span totals and counters of one owner (a :class:`~.runtime.StreamRuntime`),
    timed by ``clock``; active on a thread only inside :meth:`active`."""

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self._lock = threading.Lock()
        self._spans: dict[str, list] = {}  # name -> [count, total_s, self_s]
        self._counters: dict[str, int] = {}

    @contextlib.contextmanager
    def active(self):
        """Make this the calling thread's recorder for the block (re-entrant)."""
        _listen()
        prev = (getattr(_local, "recorder", None), getattr(_local, "stack", None))
        if prev[0] is self:
            yield self
            return
        _local.recorder, _local.stack = self, []
        try:
            yield self
        finally:
            _local.recorder, _local.stack = prev

    def add(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def _close(self, name: str, total_s: float, self_s: float) -> None:
        with self._lock:
            entry = self._spans.setdefault(name, [0, 0.0, 0.0])
            entry[0] += 1
            entry[1] += total_s
            entry[2] += self_s

    def spans(self) -> dict:
        """``{name: {"count", "total_s", "self_s"}}``, a copy."""
        with self._lock:
            return {
                name: {"count": c, "total_s": t, "self_s": s}
                for name, (c, t, s) in self._spans.items()
            }

    def counters(self) -> dict:
        """``{name: int}``, a copy."""
        with self._lock:
            return dict(self._counters)


class span:
    """Context manager: one profiler annotation and, under an active
    recorder, one timed entry.  ``t0``/``t1`` hold the recorder's clock
    readings at entry and exit (None without a recorder), so callers take
    their own timestamps from the same reads."""

    __slots__ = ("name", "t0", "t1", "_ann", "_rec", "_child")

    def __init__(self, name: str, **ids):
        self.name = name
        self.t0 = self.t1 = None
        self._ann = jax.profiler.TraceAnnotation(name, **ids)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        rec = self._rec = getattr(_local, "recorder", None)
        if rec is not None:
            _local.stack.append(self)
            self._child = 0.0
            self.t0 = rec.clock()
        return self

    def __exit__(self, *exc) -> None:
        rec = self._rec
        if rec is not None:
            self.t1 = rec.clock()
            total = self.t1 - self.t0
            stack = _local.stack
            stack.pop()
            if stack:
                stack[-1]._child += total
            rec._close(self.name, total, total - self._child)
        self._ann.__exit__(*exc)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the calling thread's recorder, if any."""
    rec = getattr(_local, "recorder", None)
    if rec is not None:
        rec.add(name, n)


def _count_compile(kind: str) -> None:
    rec = getattr(_local, "recorder", None)
    if rec is not None:
        stack = _local.stack
        rec.add(f"{kind}.{stack[-1].name}" if stack else kind)


def _on_duration(event: str, _secs: float, **_) -> None:
    if event == COMPILE_EVENT:
        _count_compile("compiles")


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        _count_compile("cache_loads")


def _listen() -> None:
    global _listening
    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
