"""One trace of a drive-loop call: host spans, counters and compilations.

``Trace(history)`` belongs to one ``run_simulation`` / ``run_lm_federation``
call and writes into that call's ``History`` / ``LMHistory``; it is the only
timing code of the drive loops and of ``DispatchPipeline``.

- ``span(name)`` times a block on ``time.perf_counter`` and adds its self
  time (its duration less that of the spans opened inside it) to the
  history's ``<name>_wall_s`` where that field exists.  It also enters
  ``jax.profiler.TraceAnnotation("dystop/<name>")``: under a profiler
  session the span lands in the ``.xplane.pb`` beside the device ops, on
  the same clock, so an idle gap of the device can be put down to the phase
  of the program the host was in.  With no session on, no annotation is
  made.
  A span of one name is one object, made on first use: it cannot open
  inside itself.
- ``count(name, n)`` adds ``n`` to ``history.counts[name]``.
- Every backend compilation is charged to the innermost open span of the
  thread that compiles, as ``counts["compiles/<span>"]``, and every program
  read from the persistent compilation cache instead as
  ``counts["cache_loads/<span>"]``.  One ``jax.monitoring`` listener per
  process does this for every trace.
- ``finish()`` writes the call's whole duration to ``wall_s``.

Totals and counters are always recorded: spans open a few times a round,
at well under a microsecond each.
"""
from __future__ import annotations

import threading
import time

import jax

PREFIX = "dystop/"

_Note = jax.profiler.TraceAnnotation
_recording = _Note.is_enabled
_clock = time.perf_counter
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# per thread: the open spans, innermost last, and whether the compile being
# timed was a persistent-cache hit (JAX records the hit inside the span it
# times the compile with, so the hit arrives first)
_local = threading.local()
_listen_lock = threading.Lock()
_listener_on = False


def _open_spans() -> list:
    spans = getattr(_local, "spans", None)
    if spans is None:
        spans = _local.spans = []
    return spans


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        _local.cache_hit = True


def _on_duration(event: str, duration: float, **_) -> None:
    if event != _COMPILE_EVENT:
        return
    hit = getattr(_local, "cache_hit", False)
    _local.cache_hit = False
    kind = "cache_loads" if hit else "compiles"
    spans = _open_spans()
    if spans:
        spans[-1].trace.count(f"{kind}/{spans[-1].name}")


def _listen() -> None:
    global _listener_on
    with _listen_lock:
        if not _listener_on:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listener_on = True


class _Span:
    """A span of one trace, made on its first use and reused each time it
    opens: the drive loops open a few a round."""
    __slots__ = ("trace", "name", "field", "children", "_t0", "_note")

    def __init__(self, trace: "Trace", name: str):
        self.trace, self.name = trace, name
        field = name + "_wall_s"
        self.field = field if hasattr(trace.history, field) else None
        self._t0 = None

    def __enter__(self) -> "_Span":
        if self._t0 is not None:
            raise RuntimeError(f"span {self.name!r} is already open")
        self.trace._open.append(self)
        self.children = 0.0
        # the annotation is made only while a profiler session records
        self._note = _Note(PREFIX + self.name) if _recording() else None
        if self._note is not None:
            self._note.__enter__()
        self._t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        took = _clock() - self._t0
        self._t0 = None
        if self._note is not None:
            self._note.__exit__(*exc)
        spans = self.trace._open
        spans.pop()
        if spans:
            spans[-1].children += took
        if self.field is not None:
            h = self.trace.history
            setattr(h, self.field, getattr(h, self.field) + took
                    - self.children)


class Trace:
    """Spans and counters of one drive-loop call (see the module
    docstring).  The call's clock starts here."""

    def __init__(self, history):
        _listen()
        self.history = history
        self._open = _open_spans()  # this thread's, read by the listener
        self._spans: dict = {}
        self._t0 = _clock()

    def span(self, name: str) -> _Span:
        span = self._spans.get(name)
        if span is None:
            span = self._spans[name] = _Span(self, name)
        return span

    def count(self, name: str, n: int = 1) -> None:
        counts = self.history.counts
        counts[name] = counts.get(name, 0) + int(n)

    def finish(self) -> None:
        """Write the call's duration, from this trace's creation, to
        ``wall_s``."""
        self.history.wall_s = _clock() - self._t0
