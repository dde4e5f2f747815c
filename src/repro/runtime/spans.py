"""Host spans and counters of the serving engine, on the profiler's clock.

One tracing system with two outputs.  ``Recorder.span(name, **ids)``
always enters ``jax.profiler.TraceAnnotation(name, **ids)``, so while a
``jax.profiler`` trace runs the span lands on the host plane of its
XSpace under its bare name (``engine.prefill``), the ids as stats, on
the clock of the device's operations.  While the recorder is on
(``start()`` .. ``stop()``) the same span is also kept in memory as
``[name, start_ns, end_ns, parent, ids]``: ``parent`` is the index of
the enclosing span in the record (-1 for none), times are
``time.time_ns()``, the wall clock the profiler stamps its host events
with.  ``count(name, n)`` adds to the record's counters while it is on
and does nothing otherwise.  The recorder writes nothing to disk.

While a recorder is on, every program JAX compiles or fetches from its
persistent cache (the ``/jax/core/compile/backend_compile_duration``
event, which covers both) counts under ``compiles.<innermost open
span>`` (``compiles.none`` outside every span), so a record says which
step compiled.  One listener serves every recorder; it is registered
with ``jax.monitoring`` by the first ``start()``.

A span adds no synchronisation with the device and goes inside no
jitted code: it only reads the host clock where the code already is.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import jax

__all__ = ["Recorder"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# the recorders that are on, for the one compile listener
_on: List["Recorder"] = []
_listener_lock = threading.Lock()
_listening = False


def _on_duration_event(event: str, duration: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    for rec in list(_on):
        rec.count("compiles." + rec.innermost())


def _listen() -> None:
    global _listening
    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration_event)
            _listening = True


class Recorder:
    """Spans and counters of one engine; off until ``start()``."""

    def __init__(self):
        self.on = False
        self._spans: List[list] = []
        self._stack: List[int] = []
        self._counters: Dict[str, int] = {}
        self._open: Dict[object, tuple] = {}

    def start(self) -> None:
        """Begin a fresh record."""
        _listen()
        self._spans, self._stack = [], []
        self._counters, self._open = {}, {}
        self.on = True
        if self not in _on:
            _on.append(self)

    def stop(self) -> dict:
        """End the record and return it: ``{"spans": [[name, start_ns,
        end_ns, parent, ids], ...], "counters": {name: n}}``, spans in
        the order they opened (one of ``begin``/``end`` where it ended).
        A span still open ends now."""
        now = time.time_ns()
        self.on = False
        if self in _on:
            _on.remove(self)
        for i in self._stack:
            self._spans[i][2] = now
        self._stack, self._open = [], {}
        return {"spans": self._spans, "counters": self._counters}

    def innermost(self) -> str:
        return self._spans[self._stack[-1]][0] if self._stack else "none"

    def span(self, name: str, **ids) -> "_Span":
        """A context manager: the profiler's annotation always, the
        record's entry while the recorder is on."""
        return _Span(self, name, ids)

    def count(self, name: str, n: int = 1) -> None:
        if self.on:
            self._counters[name] = self._counters.get(name, 0) + int(n)

    def begin(self, key, name: str, **ids) -> None:
        """Open a span that no single call covers (``end(key)`` closes
        it); it is kept in memory only, with no parent."""
        if self.on:
            self._open[key] = (name, time.time_ns(), ids)

    def end(self, key) -> None:
        opened: Optional[tuple] = self._open.pop(key, None)
        if opened is not None:
            name, t0, ids = opened
            self._spans.append([name, t0, time.time_ns(), -1, ids])


class _Span:
    __slots__ = ("_rec", "_name", "_ids", "_ann", "_entry", "_spans")

    def __init__(self, rec: Recorder, name: str, ids: dict):
        self._rec, self._name, self._ids = rec, name, ids
        self._ann = jax.profiler.TraceAnnotation(name, **ids)
        self._spans = None

    def __enter__(self) -> None:
        self._ann.__enter__()
        rec = self._rec
        if rec.on:
            self._spans = spans = rec._spans
            parent = rec._stack[-1] if rec._stack else -1
            self._entry = [self._name, time.time_ns(), None, parent,
                           self._ids]
            spans.append(self._entry)
            rec._stack.append(len(spans) - 1)

    def __exit__(self, *exc) -> None:
        rec = self._rec
        # a record begun or ended inside the span does not hold it
        if self._spans is not None and self._spans is rec._spans \
                and rec._stack:
            self._entry[2] = time.time_ns()
            rec._stack.pop()
        self._ann.__exit__(*exc)
