"""Span tracing: host spans on the profiler's clock, plus a Chrome-trace
recorder.

:func:`span` (``with span("train_step", step=i): ...``) always opens a
``jax.profiler.TraceAnnotation`` of that name and arguments, so the span
lands in any profiler trace (``jax.profiler.trace`` / ``start_trace``) on
the same clock as the device ops it dispatched; with no profiler running
that costs one TraceMe check.  While tracing is enabled
(:func:`set_enabled`) the span also records a ``B``/``E`` pair in the
`Trace Event Format` consumed by Perfetto / chrome://tracing
(``{"traceEvents": [...]}``), which the launchers' ``--trace-out`` writes.
Nest freely.

Phases inside compiled code are not marked here: the program names them
with ``jax.named_scope`` (``deq_solve``, ``deq_block``, ``qn_update``,
``implicit_backward``), which reaches the device ops of a profiler trace.

All recorded events share one pid and a single synthetic tid so nesting
is decided purely by time containment.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager

import jax

__all__ = ["TraceRecorder", "default_recorder", "set_enabled", "enabled",
           "span", "instant", "write", "clear"]

_PID = os.getpid()
_TID = 1


class TraceRecorder:
    def __init__(self):
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._t0 = time.perf_counter()

    def _now(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6  # µs

    def _append(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)

    # -- host spans --------------------------------------------------------

    @contextmanager
    def span(self, name: str, **args):
        self._append({"name": name, "ph": "B", "ts": self._now(),
                      "pid": _PID, "tid": _TID,
                      **({"args": args} if args else {})})
        try:
            yield
        finally:
            self._append({"name": name, "ph": "E", "ts": self._now(),
                          "pid": _PID, "tid": _TID})

    def instant(self, name: str, **args) -> None:
        self._append({"name": name, "ph": "i", "s": "t", "ts": self._now(),
                      "pid": _PID, "tid": _TID,
                      **({"args": args} if args else {})})

    # -- export ------------------------------------------------------------

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def to_chrome_trace(self) -> dict:
        meta = [{"name": "process_name", "ph": "M", "pid": _PID, "tid": _TID,
                 "args": {"name": "repro"}},
                {"name": "thread_name", "ph": "M", "pid": _PID, "tid": _TID,
                 "args": {"name": "steps"}}]
        return {"traceEvents": meta + self.events(),
                "displayTimeUnit": "ms"}

    def write(self, path: str) -> dict:
        trace = self.to_chrome_trace()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as fh:
            json.dump(trace, fh, indent=1)
        return trace

    def clear(self) -> None:
        with self._lock:
            self._events.clear()


_RECORDER = TraceRecorder()
_ENABLED = False


def default_recorder() -> TraceRecorder:
    return _RECORDER


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def enabled() -> bool:
    return _ENABLED


@contextmanager
def span(name: str, **args):
    """Host span: a profiler ``TraceAnnotation`` always, and a B/E pair on
    the default recorder while tracing is enabled."""
    with jax.profiler.TraceAnnotation(name, **args):
        if not _ENABLED:
            yield
            return
        with _RECORDER.span(name, **args):
            yield


def instant(name: str, **args) -> None:
    if _ENABLED:
        _RECORDER.instant(name, **args)


def write(path: str) -> dict:
    return _RECORDER.write(path)


def clear() -> None:
    _RECORDER.clear()
