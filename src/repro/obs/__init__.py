"""Unified observability: metrics registry, solver convergence tapes, and
span tracing.

Three pillars, each usable alone:

  * :mod:`repro.obs.metrics` — process-local counters / gauges /
    histograms / series with labels, JSON snapshots, and a jit-safe
    bridge (``jax.debug.callback``) so values computed inside compiled
    solves land in host metrics.
  * :mod:`repro.obs.tape` — the fixed-size per-iteration
    :class:`~repro.obs.tape.SolveTape` (residual norm, step size,
    qN-ring occupancy) every solver threads through its loop state.
  * :mod:`repro.obs.tracing` — host spans that land in profiler traces
    and, while enabled, in Chrome-trace / Perfetto JSON.  Phases inside
    jit are ``jax.named_scope`` names on the program's ops instead.

The bridge is gated at TRACE time: :func:`enable` before the first jitted
call you want instrumented.  With it off (the default) compiled programs
carry zero observability residue; the span recorder is host-only.
"""

from __future__ import annotations

from repro.obs import metrics, tape, tracing
from repro.obs.metrics import (MetricsRegistry, default_registry,
                               emit_scalar, record_backward, record_solve)
from repro.obs.tape import SolveTape, empty_tape, tape_record, tape_summary
from repro.obs.tracing import TraceRecorder, default_recorder, span

__all__ = [
    "metrics", "tape", "tracing",
    "MetricsRegistry", "default_registry", "emit_scalar",
    "record_solve", "record_backward",
    "SolveTape", "empty_tape", "tape_record", "tape_summary",
    "TraceRecorder", "default_recorder", "span",
    "enable", "disable", "status",
]


def enable(*, metrics_on: bool = True, tracing_on: bool = True) -> None:
    """Switch the jit bridge and/or the span tracer on (trace-time gates)."""
    if metrics_on:
        metrics.set_enabled(True)
    if tracing_on:
        tracing.set_enabled(True)


def disable() -> None:
    metrics.set_enabled(False)
    tracing.set_enabled(False)


def status() -> dict:
    return {"metrics": metrics.enabled(), "tracing": tracing.enabled()}
