"""The state one run of one cell carries from set-up through the window to
the check: its inputs, and what the job measured for the metric readers."""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any

from chipbench.spec import ModelSpec

LIMIT_DIR = Path(__file__).resolve().parent / "limits"


def limits(cell: str) -> dict:
    """The correctness limits of a cell, from ``limits/<cell>.json``."""
    return json.loads((LIMIT_DIR / f"{cell}.json").read_text())


@dataclasses.dataclass
class Run:
    cell: str
    spec: ModelSpec
    mix: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    limits: dict
    # filled by the job
    end_to_end: dict[str, float] = dataclasses.field(default_factory=dict)
    counters: dict[str, Any] = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    window_s: float = 0.0
    reduced_trace: Any = None      # chipbench.trace.Reduced, traced runs
    memory_peak_bytes: int = 0
    setup_end: float = 0.0         # host clock (epoch) at the window's start

    def check(self, name: str, value: float, rule: str = "<=") -> bool:
        """Record ``value`` against this cell's limit ``name``."""
        limit = self.limits[name]["limit"]
        ok = value <= limit if rule == "<=" else value >= limit
        self.checks.append((name, float(value), float(limit), rule))
        return bool(ok)

    @property
    def correct(self) -> bool:
        return all((v <= lim) if rule == "<=" else (v >= lim)
                   for _, v, lim, rule in self.checks) and bool(self.checks)
