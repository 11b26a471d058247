"""What every run shares: finding a cell by name in ``BENCHMARK.json``, the
device check, the compile counter, the set-up clock, memory, and the result
line."""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def process_start() -> float:
    """Wall-clock (epoch) time at which this process started."""
    try:
        fields = Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()
        ticks = int(fields[19])
        btime = next(int(line.split()[1]) for line in
                     Path("/proc/stat").read_text().splitlines()
                     if line.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, StopIteration, IndexError, ValueError):
        return time.time()


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(name: str) -> tuple[dict, dict]:
    """The workload entry named ``name`` and the whole benchmark."""
    bench = benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w, bench
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                     f"{[w['name'] for w in bench['workloads']]}")


def metric_names(bench: dict, workload: str, section: str) -> list[dict]:
    """The ``section`` (``end_to_end`` or ``per_layer``) metrics this cell
    reports: those that list it, or list no cells at all."""
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def check_mesh(wl: dict, mix: dict) -> None:
    """Refuse, before any set-up, a cell whose traffic runs on a mesh of
    another number of chips than the cell asks for (no mesh: one)."""
    from chipbench.traffic import chips_needed

    need = chips_needed(mix)
    if need != wl["chips"]:
        print(f"chipbench: {wl['name']} asks for {wl['chips']} chip(s), but "
              f"its traffic {wl['traffic']!r} runs on {need}. No result.",
              file=sys.stderr)
        raise SystemExit(2)


def require_chips(n: int):
    """The accelerator devices, or exit non-zero with no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"chipbench: needs {n} TPU chip(s); JAX sees {len(devs)} "
              f"{devs[0].platform} device(s). No result.", file=sys.stderr)
        raise SystemExit(3)
    return devs[:n]


def memory_peak(devs) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return max(peaks)


class CompileCounter:
    """Counts programs built (compiled or loaded from the persistent cache)
    while ``active``: JAX reports each under ``backend_compile_duration``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == self.EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))


def emit(result: dict, checks: list[tuple[str, float, float, str]]) -> None:
    """Print the compared numbers beside their limits as the last lines of
    standard error, and the result as the last line of standard output,
    with the checks under its last key."""
    for name, value, limit, rule in checks:
        print(f"check {name}: {value!r} ({rule} {limit!r})", file=sys.stderr)
    result["checks"] = {name: {"value": value, "limit": limit, "rule": rule}
                        for name, value, limit, rule in checks}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
