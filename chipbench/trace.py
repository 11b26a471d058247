"""Capture of a profiler trace around a window, and its reduction to what
the per-layer metrics read: device busy time, per-operation device time
by op name or by the program's named scopes, and idle gaps labelled by the
harness annotation open on the host.

Device planes are the ``/device:TPU:<n>`` planes of the trace; their
operations are the events of the ``XLA Ops`` line, each with its chip and
its name stack (``scopes.op_stacks``).  The window in the trace's clock
runs from the first to the last harness annotation.
"""

from __future__ import annotations

import dataclasses
import os
import re
import shutil
import tempfile
from pathlib import Path

# the annotations the jobs open around their calls into the program
ANNOTATIONS = ("data", "train_step", "sync", "submit", "step", "idle",
               "lead_in")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


# control flow whose device event spans the ops of its body
CONTAINERS = ("while", "conditional", "call")


def short_name(hlo: str) -> str:
    """``%broyden_step_pallas.10 = (f32[...]) custom-call(...)`` ->
    ``broyden_step_pallas.10``: the trace names an op by its HLO text."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """``broyden_step_pallas.10`` -> ``broyden_step_pallas``."""
    return re.sub(r"\.\d+$", "", name)


@dataclasses.dataclass
class Op:
    name: str
    start_ns: int
    dur_ns: int
    detail: str      # the op's long name / source op, where the trace has it
    device: int = 0  # index of its chip among the trace's device planes
    stack: str = ""  # its name stack (``tf_op``), "" where it has none


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                    # union of device-op intervals, mean/chip
    n_devices: int
    ops: list[Op]                    # device ops inside the window, all chips
    gaps: list[tuple[str, float]]    # 10 longest: (host annotation, s)

    def op_seconds(self, pattern: str,
                   per_chip: bool = False) -> tuple[float, float]:
        """Device seconds and count of the ops whose name or detail matches
        ``pattern`` (a regular expression): summed over the chips, or with
        ``per_chip`` their mean per chip."""
        rx = re.compile(pattern)
        hits = [o for o in self.ops if rx.search(o.name) or rx.search(o.detail)]
        seconds, count = sum(o.dur_ns for o in hits) * 1e-9, len(hits)
        if per_chip:
            return seconds / self.n_devices, count / self.n_devices
        return seconds, count

    def scope_seconds(self, *scopes: str) -> float:
        """Device seconds, mean per chip, of the ops whose name stack holds
        every one of ``scopes`` as a path segment (bare, or wrapped by a
        transform as in ``jvp(deq_solve)``).  Control-flow containers are
        left out: their bodies' ops are counted on their own.  0 where no op
        carries the scopes."""
        from chipbench.scopes import scope_names

        want = frozenset(scopes)
        ns = sum(o.dur_ns for o in self.ops
                 if o.stack and want <= scope_names(o.stack)
                 and base_name(o.name) not in CONTAINERS)
        return ns * 1e-9 / self.n_devices

    def found(self, scope: str) -> bool:
        """Whether any op's name stack holds ``scope``."""
        from chipbench.scopes import scope_names

        return any(scope in scope_names(o.stack) for o in self.ops if o.stack)

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` op kinds (HLO name without its number) that took most
        device time, control-flow containers left out."""
        tot: dict[str, int] = {}
        for o in self.ops:
            kind = base_name(o.name)
            if kind in CONTAINERS:
                continue
            tot[kind] = tot.get(kind, 0) + o.dur_ns
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / self.n_devices] for name, ns in top]


def _stats(ev) -> dict:
    try:
        return {str(k): v for k, v in ev.stats}
    except Exception:  # noqa: BLE001 - a stat of an unknown type
        return {}


def _detail(ev) -> str:
    """Every string stat of the event (its long name, source op, kernel
    details: whichever the trace has)."""
    return " ".join(v for v in _stats(ev).values() if isinstance(v, str))


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def reduce(path: str | Path, annotations=ANNOTATIONS) -> Reduced:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    from chipbench.scopes import op_stacks

    pd = ProfileData.from_file(str(path))
    stacks = op_stacks(path)
    host_spans: list[tuple[int, int, str]] = []
    device_lines = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                evs = list(lines[OPS_LINE].events)
                named = stacks.get(plane.name, [])
                if [n for n, _ in named] != [e.name for e in evs]:
                    named = [("", "")] * len(evs)
                device_lines.append([(e, st) for e, (_, st) in
                                     zip(evs, named)])
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in annotations:
                        host_spans.append((int(ev.start_ns), int(ev.end_ns),
                                           ev.name))
    if not device_lines:
        seen = [(p.name, [ln.name for ln in p.lines]) for p in pd.planes]
        raise ValueError(f"{path}: no device plane with an '{OPS_LINE}' "
                         f"line; planes and lines: {seen}")
    if host_spans:
        w0 = min(s for s, _, _ in host_spans)
        w1 = max(e for _, e, _ in host_spans)
    else:
        starts = [int(e.start_ns) for evs in device_lines for e, _ in evs]
        ends = [int(e.end_ns) for evs in device_lines for e, _ in evs]
        w0, w1 = min(starts), max(ends)
    ops: list[Op] = []
    busy_ns = 0
    raw_gaps: list[tuple[int, int]] = []
    host_spans.sort()
    for chip, evs in enumerate(device_lines):
        ivs = []
        for e, stack in evs:
            s, t = max(int(e.start_ns), w0), min(int(e.end_ns), w1)
            if t <= s:
                continue
            ivs.append((s, t))
            ops.append(Op(short_name(e.name), s, t - s, _detail(e), chip,
                          stack))
        merged = _union(ivs)
        busy_ns += sum(t - s for s, t in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        raw_gaps += [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                     if b > a]
    raw_gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [(_label(host_spans, (a + b) // 2), (b - a) * 1e-9)
            for a, b in raw_gaps[:10]]
    n = len(device_lines)
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9 / n,
                   n_devices=n, ops=ops, gaps=gaps)


def _label(spans, t: int) -> str:
    """The innermost harness annotation open at ``t``, else ``none``."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or s >= best[0]):
            best = (s, name)
    return best[1] if best else "none"


class Capture:
    """Profiler trace of a window, written under ``TMPDIR`` and removed once
    reduced."""

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-",
                                    dir=os.environ.get("TMPDIR"))
        jax.profiler.start_trace(self.dir)

    def stop(self, t0: float | None = None, t1: float | None = None,
             keep: str | None = None) -> Reduced:
        import jax

        jax.profiler.stop_trace()
        try:
            pb = sorted(Path(self.dir).rglob("*.xplane.pb"))
            if not pb:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            if keep:
                Path(keep).parent.mkdir(parents=True, exist_ok=True)
                shutil.copy(pb[0], keep)
            return reduce(pb[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
