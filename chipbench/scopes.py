"""Device time per named scope of the program, read from a profiler trace.

The program names its phases with ``jax.named_scope`` (``deq_solve``,
``deq_block``, ``qn_update``, ``implicit_backward``).  The names reach each
device op's name stack, ``jit(step)/jvp(deq_solve)/while/body/qn_update/
mul:``, which the trace keeps as the ``tf_op`` stat of the op's event
metadata.  ``jax.profiler.ProfileData`` does not expose metadata stats, so
``op_stacks`` reads them from the file's wire format with the standard
library; ``trace.reduce`` gives each op its stack, and
``Reduced.scope_seconds`` sums the ops' device time by scope.
"""

from __future__ import annotations

import functools
import re
from pathlib import Path

from chipbench.trace import DEVICE_PLANE, OPS_LINE

NAME_STACK_STAT = "tf_op"

# field numbers of the XSpace messages read here
# (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_EVENTS = 2, 4
_EVENT_META_ID = 1
_META_ID, _META_NAME, _META_STATS = 1, 2, 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_MAP_KEY, _MAP_VALUE = 1, 2


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one message: an int for a
    varint, a memoryview of the bytes for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_stacks(path: str | Path) -> dict[str, list[tuple[str, str]]]:
    """For each device plane, ``(event name, name stack)`` of every event of
    its ``XLA Ops`` line, in the file's order.  The name stack is the event
    metadata's ``tf_op`` stat, held as a string or as a reference to the
    stat metadata that names it; ``""`` where an op has none."""
    buf = memoryview(Path(path).read_bytes())
    out: dict[str, list[tuple[str, str]]] = {}
    for num, plane in _fields(buf):
        if num != _SPACE_PLANES:
            continue
        parts: dict[int, list] = {_PLANE_LINES: [], _PLANE_EVENT_META: [],
                                  _PLANE_STAT_META: []}
        name = ""
        for f, v in _fields(plane):
            if f == _PLANE_NAME:
                name = _text(v)
            elif f in parts:
                parts[f].append(v)
        if not DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for entry in parts[_PLANE_STAT_META]:
            meta = dict(_fields(dict(_fields(entry)).get(_MAP_VALUE, b"")))
            stat_names[meta.get(_META_ID, 0)] = _text(meta.get(_META_NAME,
                                                               b""))
        stack_id = next((k for k, v in stat_names.items()
                         if v == NAME_STACK_STAT), None)
        metas = {}
        for entry in parts[_PLANE_EVENT_META]:
            kv = dict(_fields(entry))
            ev_name, stack = "", ""
            for f, v in _fields(kv.get(_MAP_VALUE, b"")):
                if f == _META_NAME:
                    ev_name = _text(v)
                elif f == _META_STATS:
                    stat = dict(_fields(v))
                    if stat.get(_STAT_META_ID) != stack_id:
                        continue
                    if _STAT_STR in stat:
                        stack = _text(stat[_STAT_STR])
                    elif _STAT_REF in stat:
                        stack = stat_names.get(stat[_STAT_REF], "")
            metas[kv.get(_MAP_KEY, 0)] = (ev_name, stack)
        for line in parts[_PLANE_LINES]:
            fields = list(_fields(line))
            if any(f == _LINE_NAME and _text(v) == OPS_LINE
                   for f, v in fields):
                out[name] = [
                    metas.get(dict(_fields(ev)).get(_EVENT_META_ID, 0),
                              ("", ""))
                    for f, ev in fields if f == _LINE_EVENTS]
    return out


_WRAPPED = re.compile(r"^[^()/]*\((.*)\)$")


@functools.lru_cache(maxsize=None)
def scope_names(stack: str) -> frozenset:
    """The path segments of a name stack, each unwrapped from the
    transforms around it: ``jit(f)/transpose(jvp(implicit_backward))/mul:``
    -> ``{"f", "implicit_backward", "mul:"}``."""
    out = set()
    for seg in stack.split("/"):
        while (m := _WRAPPED.match(seg)) is not None:
            seg = m.group(1)
        out.add(seg)
    return frozenset(out)


def train_split(reduced, iterations: float) -> dict[str, float | None]:
    """The layer numbers of a traced training window (PERF.md §3):

    * ``solve_share``, ``backward_share``: device time of ``deq_solve`` and
      of ``implicit_backward``, in % of the busy time;
    * ``block_eval_ms``, ``qn_update_ms``: device time of ``deq_block`` and
      of ``qn_update`` inside ``deq_solve``, per forward iteration (the
      solve's one evaluation before its first iteration is in the block's
      share).

    ``reduced`` is the window's ``trace.Reduced``; ``iterations`` its
    forward iterations, the steps' summed ``deq_steps``.  A number whose
    scope the trace lacks is None."""
    def per(seconds, over, scale):
        return scale * seconds / over if seconds > 0 and over > 0 else None

    busy_s, seconds = reduced.busy_s, reduced.scope_seconds
    return {
        "solve_share": per(seconds("deq_solve"), busy_s, 100.0),
        "backward_share": per(seconds("implicit_backward"), busy_s, 100.0),
        "block_eval_ms": per(seconds("deq_solve", "deq_block"), iterations,
                             1000.0),
        "qn_update_ms": per(seconds("deq_solve", "qn_update"), iterations,
                            1000.0),
    }
