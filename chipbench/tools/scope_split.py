"""Trace a training window with the profiler, keep the trace file, and print
how the device time splits by the program's named scopes.

    python chipbench/tools/scope_split.py --seed N
    python chipbench/tools/scope_split.py --seed N --small OUT.xplane.pb

Without ``--small``: the cell ``minicpm-2b-deq.train`` at its size, set up
as the train job does and traced for the mix's ``trace_seconds``; the trace
is reduced as a traced run reduces it (``trace.reduce``) and removed.  With ``--small``: d=256, S=128, a group of
2 blocks, B=8, one traced step; the trace goes to ``OUT`` without its
``/host:metadata`` plane (the compiled modules, which no reduction reads),
and the window's counters beside it, under its stem with ``.json`` (the
recorded trace of ``tests/test_chipbench_scopes.py``).  Prints one JSON line: busy and window
seconds, iterations, seconds per scope, the layer numbers of
``scopes.train_split`` and the op kinds that take most time in each scope.
No correctness check runs.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CELL = "minicpm-2b-deq.train"
SCOPES = ("deq_solve", "deq_block", "qn_update", "implicit_backward")


def by_kind(reduced, scope: str, n: int = 6) -> list:
    from chipbench.scopes import scope_names
    from chipbench.trace import CONTAINERS, base_name

    tot: dict[str, int] = {}
    for op in reduced.ops:
        kind = base_name(op.name)
        if kind not in CONTAINERS and scope in scope_names(op.stack):
            tot[kind] = tot.get(kind, 0) + op.dur_ns
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, ns * 1e-9 / reduced.n_devices] for k, ns in top]


def _varint_bytes(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def drop_planes(data: bytes, names: tuple[str, ...]) -> bytes:
    """An XSpace without the planes named ``names``; every field of an
    XSpace is length-delimited, so the others are copied as they are."""
    from chipbench.scopes import _fields, _text

    out = bytearray()
    for num, value in _fields(memoryview(data)):
        if num == 1 and any(f == 2 and _text(v) in names
                            for f, v in _fields(value)):
            continue
        out += _varint_bytes(num << 3 | 2) + _varint_bytes(len(value))
        out += bytes(value)
    return bytes(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--small", default="")
    args = ap.parse_args(argv)

    import jax

    from chipbench import program, runctx, scopes, spec as spec_mod, traffic
    from chipbench import trace as trace_mod
    from chipbench.harness import cell
    from chipbench.jobs import train

    program.use_persistent_cache()
    wl, _ = cell(CELL)
    spec, _ = spec_mod.load(wl["config"])
    mix = traffic.load(wl["traffic"])
    if args.small:
        spec = dataclasses.replace(spec, d=256, heads=4, kv_heads=4,
                                   head_dim=64, d_ff=640, vocab=2048,
                                   blocks=2)
        mix.update(seq=128)
        seconds, keep = 0.0, Path(args.small)
    else:
        seconds, keep = mix["trace_seconds"], None
    run = runctx.Run(cell=CELL, spec=spec, mix=mix, seed=args.seed,
                     seconds=seconds, trace=True, devices=jax.devices()[:1],
                     limits=runctx.limits(CELL))
    step, state, feed, _, _ = train.build(run)
    first = mix["checked_steps"]
    capture = trace_mod.Capture()
    capture.start()
    state, n, dt, mets = train.window(run, step, state, feed, first,
                                      seconds, traced=False)
    reduced = capture.stop(keep=keep and str(keep))
    iters = sum(float(m["deq_steps"]) for m in mets)
    seconds = {key: reduced.scope_seconds(*key.split("/"))
               for key in ("deq_solve", "implicit_backward",
                           "deq_solve/deq_block", "deq_solve/qn_update",
                           "implicit_backward/deq_block")}
    out = {"device": jax.devices()[0].device_kind, "steps": n,
           "host_window_s": dt, "window_s": reduced.window_s,
           "busy_s": reduced.busy_s, "iterations": iters,
           "found": {s: reduced.found(s) for s in SCOPES},
           "seconds": dict(seconds, rest=reduced.busy_s
                           - seconds["deq_solve"]
                           - seconds["implicit_backward"]),
           "split": scopes.train_split(reduced, iters),
           "kinds": {s: by_kind(reduced, s) for s in SCOPES},
           "top_ops": reduced.top_ops(10)}
    if keep:
        keep.write_bytes(drop_planes(keep.read_bytes(), ("/host:metadata",)))
        keep.with_name(keep.name.split(".")[0] + ".json").write_text(
            json.dumps(
                {"steps": n,
                 "deq_steps": [float(m["deq_steps"]) for m in mets],
                 "seconds": seconds,
                 "seed": args.seed, "device": out["device"],
                 "spec": dataclasses.asdict(spec),
                 "batch": mix["batch"], "seq": mix["seq"]}, indent=1) + "\n")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
