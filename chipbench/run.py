#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic and correctness limits are the files of those names under
``chipbench/configs/``, ``chipbench/workloads/`` and ``chipbench/limits/``,
and each per-layer metric is read by ``chipbench/metrics/<metric>.py``.
With ``--trace 0`` the result line holds the cell's end-to-end metrics;
with ``--trace 1`` a profiler trace of the window gives its per-layer
metrics.  The last line of standard output is one JSON object; a machine
without the chips the cell asks for gets an exit code of 3 and no result,
and a cell whose traffic names a mesh of another size than its chips an
exit code of 2 and no result.
"""

from __future__ import annotations

import argparse
import importlib.util
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402

PROCESS_START = harness.process_start()
METRIC_DIR = Path(__file__).resolve().parent / "metrics"


def reader(name: str):
    """The ``read(run)`` of ``metrics/<name>.py``."""
    path = METRIC_DIR / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(args, devices, job_override=None):
    """Run the cell on ``devices``; returns ``(run, result)``."""
    from chipbench import runctx, spec as spec_mod, traffic
    from chipbench.jobs import train

    wl, bench = harness.cell(args.workload)
    spec, _ = spec_mod.load(wl["config"])
    mix = traffic.load(wl["traffic"])
    run = runctx.Run(cell=args.workload, spec=spec, mix=mix, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     devices=devices, limits=runctx.limits(args.workload))
    compiles = harness.CompileCounter()
    job = job_override or {"train": train.run}[mix["kind"]]
    job(run, compiles)
    setup_s = run.setup_end - PROCESS_START
    metrics = {}
    if args.trace:
        for m in harness.metric_names(bench, args.workload, "per_layer"):
            value = reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in harness.metric_names(bench, args.workload, "end_to_end"):
            value = setup_s if m["name"] == "setup_s" else \
                run.end_to_end[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if args.trace and run.reduced_trace is not None:
        tr = run.reduced_trace
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": [list(g) for g in tr.gaps[:10]]}
    return run, result


def main(argv=None) -> int:
    args = parse(argv)
    wl, _ = harness.cell(args.workload)
    from chipbench import traffic

    harness.check_mesh(wl, traffic.load(wl["traffic"]))
    devices = harness.require_chips(wl["chips"])
    from chipbench import program

    program.use_persistent_cache()
    t = time.perf_counter()
    run, result = execute(args, devices)
    notes = {k: run.counters[k] for k in ("steps", "not_compared")
             if k in run.counters}
    print(f"chipbench: {args.workload} seed {args.seed}: window "
          f"{run.window_s:.3f} s, run {time.perf_counter() - t:.1f} s after "
          f"imports; {notes}", file=sys.stderr)
    harness.emit(result, run.checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
