"""Run cells of the benchmark one after another, each in its own process
(this parent never touches JAX, so each child gets the chip), and keep each
run's output under ``chiprun_out/runs/``.

    python chipbench/tools/runs.py CELL:SEED:SECONDS:TRACE [...]

Prints one summary line per run: exit code, wall time, and the result line.
"""

from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
OUT = ROOT / "chiprun_out" / "runs"


def one(cell: str, seed: str, seconds: str, trace: str, n: int = 0) -> dict:
    OUT.mkdir(parents=True, exist_ok=True)
    cmd = [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
           seed, "--seconds", seconds, "--trace", trace]
    t = time.time()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.time() - t
    log = OUT / f"{cell}-{seed}-t{trace}-{n}.log"
    log.write_text(p.stdout + "\n----- stderr -----\n" + p.stderr)
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    try:
        res = json.loads(last)
    except json.JSONDecodeError:
        res = None
    summary = {"cell": cell, "seed": int(seed), "trace": int(trace),
               "rc": p.returncode, "wall_s": round(wall, 1), "result": res}
    summary["notes"] = [ln for ln in p.stderr.splitlines()
                        if ln.startswith(("chipbench:", "check "))]
    if res is None:
        summary["stderr_tail"] = p.stderr[-1500:]
    print(json.dumps(summary), flush=True)
    return summary


def main(argv: list[str]) -> int:
    results = [one(*a.split(":"), n=i) for i, a in enumerate(argv)]
    (OUT / f"summary-{int(time.time())}.json").write_text(
        json.dumps(results, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
