"""The readings each correctness limit of a training cell is set from,
over many seeds in one process: the program's sound runs, the control
(the plain reference in fp8 in the program's place) and the planted
faults, each judged against the cell's limits as a run would be.

    python chipbench/tools/readings.py CELL SEED [SEED ...]
    python chipbench/tools/readings.py --witness CELL SEED [SEED ...]

Per seed: the program's first steps against the f32 reference of the
configured backward; the fp8 reference against the same; half the batch
left out (the reference over the first half of the rows).  A step that
returns its state unchanged reads 1 on ``grad_gap`` and ``change_gap`` by
definition and needs no run.  ``--witness`` runs the program with its own
exact backward (``full``) against the reference's exact implicit gradient
instead, to tell the estimator's departure from a fault.
Prints one JSON line per reading and writes them to
``chiprun_out/readings/``.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, runctx, spec as spec_mod  # noqa: E402
from chipbench import traffic  # noqa: E402

OUT = ROOT / "chiprun_out" / "readings"


def new_run(cell: str, seed: int, devices, backward=None):
    wl, _ = harness.cell(cell)
    spec, _ = spec_mod.load(wl["config"])
    if backward:
        spec = dataclasses.replace(spec, backward=backward)
    return runctx.Run(cell=cell, spec=spec, mix=traffic.load(wl["traffic"]),
                      seed=seed, seconds=0, trace=False, devices=devices,
                      limits=runctx.limits(cell))


def judged(run, g: dict) -> dict:
    """The readings ``g`` as a run would judge them."""
    from chipbench.jobs import train

    run.checks = []
    train.judge(run, g)
    return dict(g, correct=run.correct)


def train_readings(cell, seeds, devices, emit, witness=False):
    from chipbench.jobs import train

    for seed in seeds:
        t = time.time()
        run = new_run(cell, seed, devices, "full" if witness else None)
        step, state, feed, prog, _ = train.build(run)
        del step, state, feed
        gc.collect()
        if witness:
            ref = train.reference_readings(run, "f32", backward="exact")
            emit({"seed": seed, "witness_full_vs_exact": judged(
                run, train.gaps(prog, ref)), "grad": prog["grad"],
                "ref_grad": ref["grad"], "s": time.time() - t})
            continue
        ref = train.reference_readings(run, "f32")
        t_ref = time.time() - t
        ctl = train.reference_readings(run, "fp8")
        half = train.reference_readings(run, "f32", rows=slice(
            0, run.mix["batch"] // 2))
        emit({"seed": seed, "program": judged(run, train.gaps(prog, ref)),
              "control": judged(run, train.gaps(ctl, ref)),
              "half_batch": judged(run, train.gaps(half, ref)),
              "deq_steps": prog["deq_steps"], "loss": prog["loss"],
              "ref_loss": ref["loss"], "ctl_loss": ctl["loss"],
              "grad": prog["grad"], "ref_grad": ref["grad"],
              "ctl_grad": ctl["grad"], "skipped": prog["skipped"],
              "ref_s": t_ref, "s": time.time() - t})


def main(argv):
    witness = argv[0] == "--witness"
    argv = argv[1:] if witness else argv
    cell, seeds = argv[0], [int(s) for s in argv[1:]]
    wl, _ = harness.cell(cell)
    devices = harness.require_chips(wl["chips"])
    from chipbench import program

    program.use_persistent_cache()
    OUT.mkdir(parents=True, exist_ok=True)
    out = OUT / f"{cell}.jsonl"

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        with out.open("a") as f:
            f.write(line + "\n")

    train_readings(cell, seeds, devices, emit, witness)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
