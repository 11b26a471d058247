"""The control: the plain reference computed in fp8 (the precision below
the configuration's bfloat16), put in the program's place, comes out as
not correct under the cell's limits, while the program comes out correct.
At a size a CPU test run can hold; the cell's limits come from the same
comparison on the chip at the cell's size (PERF.md)."""

import gc

import pytest

from _tiny import train_run
from chipbench.jobs import train


@pytest.mark.parametrize("seed", [3, 2 ** 40 + 1])
def test_training_control_is_not_correct(seed):
    run = train_run(seed=seed)
    step, state, feed, prog, _ = train.build(run)
    del step, state, feed
    gc.collect()
    ref = train.reference_readings(run, "f32")
    ctl = train.reference_readings(run, "fp8")

    train.judge(run, train.gaps(prog, ref))
    assert run.correct, run.checks
    run.checks = []
    train.judge(run, train.gaps(ctl, ref))
    assert not run.correct, run.checks
