"""The training check sees a broken timed path: the rest of a run is
driven on the CPU at a small size (the look for a chip skipped) with the
train step broken underneath, and ``correct`` comes out false."""

import pytest

from _tiny import NoCompiles, train_run
from chipbench.faults import train_half_batch, train_unchanged
from chipbench.jobs import train


def run_with(wrapper):
    run = train_run()
    train.run(run, NoCompiles(), step_wrapper=wrapper)
    return run


@pytest.fixture(scope="module")
def sound():
    return run_with(None)


def number(run, name):
    return next(v for n, v, _, _ in run.checks if n == name)


@pytest.mark.parametrize("fault,name", [(train_unchanged, "grad_gap"),
                                        (train_unchanged, "change_gap"),
                                        (train_half_batch, "grad_angle")])
def test_fault_makes_the_run_incorrect(sound, fault, name):
    broken = run_with(fault)
    assert not broken.correct
    limit = broken.limits[name]["limit"]
    assert number(broken, name) > limit >= number(sound, name)


def test_sound_run_reports_its_numbers(sound):
    assert sound.correct, sound.checks
    assert {n for n, *_ in sound.checks} == set(sound.limits)
    assert sound.end_to_end["train_tokens_per_s"] > 0
    assert sound.attempted > 0 and sound.failed == 0
