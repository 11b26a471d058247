"""A tiny configuration and mixes of the benchmark's cells, for driving
the jobs end to end on the CPU."""

import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import runctx, spec as spec_mod, traffic  # noqa: E402


def tiny_spec(name="minicpm-2b-deq"):
    spec, _ = spec_mod.load(name)
    return dataclasses.replace(spec, d=64, heads=4, kv_heads=4, head_dim=16,
                               d_ff=128, vocab=503, blocks=2)


def small_spec(name="minicpm-2b-deq"):
    """Small enough for a CPU test run, wide enough that the program's
    first gradient reads as close to the reference as at the cell's size."""
    spec, _ = spec_mod.load(name)
    return dataclasses.replace(spec, d=128, heads=4, kv_heads=4, head_dim=32,
                               d_ff=320, vocab=1000)


def train_run(seed=2 ** 33 + 5, spec=None, devices=None, **mix_kw):
    import jax

    mix = traffic.load("train-b8-s512")
    mix.update(batch=4, seq=64, trace_seconds=1, **mix_kw)
    return runctx.Run(cell="minicpm-2b-deq.train",
                      spec=spec or small_spec(), mix=mix,
                      seed=seed, seconds=1.0, trace=False,
                      devices=devices or jax.devices(),
                      limits=runctx.limits("minicpm-2b-deq.train"))


class NoCompiles:
    active = False
    count = 0
    names: list = []
