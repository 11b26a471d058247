"""The one generator of the benchmark's traffic: every mix is a data file
``chipbench/workloads/<traffic>.json`` of parameters, read here.

One kind so far, ``train``: batches of ``batch`` rows of ``seq + 1``
uniform token ids, made on the device from the seed and the step index
(inputs shifted by one give the targets).  Every row of every step differs,
and every seed gets the same shapes.
"""

from __future__ import annotations

import json
from pathlib import Path

WORKLOAD_DIR = Path(__file__).resolve().parent / "workloads"
KINDS = ("train",)


def load(name: str) -> dict:
    path = WORKLOAD_DIR / f"{name}.json"
    mix = json.loads(path.read_text())
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}")
    return mix


def train_batch_fn(mix: dict, vocab: int):
    """``(key, step) -> (tokens, targets)``, jittable; ``key`` is the run's
    data key."""
    import jax
    import jax.numpy as jnp

    b, s = mix["batch"], mix["seq"]

    def batch(key, step):
        ids = jax.random.randint(jax.random.fold_in(key, step), (b, s + 1),
                                 0, vocab, dtype=jnp.int32)
        return ids[:, :-1], ids[:, 1:]

    return batch
