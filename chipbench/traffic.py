"""The one generator of the benchmark's traffic: every mix is a data file
``chipbench/workloads/<traffic>.json`` of parameters, read here.

One kind so far, ``train``: batches of ``batch`` rows of ``seq + 1``
uniform token ids, made on the device from the seed and the step index
(inputs shifted by one give the targets).  Every row of every step differs,
and every seed gets the same shapes.

A mix may name a mesh, ``"mesh": {"data": D, "model": M}``, over which the
program runs as its ``launch/train.py --mesh DxM`` path does, ZeRO-1
included.  A mix that names no mesh runs on one device.
"""

from __future__ import annotations

import json
from pathlib import Path

WORKLOAD_DIR = Path(__file__).resolve().parent / "workloads"
KINDS = ("train",)


def load(name: str) -> dict:
    return load_file(WORKLOAD_DIR / f"{name}.json")


def load_file(path: str | Path) -> dict:
    """Read a mix file, refusing a kind or mesh it cannot run."""
    mix = json.loads(Path(path).read_text())
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}")
    mesh = mix.get("mesh")
    if mesh is not None and (
            not isinstance(mesh, dict) or set(mesh) != {"data", "model"}
            or not all(type(v) is int and v >= 1 for v in mesh.values())):
        raise ValueError(f"{path}: mesh must be {{\"data\": D, \"model\": M}} "
                         f"with whole numbers D, M >= 1; got {mesh!r}")
    return mix


def mesh_shape(mix: dict) -> tuple[int, int] | None:
    """The ``(data, model)`` mesh the mix runs on; None for one device."""
    mesh = mix.get("mesh")
    return None if mesh is None else (mesh["data"], mesh["model"])


def chips_needed(mix: dict) -> int:
    shape = mesh_shape(mix)
    return 1 if shape is None else shape[0] * shape[1]


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
