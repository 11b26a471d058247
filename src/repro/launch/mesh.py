"""Device meshes.

``make_production_mesh`` (the dry-run's 256/512-chip layouts) and
``make_device_mesh`` (a ``DxM`` mesh over the devices present: one host's
chips, or forced CPU devices in a rehearsal) are FUNCTIONS — importing this
module never touches jax device state.  Single pod: (data=16, model=16) =
256 chips (TPU v5e pod slice); multi-pod: (pod=2, data=16, model=16) = 512
chips.  Scaling to 1000+ nodes grows the "pod" (pure-DP, compressed link)
and "data" axes — the sharding rules never change.
"""

from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    if jax.device_count() < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {jax.device_count()} "
            "(the dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512)"
        )
    return make_device_mesh(shape, axes)


def make_device_mesh(shape=(2, 2), axes=("data", "model")):
    """A mesh of ``shape`` over the first ``prod(shape)`` devices present,
    every axis in GSPMD's automatic sharding mode."""
    import jax

    n = int(np.prod(shape))
    if jax.device_count() < n:
        raise RuntimeError(f"mesh {tuple(shape)} needs {n} devices, have "
                           f"{jax.device_count()}")
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=jax.devices()[:n])


def parse_mesh(spec: str) -> tuple[int, int]:
    """``"DxM"`` -> ``(D, M)`` for a (data=D, model=M) mesh."""
    try:
        d, m = (int(v) for v in spec.lower().split("x"))
    except ValueError:
        raise SystemExit(f"--mesh wants DxM (e.g. 2x2), got {spec!r}")
    return d, m
