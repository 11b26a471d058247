"""Faults planted under the timed path, to show the check catches them:
used by the tests (at a tiny size on the CPU) and by ``tools/readings.py``
(at the cell's size on the chip)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def train_unchanged(step):
    """A train step that returns its state unchanged."""
    def broken(state, batch):
        _, met = step(jax.tree_util.tree_map(jnp.copy, state), batch)
        return state, met
    return broken


def train_half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def broken(state, batch):
        b = batch["targets"].shape[0]
        return step(state, {"tokens": batch["tokens"],
                            "targets": batch["targets"].at[b // 2:].set(-1)})
    return broken
