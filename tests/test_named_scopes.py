"""The program's named scopes: the phases a profiler trace attributes
device time to (``deq_solve``, ``deq_block``, ``qn_update``,
``implicit_backward``; API.md "Observability").

The names live in op metadata only: the train step lowered with debug
info names all four, and without debug info it is the same text as the
step lowered with ``jax.named_scope`` replaced by a no-op, so the scopes
cost nothing in the program the device runs."""

import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import TrainConfig
from repro.configs.registry import smoke_config
from repro.launch import steps
from repro.parallel.sharding import ShardCtx

CTX = ShardCtx.for_mesh(None)
SCOPES = ("deq_solve", "deq_block", "qn_update", "implicit_backward")


def _names(text: str, scope: str) -> bool:
    """Whether ``scope`` is a segment of a name stack in ``text``: bare
    (``/deq_block/``) or wrapped by a transform (``jvp(deq_solve)/``)."""
    return re.search(rf"[/(]{scope}[/)]", text) is not None


def _lowered(backward: str):
    cfg = smoke_config("minicpm-2b", deq=True)
    cfg = dataclasses.replace(
        cfg, num_layers=2, d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
        vocab_size=128, head_dim=16,
        deq=dataclasses.replace(cfg.deq, backward=backward))
    tcfg = TrainConfig(steps=1, global_batch=2, seq_len=8, lr=1e-3,
                       zero1=False, seed=0)
    state = jax.eval_shape(lambda: steps.init_train_state(cfg, tcfg, CTX))
    batch = {k: jax.ShapeDtypeStruct((2, 8), jnp.int32)
             for k in ("tokens", "targets")}
    return jax.jit(steps.build_train_step(cfg, tcfg, CTX)).lower(state, batch)


@pytest.mark.parametrize("backward", ["shine_fallback", "full"])
def test_train_step_names_every_scope(backward):
    text = _lowered(backward).as_text(debug_info=True)
    for scope in SCOPES:
        assert _names(text, scope), scope


@pytest.mark.parametrize("backward", ["shine_fallback", "full"])
def test_scopes_leave_the_program_unchanged(backward, monkeypatch):
    scoped = _lowered(backward).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lowered(backward).as_text()
    assert not any(_names(scoped, scope) for scope in SCOPES)
    assert scoped == bare
