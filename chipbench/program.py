"""The system under test, as the benchmark drives it: the repository's DEQ
language model (``repro``), its trainer step and its serving loop.

Everything the benchmark takes from the program goes through here: the
model configuration built from a configuration file, the sharding context
and layouts of a mesh the traffic names, the jitted train step, and the
counters it keeps.
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[1]
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

from repro.configs.base import DEQSettings, ModelConfig  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.configs.shapes import SHAPES, make_ctx  # noqa: E402
from repro.launch.compile_cache import use_persistent_cache  # noqa: E402
from repro.launch.steps import param_shardings  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.parallel.sharding import ShardCtx  # noqa: E402

from chipbench.spec import ModelSpec  # noqa: E402
from chipbench.traffic import mesh_shape  # noqa: E402

__all__ = ["model_config", "check_layout", "use_persistent_cache", "ctx",
           "device_mesh", "param_shardings", "batch_sharding", "lm", "AXES"]

AXES = ("data", "model")


def model_config(spec: ModelSpec) -> ModelConfig:
    """The program's configuration, every size taken from ``spec``."""
    cfg = get_config(spec.program_arch)
    return dataclasses.replace(
        cfg, d_model=spec.d, num_heads=spec.heads,
        num_kv_heads=spec.kv_heads, head_dim=spec.head_dim, d_ff=spec.d_ff,
        vocab_size=spec.vocab, tie_embeddings=spec.tied,
        rope_theta=spec.rope_theta, norm_eps=spec.norm_eps,
        dtype="bfloat16", act="silu", family="dense",
        deq=DEQSettings(enabled=True, num_blocks=spec.blocks,
                        solver=spec.solver, max_steps=spec.max_steps,
                        tol=spec.tol, memory=spec.memory,
                        backward=spec.backward, qn_dtype=spec.qn_dtype))


def check_layout(cfg: ModelConfig, params) -> None:
    """Refuse weights whose tree or shapes differ from what the program's
    model declares."""
    want = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), params)
    need = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), want)
    if got != need:
        raise ValueError(f"weights layout {got} differs from the model's "
                         f"{need}")


def ctx(cfg: ModelConfig, mix: dict, devices) -> ShardCtx:
    """The program's sharding context for the mix: none for a mix that names
    no mesh; else the one ``launch/train.py --mesh DxM`` builds, over
    ``devices``."""
    shape = mesh_shape(mix)
    if shape is None:
        return ShardCtx.for_mesh(None)
    # the rules ``launch/train.py`` takes: its ``train_4k`` shape's
    return make_ctx(cfg, device_mesh(shape, devices), SHAPES["train_4k"])


def device_mesh(shape: tuple[int, int], devices):
    """The ``(data, model)`` mesh of ``shape`` over ``devices``, laid out as
    the program's ``launch.mesh.make_device_mesh`` lays it out over the
    devices present: every axis in GSPMD's automatic sharding mode."""
    return jax.make_mesh(tuple(shape), AXES,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(AXES),
                         devices=list(devices))


def batch_sharding(ctx: ShardCtx):
    """Where a ``(batch, seq)`` token array goes: the layout the program's
    data pipeline (``data.pipeline.shard_batch``) gives it; None off a
    mesh."""
    return None if ctx.mesh is None else ctx.sharding(("batch", "seq"))
