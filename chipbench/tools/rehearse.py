"""Compile the training cell's step for a described TPU v5e, without the
chip, and print its ``memory_analysis()``.

    JAX_PLATFORMS=cpu python chipbench/tools/rehearse.py

Shapes only: nothing is allocated.  The kernels are steered to their
Pallas path, as on the chip (on a CPU host the program would take its
reference path).
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from chipbench import program, spec as spec_mod, traffic  # noqa: E402
from chipbench.weights import make_params  # noqa: E402


def structs(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def report(name, compiled):
    m = compiled.memory_analysis()
    row = {"program": name,
           "arguments": m.argument_size_in_bytes,
           "outputs": m.output_size_in_bytes,
           "temporaries": m.temp_size_in_bytes,
           "aliased": m.alias_size_in_bytes,
           "pallas": "tpu_custom_call" in compiled.as_text()}
    print(json.dumps(row), flush=True)


def train(one):
    from repro.launch.steps import TrainState, build_train_step
    from repro.optim.optimizers import adamw_init

    from chipbench.jobs.train import train_config

    spec, _ = spec_mod.load("minicpm-2b-deq")
    mix = traffic.load("train-b8-s512")
    cfg = program.model_config(spec)
    tcfg = train_config(mix, cfg)
    params = jax.eval_shape(lambda: make_params(spec, 0))
    state = jax.eval_shape(lambda p: TrainState(
        jnp.zeros((), jnp.int32), p, adamw_init(p),
        program.lm.deq_solve_carry(cfg, mix["batch"], mix["seq"]),
        jnp.zeros((), jnp.int32)), params)
    batch = {k: jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), jnp.int32)
             for k in ("tokens", "targets")}
    step = jax.jit(build_train_step(cfg, tcfg, program.ctx()),
                   donate_argnums=(0,))
    report("minicpm-2b-deq train step B8 S512",
           step.lower(structs(state, one), structs(batch, one)).compile())


def main(argv):
    from repro.kernels import ops

    ops._FORCED_IMPL = "pallas"
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    train(one)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
