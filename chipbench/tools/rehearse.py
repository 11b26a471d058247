"""Compile a training cell's step, and the reference that checks it, for a
described TPU v5e 2x2 host, without the chip, and print each program's
``memory_analysis()`` per chip.

    JAX_PLATFORMS=cpu python chipbench/tools/rehearse.py \\
        [--config CONFIG.json] [--mix MIX.json]

The configuration and the mix are files of the forms under
``chipbench/configs/`` and ``chipbench/workloads/`` (by default the
``minicpm-2b-deq.train`` cell's), read from any path.  The mix's ``mesh``
lays the program's step out over that many of the host's chips, ZeRO-1
included, as a run does; the reference's gradient and optimizer programs
are laid out by its own rule over the same chips.  Shapes only: nothing is
allocated.  The kernels are steered to their Pallas path, as on the chip (on
a CPU host the program would take its reference path).  One JSON line per
program.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from chipbench import model_ref, program, spec as spec_mod, traffic  # noqa: E402
from chipbench.weights import make_params  # noqa: E402

COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
               "collective-permute")


def structs(tree, shardings):
    """Shapes of ``tree`` laid out by the tree ``shardings``."""
    return jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


def report(name, compiled, chips):
    m = compiled.memory_analysis()
    text = compiled.as_text()
    row = {"program": name, "chips": chips,
           "arguments": m.argument_size_in_bytes,
           "outputs": m.output_size_in_bytes,
           "temporaries": m.temp_size_in_bytes,
           "aliased": m.alias_size_in_bytes,
           "arguments_plus_temporaries": (m.argument_size_in_bytes
                                          + m.temp_size_in_bytes),
           "pallas": "tpu_custom_call" in text,
           "collectives": {c: len(re.findall(rf"= \S+ {c}(-start)?\(", text))
                           for c in COLLECTIVES}}
    print(json.dumps(row), flush=True)


def step(spec, mix, devices, label):
    """The program's train step as a run jits it (``Trainer``), on the
    mix's mesh over ``devices`` or on the first of them."""
    from repro.launch.steps import TrainState
    from repro.optim.optimizers import adamw_init
    from repro.runtime.trainer import Trainer

    from chipbench.jobs.train import train_config

    cfg = program.model_config(spec)
    tcfg = train_config(mix, cfg)
    shape = traffic.mesh_shape(mix)
    chips = traffic.chips_needed(mix)
    ctx = program.ctx(cfg, mix, devices[:chips])
    trainer = Trainer(cfg, tcfg, ctx)
    params = jax.eval_shape(lambda: make_params(spec, 0))
    state = jax.eval_shape(lambda p: TrainState(
        jnp.zeros((), jnp.int32), p, adamw_init(p),
        program.lm.deq_solve_carry(cfg, mix["batch"], mix["seq"]),
        jnp.zeros((), jnp.int32)), params)
    batch = {k: jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), jnp.int32)
             for k in ("tokens", "targets")}
    if shape is None:
        one = SingleDeviceSharding(devices[0])
        state_sh = jax.tree_util.tree_map(lambda _: one, state)
        batch_sh = {k: one for k in batch}
    else:
        state_sh = trainer.state_sharding
        batch_sh = {k: program.batch_sharding(ctx) for k in batch}
    compiled = trainer._train_step.lower(
        structs(state, state_sh), structs(batch, batch_sh)).compile()
    report(f"{label} train step", compiled, chips)
    return compiled


def reference(spec, mix, devices, label):
    """The reference's gradient and optimizer programs at the mix's size,
    laid out by its own rule over the chips the mix's mesh takes."""
    chips = traffic.chips_needed(mix)
    mesh = model_ref.mesh_over(devices[:chips])
    params = jax.eval_shape(lambda: make_params(spec, 0))
    if mesh is None:
        psh = jax.tree_util.tree_map(
            lambda _: SingleDeviceSharding(devices[0]), params)
        rep = SingleDeviceSharding(devices[0])
    else:
        psh = model_ref.layout(params, mesh)
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    b, s = mix["batch"], mix["seq"]
    ids = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=rep)
    z = jax.ShapeDtypeStruct((b, s, spec.d), jnp.float32, sharding=rep)
    p16 = structs(params, psh)
    p32 = structs(jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), params), psh)
    tcfg = tuple(sorted({"lr": mix["lr"], "warmup_steps": mix["warmup_steps"],
                         "z_loss": mix["z_loss"],
                         "clip_norm": mix["clip_norm"],
                         "weight_decay": mix["weight_decay"], "b1": 0.9,
                         "b2": 0.95, "eps": 1e-8}.items()))
    with jax.default_matmul_precision("highest"):
        grad = model_ref._grad.lower(p16, ids, ids, z, spec, mix["z_loss"],
                                     "f32", spec.backward, mesh).compile()
        report(f"{label} reference gradient", grad, chips)
        adam = model_ref._adam.lower(p16, p32, p32, p32, tcfg, 1e-4,
                                     1.0).compile()
        report(f"{label} reference optimizer", adam, chips)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config",
                    default=str(spec_mod.CONFIG_DIR / "minicpm-2b-deq.json"))
    ap.add_argument("--mix", default=str(traffic.WORKLOAD_DIR
                                         / "train-b8-s512.json"))
    args = ap.parse_args(argv)

    from repro.kernels import ops

    ops._FORCED_IMPL = "pallas"
    jax.config.update("jax_enable_compilation_cache", False)
    spec, _ = spec_mod.load_file(args.config)
    mix = traffic.load_file(args.mix)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    label = f"{spec.name} B{mix['batch']} S{mix['seq']}"
    step(spec, mix, list(topo.devices), label)
    reference(spec, mix, list(topo.devices), label)
    return 0


if __name__ == "__main__":
    sys.exit(main())
