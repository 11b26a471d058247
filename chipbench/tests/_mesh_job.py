"""The train job on a (2, 2) mesh with ZeRO-1 and on one device, at a small
size on four virtual CPU devices; prints what ``test_chipbench_mesh.py``
checks as one JSON line.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python chipbench/tests/_mesh_job.py
"""

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src"), str(HERE)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from _tiny import NoCompiles, train_run  # noqa: E402
from chipbench import model_ref, program  # noqa: E402
from chipbench.jobs import train  # noqa: E402
from chipbench.weights import make_params  # noqa: E402

MESH = {"data": 2, "model": 2}


def held(tree) -> dict:
    """Bytes of ``tree`` in all, and the most any one device holds."""
    per: dict[int, int] = {}
    total = 0
    for a in jax.tree_util.tree_leaves(tree):
        total += a.nbytes
        for shard in a.addressable_shards:
            per[shard.device.id] = per.get(shard.device.id, 0) \
                + shard.data.nbytes
    return {"total": total, "most_on_a_device": max(per.values()),
            "devices": len(per)}


def numbers(run) -> dict:
    """Every reading of the check, the compared and the others."""
    out = {name: value for name, value, _, _ in run.checks}
    out.update({k: v for k, v in run.counters["not_compared"].items()
                if isinstance(v, float)})
    return out


def same_bits(a, b) -> bool:
    return all(np.array_equal(np.asarray(x).view(np.uint16),
                              np.asarray(y).view(np.uint16))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def main():
    devices = jax.devices()
    assert len(devices) == 4, devices
    out = {}

    # the timed path on the mesh: what the first step receives, then the
    # run's own check
    seen = {}

    def spy(step):
        def first(state, batch):
            if not seen:
                seen.update(
                    moments=held((state.opt.mu, state.opt.nu)),
                    ring=held((state.carry.lowrank.u, state.carry.lowrank.v)),
                    params=held(state.params),
                    batch_spec=str(batch["tokens"].sharding.spec))
            return step(state, batch)
        return first

    mesh_run = train_run(devices=devices, mesh=MESH)
    train.run(mesh_run, NoCompiles(), step_wrapper=spy)
    one_run = train_run(devices=devices[:1])
    train.run(one_run, NoCompiles())
    out.update(mesh_correct=mesh_run.correct, one_correct=one_run.correct,
               mesh_numbers=numbers(mesh_run), one_numbers=numbers(one_run),
               held=seen)

    # the weights: one device, the program's layout, the reference's
    spec, mix = mesh_run.spec, mesh_run.mix
    cfg = program.model_config(spec)
    ctx = program.ctx(cfg, mix, devices)
    out["batch_spec_wanted"] = str(program.batch_sharding(ctx).spec)
    one = make_params(spec, mesh_run.seed)
    out["weights_same_bits"] = {
        "program": same_bits(one, make_params(
            spec, mesh_run.seed,
            shardings=program.param_shardings(cfg, ctx))),
        "reference": same_bits(one, make_params(
            spec, mesh_run.seed, shardings=model_ref.layout(
                one, model_ref.mesh_over(devices))))}

    # the reference over four devices against itself on one
    spread = train.reference_readings(mesh_run, "f32")
    alone = train.reference_readings(one_run, "f32")
    gaps = train.gaps(spread, alone)
    out["reference_spread"] = {
        "loss": max(abs(a - b) / abs(b)
                    for a, b in zip(spread["loss"], alone["loss"])),
        **{k: gaps[k] for k in ("grad_gap", "change_gap", "grad_angle")}}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
