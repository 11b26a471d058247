"""A train cell whose mix names a mesh runs the program's sharded path: on
four virtual CPU devices (in a subprocess, so that this process keeps its
one device), the train job on a (2, 2) mesh with ZeRO-1 reads correct and
as the one-device run does, its weights are the same bits in every layout,
its moments and qN ring are spread over the devices, and the reference
spread over them reads as on one.  A mix whose mesh differs from the cell's
chips is refused before set-up."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness, traffic  # noqa: E402

# each gap is mostly the bf16 program's departure from the f32 reference;
# another layout rounds the program's sums apart, which moves a gap by a part
# of itself, not by a factor: a mesh fault that multiplies a gap shows here
# while it stays under the cell's limits
GAP_RATIO = 2.0
# the first step's loss gap reads at the rounding of one mean loss (sound
# runs at the cell's size: 2.2e-6 to 6.4e-5), where a factor says nothing
GAP_FLOOR = {"first_loss_gap": 1e-5}
# f32 sums over some 10^4 terms (a norm scale's gradient over every token),
# added in another order on four devices, with cancellation: about a hundred
# units in the last place (2**-23) of the median leaf's norm
F32_REORDER = 1e-5
# the change is of parameters stored in bf16 after each step: an update that
# differs in its last f32 places rounds a few elements to the neighbouring
# bf16 value, 2**-8 of a weight (about 3.5e-5 here) against a change over
# two steps of about 9e-5, each such element some 1e-5 of a leaf's change
BF16_FLIPS = 1e-4


@pytest.fixture(scope="module")
def mesh_job():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip())
    p = subprocess.run([sys.executable, "chipbench/tests/_mesh_job.py"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-6000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_run_is_correct(mesh_job):
    assert mesh_job["mesh_correct"], mesh_job["mesh_numbers"]
    assert mesh_job["one_correct"], mesh_job["one_numbers"]


def test_mesh_gaps_match_one_device(mesh_job):
    mesh, one = mesh_job["mesh_numbers"], mesh_job["one_numbers"]
    assert set(mesh) == set(one) >= {"grad_gap", "change_gap", "grad_angle",
                                     "first_loss_gap", "loss_gap"}
    for name in one:
        floor = GAP_FLOOR.get(name, 0.0)
        assert ((one[name] - floor) / GAP_RATIO <= mesh[name]
                <= (one[name] + floor) * GAP_RATIO), (name, mesh[name],
                                                      one[name])


def test_weights_are_the_same_bits_in_every_layout(mesh_job):
    assert mesh_job["weights_same_bits"] == {"program": True,
                                             "reference": True}


@pytest.mark.parametrize("part", ["moments", "ring"])
def test_state_is_spread(mesh_job, part):
    held = mesh_job["held"][part]
    assert held["devices"] == 4
    assert held["most_on_a_device"] <= held["total"] / 2


def test_no_device_holds_the_whole_model_and_batches_are_placed(mesh_job):
    held = mesh_job["held"]["params"]
    assert held["devices"] == 4
    assert held["most_on_a_device"] < held["total"]
    assert mesh_job["held"]["batch_spec"] == mesh_job["batch_spec_wanted"]


def test_reference_spread_matches_one_device(mesh_job):
    """The reference over four devices against itself on one, by the
    numbers a run compares: summation order alone sets them apart, and the
    rounding of the stored parameters that it moves."""
    spread = mesh_job["reference_spread"]
    for name in ("loss", "grad_gap", "grad_angle"):
        assert spread[name] <= F32_REORDER, (name, spread[name])
    assert spread["change_gap"] <= BF16_FLIPS, spread["change_gap"]


def write_cell(root: Path, chips: int, mesh) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    mix = traffic.load("train-b8-s512")
    mix.pop("mesh", None)
    if mesh:
        mix.update(mesh=mesh)
    (root / "chipbench" / "workloads" / "odd-mesh.json").write_text(
        json.dumps(mix))
    wl = dict(bench["workloads"][0], name="odd-mesh", traffic="odd-mesh",
              chips=chips)
    bench["workloads"].append(wl)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    limits = root / "chipbench" / "limits"
    shutil.copy(limits / f"{bench['workloads'][0]['name']}.json",
                limits / "odd-mesh.json")
    return wl


@pytest.mark.parametrize("chips,mesh", [(1, {"data": 2, "model": 2}),
                                        (4, None),
                                        (4, {"data": 2, "model": 1})])
def test_mesh_of_other_size_is_refused(tmp_path, chips, mesh):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    wl = write_cell(tmp_path, chips, mesh)
    mix = traffic.load_file(tmp_path / "chipbench" / "workloads"
                            / "odd-mesh.json")
    with pytest.raises(SystemExit):
        harness.check_mesh(wl, mix)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "odd-mesh",
         "--seed", "4294967311", "--seconds", "1"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "runs on" in p.stderr and not p.stdout.strip()
