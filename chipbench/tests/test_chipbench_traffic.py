"""The traffic generator: deterministic for a seed, faithful to the mix's
parameters, and the same amount of work for every seed."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import traffic  # noqa: E402
from chipbench.weights import key_from_seed  # noqa: E402

MIX = {"kind": "train", "batch": 4, "seq": 16}


def batches(seed, steps, vocab=503):
    import jax

    fn = jax.jit(traffic.train_batch_fn(MIX, vocab))
    key = jax.random.fold_in(key_from_seed(seed), 1)
    return [tuple(np.asarray(a) for a in fn(key, i)) for i in steps]


def test_train_batches_are_fresh_and_repeatable():
    (t0, y0), (t1, _), (again, _) = batches(0, (0, 1, 0))
    assert t0.shape == (4, 16) and (t0 == again).all()
    assert (t0[:, 1:] == y0[:, :-1]).all()
    assert not (t0 == t1).all()
    assert int(t0.max()) < 503 and int(t0.min()) >= 0


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 11, 2 ** 62 + 5])
def test_every_seed_gets_the_same_shapes_and_other_tokens(seed):
    (t, y), = batches(seed, (0,))
    (base, _), = batches(7, (0,))
    assert t.shape == base.shape == (4, 16) and y.shape == t.shape
    assert t.dtype == base.dtype
    assert not (t == base).all()


def test_rows_within_a_batch_differ():
    (t, _), = batches(2 ** 40 + 3, (0,))
    assert len({tuple(r) for r in t}) == t.shape[0]


def test_mix_files_load():
    files = list(traffic.WORKLOAD_DIR.glob("*.json"))
    assert files
    for path in files:
        assert traffic.load(path.stem)["kind"] in traffic.KINDS


def test_unknown_kind_is_refused(tmp_path, monkeypatch):
    (tmp_path / "odd.json").write_text('{"kind": "stream"}')
    monkeypatch.setattr(traffic, "WORKLOAD_DIR", tmp_path)
    with pytest.raises(ValueError):
        traffic.load("odd")


@pytest.mark.parametrize("extra", [
    {"mesh": {"data": 2}},
    {"mesh": {"data": 2, "model": 0}},
    {"mesh": [2, 2]},
    {"mesh": {"data": 2, "model": 2, "pod": 1}},
    {"mesh": {"data": 2.0, "model": 2}},
    {"mesh": {"data": True, "model": 2}},
])
def test_bad_mesh_is_refused(tmp_path, extra):
    path = tmp_path / "odd.json"
    path.write_text(json.dumps(dict(traffic.load("train-b8-s512"), **extra)))
    with pytest.raises(ValueError):
        traffic.load_file(path)


def test_mesh_of_a_mix():
    mix = traffic.load("train-b8-s512")
    assert traffic.mesh_shape(mix) is None and traffic.chips_needed(mix) == 1
    mix.update(mesh={"data": 2, "model": 2})
    assert traffic.mesh_shape(mix) == (2, 2)
    assert traffic.chips_needed(mix) == 4
