"""Device time per named scope (``chipbench/scopes.py``,
``Reduced.scope_seconds``): the name-stack reader on a hand-built trace
whose answers are known, in both forms the ``tf_op`` stat takes, and on
traces recorded on a TPU v5e; and the existing reduction of the recorded
forward trace, pinned."""

import json
import math
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import scopes, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
FORWARD = DATA / "v5e_forward.xplane.pb"
TRAIN = DATA / "v5e_train_scopes.xplane.pb"
SCOPES = ("deq_solve", "deq_block", "qn_update", "implicit_backward")

# device ops (us): fusion.6 [0, 1] (the solve's first block evaluation),
# while.1 [1, 11] holding fusion.2 [2, 5] (block) and custom-call.3 [5, 7]
# (qN update), fusion.4 [12, 14] (backward, its stack held by reference),
# copy.5 [14, 15] (no stack); the harness annotation train_step [0, 13]
US = 1_000_000  # ps
XSPACE = f'''
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events {{ metadata_id: 6 offset_ps: 0 duration_ps: {1 * US} }}
    events {{ metadata_id: 1 offset_ps: {1 * US} duration_ps: {10 * US} }}
    events {{ metadata_id: 2 offset_ps: {2 * US} duration_ps: {3 * US} }}
    events {{ metadata_id: 3 offset_ps: {5 * US} duration_ps: {2 * US} }}
    events {{ metadata_id: 4 offset_ps: {12 * US} duration_ps: {2 * US} }}
    events {{ metadata_id: 5 offset_ps: {14 * US} duration_ps: {1 * US} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "while.1"
    stats {{ metadata_id: 1 str_value: "jit(f)/jvp(deq_solve)/while" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "fusion.2"
    stats {{ metadata_id: 1
             str_value: "jit(f)/jvp(deq_solve)/while/body/deq_block/dot:" }}
  }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "custom-call.3"
    stats {{ metadata_id: 3 str_value: "a long name" }}
    stats {{ metadata_id: 1 str_value:
      "jit(f)/jvp(deq_solve)/while/body/qn_update/jit(broyden_step)/pallas:" }}
  }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "fusion.4"
    stats {{ metadata_id: 1 ref_value: 2 }} }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "copy.5" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "fusion.6"
    stats {{ metadata_id: 1 str_value: "jit(f)/jvp(deq_solve)/deq_block/add:" }}
  }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2
    name: "jit(f)/transpose(jvp(implicit_backward))/deq_block/mul:" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "long_name" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{
    id: 1
    name: "python"
    timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {13 * US} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "train_step" }} }}
}}
'''


@pytest.fixture
def hand_built(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return path


def test_name_stacks_as_string_and_as_reference(hand_built):
    (plane, ops), = scopes.op_stacks(hand_built).items()
    assert plane == "/device:TPU:0"
    assert [name for name, _ in ops] == ["fusion.6", "while.1", "fusion.2",
                                         "custom-call.3", "fusion.4",
                                         "copy.5"]
    stacks = dict(ops)
    assert stacks["custom-call.3"].endswith("/qn_update/jit(broyden_step)/"
                                            "pallas:")
    assert stacks["fusion.4"] == ("jit(f)/transpose(jvp(implicit_backward))/"
                                  "deq_block/mul:")
    assert stacks["copy.5"] == ""


def test_scope_seconds_leave_the_container_out(hand_built):
    r = trace.reduce(hand_built)
    us = 1e-6
    assert r.scope_seconds("deq_solve") == pytest.approx(6 * us)
    assert r.scope_seconds("deq_solve", "deq_block") == pytest.approx(4 * us)
    assert r.scope_seconds("deq_solve", "qn_update") == pytest.approx(2 * us)
    # clipped at the window's end (13 us)
    assert r.scope_seconds("implicit_backward") == pytest.approx(1 * us)
    assert r.scope_seconds("deq_block") == pytest.approx(5 * us)
    assert r.scope_seconds("serve_tick") == 0.0
    assert not r.found("serve_tick") and r.found("qn_update")
    assert r.busy_s == pytest.approx(12 * us)
    split = scopes.train_split(r, iterations=2)
    assert split == pytest.approx({"solve_share": 50.0,
                                   "backward_share": 100 / 12,
                                   "block_eval_ms": 2e-3,
                                   "qn_update_ms": 1e-3})


def test_missing_scope_reads_none(hand_built):
    r = trace.reduce(hand_built)
    r.ops = [op for op in r.ops if "qn_update" not in op.stack]
    split = scopes.train_split(r, iterations=2)
    assert split["qn_update_ms"] is None
    assert split["block_eval_ms"] is not None
    assert scopes.train_split(r, iterations=0)["block_eval_ms"] is None


@pytest.mark.parametrize("stack, names", [
    ("jit(f)/jvp(deq_solve)/while/body/qn_update/gt:",
     {"f", "deq_solve", "while", "body", "qn_update", "gt:"}),
    ("jit(train_step)/transpose(jvp(implicit_backward))/mul:",
     {"train_step", "implicit_backward", "mul:"}),
    ("jit(<lambda>)/while/body/bsd,de->bse/dot_general:",
     {"<lambda>", "while", "body", "bsd,de->bse", "dot_general:"}),
])
def test_scope_names_unwrap_transforms(stack, names):
    assert scopes.scope_names(stack) == names


def test_recorded_forward_has_name_stacks():
    named = scopes.op_stacks(FORWARD)["/device:TPU:0"]
    with_stack = [s for _, s in named if s]
    assert len(with_stack) > 0.9 * len(named)
    assert any("/while/body/" in s for s in with_stack)
    assert any("jit(flash_attention_pallas)/pallas_call" in s
               for s in with_stack)
    # the reduction's ops carry their stacks and chips
    r = trace.reduce(FORWARD, annotations=("step", "host_gap"))
    assert sum(1 for o in r.ops if o.stack) > 0.9 * len(r.ops)
    assert {o.device for o in r.ops} == {0}


def test_recorded_forward_reduction_pinned():
    """The reduction the accepted per-layer metrics read, pinned to the
    values it gave before the name-stack reader came beside it."""
    r = trace.reduce(FORWARD, annotations=("step", "host_gap"))
    assert r.window_s == 0.079612448
    assert r.busy_s == 0.053845853000000006
    assert r.gaps == [
        ("host_gap", 0.013071398000000001), ("host_gap", 0.012692455),
        ("step", 8.890000000000001e-07), ("step", 6.900000000000001e-07),
        ("step", 4.25e-07), ("step", 4.1900000000000003e-07),
        ("step", 1e-08), ("step", 1e-08), ("step", 3.0000000000000004e-09),
        ("step", 2e-09)]
    assert r.top_ops(10) == [
        ["fusion", 0.017409763], ["broyden_step_pallas", 0.010716182000000001],
        ["convolution_bitcast_fusion", 0.008049949],
        ["bitcast_add_fusion", 0.003677544],
        ["flash_attention_pallas", 0.0023910840000000004],
        ["reshape", 0.002093634], ["copy", 0.002004145],
        ["multiply_subtract_fusion", 0.001236581],
        ["rmsnorm_pallas", 0.001040513], ["maximum_bitcast_fusion", 0.00098644]]
    assert r.op_seconds("broyden_step") == (0.010716182000000001, 24)


@pytest.fixture(scope="module")
def recorded_train():
    """One train step at d=256, S=128, 2 blocks, B=8, traced on a TPU v5e
    (``tools/scope_split.py --small``), and its counters."""
    counters = json.loads((DATA / "v5e_train_scopes.json").read_text())
    return trace.reduce(TRAIN), counters


def test_recorded_train_finds_every_scope(recorded_train):
    r, _ = recorded_train
    for scope in SCOPES:
        assert r.found(scope), scope
        assert r.scope_seconds(scope) > 0, scope


def test_recorded_train_scope_seconds_pinned(recorded_train):
    """``Reduced.scope_seconds`` gives what the recording's own split gave
    (the ``seconds`` of its counters file, per chip)."""
    r, counters = recorded_train
    assert counters["seconds"]
    for key, want in counters["seconds"].items():
        assert r.scope_seconds(*key.split("/")) == pytest.approx(
            want, rel=1e-12), key


def test_recorded_train_scopes_nest(recorded_train):
    r, _ = recorded_train
    solve = r.scope_seconds("deq_solve")
    assert (r.scope_seconds("deq_solve", "deq_block")
            + r.scope_seconds("deq_solve", "qn_update")) <= solve
    assert solve + r.scope_seconds("implicit_backward") <= r.busy_s
    # the forward solve's ops are not the backward's
    assert r.scope_seconds("deq_solve", "implicit_backward") == 0.0
    # the fused kernel sits in the qN update of the forward solve
    kernel = sum(o.dur_ns for o in r.ops
                 if trace.base_name(o.name) == "broyden_step_pallas")
    assert kernel > 0
    assert r.scope_seconds("deq_solve", "qn_update") >= kernel * 1e-9


def test_recorded_train_split_is_finite(recorded_train):
    r, counters = recorded_train
    split = scopes.train_split(r, sum(counters["deq_steps"]))
    assert set(split) == {"solve_share", "backward_share", "block_eval_ms",
                          "qn_update_ms"}
    for name, value in split.items():
        assert value is not None and math.isfinite(value) and value > 0, name
    assert split["solve_share"] + split["backward_share"] <= 100.0


@pytest.mark.parametrize("name", ["solve_share", "backward_share",
                                  "block_eval_ms", "qn_update_ms"])
def test_scope_metric_readers(recorded_train, name):
    """Each per-layer reader gives its entry of the split that
    ``tools/scope_split.py`` prints for the same trace, and nothing for a
    run without one."""
    import types

    from chipbench.run import reader

    r, counters = recorded_train
    run = types.SimpleNamespace(reduced_trace=r,
                                counters={"deq_steps": counters["deq_steps"]})
    split = scopes.train_split(r, sum(counters["deq_steps"]))
    assert reader(f"{name}.train")(run) == split[name]
    run.reduced_trace = None
    assert reader(f"{name}.train")(run) is None
