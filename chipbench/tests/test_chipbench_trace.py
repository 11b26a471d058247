"""The trace reduction: device busy time, idle share, per-op time and gap
attribution, on a hand-built trace whose answers are known, and on a trace
recorded on a TPU v5e."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"

# device ops [1000, 3000] and [4000, 5000] ns; host annotations
# train_step [1000, 3600] and sync [3600, 5500]
XSPACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000
             stats { metadata_id: 1 str_value: "jit(f)/broyden_step_pallas" } }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "custom-call.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  stat_metadata { key: 1 value { id: 1 name: "long_name" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2600000 }
    events { metadata_id: 2 offset_ps: 2600000 duration_ps: 1900000 }
  }
  event_metadata { key: 1 value { id: 1 name: "train_step" } }
  event_metadata { key: 2 value { id: 2 name: "sync" } }
}
'''


@pytest.fixture
def hand_built(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return trace.reduce(path)


def test_window_busy_and_idle(hand_built):
    r = hand_built
    assert r.window_s == pytest.approx(4500e-9)
    assert r.busy_s == pytest.approx(3000e-9)
    assert r.n_devices == 1
    from chipbench.metrics_lib import idle_share

    class Run:
        reduced_trace = r

    assert idle_share(Run) == pytest.approx(100 / 3)


def test_op_time_by_name_or_detail(hand_built):
    assert hand_built.op_seconds("broyden_step")[0] == pytest.approx(2e-6)
    assert hand_built.op_seconds("broyden_step")[1] == 1
    assert hand_built.op_seconds("fusion")[1] == 1
    assert hand_built.top_ops(1) == [["custom-call", pytest.approx(2e-6)]]


def test_gaps_labelled_by_open_annotation(hand_built):
    labels = [(name, round(s * 1e9)) for name, s in hand_built.gaps]
    assert labels == [("train_step", 1000), ("sync", 500)]


def test_recorded_tpu_trace():
    pb = DATA / "v5e_forward.xplane.pb"
    r = trace.reduce(pb, annotations=("step", "host_gap"))
    assert r.n_devices == 1
    assert 0 < r.busy_s < r.window_s
    # two traced forwards with a 10 ms host sleep between them
    assert r.gaps[0][0] == "host_gap" and r.gaps[0][1] >= 0.009
    secs, calls = r.op_seconds(trace_kernel_pattern())
    assert calls > 0 and 0 < secs < r.busy_s
    kinds = [k for k, _ in r.top_ops(10)]
    assert "broyden_step_pallas" in kinds and "while" not in kinds


def test_op_names_from_hlo_text():
    assert trace.short_name("%fusion.3 = bf16[8]{0} fusion(x)") == "fusion.3"
    assert trace.base_name("broyden_step_pallas.10") == "broyden_step_pallas"


def trace_kernel_pattern():
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from chipbench.run import reader

    return reader("broyden_step_roofline").__globals__["KERNEL"]
