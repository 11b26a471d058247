"""Fixed model work and kernel byte counts against hand-computed values,
and the peaks table."""

import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import metrics_lib, peaks, spec as spec_mod  # noqa: E402
from chipbench.run import reader  # noqa: E402


def minicpm():
    return spec_mod.load("minicpm-2b-deq")[0]


def test_parameter_counts():
    s = minicpm()
    # per block: q, k, v, o of 2304 x 2304 and three 2304 x 5760 matrices
    assert metrics_lib.group_matrix_params(s) == 4 * (4 * 2304 * 2304
                                                     + 3 * 2304 * 5760)
    assert s.padded_vocab == 122880
    assert metrics_lib.group_matrix_params(s) + s.head_params == 527_302_656


def test_train_flops():
    s = minicpm()
    n = 527_302_656
    attention = 4 * (8 * 512 * 513 // 2) * 36 * 64 * 4
    assert metrics_lib.train_flops(s, 8, 512) == 3 * (2 * n * 8 * 512
                                                      + attention)
    assert abs(metrics_lib.train_flops(s, 8, 512) / 13.08e12 - 1) < 0.01


def test_broyden_step_bytes():
    read = reader("broyden_step_roofline")
    glob = read.__globals__
    d = 512 * 2304
    assert glob["call_bytes"](8, 8, d, 2) == (4 * 8 * 8 * d * 2
                                              + 8 * d * 4 * 4 + 2 * 8 * d * 2)
    assert glob["call_flops"](8, 8, d) == 8 * 8 * 8 * d


def fake_run(**kw):
    dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    base = dict(spec=minicpm(), mix={"batch": 8, "seq": 512, "memory": 8},
                devices=[dev], counters={}, window_s=0.0, reduced_trace=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_mfu_train_reader():
    run = fake_run(counters={"steps": 10}, window_s=5.0)
    want = 100 * metrics_lib.train_flops(minicpm(), 8, 512) * 2 / 197e12
    assert reader("mfu.train")(run) == pytest.approx(want)
    assert reader("mfu.train")(fake_run()) is None


def test_roofline_reader_from_trace():
    from chipbench.trace import Op, Reduced

    d = 512 * 2304
    least = (4 * 8 * 8 * d * 2 + 8 * d * 4 * 4 + 2 * 8 * d * 2) / 819e9
    ops = [Op("custom-call.1", 0, int(least * 2e9), "broyden_step_pallas"),
           Op("custom-call.1", 0, int(least * 2e9), "broyden_step_pallas"),
           Op("fusion.3", 0, 10 ** 9, "jit(train_step)/dot")]
    tr = Reduced(window_s=1.0, busy_s=0.5, n_devices=1, ops=ops, gaps=[])
    share = reader("broyden_step_roofline")(
        fake_run(reduced_trace=tr, counters={"deq_steps": [1.0, 1.0]}))
    assert share == pytest.approx(50.0, rel=1e-6)
    none = Reduced(window_s=1.0, busy_s=0.5, n_devices=1, ops=ops[2:],
                   gaps=[])
    assert reader("broyden_step_roofline")(
        fake_run(reduced_trace=none, counters={"deq_steps": [1.0]})) is None
    assert reader("idle_share.train")(fake_run(reduced_trace=tr)) == 50.0


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
