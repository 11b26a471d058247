"""Roofline share of the fused Broyden-step kernel (``kernels/qn_apply.py``,
``broyden_step_pallas``) in the traced training window, in percent.

Least time per call = max(operations / peak FLOP/s, bytes / peak HBM
bandwidth) at the call's shapes, times the calls (one per forward-solve
iteration: the steps' own ``deq_steps``), over the device time of the ops
the trace attributes to ``broyden_step`` (the kernel, and any XLA op of its
jitted wrapper, which can only lower the share).  Bytes follow the repository's qN stream model
(``ops.qn_stream_bytes``, copied here): with mixed transpose flags each of
U and V is read in both phases, 4 * m * B * D * itemsize; plus the two f32
right-hand sides read once, the two f32 results and the evicted U/V rows
written once.  Operations: coefficient and apply products, 2 * 2 * 2 * m *
B * D.  The train step's solve state is (B, seq, d), so D = seq * d.
"""

from chipbench.metrics_lib import hbm_bw, peak_flops

KERNEL = r"broyden_step"


def call_bytes(m: int, b: int, dim: int, itemsize: int) -> int:
    uv = 4 * m * b * dim * itemsize
    vectors = 2 * b * dim * 4 + 2 * b * dim * 4 + 2 * b * dim * itemsize
    return uv + vectors


def call_flops(m: int, b: int, dim: int) -> int:
    return 8 * m * b * dim


def read(run):
    tr = run.reduced_trace
    if tr is None:
        return None
    seconds, _ = tr.op_seconds(KERNEL)
    calls = sum(run.counters.get("deq_steps", []))
    if not calls or seconds <= 0:
        return None
    spec, mix = run.spec, run.mix
    dim = mix["seq"] * spec.d
    itemsize = 2 if spec.qn_dtype == "bfloat16" else 4
    least = max(call_flops(spec.memory, mix["batch"], dim) / peak_flops(run),
                call_bytes(spec.memory, mix["batch"], dim, itemsize)
                / hbm_bw(run))
    return 100.0 * least * calls / seconds
