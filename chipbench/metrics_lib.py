"""Fixed model work and the helpers the per-layer readers share.

Model work is what an explicit model with the DEQ group's weights needs:
the group evaluated once forward (and once backward, twice its cost, for
training), causal attention, and the output head.  Solver iterations are
not counted, so a DEQ reads below its hardware FLOP rate by design, and a
change that cuts iterations raises these shares as it raises the rate.
A multiply-add counts as two operations.
"""

from __future__ import annotations

from chipbench.peaks import peaks
from chipbench.spec import ModelSpec


def group_matrix_params(spec: ModelSpec) -> int:
    d, hd = spec.d, spec.head_dim
    per_block = (d * spec.heads * hd * 2 + d * spec.kv_heads * hd * 2
                 + 3 * d * spec.d_ff)
    return spec.blocks * per_block


def attention_flops(spec: ModelSpec, n_keys_total: float) -> float:
    """Forward QK^T and PV over ``n_keys_total`` (query, key) pairs, every
    block."""
    return 4.0 * n_keys_total * spec.heads * spec.head_dim * spec.blocks


def causal_pairs(s: int) -> int:
    return s * (s + 1) // 2


def train_flops(spec: ModelSpec, batch: int, seq: int) -> float:
    """Per step: forward and backward (3x forward) of the group and head
    over every token, and causal attention over every row."""
    n = group_matrix_params(spec) + spec.head_params
    fwd = 2.0 * n * batch * seq + attention_flops(spec,
                                                  batch * causal_pairs(seq))
    return 3.0 * fwd


def peak_flops(run) -> float:
    return peaks(run.devices[0].device_kind)["bf16_flops"] * len(run.devices)


def hbm_bw(run) -> float:
    return peaks(run.devices[0].device_kind)["hbm_bytes_per_s"]


def idle_share(run) -> float | None:
    tr = run.reduced_trace
    if tr is None or tr.window_s <= 0 or tr.busy_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def train_scopes(run) -> dict:
    """``scopes.train_split`` of the traced training window; ``{}`` where
    the run has no trace."""
    from chipbench.scopes import train_split

    if run.reduced_trace is None:
        return {}
    return train_split(run.reduced_trace, sum(run.counters.get("deq_steps",
                                                               [])))
