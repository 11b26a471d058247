"""Kernel sweeps: every Pallas kernel against its pure-jnp oracle, executed
with interpret=True on CPU (validates the TPU code path), plus the flash_xla
execution path against the dense oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref
from repro.kernels.flash_attention import (
    decode_attention_pallas,
    flash_attention_pallas,
    flash_plan,
)
from repro.kernels.flash_xla import flash_attention_xla
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.obs import metrics as obs_metrics

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    # f32 atol scales with output magnitude (~m * sqrt(d) accumulations in a
    # different order than the einsum oracle)
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# qn_apply (THE SHINE op)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,bsz,d", [(1, 1, 8), (4, 2, 64), (8, 3, 100),
                                     (16, 2, 512), (30, 1, 1000),
                                     (8, 12, 300), (8, 16, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qn_apply_pallas_vs_oracle(m, bsz, d, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, m * 1000 + d), 4)
    u = jax.random.normal(ks[0], (m, bsz, d), dtype)
    v = jax.random.normal(ks[1], (m, bsz, d), dtype)
    x = jax.random.normal(ks[2], (bsz, d), dtype)
    count = jax.random.randint(ks[3], (bsz,), 0, m + 1)
    mask = (jnp.arange(m)[:, None] < count[None, :]).astype(jnp.float32)
    alpha = jnp.float32(0.7)
    want = ref.qn_apply_ref(u, v, x, alpha, mask)
    got = ops.qn_apply(u, v, x, alpha, mask, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_qn_apply_block_tiling_edges():
    """d not divisible by the block and m not a sublane multiple."""
    m, bsz, d = 5, 2, 777
    ks = jax.random.split(KEY, 3)
    u = jax.random.normal(ks[0], (m, bsz, d))
    v = jax.random.normal(ks[1], (m, bsz, d))
    x = jax.random.normal(ks[2], (bsz, d))
    mask = jnp.ones((m, bsz), jnp.float32)
    want = ref.qn_apply_ref(u, v, x, jnp.float32(1.0), mask)
    got = ops.qn_apply(u, v, x, jnp.float32(1.0), mask,
                       impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_qn_apply_small_dim_lane_padding():
    """dim < block_d and not a multiple of 128: the feature axis must be
    padded up to the lane boundary, never tiled raggedly."""
    from repro.kernels.qn_apply import _pad_features
    blk, u = _pad_features(512, 100, jnp.zeros((4, 2, 100)))
    assert blk % 128 == 0 and u.shape[-1] % blk == 0
    m, bsz, d = 4, 2, 100
    ks = jax.random.split(jax.random.fold_in(KEY, 99), 3)
    u = jax.random.normal(ks[0], (m, bsz, d))
    v = jax.random.normal(ks[1], (m, bsz, d))
    x = jax.random.normal(ks[2], (bsz, d))
    mask = jnp.ones((m, bsz), jnp.float32)
    want = ref.qn_apply_ref(u, v, x, jnp.float32(0.3), mask)
    got = ops.qn_apply(u, v, x, jnp.float32(0.3), mask,
                       impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# qn_apply_multi (the fused Broyden-step primitive)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,bsz,d", [(1, 1, 8), (4, 2, 100), (8, 3, 256),
                                     (30, 2, 777)])
@pytest.mark.parametrize("transpose", [
    (False,), (True,), (False, True), (True, True, True),
    (False, True, False, True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qn_apply_multi_pallas_vs_oracle(m, bsz, d, transpose, dtype):
    kk = len(transpose)
    ks = jax.random.split(jax.random.fold_in(KEY, m * 977 + d + kk), 4)
    u = jax.random.normal(ks[0], (m, bsz, d), dtype)
    v = jax.random.normal(ks[1], (m, bsz, d), dtype)
    xs = jax.random.normal(ks[2], (kk, bsz, d), dtype)
    count = jax.random.randint(ks[3], (bsz,), 0, m + 1)
    mask = (jnp.arange(m)[:, None] < count[None, :]).astype(jnp.float32)
    alpha = jnp.float32(0.7)
    want = ref.qn_apply_multi_ref(u, v, xs, alpha, mask, transpose)
    got = ops.qn_apply_multi(u, v, xs, alpha, mask, transpose,
                             impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_qn_apply_multi_matches_single_calls():
    """The fused op must agree with K independent qn_apply calls."""
    m, bsz, d = 6, 2, 160
    ks = jax.random.split(jax.random.fold_in(KEY, 5), 3)
    u = jax.random.normal(ks[0], (m, bsz, d))
    v = jax.random.normal(ks[1], (m, bsz, d))
    xs = jax.random.normal(ks[2], (2, bsz, d))
    mask = jnp.ones((m, bsz), jnp.float32)
    alpha = jnp.float32(1.0)
    fused = ops.qn_apply_multi(u, v, xs, alpha, mask, (False, True),
                               impl="pallas_interpret")
    single_f = ops.qn_apply(u, v, xs[0], alpha, mask, impl="pallas_interpret")
    single_t = ops.qn_apply(v, u, xs[1], alpha, mask, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(fused[0]), np.asarray(single_f),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fused[1]), np.asarray(single_t),
                               rtol=1e-5, atol=1e-5)


def test_qn_stream_bytes_accounting():
    """Uniform flags stream one U + one V pass total; mixed flags two each."""
    m, bsz, d, item = 8, 2, 256, 4
    uni = ops.qn_stream_bytes(m, bsz, d, item, (False, False, False))
    mixed = ops.qn_stream_bytes(m, bsz, d, item, (False, True))
    assert uni == 2 * m * bsz * d * item
    assert mixed == 4 * m * bsz * d * item


# ---------------------------------------------------------------------------
# lowrank_append (fused Broyden ring-buffer update)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,bsz,d", [(2, 1, 8), (6, 3, 100), (16, 2, 512),
                                     (8, 13, 200), (8, 16, 640)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lowrank_append_pallas_vs_oracle(m, bsz, d, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, m * 31 + d), 7)
    u = jax.random.normal(ks[0], (m, bsz, d), dtype)
    v = jax.random.normal(ks[1], (m, bsz, d), dtype)
    s = jax.random.normal(ks[2], (bsz, d))
    hy = jax.random.normal(ks[3], (bsz, d))
    b = jax.random.normal(ks[4], (bsz, d))
    inv_den = jax.random.normal(ks[5], (bsz,))
    slot = jax.random.randint(ks[6], (bsz,), 0, m)
    upd = (jnp.arange(bsz) % 2 == 0).astype(jnp.float32)
    want = ref.lowrank_append_ref(u, v, s, hy, b, inv_den, slot, upd)
    got = ops.lowrank_append(u, v, s, hy, b, inv_den, slot, upd,
                             impl="pallas_interpret")
    for got_a, want_a in zip(got, want):
        np.testing.assert_allclose(np.asarray(got_a, np.float32),
                                   np.asarray(want_a, np.float32),
                                   **_tol(dtype))


# ---------------------------------------------------------------------------
# broyden_step (single-launch fused apply + denominator + ring append)
# ---------------------------------------------------------------------------


def _broyden_step_inputs(m, bsz, d, dtype, key):
    ks = jax.random.split(jax.random.fold_in(KEY, key), 6)
    u = jax.random.normal(ks[0], (m, bsz, d), dtype)
    v = jax.random.normal(ks[1], (m, bsz, d), dtype)
    g = jax.random.normal(ks[2], (bsz, d))
    s = jax.random.normal(ks[3], (bsz, d))
    hg = jax.random.normal(ks[4], (bsz, d))
    # ragged ring: rows span empty, partial and wrapped (count > m)
    count = jax.random.randint(ks[5], (bsz,), 0, 2 * m)
    slot = (count % m).astype(jnp.int32)
    mask = (jnp.arange(m)[:, None]
            < jnp.minimum(count, m)[None, :]).astype(jnp.float32)
    return u, v, g, s, hg, mask, slot


@pytest.mark.parametrize("m,bsz,d", [(1, 1, 8), (5, 2, 777), (16, 3, 512),
                                     (8, 12, 300), (8, 16, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_broyden_step_pallas_vs_oracle(m, bsz, d, dtype):
    """Fused kernel vs the ref oracle: ragged ring counts, m % 8 != 0 (the
    (5, 2, 777) case also hits feature-lane padding), freeze-mask rows."""
    u, v, g, s, hg, mask, slot = _broyden_step_inputs(
        m, bsz, d, dtype, m * 131 + d)
    active = (jnp.arange(bsz) % 2 == 0).astype(jnp.float32)  # frozen rows
    alpha = jnp.float32(0.7)
    want = ref.broyden_step_ref(u, v, g, s, hg, alpha, mask, slot, active,
                                1e-8)
    got = ops.broyden_step(u, v, g, s, hg, alpha, mask, slot, active, 1e-8,
                           impl="pallas_interpret")
    assert got[0].dtype == dtype and got[1].dtype == dtype  # ring storage
    assert got[2].dtype == jnp.float32                      # f32 accumulate
    # normalized error: on random data the denominator s^T H y is a small
    # difference of O(m sqrt(d)) terms, so 1/den amplifies the (benign,
    # order-of-accumulation) f32 discrepancy of the appended pair by the
    # cancellation factor — compare relative to each output's magnitude
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    for got_a, want_a in zip(got, want):
        ga = np.asarray(got_a, np.float32)
        wa = np.asarray(want_a, np.float32)
        denom = 1.0 + np.max(np.abs(wa))
        assert np.max(np.abs(ga - wa)) / denom < tol


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_broyden_step_ref_matches_unfused_composition(dtype):
    """The oracle must equal the legacy unfused sequence it replaces:
    qn_apply_multi (H@g_new, H^T@s) -> denominator -> lowrank_append."""
    m, bsz, d = 6, 4, 100
    u, v, g, s, hg, mask, slot = _broyden_step_inputs(m, bsz, d, dtype, 42)
    active = jnp.ones((bsz,), jnp.float32)
    alpha = jnp.float32(0.9)
    eps = 1e-8

    out = ref.qn_apply_multi_ref(
        u, v, jnp.stack([g, s]), alpha, mask, (False, True))
    hg_new, b = out[0], out[1]
    hy = hg_new - hg
    den = jnp.sum(s * hy, axis=1)
    safe = jnp.abs(den) > eps
    upd = (active > 0.5) & safe
    inv_den = jnp.where(safe, 1.0 / jnp.where(safe, den, 1.0), 0.0)
    want_append = ref.lowrank_append_ref(u, v, s, hy, b, inv_den, slot, upd)

    got = ref.broyden_step_ref(u, v, g, s, hg, alpha, mask, slot, active, eps)
    want = (*want_append[:2], hg_new, b, den, *want_append[2:])
    for got_a, want_a in zip(got, want):
        np.testing.assert_allclose(np.asarray(got_a, np.float32),
                                   np.asarray(want_a, np.float32),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_broyden_step_freeze_rows_leave_ring_untouched(dtype):
    """Inactive rows must come back bit-for-bit: no append, same slot row."""
    m, bsz, d = 4, 3, 64
    u, v, g, s, hg, mask, slot = _broyden_step_inputs(m, bsz, d, dtype, 7)
    active = jnp.zeros((bsz,), jnp.float32)
    got = ops.broyden_step(u, v, g, s, hg, jnp.float32(1.0), mask, slot,
                           active, 1e-8, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(u))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(v))


def test_broyden_step_multidim_features():
    """(B, S, d) solver states flatten through the dispatch and come back."""
    m, bsz, seq, d = 3, 2, 4, 40
    ks = jax.random.split(jax.random.fold_in(KEY, 1234), 6)
    u = jax.random.normal(ks[0], (m, bsz, seq, d))
    v = jax.random.normal(ks[1], (m, bsz, seq, d))
    g = jax.random.normal(ks[2], (bsz, seq, d))
    s = jax.random.normal(ks[3], (bsz, seq, d))
    hg = jax.random.normal(ks[4], (bsz, seq, d))
    slot = jnp.zeros((bsz,), jnp.int32)
    mask = jnp.ones((m, bsz), jnp.float32)
    active = jnp.ones((bsz,), jnp.float32)
    want = ref.broyden_step_ref(u, v, g, s, hg, jnp.float32(1.0), mask, slot,
                                active, 1e-8)
    got = ops.broyden_step(u, v, g, s, hg, jnp.float32(1.0), mask, slot,
                           active, 1e-8, impl="pallas_interpret")
    for got_a, want_a in zip(got, want):
        assert got_a.shape == want_a.shape
        np.testing.assert_allclose(np.asarray(got_a), np.asarray(want_a),
                                   rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(4, 128), (2, 16, 256), (1, 7, 1000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_pallas_vs_oracle(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    w = jax.random.normal(jax.random.fold_in(KEY, 1), shape[-1:], dtype)
    want = ref.rmsnorm_ref(x, w, 1e-6)
    got = rmsnorm_pallas(x, w, eps=1e-6, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# flash attention (Pallas, interpret mode)
# ---------------------------------------------------------------------------


def _attn_cases(*cases):
    """Cases ``(b, s, h, kv, hd, causal[, t])``; ``t`` (kv length, default
    ``s``) shows in the id only where it differs from ``s``."""
    out = []
    for c in cases:
        b, s, h, kv, hd, causal, t = c if len(c) == 7 else c + (c[1],)
        tag = "-".join(map(str, (b, s, h, kv, hd, causal)))
        out.append(pytest.param(b, s, t, h, kv, hd, causal,
                                id=tag if t == s else f"{tag}-t{t}"))
    return out


def _qkv(seed, b, s, t, h, kv, hd, dtype=jnp.float32):
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 4)
    return (jax.random.normal(ks[0], (b, s, h, hd), dtype),
            jax.random.normal(ks[1], (b, t, kv, hd), dtype),
            jax.random.normal(ks[2], (b, t, kv, hd), dtype), ks[3])


@pytest.mark.parametrize("b,s,t,h,kv,hd,causal", _attn_cases(
    (1, 128, 4, 4, 64, True),
    (2, 256, 4, 2, 64, True),
    (1, 128, 8, 8, 64, False),
    (2, 128, 4, 1, 128, True),
    (1, 512, 36, 36, 64, True),     # the train cell's heads: 2 heads a block
    (2, 128, 5, 5, 64, True),       # odd head count: all heads a block
    (2, 256, 8, 2, 64, True),       # GQA: a block spans both kv groups
    (2, 128, 8, 8, 64, True, 384),  # chunked prefill: q is the kv tail
    (1, 1152, 2, 2, 64, True),      # past the whole-sequence cap: tiled
))
def test_flash_attention_pallas_vs_oracle(b, s, t, h, kv, hd, causal):
    q, k, v, _ = _qkv(s * h, b, s, t, h, kv, hd)
    want = ref.attention_ref(q, k, v, causal=causal, q_offset=t - s)
    got = flash_attention_pallas(q, k, v, None, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("b,s,t,h,kv,hd,causal", _attn_cases(
    (2, 100, 4, 4, 64, True),
    (3, 300, 4, 2, 64, True),
    (1, 77, 8, 2, 64, False),
    (2, 200, 36, 36, 64, True),
    (2, 90, 5, 5, 64, True),
    (2, 100, 8, 2, 64, True, 333),
    (2, 1100, 2, 2, 64, True),
))
def test_flash_attention_ragged_length_kv_masking(b, s, t, h, kv, hd, causal):
    """Prompt lengths off the 128-row block are padded and the valid kv
    length masks both the padding and each row's own tail."""
    q, k, v, kl = _qkv(s + 7 * h, b, s, t, h, kv, hd)
    kv_len = jax.random.randint(kl, (b,), t // 2, t + 1)
    want = ref.attention_ref(q, k, v, causal=causal, kv_length=kv_len,
                             q_offset=t - s)
    got = ops.attention(q, k, v, causal=causal, kv_length=kv_len,
                        impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("s,t,causal,blk", [
    (256, 256, True, 128), (200, 328, True, 64), (256, 256, False, 128),
    (320, 320, True, 128)])
def test_flash_attention_pinned_tiles_vs_oracle(s, t, causal, blk):
    """Pinned flash tiles: the online softmax across kv blocks, causal
    blocks skipped with their copies clamped, chunked prefill's offset."""
    q, k, v, kl = _qkv(s + t, 2, s, t, 4, 2, 64)
    kv_len = jax.random.randint(kl, (2,), t // 2, t + 1)
    plan = flash_plan(q.shape, k.shape, q.dtype, block_q=blk, block_k=blk)
    assert plan.grid[2] > 1 and plan.grid[3] > 1
    want = ref.attention_ref(q, k, v, causal=causal, kv_length=kv_len,
                             q_offset=t - s)
    got = flash_attention_pallas(q, k, v, kv_len, causal=causal,
                                 block_q=blk, block_k=blk, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# the train cell's prefill (B=8, S=512, 36 heads x 64, bf16), the same with
# GQA, odd heads, long and chunked prompts, f32 and hd 128
_PLAN_SHAPES = [
    ((8, 512, 36, 64), (8, 512, 36, 64), jnp.bfloat16),
    ((8, 512, 32, 64), (8, 512, 8, 64), jnp.bfloat16),
    ((4, 300, 5, 64), (4, 300, 5, 64), jnp.bfloat16),
    ((8, 512, 9, 64), (8, 512, 9, 64), jnp.bfloat16),
    ((1, 4096, 36, 64), (1, 4096, 36, 64), jnp.bfloat16),
    ((16, 128, 36, 64), (16, 1152, 36, 64), jnp.bfloat16),
    ((2, 2048, 8, 128), (2, 2048, 2, 128), jnp.bfloat16),
    ((1, 1152, 2, 64), (1, 1152, 2, 64), jnp.float32),
    ((1, 32768, 16, 64), (1, 32768, 16, 64), jnp.bfloat16),
]


def test_flash_plan_at_the_train_cell():
    plan = flash_plan((8, 512, 36, 64), (8, 512, 36, 64), jnp.bfloat16)
    assert plan.grid_steps <= 300          # 4,608 with 128 x 128 tiles
    assert (plan.hb * 64) % 128 == 0
    assert plan.grid[2:] == (1, 1)         # the whole sequence in one block


@pytest.mark.parametrize("q_shape,k_shape,dtype", _PLAN_SHAPES)
def test_flash_plan_vmem_under_budget(q_shape, k_shape, dtype):
    plan = flash_plan(q_shape, k_shape, dtype)
    assert plan.vmem_bytes <= fa._VMEM_BUDGET
    assert plan.s_pad >= q_shape[1] and plan.t_pad >= k_shape[1]
    assert plan.s_pad % plan.blk_q == 0 and plan.t_pad % plan.blk_k == 0
    assert plan.blk_q % 16 == 0 and plan.blk_k % 16 == 0


@pytest.mark.parametrize("q_shape,k_shape,dtype",
                         [c for c in _PLAN_SHAPES if c[1][2] % 2 == 0])
def test_flash_plan_divides_local_heads_under_two_way_split(
        q_shape, k_shape, dtype):
    """Under a 2-way heads split each device plans its own heads."""
    (b, s, h, hd), (_, t, kv, _) = q_shape, k_shape
    plan = flash_plan((b, s, h // 2, hd), (b, t, kv // 2, hd), dtype)
    assert (h // 2) % plan.hb == 0 and plan.grid[1] == h // 2 // plan.hb
    assert plan.hb == h // 2 or (plan.hb * hd) % 128 == 0


def test_flash_plan_head_blocks():
    # no lane-dense divisor: one block holds the whole head axis
    odd = flash_plan((1, 128, 5, 64), (1, 128, 5, 64), jnp.bfloat16)
    assert (odd.hb, odd.kvb) == (5, 5)
    gqa = flash_plan((1, 256, 8, 64), (1, 256, 2, 64), jnp.bfloat16)
    assert (gqa.hb, gqa.kvb) == (8, 2)
    assert flash_plan((1, 128, 4, 128), (1, 128, 4, 128), jnp.float32).hb == 1


def test_attention_grid_steps_counts_traced_calls():
    reg = obs_metrics.default_registry()
    reg.counter("attention_grid_steps").value = 0.0
    q1, k1, v1, _ = _qkv(1, 2, 128, 128, 4, 4, 64)
    q2, k2, v2, _ = _qkv(2, 1, 64, 192, 8, 2, 64)

    def f(q1, k1, v1, q2, k2, v2):
        return (ops.attention(q1, k1, v1, impl="pallas_interpret"),
                ops.attention(q2, k2, v2, impl="pallas_interpret"))

    jax.jit(f).lower(q1, k1, v1, q2, k2, v2)
    p1 = flash_plan(q1.shape, k1.shape, q1.dtype)
    p2 = flash_plan(q2.shape, k2.shape, q2.dtype)
    assert reg.counter("attention_grid_steps").value == (
        p1.grid_steps + p2.grid_steps)
    assert reg.gauge("attention_head_block").value == p2.hb
    assert reg.gauge("attention_block_q").value == p2.blk_q
    assert reg.gauge("attention_block_k").value == p2.blk_k


@pytest.mark.parametrize("b,t,h,kv,hd", [(2, 256, 4, 4, 64), (1, 512, 8, 2, 64),
                                         (3, 512, 36, 36, 64),
                                         (2, 200, 8, 2, 64)])
def test_decode_attention_pallas_vs_oracle(b, t, h, kv, hd):
    ks = jax.random.split(jax.random.fold_in(KEY, t + h), 4)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, kv, hd), jnp.float32)
    kv_len = jax.random.randint(ks[3], (b,), 1, t + 1)
    want = ref.decode_attention_ref(q, k, v, kv_len)
    got = decode_attention_pallas(q, k, v, kv_len, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# flash_xla (the CPU/dry-run execution path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,s,t,h,kv,hd,causal,bq,bkv,unroll", [
    (2, 128, 128, 4, 4, 16, True, 32, 32, False),
    (2, 128, 128, 4, 4, 16, True, 32, 32, True),
    (2, 128, 128, 8, 2, 16, True, 32, 64, False),
    (2, 128, 128, 8, 2, 16, False, 32, 64, True),
    (1, 100, 100, 4, 4, 16, True, 32, 32, False),     # ragged padding
    (1, 96, 160, 4, 2, 16, False, 32, 32, False),     # cross attention
])
def test_flash_xla_fwd_bwd_vs_oracle(b, s, t, h, kv, hd, causal, bq, bkv,
                                     unroll):
    ks = jax.random.split(jax.random.fold_in(KEY, s + t + h), 4)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, kv, hd), jnp.float32)

    ref_fn = lambda q, k, v: ref.attention_ref(q, k, v, causal=causal)
    fx_fn = lambda q, k, v: flash_attention_xla(
        q, k, v, causal=causal, block_q=bq, block_kv=bkv, unroll=unroll)
    np.testing.assert_allclose(np.asarray(fx_fn(q, k, v)),
                               np.asarray(ref_fn(q, k, v)),
                               rtol=5e-5, atol=5e-5)
    g = jax.random.normal(ks[3], (b, s, h, hd), jnp.float32)
    gr = jax.vjp(ref_fn, q, k, v)[1](g)
    gf = jax.vjp(fx_fn, q, k, v)[1](g)
    for a, b_ in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=5e-4, atol=5e-4)


def test_flash_xla_unroll_matches_scan():
    """Costing mode (unrolled tiles) must be numerically identical to the
    production scan path — same algorithm, different HLO shape."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 128, 4, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 128, 4, 32), jnp.bfloat16)
    a = flash_attention_xla(q, k, v, block_q=32, block_kv=64, unroll=False)
    b = flash_attention_xla(q, k, v, block_q=32, block_kv=64, unroll=True)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=1e-6, atol=1e-6)


def test_ops_attention_auto_dispatch_large_uses_flash():
    """auto policy: big S*T goes through flash_xla (tiled), result must agree
    with the dense oracle."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 1024, 2, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 1024, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 1024, 2, 32), jnp.float32)
    got = ops.attention(q, k, v, causal=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-5, atol=5e-5)
