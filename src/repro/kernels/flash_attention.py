"""Pallas TPU attention: flash-attention forward (prefill / training) and
single-token decode, both with GQA and a per-row valid KV length.

Prefill runs head-major: the wrapper moves heads in front of the sequence
(``(B, S, H, hd) -> (B, H, S, hd)``) so a block is a ``(rows, hd)`` slab whose
last two dims the TPU lowering accepts (``hd`` is the whole axis).  Grid
``(batch, q_heads, q blocks, kv blocks)``, executed sequentially: the
online-softmax running max/denominator/accumulator live in VMEM scratch and
carry across the kv-block axis.  Kv blocks wholly above the causal diagonal
are skipped with ``pl.when``; the diagonal block and padding are masked by
position.  GQA is handled in the index_map (kv head ``h // group``).

Sequence lengths need not divide the blocks: q, k and v are zero-padded up to
the block and padded keys are masked by ``kv_length`` (scalar-prefetched into
SMEM), so any prompt length compiles.

Decode (one query per row) keeps the cache layout ``(B, T, KV, hd)``: a block
is ``(blk_k, KV, hd)`` — its last two dims are whole axes — and all heads of a
row are scored at once on the VPU (a decode step is bound by reading the
cache, not by FLOPs).  Queries are regrouped as ``(group, KV, hd)`` so every
product is an elementwise broadcast against the kv block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
_ROWS = 16  # sublane tile of a bf16 block


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def _pad_axis(x: jax.Array, axis: int, size: int) -> jax.Array:
    if x.shape[axis] == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad)


def _flash_kernel(
    len_ref,    # (B,) int32 valid kv length per row (SMEM, scalar prefetch)
    q_ref,      # (blk_q, hd)
    k_ref,      # (blk_k, hd)
    v_ref,      # (blk_k, hd)
    out_ref,    # (blk_q, hd)
    m_scr,      # (blk_q, 1) f32 running max
    l_scr,      # (blk_q, 1) f32 running denominator
    acc_scr,    # (blk_q, hd) f32 accumulator
    *,
    scale: float,
    causal: bool,
    blk_q: int,
    blk_k: int,
    q_offset: int,
):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # kv position of this q block's last row; blocks past it are all masked
    q_last = iq * blk_q + blk_q - 1 + q_offset
    run = (ik * blk_k <= q_last) if causal else (ik >= 0)

    @pl.when(run)
    def _body():
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (blk_q, blk_k)
        kpos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < len_ref[b]
        if causal:
            qpos = (iq * blk_q + q_offset
                    + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            valid = valid & (kpos <= qpos)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        out_ref[...] = (acc_scr[...] / denom).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,                   # (B, S, H, hd)
    k: jax.Array,                   # (B, T, KV, hd)
    v: jax.Array,                   # (B, T, KV, hd)
    kv_length: jax.Array | None = None,  # (B,)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    b, s, h, hd = q.shape
    t, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    scale = (hd ** -0.5) if scale is None else scale
    blk_q = min(block_q, _round_up(s, _ROWS))
    blk_k = min(block_k, _round_up(t, _ROWS))
    s_p, t_p = _round_up(s, blk_q), _round_up(t, blk_k)
    # q row i sits at kv position i + (t - s): the tail of a longer kv axis
    # (chunked prefill); self-attention has t == s -> offset 0
    q_offset = (t - s) if causal else 0
    if kv_length is None:
        kv_length = jnp.full((b,), t, jnp.int32)

    qh = _pad_axis(jnp.swapaxes(q, 1, 2), 2, s_p)          # (B, H, S_p, hd)
    kh = _pad_axis(jnp.swapaxes(k, 1, 2), 2, t_p)          # (B, KV, T_p, hd)
    vh = _pad_axis(jnp.swapaxes(v, 1, 2), 2, t_p)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
        q_offset=q_offset)
    q_spec = pl.BlockSpec((None, None, blk_q, hd),
                          lambda b_, h_, iq, ik, ln: (b_, h_, iq, 0))
    kv_spec = pl.BlockSpec((None, None, blk_k, hd),
                           lambda b_, h_, iq, ik, ln: (b_, h_ // group, ik, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, s_p // blk_q, t_p // blk_k),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((blk_q, 1), jnp.float32),
                pltpu.VMEM((blk_q, 1), jnp.float32),
                pltpu.VMEM((blk_q, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=interpret,
    )(kv_length.astype(jnp.int32), qh, kh, vh)
    return jnp.swapaxes(out[:, :, :s], 1, 2)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, out_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, group: int, blk_k: int):
    b = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k = k_ref[...].astype(jnp.float32)                      # (blk_k, KV, hd)
    v = v_ref[...].astype(jnp.float32)
    kpos = ik * blk_k + jax.lax.broadcasted_iota(
        jnp.int32, (k.shape[0], k.shape[1], 1), 0)
    valid = kpos < len_ref[b]                               # (blk_k, KV, 1)
    for g in range(group):
        qg = q_ref[g].astype(jnp.float32) * scale           # (KV, hd)
        s = jnp.sum(k * qg[None], axis=-1, keepdims=True)   # (blk_k, KV, 1)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[g]                                   # (KV, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        p = jnp.exp(s - m_new[None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[g] = corr * l_scr[g] + jnp.sum(p, axis=0)
        acc_scr[g] = acc_scr[g] * corr + jnp.sum(p * v, axis=0)
        m_scr[g] = m_new

    @pl.when(ik == pl.num_programs(1) - 1)
    def _finalize():
        out_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention_pallas(
    q: jax.Array,          # (B, H, hd) one new token per sequence
    k: jax.Array,          # (B, T, KV, hd) KV cache
    v: jax.Array,          # (B, T, KV, hd)
    kv_length: jax.Array,  # (B,)
    *,
    scale: float | None = None,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Single-token flash-decode: the cache streams in ``(blk_k, KV, hd)``
    blocks, all query heads of a row are scored against each block.  A
    ``(KV, hd)`` slab pads to whole (16, 128) tiles in VMEM, so 128 cache
    rows per block keep the double-buffered blocks and their f32 copies
    inside the default scoped VMEM (512 does not fit at 36 x 64 heads)."""
    b, h, hd = q.shape
    t, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    scale = (hd ** -0.5) if scale is None else scale
    blk_k = min(block_k, _round_up(t, _ROWS))
    t_p = _round_up(t, blk_k)
    kp, vp = _pad_axis(k, 1, t_p), _pad_axis(v, 1, t_p)
    # head h = kv_head * group + g  ->  (B, group, KV, hd)
    qg = jnp.swapaxes(q.reshape(b, kvh, group, hd), 1, 2)

    q_spec = pl.BlockSpec((None, group, kvh, hd),
                          lambda b_, ik, ln: (b_, 0, 0, 0))
    kv_spec = pl.BlockSpec((None, blk_k, kvh, hd),
                           lambda b_, ik, ln: (b_, ik, 0, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, group=group,
                          blk_k=blk_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t_p // blk_k),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((group, kvh, 1), jnp.float32),
                pltpu.VMEM((group, kvh, 1), jnp.float32),
                pltpu.VMEM((group, kvh, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
    )(kv_length.astype(jnp.int32), qg, kp, vp)
    return jnp.swapaxes(out, 1, 2).reshape(b, h, hd)
