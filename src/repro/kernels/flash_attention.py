"""Pallas TPU attention: flash-attention forward (prefill / training) and
single-token decode, both with GQA and a per-row valid KV length.

Prefill reads q, k and v in the model's own layout, viewed as ``(B, S,
H·hd)``: a block is ``(rows, hb·hd)``, the lanes of ``hb`` whole heads (and
of the ``kvb`` kv heads they read), so no head-major copy is made.  The tile
plan (:func:`flash_plan`) follows the shapes alone:

* ``hb`` is a divisor of the head count whose q and kv lanes are whole
  128-lane tiles and whose q heads map onto whole kv heads (GQA): the fewest
  such heads.  Where none exists (an odd head count at ``hd`` 64) a block
  holds every head: its lanes are the array's whole last axis.
* Where the whole sequence fits the scoped VMEM budget, one q and one kv
  block cover it: the grid is ``(B, H/hb, 1, 1)``, and each program
  computes plain softmax rows of its heads, one whole-block matmul pair a
  head (on a v5e fewer, larger matmuls beat skipping the masked half of
  the causal square in smaller pieces; and the online softmax below, run
  on one kv block, took twice the time of these plain rows).
* Longer sequences take the fewest equal square flash tiles that fit, on a grid
  ``(B, H/hb, q blocks, kv blocks)``, executed sequentially: the
  online-softmax running max, denominator and accumulator live in VMEM
  scratch and carry across the kv-block axis.  A kv block wholly above the
  causal diagonal is skipped, and its ``index_map`` is clamped to the last
  block the q block needs, so it issues no copy.

Sequence lengths need not divide the blocks: q, k and v are zero-padded up to
the block and padded keys are masked by ``kv_length`` (scalar-prefetched into
SMEM), so any prompt length compiles.  Scores, softmax statistics and the
accumulator are f32.

Decode (one query per row) keeps the cache layout ``(B, T, KV, hd)``: a block
is ``(blk_k, KV, hd)`` — its last two dims are whole axes — and all heads of a
row are scored at once on the VPU (a decode step is bound by reading the
cache, not by FLOPs).  Queries are regrouped as ``(group, KV, hd)`` so every
product is an elementwise broadcast against the kv block.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_ROWS = 16            # sublane tile of a bf16 block
_LANES = 128          # lane tile
# what the plan lets a program's blocks and working set take: the TPU's
# default scoped VMEM (16 MiB on v5e) less room for the compiler's own
_VMEM_BUDGET = 12 * 2**20
# f32 (blk_q, blk_k) arrays a program keeps live (scores, mask,
# exponentials): compiling for a v5e needed 0.7-2.2 of them, whatever the
# number of heads in the block, which are scored one after another
_LIVE_SCORES = 3


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def _pad_axis(x: jax.Array, axis: int, size: int) -> jax.Array:
    if x.shape[axis] == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad)


@dataclasses.dataclass(frozen=True)
class FlashPlan:
    """Tiles of one prefill call.  A program holds ``hb`` q heads and the
    ``kvb`` kv heads they read, ``blk_q`` q rows and ``blk_k`` kv rows."""

    hb: int
    kvb: int
    blk_q: int
    blk_k: int
    s_pad: int
    t_pad: int
    grid: tuple[int, int, int, int]
    vmem_bytes: int

    @property
    def grid_steps(self) -> int:
        return math.prod(self.grid)


def _head_blocks(h: int, kvh: int, hd: int) -> list[tuple[int, int]]:
    """``(hb, kvb)`` pairs, fewest heads first, whose q and kv blocks are
    whole lane tiles and whose q heads read whole kv heads."""
    group = h // kvh
    out = []
    for hb in range(1, h + 1):
        if h % hb or (hb % group and group % hb):
            continue
        kvb = max(hb // group, 1)
        if (hb * hd) % _LANES == 0 and (kvb * hd) % _LANES == 0:
            out.append((hb, kvb))
    return out


def _vmem_bytes(hb: int, kvb: int, hd: int, blk_q: int, blk_k: int,
                itemsize: int, online: bool) -> int:
    """Scoped VMEM a program takes: double-buffered q, out, k and v blocks,
    the f32 score-sized temporaries, and the online-softmax scratch where
    the kv axis is tiled."""
    lanes = lambda n: _round_up(n, _LANES)
    io = 2 * 2 * itemsize * (blk_q * lanes(hb * hd) + blk_k * lanes(kvb * hd))
    work = _LIVE_SCORES * 4 * blk_q * lanes(blk_k)
    scratch = 4 * hb * blk_q * (lanes(hd) + 2 * _LANES) if online else 0
    return io + work + scratch


def flash_plan(q_shape, k_shape, dtype, *, block_q: int | None = None,
               block_k: int | None = None) -> FlashPlan:
    """The tile plan for ``q: (B, S, H, hd)`` against ``k: (B, T, KV, hd)``,
    from the shapes and the VMEM estimate alone.  ``block_q``/``block_k``
    pin the flash tiles (tests of the tiled path)."""
    b, s, h, hd = q_shape
    t, kvh = k_shape[1], k_shape[2]
    itemsize = jnp.dtype(dtype).itemsize
    heads = _head_blocks(h, kvh, hd)
    hb, kvb = heads[0] if heads else (h, kvh)

    s_all, t_all = _round_up(s, _ROWS), _round_up(t, _ROWS)

    def plan(blk_q, blk_k):
        blk_q, blk_k = min(blk_q, s_all), min(blk_k, t_all)
        s_pad, t_pad = _round_up(s, blk_q), _round_up(t, blk_k)
        nk = t_pad // blk_k
        vmem = _vmem_bytes(hb, kvb, hd, blk_q, blk_k, itemsize, nk > 1)
        return FlashPlan(hb, kvb, blk_q, blk_k, s_pad, t_pad,
                         (b, h // hb, s_pad // blk_q, nk), vmem)

    if block_q is not None or block_k is not None:
        return plan(block_q or _LANES, block_k or _LANES)
    # the whole sequence in one block, else the fewest equal square tiles
    # that fit
    longest, n = max(s_all, t_all), 1
    while True:
        tile = _round_up(pl.cdiv(longest, n), _ROWS)
        p = plan(tile, tile)
        if p.vmem_bytes <= _VMEM_BUDGET or tile <= _LANES:
            return p
        n += 1


def _flash_kernel(
    len_ref,    # (B,) int32 valid kv length per row (SMEM, scalar prefetch)
    q_ref,      # (blk_q, hb·hd)
    k_ref,      # (blk_k, kvb·hd)
    v_ref,      # (blk_k, kvb·hd)
    out_ref,    # (blk_q, hb·hd)
    *scratch,   # tiled kv axis: running max and denominator (hb, blk_q, 1),
                # accumulator (hb, blk_q, hd), f32
    scale: float,
    causal: bool,
    hd: int,
    group: int,
    q_offset: int,
):
    b = pl.program_id(0)
    iq, ik = pl.program_id(2), pl.program_id(3)
    blk_q, blk_k = q_ref.shape[0], k_ref.shape[0]
    hb = q_ref.shape[1] // hd

    def lanes(j):
        return slice(j * hd, (j + 1) * hd)

    def head(j):
        """Masked f32 scores of q head j against its kv head, and that
        head's values."""
        kv = lanes(j // group if hb >= group else 0)
        s = jax.lax.dot_general(
            q_ref[:, lanes(j)], k_ref[:, kv], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (blk_q, blk_k)
        kpos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)
        valid = kpos < len_ref[b]
        if causal:
            qpos = (iq * blk_q + q_offset
                    + jax.lax.broadcasted_iota(jnp.int32, (blk_q, 1), 0))
            valid = valid & (kpos <= qpos)
        return jnp.where(valid, s, NEG_INF), v_ref[:, kv]

    def pv(p, v):
        return jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if not scratch:
        # the whole kv axis is in the block: plain softmax rows
        for j in range(hb):
            s, v = head(j)
            p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
            l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
            out_ref[:, lanes(j)] = (pv(p, v) / l).astype(out_ref.dtype)
        return

    m_scr, l_scr, acc_scr = scratch

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # kv position of the block's last q row; kv blocks past it are masked
    q_last = iq * blk_q + blk_q - 1 + q_offset

    @pl.when((ik * blk_k <= q_last) if causal else (ik >= 0))
    def _body():
        for j in range(hb):
            s, v = head(j)
            m_prev = m_scr[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[j] = corr * l_scr[j] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[j] = acc_scr[j] * corr + pv(p, v)
            m_scr[j] = m_new

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finalize():
        for j in range(hb):
            denom = jnp.maximum(l_scr[j], 1e-30)
            out_ref[:, lanes(j)] = (acc_scr[j] / denom).astype(out_ref.dtype)


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
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, s, h, hd = q.shape
    t, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    scale = (hd ** -0.5) if scale is None else scale
    plan = flash_plan(q.shape, k.shape, q.dtype, block_q=block_q,
                      block_k=block_k)
    blk_q, blk_k = plan.blk_q, plan.blk_k
    # q row i sits at kv position i + (t - s): the tail of a longer kv axis
    # (chunked prefill); self-attention has t == s -> offset 0
    q_offset = (t - s) if causal else 0
    if kv_length is None:
        kv_length = jnp.full((b,), t, jnp.int32)

    def kv_row(iq, ik):
        if not causal:
            return ik
        # above the diagonal: stay on the q block's last kv block (no copy)
        last = (iq * blk_q + blk_q - 1 + q_offset) // blk_k
        return jnp.minimum(ik, jnp.maximum(last, 0))

    # head group g holds q heads g·hb.. and kv heads g·hb/group.. (GQA)
    kv_grp = lambda g: (g * plan.hb) // (group * plan.kvb)
    qp, kp, vp = (x.reshape(x.shape[:2] + (-1,)) for x in (
        _pad_axis(q, 1, plan.s_pad), _pad_axis(k, 1, plan.t_pad),
        _pad_axis(v, 1, plan.t_pad)))
    q_spec = pl.BlockSpec((None, blk_q, plan.hb * hd),
                          lambda b_, g, iq, ik, ln: (b_, iq, g))
    kv_spec = pl.BlockSpec(
        (None, blk_k, plan.kvb * hd),
        lambda b_, g, iq, ik, ln: (b_, kv_row(iq, ik), kv_grp(g)))
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, hd=hd, group=group,
        q_offset=q_offset)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=plan.grid,
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[] if plan.grid[3] == 1 else [
                pltpu.VMEM((plan.hb, blk_q, 1), jnp.float32),
                pltpu.VMEM((plan.hb, blk_q, 1), jnp.float32),
                pltpu.VMEM((plan.hb, blk_q, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        interpret=interpret,
    )(kv_length.astype(jnp.int32), qp, kp, vp)
    return out.reshape(b, plan.s_pad, h, hd)[:, :s]


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
