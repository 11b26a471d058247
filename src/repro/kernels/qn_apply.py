"""Pallas TPU kernels for the SHINE hot path: applying a limited-memory
quasi-Newton inverse ``H = alpha*I + U^T V`` — and maintaining it.

    H @ x   = alpha * x[b] + sum_i mask[i,b] * u[i,b,:] * <v[i,b,:], x[b,:]>
    H^T @ x = alpha * x[b] + sum_i mask[i,b] * v[i,b,:] * <u[i,b,:], x[b,:]>

This op runs in every Broyden iteration of the forward pass and once in the
SHINE backward pass — it IS the "shared inverse estimate".  It is
memory-bound: the U/V streams dominate, so one kernel amortizes ONE stream
over U/V across a whole stack of right-hand sides.

Kernels in this module:

``qn_apply_multi_pallas``  K stacked RHS, each independently applying H or
                           H^T (static ``transpose`` flags), in one
                           pallas_call over the grid ``(batch blocks, phase,
                           d-tiles)``: phase 0 accumulates the (K, m)
                           coefficients of a batch block in VMEM scratch
                           across its d-tiles, phase 1 emits the K output
                           tiles.  A buffer is streamed only in a phase that
                           needs it (its index map stands still in the other
                           phase, so the pipeline does not refetch it): with
                           uniform flags each phase reads one of U/V (K
                           same-direction applications cost one U plus one V
                           stream), mixed flags read both in both phases.
                           The single-RHS ``qn_apply`` is its K = 1 case.
``lowrank_append_pallas``  Broyden ring-buffer update: computes the
                           rank-one pair a_n = (s - Hy)/den in-kernel and
                           writes it into each row's ring slot in place
                           (input/output aliasing), returning the evicted
                           pair so the solver can rank-one-correct carried
                           products.  It reads and writes every U/V tile
                           once.
``broyden_step_pallas``    one Broyden iteration's U/V work as ONE
                           pallas_call and one mixed-flag U/V pass: H @ g_new
                           and H^T @ s, plus the ring slot's previous rows
                           (picked out of the phase-0 tiles).  The
                           denominator s^T H y, the guarded pair and the ring
                           write are O(B·D) and run in XLA on the kernel's
                           outputs; the write is a row scatter into the
                           loop-carried ring, not a second ring pass.

Tiling (what the TPU lowering accepts): the last two block dims must be
multiples of (8, 128) or the whole array dims.  The ring is ``(m, B, D)``,
so a tile is ``(m, bb, block_d)`` with ``bb`` = 8 batch rows when B is a
multiple of 8 and all of B otherwise; per-row scalars (slot, 1/den, update
flags) ride as ``(B, 1)`` columns and the mask as ``(m, B, 1)``, so they
broadcast against tiles along lanes; ``alpha`` sits in SMEM.  Products are
lane reductions and leading-axis sums on the VPU — the op is memory-bound,
so nothing is gained by feeding the MXU m-wide matrices.  The d-tile is
clamped to a multiple of 128 lanes and the feature axis zero-padded up to
it; the m axis is zero-padded to a multiple of 8 by the wrapper in ops.py.
Tiles are upcast to f32 on read, so the ring may be stored bf16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def _pad_features(block_d: int, dim: int, *arrays):
    """Clamp the d-tile to a lane-aligned size and pad the feature axis of
    each array (last axis) up to a multiple of it.  Returns (block_d, padded
    arrays...).  ``min(block_d, dim)`` alone would produce unaligned tiles
    whenever dim < block_d and dim % 128 != 0."""
    block_d = min(block_d, _round_up(dim, _LANES))
    dim_p = _round_up(dim, block_d)
    if dim_p != dim:
        arrays = tuple(
            jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, dim_p - dim),))
            for a in arrays
        )
    return (block_d,) + arrays


def _batch_block(bsz: int) -> int:
    """Rows per batch block: a sublane multiple, or the whole batch."""
    return _SUBLANES if bsz % _SUBLANES == 0 else bsz


def _tile_width(block_d: int, m: int, bb: int) -> int:
    """Narrow the d-tile when a whole-batch block is wide, so an ``(m, bb,
    block_d)`` tile stays within the 8 x 8 x 512 elements of the default
    (and its f32 copies within the scoped VMEM)."""
    cap = (_SUBLANES * _SUBLANES * 512) // (m * bb)
    return max(_LANES, min(block_d, cap // _LANES * _LANES))


def _smem_scalar(x) -> jax.Array:
    return jnp.reshape(jnp.asarray(x, jnp.float32), (1,))


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# K-RHS apply (and the fused Broyden step): grid (batch block, phase, d-tile)
# ---------------------------------------------------------------------------


def _make_qn_kernel(transpose: tuple[bool, ...], evict: bool):
    """Phase 0 accumulates ``coeff[k] = <w_k, x_k>`` (w = U for an H^T row,
    V for an H row) over the d-tiles; phase 1 writes ``alpha*x_k + sum_i
    mask_i coeff[k]_i w'_i`` (w' the other buffer).  With ``evict`` the
    phase-0 tiles also yield ring slot ``slot[b]``'s current rows."""

    def kernel(alpha_ref, u_ref, v_ref, xs_ref, mask_ref, *rest):
        if evict:
            slot_ref, out_ref, ev_u_ref, ev_v_ref, coeff_ref = rest
        else:
            out_ref, coeff_ref = rest
        ph = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when((ph == 0) & (j == 0))
        def _init():
            coeff_ref[...] = jnp.zeros_like(coeff_ref)

        @pl.when(ph == 0)
        def _coefficients():
            for k, t in enumerate(transpose):
                w = (u_ref if t else v_ref)[...].astype(jnp.float32)
                x = xs_ref[k].astype(jnp.float32)             # (bb, blk)
                coeff_ref[k] += jnp.sum(w * x[None], axis=-1, keepdims=True)
            if evict:
                u = u_ref[...]
                rows = jax.lax.broadcasted_iota(jnp.int32, (u.shape[0],)
                                                + u.shape[1:2] + (1,), 0)
                hot = rows == slot_ref[...][None]             # (m, bb, 1)
                ev_u_ref[...] = jnp.sum(
                    jnp.where(hot, u.astype(jnp.float32), 0.0),
                    axis=0).astype(ev_u_ref.dtype)
                ev_v_ref[...] = jnp.sum(
                    jnp.where(hot, v_ref[...].astype(jnp.float32), 0.0),
                    axis=0).astype(ev_v_ref.dtype)

        @pl.when(ph == 1)
        def _apply():
            maskv = mask_ref[...]                             # (m, bb, 1)
            alpha = alpha_ref[0]
            for k, t in enumerate(transpose):
                w = (v_ref if t else u_ref)[...].astype(jnp.float32)
                c = coeff_ref[k] * maskv
                out_ref[k] = (alpha * xs_ref[k].astype(jnp.float32)
                              + jnp.sum(c * w, axis=0)).astype(out_ref.dtype)

    return kernel


def _qn_call(u, v, xs, alpha, mask, transpose, *, slot=None, block_d,
             interpret):
    """Shared pallas_call of the K-RHS apply; ``slot`` adds the evicted-row
    outputs.  Returns ``out`` or ``(out, ev_u, ev_v)`` at the input width."""
    m, bsz, dim = u.shape
    kk = xs.shape[0]
    any_t, any_f = any(transpose), not all(transpose)
    bb = _batch_block(bsz)
    block_d, u, v, xs = _pad_features(_tile_width(block_d, m, bb), dim,
                                      u, v, xs)
    dim_p = xs.shape[-1]
    nd = dim_p // block_d
    evict = slot is not None

    def tile_map(phase0: bool, phase1: bool):
        # stand still (no refetch) in the phase that does not read the tile
        if phase0 and phase1:
            return lambda b, ph, j: (0, b, j)
        if phase1:
            return lambda b, ph, j: (0, b, j * ph)
        return lambda b, ph, j: (0, b, j * (1 - ph) + (nd - 1) * ph)

    tile = (m, bb, block_d)
    in_specs = [
        _SMEM,
        pl.BlockSpec(tile, tile_map(any_t, any_f)),           # u
        pl.BlockSpec(tile, tile_map(any_f, any_t)),           # v
        pl.BlockSpec((kk, bb, block_d), lambda b, ph, j: (0, b, j)),
        pl.BlockSpec((m, bb, 1), lambda b, ph, j: (0, b, 0)),
    ]
    out_specs = [pl.BlockSpec((kk, bb, block_d),
                              lambda b, ph, j: (0, b, j * ph))]
    out_shape = [jax.ShapeDtypeStruct((kk, bsz, dim_p), xs.dtype)]
    args = [_smem_scalar(alpha), u, v, xs, mask[..., None]]
    if evict:
        in_specs.append(pl.BlockSpec((bb, 1), lambda b, ph, j: (b, 0)))
        args.append(slot.astype(jnp.int32)[:, None])
        row = pl.BlockSpec((bb, block_d),
                           lambda b, ph, j: (b, j * (1 - ph) + (nd - 1) * ph))
        out_specs += [row, row]
        out_shape += [jax.ShapeDtypeStruct((bsz, dim_p), u.dtype),
                      jax.ShapeDtypeStruct((bsz, dim_p), v.dtype)]
    outs = pl.pallas_call(
        _make_qn_kernel(tuple(transpose), evict),
        grid=(bsz // bb, 2, nd),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((kk, m, bb, 1), jnp.float32)],
        interpret=interpret,
    )(*args)
    outs = [o[..., :dim] for o in outs]
    return outs[0] if not evict else tuple(outs)


@functools.partial(jax.jit, static_argnames=("transpose", "block_d",
                                             "interpret"))
def qn_apply_multi_pallas(
    u: jax.Array,      # (m, B, D)
    v: jax.Array,      # (m, B, D)
    xs: jax.Array,     # (K, B, D) stacked right-hand sides
    alpha: jax.Array,  # scalar f32
    mask: jax.Array,   # (m, B) f32
    *,
    transpose: tuple[bool, ...],   # per-RHS: apply H^T instead of H
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """out[k] = (H^T if transpose[k] else H) @ xs[k], one stream over U/V."""
    assert len(transpose) == xs.shape[0], (len(transpose), xs.shape[0])
    return _qn_call(u, v, xs, alpha, mask, transpose, block_d=block_d,
                    interpret=interpret)


# ---------------------------------------------------------------------------
# Broyden ring-buffer update (in place, one read + one write of the ring)
# ---------------------------------------------------------------------------


def _append_kernel(u_ref, v_ref, s_ref, hy_ref, b_ref, inv_den_ref, slot_ref,
                   upd_ref, out_u_ref, out_v_ref, ev_u_ref, ev_v_ref):
    u = u_ref[...]
    v = v_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.int32, u.shape[:2] + (1,), 0)
    hot = rows == slot_ref[...][None]                         # (m, bb, 1)
    ev_u_ref[...] = jnp.sum(jnp.where(hot, u.astype(jnp.float32), 0.0),
                            axis=0).astype(ev_u_ref.dtype)
    ev_v_ref[...] = jnp.sum(jnp.where(hot, v.astype(jnp.float32), 0.0),
                            axis=0).astype(ev_v_ref.dtype)
    write = hot & (upd_ref[...][None] > 0.5)
    a = ((s_ref[...].astype(jnp.float32) - hy_ref[...].astype(jnp.float32))
         * inv_den_ref[...])                                  # (bb, blk)
    out_u_ref[...] = jnp.where(write, a.astype(u.dtype)[None], u)
    out_v_ref[...] = jnp.where(write, b_ref[...].astype(v.dtype)[None], v)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def lowrank_append_pallas(
    u: jax.Array,        # (m, B, D)
    v: jax.Array,        # (m, B, D)
    s: jax.Array,        # (B, D) step
    hy: jax.Array,       # (B, D) H @ y
    b: jax.Array,        # (B, D) H^T s — the second half of the pair
    inv_den: jax.Array,  # (B,) f32 1 / (s^T H y), pre-guarded
    slot: jax.Array,     # (B,) int32 ring slot to write
    upd: jax.Array,      # (B,) f32 1.0 where the sample appends
    *,
    block_d: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Write the Broyden pair ``a = (s - Hy) * inv_den``, ``b`` into ring
    slot ``slot[bb]`` of U/V in place (input/output aliasing).

    Returns ``(new_u, new_v, evicted_u, evicted_v)``; the evicted row is the
    slot's previous content, letting callers rank-one-correct carried
    products like ``H @ g`` when the ring wraps.
    """
    m, bsz, dim = u.shape
    bb = _batch_block(bsz)
    block_d, u, v, s, hy, b = _pad_features(_tile_width(block_d, m, bb), dim,
                                            u, v, s, hy, b)
    dim_p = u.shape[-1]

    tile = pl.BlockSpec((m, bb, block_d), lambda i, j: (0, i, j))
    vec = pl.BlockSpec((bb, block_d), lambda i, j: (i, j))
    col = pl.BlockSpec((bb, 1), lambda i, j: (i, 0))
    new_u, new_v, ev_u, ev_v = pl.pallas_call(
        _append_kernel,
        grid=(bsz // bb, dim_p // block_d),
        in_specs=[tile, tile, vec, vec, vec, col, col, col],
        out_specs=[tile, tile, vec, vec],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, u.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((bsz, dim_p), u.dtype),
            jax.ShapeDtypeStruct((bsz, dim_p), v.dtype),
        ],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(u, v, s, hy, b, inv_den.astype(jnp.float32)[:, None],
      slot.astype(jnp.int32)[:, None], upd.astype(jnp.float32)[:, None])
    return (new_u[..., :dim], new_v[..., :dim], ev_u[..., :dim],
            ev_v[..., :dim])


# ---------------------------------------------------------------------------
# Fused Broyden step: one launch and one U/V pass per iteration
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def broyden_step_pallas(
    u: jax.Array,        # (m, B, D) qN ring (storage dtype: f32 or bf16)
    v: jax.Array,        # (m, B, D)
    g_new: jax.Array,    # (B, D) residual at the new iterate
    s: jax.Array,        # (B, D) step z_new - z
    alpha: jax.Array,    # scalar f32
    mask: jax.Array,     # (m, B) f32 validity of the PRE-update ring
    slot: jax.Array,     # (B,) int32 ring slot to be written
    *,
    block_d: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The U/V work of one Broyden iteration in one launch: returns
    ``(hg_new, b, ev_u, ev_v)`` with ``hg_new = H @ g_new`` and ``b = H^T
    s`` in f32 and ``ev_u/ev_v`` ring slot ``slot``'s current rows (storage
    dtype).  U/V traffic is the mixed-flag apply model (4·m·B·D·itemsize);
    the caller forms the pair and writes the slot rows (ops.broyden_step).
    """
    xs = jnp.stack([g_new.astype(jnp.float32), s.astype(jnp.float32)])
    out, ev_u, ev_v = _qn_call(u, v, xs, alpha, mask, (False, True),
                               slot=slot, block_d=block_d,
                               interpret=interpret)
    return out[0], out[1], ev_u, ev_v
