"""Jit'd public wrappers around the kernel layer.

Dispatch table (every op takes ``impl``; ``None``/"auto" resolves by
backend, and ``force_impl`` overrides globally for tests):

  impl               | executes                          | selected when
  -------------------+-----------------------------------+------------------
  "ref"              | pure-jnp oracle (kernels/ref.py)  | auto on non-TPU
                     |                                   | backends (CPU
                     |                                   | container, tests)
  "flash_xla"        | tiled online-softmax attention in | auto on CPU for
                     | plain XLA (memory-faithful to the | attention with
                     | Pallas kernel)                    | S*T >= 2^20 cells
  "pallas"           | compiled Pallas TPU kernels       | always on TPU (the
                     |                                   | deployment path)
  "pallas_interpret" | Pallas kernel bodies interpreted  | explicit only:
                     | in Python on CPU                  | kernel test sweeps
                     |                                   | (./test.sh kernels)

On a TPU every op runs its Pallas kernel: asking for a CPU path there raises
instead of quietly running something else.

Ops dispatched here: ``qn_apply`` (single-RHS SHINE inverse application),
``qn_apply_multi`` (K stacked RHS, per-RHS H vs H^T, ONE stream over U/V),
``lowrank_append`` (Broyden ring-buffer update in place), ``broyden_step``
(the U/V work of one Broyden iteration in a single launch plus a slot-row
write — the hot path of the forward solve), ``attention``,
``decode_attention``, ``rmsnorm``.

Precision: the qN ring may be stored bf16 (``SolverConfig.qn_dtype``); every
path upcasts U/V tiles on read and accumulates coefficients, denominators
and outputs in f32, so halving the storage dtype halves U/V stream bytes
without touching the accumulation precision.  The stream counters use the
actual ``u.dtype.itemsize``, and a ``qn_ring_bytes`` gauge labelled by dtype
records the resident ring footprint.

SPMD posture (the sharded batched fixed-point engine): the solvers pin the
(U, V) chain batch-sharded next to the state.  On the ref path GSPMD keeps
every qn op device-local over batch; when the *feature* axes are
TP-sharded, the RHS are grouped by transpose flag and each group's
coefficients reduce in ONE einsum over the whole (K_g, m, B) block —
a single collective per flag group, not one per RHS (kernels/ref.py).  A
pallas_call cannot be partitioned, so under a mesh every kernel path runs
under ``shard_map`` on its device's rows (see :func:`sharded_kernels`).

The qn ops also keep trace-time stream statistics
(``reset_qn_stream_stats``/``qn_stream_stats``): inside a ``lax.while_loop``
the body traces once, so the counters report per-iteration call/byte costs —
the bench harness uses them to verify a Broyden step performs exactly one
fused U/V pass.

Training differentiability: the Pallas flash-attention here implements the
forward only; ``attention`` wraps it in a custom_vjp whose backward
re-derives gradients from the reference oracle (recompute — consistent with
the DEQ O(1)-memory posture). The qn ops are only ever used inside
custom_vjp forward/backward bodies of the DEQ layer, so they need no VJP of
their own.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
from typing import Literal, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.obs import metrics as obs_metrics
from repro.kernels.flash_attention import (
    decode_attention_pallas,
    flash_attention_pallas,
)
from repro.kernels.flash_xla import flash_attention_xla
from repro.kernels.qn_apply import (
    broyden_step_pallas,
    lowrank_append_pallas,
    qn_apply_multi_pallas,
)
from repro.kernels.rmsnorm import rmsnorm_pallas

Impl = Literal["auto", "ref", "flash_xla", "pallas", "pallas_interpret"]

# Above this many score-matrix cells (S*T) the CPU auto policy switches from
# the dense oracle to the tiled flash_xla path, which is memory-faithful to
# the TPU Pallas kernel (the dense oracle materializes an S x T f32 tensor).
_FLASH_XLA_CELLS = 1 << 20

_FORCED_IMPL: Impl | None = None


def force_impl(impl: Impl | None) -> None:
    """Test hook: globally force a kernel implementation."""
    global _FORCED_IMPL
    _FORCED_IMPL = impl


def _resolve(impl: Impl | None) -> Impl:
    want = _FORCED_IMPL if _FORCED_IMPL is not None else impl
    if jax.default_backend() == "tpu":
        if want not in (None, "auto", "pallas"):
            raise ValueError(f"kernel impl {want!r} does not run on a TPU; "
                             "only 'pallas' does")
        return "pallas"
    return "ref" if want in (None, "auto") else want


# ---------------------------------------------------------------------------
# qn_apply / qn_apply_multi — the SHINE inverse-estimate application
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class QNStreamStats:
    """Trace-time counters of qn inverse-application streaming cost.

    ``calls`` counts qn_apply/qn_apply_multi invocations, ``rhs`` the total
    right-hand sides applied, ``uv_bytes`` the analytic HBM bytes the kernel
    streaming model reads from U/V.  Counters increment when the op is
    TRACED: under ``lax.while_loop`` the body traces once, so after tracing
    a solver these are exact per-iteration costs.

    Storage lives in the observability registry (``repro.obs.metrics``,
    counters ``qn_stream_{calls,rhs,uv_bytes}``) so bench rows and metrics
    snapshots share one source of truth; this dataclass is the legacy view
    the bench harness reads.  Recording is unconditional (host-side,
    trace-time — it costs nothing per executed iteration).
    """

    calls: int = 0
    rhs: int = 0
    uv_bytes: int = 0


_QN_COUNTERS = ("qn_stream_calls", "qn_stream_rhs", "qn_stream_uv_bytes")


def reset_qn_stream_stats() -> None:
    reg = obs_metrics.default_registry()
    for name in _QN_COUNTERS:
        reg.counter(name).value = 0.0


def qn_stream_stats() -> QNStreamStats:
    reg = obs_metrics.default_registry()
    calls, rhs, uv_bytes = (int(reg.counter(n).value) for n in _QN_COUNTERS)
    return QNStreamStats(calls=calls, rhs=rhs, uv_bytes=uv_bytes)


def qn_stream_bytes(m: int, bsz: int, dim: int, itemsize: int,
                    transpose: Sequence[bool]) -> int:
    """Analytic U/V bytes one fused application streams from HBM.

    Per phase (coefficient, apply) a buffer is read once iff some RHS needs
    it: uniform flags read one buffer per phase (2·m·B·D total, independent
    of K); mixed flags read both per phase (4·m·B·D)."""
    any_t, any_f = any(transpose), not all(transpose)
    streams = 2 * (int(any_t) + int(any_f))
    return streams * m * bsz * dim * itemsize


def _record_stream(u: jax.Array, transpose: Sequence[bool]) -> None:
    m, bsz = u.shape[0], u.shape[1]
    dim = 1
    for f in u.shape[2:]:
        dim *= f
    reg = obs_metrics.default_registry()
    reg.counter("qn_stream_calls").inc()
    reg.counter("qn_stream_rhs").inc(len(transpose))
    reg.counter("qn_stream_uv_bytes").inc(
        qn_stream_bytes(m, bsz, dim, u.dtype.itemsize, transpose))
    # resident ring footprint by storage dtype (U + V), trace-time gauge
    reg.gauge("qn_ring_bytes", {"dtype": jnp.dtype(u.dtype).name}).set(
        2 * m * bsz * dim * u.dtype.itemsize)


def _pad_memory_axis(u2, v2, mask):
    if u2.shape[0] % 8 != 0:  # pad qN memory axis to sublane multiple
        pad = 8 - u2.shape[0] % 8
        u2 = jnp.pad(u2, ((0, pad), (0, 0), (0, 0)))
        v2 = jnp.pad(v2, ((0, pad), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
    return u2, v2, mask


# A pallas_call cannot be partitioned by GSPMD.  Every kernel here is
# independent across batch rows (and attention across heads), so under a
# mesh each device runs it on its own rows: ``sharded_kernels(ctx)`` (opened
# by the model entry points, the solve entry points and the DEQ backward
# while they trace) makes the kernel paths below run under ``jax.shard_map``
# with the ctx's layout of those axes.  Axes the kernel cannot split stay
# whole on every device.
_KERNEL_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "kernel_ctx", default=None)


@contextlib.contextmanager
def sharded_kernels(ctx):
    """While tracing inside this context, kernels map over ``ctx.mesh``
    (a ``ShardCtx``; one without a mesh, or None, is a no-op)."""
    token = _KERNEL_CTX.set(
        ctx if ctx is not None and ctx.mesh is not None else None)
    try:
        yield
    finally:
        _KERNEL_CTX.reset(token)


def _names(ndim: int, at: dict[int, str | None]) -> tuple:
    """Logical axis names of an ``ndim`` array: ``at={1: "batch"}`` puts
    "batch" on dim 1, every other dim is unsplit."""
    return tuple(at.get(i) for i in range(ndim))


def _on_mesh(fn, in_names, out_names):
    """``fn`` as is, or mapped over the active mesh.  ``in_names`` holds
    each argument's logical axis names (None: replicated); ``out_names`` the
    output's, or a list of each output's.  A logical axis splits only if
    every argument dim carrying it divides by its mesh extent."""
    ctx = _KERNEL_CTX.get()
    if ctx is None:
        return fn

    def mapped(*args):
        ok: dict[str, bool] = {}
        for names, a in zip(in_names, args):
            for n, dim in zip(names or (), jnp.shape(a)):
                if n is not None:
                    ok[n] = ok.get(n, True) and dim % ctx.axis_size(n) == 0

        def spec(names):
            if names is None:
                return P()
            return ctx.rules.spec([n if n and ok[n] else None for n in names])

        out_specs = (tuple(spec(n) for n in out_names)
                     if isinstance(out_names, list) else spec(out_names))
        return jax.shard_map(
            fn, mesh=ctx.mesh, in_specs=tuple(spec(n) for n in in_names),
            out_specs=out_specs, check_vma=False)(*args)
    return mapped


def qn_apply(u, v, x, alpha, mask, impl: Impl | None = None) -> jax.Array:
    """``H @ x`` for one right-hand side; the kernel path is the K = 1 case
    of :func:`qn_apply_multi`."""
    if _resolve(impl) == "ref":
        _record_stream(u, (False,))
        return ref.qn_apply_ref(u, v, x, alpha, mask)
    return qn_apply_multi(u, v, x[None], alpha, mask, (False,), impl=impl)[0]


def qn_apply_multi(u, v, xs, alpha, mask,
                   transpose: Sequence[bool] | None = None,
                   impl: Impl | None = None,
                   block_d: int = 512) -> jax.Array:
    """Apply H (and/or H^T, per the ``transpose`` flags) to the K stacked
    right-hand sides ``xs: (K, B, *F)`` in ONE streaming pass over U/V.

    Returns ``(K, B, *F)``; ``out[k] = (H^T if transpose[k] else H) @
    xs[k]``.  This is THE fused Broyden-step primitive: the per-step
    direction/matvec/rmatvec all batch through one invocation.
    ``block_d`` pins the kernel's feature tile (Pallas paths only).
    """
    kk = xs.shape[0]
    transpose = tuple(bool(t) for t in
                      ((False,) * kk if transpose is None else transpose))
    if len(transpose) != kk:
        raise ValueError(f"transpose has {len(transpose)} flags for {kk} RHS")
    impl = _resolve(impl)
    _record_stream(u, transpose)
    if impl == "ref":
        return ref.qn_apply_multi_ref(u, v, xs, alpha, mask, transpose)

    def local(u, v, xs, alpha, mask):
        m, bsz = u.shape[0], u.shape[1]
        u2, v2 = u.reshape(m, bsz, -1), v.reshape(m, bsz, -1)
        u2, v2, mask2 = _pad_memory_axis(u2, v2, mask)
        out = qn_apply_multi_pallas(
            u2, v2, xs.reshape(kk, bsz, -1), alpha, mask2,
            transpose=transpose, block_d=block_d,
            interpret=(impl == "pallas_interpret"))
        return out.reshape(xs.shape)

    ring = _names(u.ndim, {1: "batch"})
    rhs = _names(xs.ndim, {1: "batch"})
    return _on_mesh(local, (ring, ring, rhs, None, ring[:2]), rhs)(
        u, v, xs, jnp.asarray(alpha, jnp.float32), mask)


def _write_slot_rows(u, v, slot, row_u, row_v):
    """Write ``row_*[b]`` into ring slot ``slot[b]`` of sample ``b``: a row
    scatter into the (donated, loop-carried) ring — B rows written, not m."""
    bidx = jnp.arange(u.shape[1])
    return (u.at[slot, bidx].set(row_u, unique_indices=True),
            v.at[slot, bidx].set(row_v, unique_indices=True))


def lowrank_append(u, v, s, hy, b, inv_den, slot, upd,
                   impl: Impl | None = None):
    """Fused Broyden ring-buffer update: write ``a = (s - Hy) * inv_den``
    and ``b`` into ring slot ``slot`` of U/V for samples where ``upd``.
    Returns ``(new_u, new_v, evicted_u, evicted_v)``.
    """
    impl = _resolve(impl)
    if impl == "ref":
        return ref.lowrank_append_ref(u, v, s, hy, b, inv_den, slot, upd)

    def local(u, v, s, hy, b, inv_den, slot, upd):
        m, bsz = u.shape[0], u.shape[1]
        flat = lambda a, lead: a.reshape(lead + (-1,))
        new_u, new_v, ev_u, ev_v = lowrank_append_pallas(
            flat(u, (m, bsz)), flat(v, (m, bsz)), flat(s, (bsz,)),
            flat(hy, (bsz,)), flat(b, (bsz,)), inv_den,
            slot.astype(jnp.int32), upd,
            interpret=(impl == "pallas_interpret"),
        )
        return (new_u.reshape(u.shape), new_v.reshape(v.shape),
                ev_u.reshape(s.shape), ev_v.reshape(s.shape))

    ring, row = _names(u.ndim, {1: "batch"}), _names(s.ndim, {0: "batch"})
    vec = ("batch",)
    return _on_mesh(local, (ring, ring, row, row, row, vec, vec, vec),
                    [ring, ring, row, row])(
        u, v, s, hy, b, inv_den, slot, upd)


def broyden_step(u, v, g_new, s, hg_old, alpha, mask, slot, active, eps,
                 impl: Impl | None = None):
    """The whole Broyden iteration's memory work: ONE kernel launch and one
    U/V pass computes ``H @ g_new``, ``H^T @ s`` and the slot's current
    rows; the denominator ``s^T H y``, the guarded pair and the slot-row
    write are O(B·D) XLA work on its outputs.  ``hg_old`` is the carried
    ``H @ g_old`` (so ``H y`` falls out by linearity).  Counts as exactly
    one stream call.

    Returns ``(new_u, new_v, hg_new, b, den, ev_u, ev_v)``; see
    ``kernels/ref.broyden_step_ref`` for the per-output contract.
    """
    impl = _resolve(impl)
    _record_stream(u, (False, True))
    if impl == "ref":
        return ref.broyden_step_ref(u, v, g_new, s, hg_old, alpha, mask,
                                    slot, active, eps)

    def local(u, v, g_new, s, hg_old, alpha, mask, slot, active):
        m, bsz = u.shape[0], u.shape[1]
        flat = lambda a, lead: a.reshape(lead + (-1,))
        slot = slot.astype(jnp.int32)
        u2, v2, mask2 = _pad_memory_axis(flat(u, (m, bsz)), flat(v, (m, bsz)),
                                         mask)
        s2 = flat(s, (bsz,)).astype(jnp.float32)
        hg_new, b, ev_u, ev_v = broyden_step_pallas(
            u2, v2, flat(g_new, (bsz,)), s2, alpha, mask2, slot,
            interpret=(impl == "pallas_interpret"))
        hy = hg_new - flat(hg_old, (bsz,)).astype(jnp.float32)
        den = jnp.sum(s2 * hy, axis=1)
        safe = jnp.abs(den) > eps
        upd = (active > 0.5) & safe
        inv_den = jnp.where(safe, 1.0 / jnp.where(safe, den, 1.0), 0.0)
        a = (s2 - hy) * inv_den[:, None]
        row_u = jnp.where(upd[:, None], a.astype(u.dtype), ev_u)
        row_v = jnp.where(upd[:, None], b.astype(v.dtype), ev_v)
        new_u, new_v = _write_slot_rows(
            u, v, slot, row_u.reshape(s.shape), row_v.reshape(s.shape))
        return (new_u, new_v, hg_new.reshape(s.shape), b.reshape(s.shape),
                den, ev_u.reshape(s.shape), ev_v.reshape(s.shape))

    ring, row = _names(u.ndim, {1: "batch"}), _names(s.ndim, {0: "batch"})
    vec = ("batch",)
    return _on_mesh(
        local, (ring, ring, row, row, row, None, ring[:2], vec, vec),
        [ring, ring, row, row, vec, row, row])(
        u, v, g_new, s, hg_old, jnp.asarray(alpha, jnp.float32), mask, slot,
        jnp.asarray(active, jnp.float32))


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _heads_axis(num_heads: int, num_kv_heads: int) -> str | None:
    """The logical name attention kernels may split heads over: only when q
    and kv heads share one layout that divides both (each device then keeps
    whole GQA groups)."""
    ctx = _KERNEL_CTX.get()
    if ctx is None:
        return None
    phys = ctx.rules.physical("heads_act")
    n = ctx.axis_size("heads_act")
    if (phys is None or phys != ctx.rules.physical("kv_heads_act")
            or num_heads % n or num_kv_heads % n):
        return None
    return "heads_act"


def _attention_fwd_impl(q, k, v, kv_length, causal, scale, impl):
    if impl == "ref":
        return ref.attention_ref(q, k, v, causal=causal, kv_length=kv_length,
                                 scale=scale)
    if kv_length is None:
        kv_length = jnp.full((q.shape[0],), k.shape[1], jnp.int32)

    def local(q, k, v, kv_length):
        return flash_attention_pallas(
            q, k, v, kv_length, causal=causal, scale=scale,
            interpret=(impl == "pallas_interpret"))

    heads = _names(4, {0: "batch", 2: _heads_axis(q.shape[2], k.shape[2])})
    return _on_mesh(local, (heads, heads, heads, ("batch",)), heads)(
        q, k, v, kv_length)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attention(q, k, v, kv_length, causal, scale, impl):
    return _attention_fwd_impl(q, k, v, kv_length, causal, scale, impl)


def _attention_fwd(q, k, v, kv_length, causal, scale, impl):
    out = _attention_fwd_impl(q, k, v, kv_length, causal, scale, impl)
    return out, (q, k, v, kv_length)


def _attention_bwd(causal, scale, impl, res, g):
    q, k, v, kv_length = res
    # Backward through the reference oracle (recompute): numerically identical
    # to the kernel forward, no saved probabilities.
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention_ref(
            q_, k_, v_, causal=causal, kv_length=kv_length, scale=scale
        ),
        q, k, v,
    )
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None


_attention.defvjp(_attention_fwd, _attention_bwd)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    kv_length: jax.Array | None = None,
    scale: float | None = None,
    impl: Impl | None = None,
    block_q: int = 512,
    block_kv: int = 1024,
    unroll: bool = False,
) -> jax.Array:
    """Differentiable multi-head attention: (B,S,H,hd)x(B,T,KV,hd) -> (B,S,H,hd).

    ``block_q``/``block_kv``/``unroll`` apply to the flash_xla path only
    (unroll=True is the dry-run costing mode: every tile appears in the HLO).
    """
    requested = impl
    impl = _resolve(impl)
    if (impl == "ref" and requested in (None, "auto") and _FORCED_IMPL is None
            and q.shape[1] * k.shape[1] >= _FLASH_XLA_CELLS):
        impl = "flash_xla"
    if impl == "flash_xla":
        return flash_attention_xla(
            q, k, v, causal=causal, kv_length=kv_length, scale=scale,
            block_q=block_q, block_kv=block_kv, unroll=unroll,
        )
    return _attention(q, k, v, kv_length, causal, scale, impl)


def decode_attention(
    q: jax.Array,          # (B, H, hd)
    k: jax.Array,          # (B, T, KV, hd)
    v: jax.Array,
    kv_length: jax.Array,  # (B,)
    *,
    scale: float | None = None,
    impl: Impl | None = None,
) -> jax.Array:
    impl = _resolve(impl)
    if impl == "ref":
        return ref.decode_attention_ref(q, k, v, kv_length, scale=scale)

    def local(q, k, v, kv_length):
        return decode_attention_pallas(
            q, k, v, kv_length, scale=scale,
            interpret=(impl == "pallas_interpret"))

    # the cache's sequence axis stays whole: a split softmax would need a
    # cross-device combine
    heads = _heads_axis(q.shape[1], k.shape[2])
    q_names = _names(3, {0: "batch", 1: heads})
    kv_names = _names(4, {0: "batch", 2: heads})
    return _on_mesh(local, (q_names, kv_names, kv_names, ("batch",)),
                    q_names)(q, k, v, kv_length)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm(x, w, eps, impl):
    if impl == "ref":
        return ref.rmsnorm_ref(x, w, eps)

    def local(x, w):
        return rmsnorm_pallas(x, w, eps=eps,
                              interpret=(impl == "pallas_interpret"))

    rows = _names(x.ndim, {0: "batch"})
    return _on_mesh(local, (rows, None), rows)(x, w)


def _rmsnorm_fwd(x, w, eps, impl):
    return _rmsnorm(x, w, eps, impl), (x, w)


def _rmsnorm_bwd(eps, impl, res, g):
    x, w = res
    _, vjp = jax.vjp(lambda x_, w_: ref.rmsnorm_ref(x_, w_, eps), x, w)
    return vjp(g)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6,
            impl: Impl | None = None) -> jax.Array:
    return _rmsnorm(x, w, eps, _resolve(impl))
