"""The pytree-native differentiable fixed point: SHINE's forward/backward.

``implicit_fixed_point(f, params, x, z0, cfg)`` computes ``z* = f(params,
x, z*)`` with the registered forward solver and registers a ``custom_vjp``
that implements Theorem 1's hypergradient with the registered cotangent
estimator (full / shine / jfb / fallback / refine — see
implicit/estimators.py).

``z0`` may be ANY pytree of ``(B, ...)`` arrays — a bare activation, a
tuple of per-scale feature maps (MDEQ), a dict of module states.  The
state is packed to one solver buffer internally (implicit/pytree.py); a
single-leaf state passes through unflattened so TP-sharded LM activations
keep their sharding.

Memory behaviour matches the paper's O(1) claim: the residuals saved for
backward are (params, x, z*, qN chain) — no unrolled activations.  The
backward evaluates one fresh VJP of f at z*.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.solvers import SolveCarry, SolveSharding, init_solve_carry
from repro.implicit.config import ImplicitConfig
from repro.kernels import ops as kernel_ops
from repro.implicit.estimators import estimate_cotangent
from repro.implicit.pytree import ravel_state
from repro.implicit.registry import SOLVERS
from repro.obs import metrics as obs_metrics
from repro.obs.tape import SolveTape

# populate the registry with the built-in solvers on import
from repro.implicit import solvers as _builtin_solvers  # noqa: F401

Array = jax.Array
Pytree = Any


class ImplicitStats(NamedTuple):
    residual: Array    # (B,) forward residual at z*
    n_steps: Array     # () forward iterations
    converged: Array   # (B,)
    trace: Array       # (max_steps, B)
    # full per-iteration convergence tape of the forward solve (residual,
    # step size, qN occupancy); see repro.obs.tape
    tape: SolveTape | None = None
    # per-sample solve-health code (core.solvers.STATUS_*) of the forward
    # solve — the containment signal serving/training route on
    status: Array | None = None


def solve_sharding(ctx, state_axes) -> SolveSharding | None:
    """Build the solver layout hooks from a :class:`ShardCtx`.

    ``state_axes`` are the logical axis names of the (single-leaf) solver
    state, e.g. ``("batch", "seq_res", "embed_act")`` for the DEQ-LM or
    ``("batch", "flat")`` for a packed multi-leaf state.  The quasi-Newton
    (U, V) memory is ``(m,) + state`` and rides the same rules with the
    ``qn_mem`` logical axis prepended, so it stays batch-sharded next to
    the state it preconditions.  Returns None (identity hooks) off-mesh.
    """
    if ctx is None or ctx.mesh is None:
        return None
    axes = tuple(state_axes)
    return SolveSharding(
        state=lambda a: ctx.constrain(a, axes),
        memory=lambda a: ctx.constrain(a, ("qn_mem",) + axes),
        kernels=lambda: kernel_ops.sharded_kernels(ctx),
    )


def kernel_scope(sharding: SolveSharding | None):
    """Context in which a solve traces its kernels per device shard of the
    solve's mesh (a no-op off-mesh)."""
    return contextlib.nullcontext() if sharding is None else sharding.kernels()


def prepare_flat_problem(f, z0, ctx, state_axes):
    """Shared preamble of ``implicit_fixed_point`` and ``engine.batched_solve``:
    pack the state, resolve the effective state axes (packed / multi-leaf
    states use ``("batch", flat...)``), build the layout hooks, and wrap the
    user's pytree map ``f(params, x, z)`` into its flat-state counterpart.

    Returns ``(z0_flat, unravel, f_flat, sharding)``.
    """
    z0_flat, unravel = ravel_state(z0)
    packed = len(jax.tree_util.tree_leaves(z0)) > 1
    if packed or state_axes is None:
        state_axes = ("batch",) + (None,) * (z0_flat.ndim - 1)
    sharding = solve_sharding(ctx, state_axes)

    def f_flat(p, xx, z_flat):
        return ravel_state(f(p, xx, unravel(z_flat)))[0]

    return z0_flat, unravel, f_flat, sharding


def _solve_forward(f_z, z0, cfg: ImplicitConfig, outer_grad=None,
                   sharding=None, freeze_mask=None, carry=None):
    solver = SOLVERS.get(cfg.forward.solver)
    with kernel_scope(sharding), jax.named_scope("deq_solve"):
        return _builtin_solvers.call_solver(
            solver, f_z, z0, cfg.solver_cfg(), outer_grad=outer_grad,
            sharding=sharding, freeze_mask=freeze_mask, carry=carry)


def _bind_outer(outer_grad, params, x):
    if outer_grad is None:
        return None
    return lambda z: outer_grad(params, x, z)


def _shape_structs(tree):
    """Shape/dtype skeleton of a pytree — saved in the custom_vjp residuals
    instead of the real buffers, so the backward can synthesize zero
    cotangents without keeping the (m, B, *F) ring buffers alive from
    forward to backward."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.result_type(x)), tree)


def _zeros_cotangent(tree):
    """Symbolically-zero cotangent for an arbitrary (possibly int/bool)
    pytree of arrays or ShapeDtypeStructs: float leaves get dense zeros,
    non-inexact leaves get float0 — the stop-gradient guarantee for carried
    solve state."""
    import numpy as np

    def zero(leaf):
        if jnp.issubdtype(leaf.dtype, jnp.inexact):
            return jnp.zeros(leaf.shape, leaf.dtype)
        return np.zeros(leaf.shape, jax.dtypes.float0)

    return jax.tree_util.tree_map(zero, tree)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _implicit(f, cfg: ImplicitConfig, outer_grad, sharding, params, x, z0,
              carry):
    res = _solve_forward(lambda z: f(params, x, z), z0, cfg,
                         _bind_outer(outer_grad, params, x), sharding,
                         carry=carry)
    stats = ImplicitStats(res.residual, res.n_steps, res.converged, res.trace,
                          res.tape, res.status)
    obs_metrics.record_solve("forward", res, carry=carry)
    return res.z, stats, res.carry


def _implicit_fwd(f, cfg: ImplicitConfig, outer_grad, sharding, params, x, z0,
                  carry):
    # The carry is a pure warm start: stop_gradient here makes the intent
    # explicit (the bwd below also returns a symbolically-zero cotangent for
    # it), so stale state can NEVER perturb the implicit gradient.
    carry = jax.tree_util.tree_map(jax.lax.stop_gradient, carry)
    res = _solve_forward(lambda z: f(params, x, z), z0, cfg,
                         _bind_outer(outer_grad, params, x), sharding,
                         carry=carry)
    stats = ImplicitStats(res.residual, res.n_steps, res.converged, res.trace,
                          res.tape, res.status)
    obs_metrics.record_solve("forward", res, carry=carry)
    return (res.z, stats, res.carry), (params, x, res.z, res.lowrank,
                                       res.status, _shape_structs(carry))


def _implicit_bwd(f, cfg: ImplicitConfig, outer_grad, sharding, saved,
                  cotangents):
    # traced after the forward's scopes have closed: re-open the kernel
    # scope for the VJP of f and the estimator's solves
    with kernel_scope(sharding), jax.named_scope("implicit_backward"):
        return _implicit_bwd_body(f, cfg, sharding, saved, cotangents)


def _implicit_bwd_body(f, cfg: ImplicitConfig, sharding, saved, cotangents):
    params, x, z_star, H, status, carry = saved  # carry: shape structs only
    w, _stats_bar, _carry_bar = cotangents  # stats/carry carry no gradient

    # One VJP of f at the fixed point (recompute — O(1) memory).
    _, vjp = jax.vjp(lambda p, xx, z: f(p, xx, z), params, x, z_star)
    vjp_z = lambda u: vjp(u.astype(z_star.dtype))[2]

    adj = estimate_cotangent(cfg, vjp_z, w, H, sharding=sharding,
                             forward_status=status)
    obs_metrics.record_backward(cfg.backward.estimator, adj)
    # Per-sample containment: a non-finite cotangent row (poisoned chain,
    # upstream NaN loss, faulted solve) skips its gradient contribution
    # instead of NaN-poisoning the whole batch's parameter gradient.
    u = adj.u
    row_ok = jnp.isfinite(u).reshape(u.shape[0], -1).all(axis=1)
    u = jnp.where(row_ok.reshape((-1,) + (1,) * (u.ndim - 1)), u,
                  jnp.zeros((), u.dtype))
    obs_metrics.emit_scalar(
        "backward_cotangents_zeroed_total",
        (~row_ok).sum().astype(jnp.float32), kind="counter")
    p_bar, x_bar, _ = vjp(u.astype(z_star.dtype))
    z0_bar = jnp.zeros_like(z_star)  # init point does not influence z*
    return p_bar, x_bar, z0_bar, _zeros_cotangent(carry)


_implicit.defvjp(_implicit_fwd, _implicit_bwd)


def implicit_fixed_point(
    f: Callable[[Any, Any, Pytree], Pytree],
    params: Any,
    x: Any,
    z0: Pytree,
    cfg: ImplicitConfig,
    *,
    outer_grad: Callable[[Any, Any, Pytree], Pytree] | None = None,
    ctx=None,
    state_axes: tuple[str | None, ...] | None = None,
    carry: SolveCarry | None = None,
) -> tuple[Pytree, ImplicitStats] | tuple[Pytree, ImplicitStats, SolveCarry]:
    """Differentiable fixed point of ``z = f(params, x, z)`` over pytrees.

    ``f`` must map a state pytree to one of identical structure/shapes.
    ``outer_grad(params, x, z) -> dL/dz`` (same pytree structure) enables
    OPA extra updates in the adjoint-Broyden forward (paper §2.3); leave
    None otherwise.

    ``carry`` (see :func:`carry_for_state`) warm-starts the solve from a
    previous call's state and makes the return a 3-tuple ``(z*, stats,
    new_carry)``.  Stop-gradient guarantees: the carry contributes NOTHING
    to the implicit gradient — the backward returns a symbolically-zero
    cotangent for it, and the returned carry is stop_gradient'ed — so
    warm-started training steps compute bit-identical gradients to cold
    ones once the forward converges to the same fixed point.

    Sharded solves: pass the model's ``ctx: ShardCtx`` plus the logical axis
    names of the *single-leaf* state (``state_axes``) to pin the solver
    iterate and the quasi-Newton (U, V) memory to the activation layout —
    batch over the DP mesh axes, so the inverse-estimate application is
    device-local and only the per-step convergence reduction crosses
    devices.  Multi-leaf states pack to ``(B, D)`` and use
    ``("batch", "flat")`` regardless of ``state_axes``.

    IMPORTANT: everything traced must flow through the differentiable args
    ``(params, x, z0)``, never through f's closure (tracer leak otherwise).
    """
    z0_flat, unravel, f_flat, sharding = prepare_flat_problem(
        f, z0, ctx, state_axes)

    outer_flat = None
    if outer_grad is not None:
        def outer_flat(p, xx, z_flat):  # noqa: F811
            return ravel_state(outer_grad(p, xx, unravel(z_flat)))[0]

    z_flat, stats, carry_out = _implicit(f_flat, cfg, outer_flat, sharding,
                                         params, x, z0_flat, carry)
    if carry is None:
        return unravel(z_flat), stats
    return unravel(z_flat), stats, jax.tree_util.tree_map(
        jax.lax.stop_gradient, carry_out)


def carry_for_state(z0: Pytree, cfg: ImplicitConfig, *,
                    dtype=None) -> SolveCarry:
    """Build an all-cold :class:`SolveCarry` matching the FLAT solver state
    of ``z0`` (single-leaf states keep their shape; multi-leaf states pack
    to ``(B, D)``) and ``cfg.memory`` ring slots."""
    z0_flat, _ = ravel_state(z0)
    return init_solve_carry(
        z0_flat.shape[0], z0_flat.shape[1:], cfg.memory,
        dtype=dtype or z0_flat.dtype, qn_dtype=cfg.qn_dtype)
