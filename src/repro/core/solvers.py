"""Quasi-Newton root solvers whose inverse estimates SHINE shares backward.

Implements Algorithm 1 of the paper in three flavours:

  * ``broyden_solve``          Broyden's "good" method (DEQ forward pass;
                               Bai et al. 2019/2020 setting), batched, limited
                               memory, per-sample freeze masks.
  * ``adjoint_broyden_solve``  Schlenkrich et al. adjoint Broyden, with the
                               paper's OPA extra updates in the direction
                               v_n^T = dL/dz(z_n) B_n^{-1}   (Eq. 7-8, Thm 4).
  * ``lbfgs_solve``            (L)BFGS for the bi-level/hyperparameter
                               setting (Pedregosa 2016), with OPA extra
                               secant pairs in the direction
                               e_n = t_n B_n^{-1} dg/dtheta  (Eq. 5, Thm 3).

plus ``fixed_point_solve`` (Picard/damped iteration; the Jacobian-Free
baseline's forward) and ``anderson_solve``.

TPU adaptation (DESIGN.md §3): every solver is a ``lax.while_loop`` over the
*whole batch* with a fixed iteration budget; converged samples freeze (their
updates are masked out), which emulates per-sample early stopping without
dynamic shapes. All inner products/denominators are f32.
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.lowrank import LowRank, _expand, bdot, bnorm
from repro.obs.tape import SolveTape, empty_tape, tape_record

Array = jax.Array


# ---------------------------------------------------------------------------
# Solve-health status codes (ISSUE 10): every solver reports a per-sample
# int32 code on SolveResult/LBFGSResult.status.  With SolverConfig.guard on,
# DIVERGED/NONFINITE/STALLED are detected INSIDE the while_loop (the sample
# freezes and stops consuming iterations, after bounded in-jit recovery);
# with guard off only CONVERGED/MAX_ITERS are derived at exit.
# ---------------------------------------------------------------------------

STATUS_CONVERGED = 0
STATUS_MAX_ITERS = 1
STATUS_DIVERGED = 2
STATUS_NONFINITE = 3
STATUS_STALLED = 4

STATUS_NAMES = {
    STATUS_CONVERGED: "converged",
    STATUS_MAX_ITERS: "max_iters",
    STATUS_DIVERGED: "diverged",
    STATUS_NONFINITE: "nonfinite",
    STATUS_STALLED: "stalled",
}

# Armed by repro.runtime.faultinject (chaos testing): when set, every batched
# solver perturbs its post-step iterate through this hook.  None = zero
# compiled residue (trace-time gate, same discipline as repro.obs).
_FAULT_HOOK = None


class _GuardState(NamedTuple):
    """Per-sample fault-containment state riding a guarded solver loop."""

    sick: Array       # (B,) bool — faulted rows frozen out of the loop
    status: Array     # (B,) int32 — sticky STATUS_* (MAX_ITERS while live)
    stall: Array      # (B,) int32 — consecutive zero-step count
    restarts: Array   # (B,) int32 — recovery rounds consumed
    stepscale: Array  # (B,) f32 — damping multiplier (1.0 until a restart)


def _guard_init(bsz: int | None) -> _GuardState:
    shape = () if bsz is None else (bsz,)
    return _GuardState(
        sick=jnp.zeros(shape, bool),
        status=jnp.full(shape, STATUS_MAX_ITERS, jnp.int32),
        stall=jnp.zeros(shape, jnp.int32),
        restarts=jnp.zeros(shape, jnp.int32),
        stepscale=jnp.ones(shape, jnp.float32),
    )


def _guard_detect(gs: _GuardState, cfg: "SolverConfig", active: Array,
                  res: Array, step_norm: Array, div_ref: Array):
    """One iteration of per-sample fault detection and recovery bookkeeping.

    A non-finite residual, a residual past ``divergence_ratio x`` the
    divergence reference (``max(res0, ||z0||)`` — the iterate norm supplies
    the problem scale for warm starts, whose post-carry entry residual is
    near zero and would otherwise flag the normal qN chain-rebuild
    overshoot), or ``stall_patience`` consecutive zero-length steps marks
    the sample faulted.  Faulted samples within ``restart_budget`` get a
    recovery round (``do_restart``: the caller resets its state for those
    rows); past the budget they freeze (``sick``) with a sticky status.

    Returns ``(gs', do_restart, code, res_safe)``; ``res_safe`` replaces
    non-finite residuals with +inf — bit-identical for finite rows — so
    best-iterate min/compare logic can't be NaN-poisoned.
    """
    finite = jnp.isfinite(res)
    nonfin = active & ~finite
    div = active & finite & (
        res > cfg.divergence_ratio * jnp.maximum(div_ref, cfg.eps))
    stall_hit = active & finite & (step_norm <= cfg.stall_tol)
    stall = jnp.where(stall_hit, gs.stall + 1, 0)
    stalled = stall_hit & (stall >= cfg.stall_patience)
    fault = nonfin | div | stalled
    code = jnp.where(nonfin, STATUS_NONFINITE,
                     jnp.where(div, STATUS_DIVERGED,
                               STATUS_STALLED)).astype(jnp.int32)
    can_restart = gs.restarts < cfg.restart_budget
    do_restart = fault & can_restart
    freeze = fault & ~can_restart
    gs2 = _GuardState(
        sick=gs.sick | freeze,
        # STICKY on any fault (not only on freeze): a row that recovers
        # in-jit still reports what happened — the backward escalation and
        # the serving retry/eviction paths need the signal even when the
        # iterate healed
        status=jnp.where(fault, code, gs.status),
        stall=jnp.where(fault, 0, stall),
        restarts=gs.restarts + do_restart.astype(jnp.int32),
        stepscale=jnp.where(do_restart, gs.stepscale * cfg.restart_damping,
                            gs.stepscale),
    )
    res_safe = jnp.where(finite, res, jnp.inf)
    return gs2, do_restart, code, res_safe


def _damped(p: Array, gs: _GuardState) -> Array:
    """Apply the per-sample restart damping to a step direction.  Healthy
    rows (stepscale == 1.0) select the ORIGINAL array — bit-identical to
    the unguarded program regardless of dtype rounding."""
    damped = gs.stepscale < 1.0
    return jnp.where(_expand(damped, p), _expand(gs.stepscale, p) * p, p)


def _exit_status(conv: Array, gs: _GuardState | None) -> Array:
    """Final per-sample status.  Fault codes are STICKY: a row that faulted
    and then recovered in-jit still reports the fault code (callers decide
    whether to escalate / retry / evict the state that caused it);
    CONVERGED wins only over the pending MAX_ITERS code."""
    if gs is None:
        return jnp.where(conv, STATUS_CONVERGED,
                         STATUS_MAX_ITERS).astype(jnp.int32)
    faulted = gs.status >= STATUS_DIVERGED
    return jnp.where(faulted, gs.status,
                     jnp.where(conv, STATUS_CONVERGED,
                               gs.status)).astype(jnp.int32)


def _guard_entry(cfg: "SolverConfig", carry, z0: Array, z_cold: Array):
    """Pre-loop containment for a POISONED WARM START: rows whose carried
    iterate is non-finite re-enter at the cold start with one recovery
    round consumed and a sticky NONFINITE status.  Without this the very
    first residual is NaN and poisons the stop threshold, the divergence
    reference, and best-iterate tracking for the whole solve (NaN
    comparisons are all False: the loop would run to max_steps and return
    the NaN entry iterate as "best").  Returns ``(z0, gs0, bad)``;
    ``bad=None`` when nothing was checked (unguarded, or no carry so the
    entry iterate is the caller's own z0)."""
    if not cfg.guard:
        return z0, None, None
    bsz = z0.shape[0]
    gs0 = _guard_init(bsz)
    if carry is None:
        return z0, gs0, None
    bad = ~jnp.all(jnp.isfinite(z0.reshape(bsz, -1)), axis=-1)
    z0 = jnp.where(_expand(bad, z0), z_cold, z0)
    gs0 = gs0._replace(
        status=jnp.where(bad, STATUS_NONFINITE, gs0.status),
        restarts=bad.astype(jnp.int32),
        stepscale=jnp.where(bad, cfg.restart_damping * gs0.stepscale,
                            gs0.stepscale),
    )
    return z0, gs0, bad


# ---------------------------------------------------------------------------
# Persistent solve state: the carry threaded across outer iterations
# ---------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("z", "lowrank", "warm", "age"),
    meta_fields=(),
)
@dataclasses.dataclass
class SolveCarry:
    """Reusable solver state threaded ACROSS solves (train steps, decode
    tokens, bilevel outer iterations) — SHINE's shared inverse estimate made
    first-class beyond the boundary of one call.

    ``z: (B, *F)``        the previous converged iterate (warm-start point).
    ``lowrank``           the quasi-Newton ring memory ``(m, B, *F)`` with
                          its per-sample validity ``count`` — the inverse
                          estimate carried forward, so ``lowrank_append``
                          keeps its fused one-pass ring semantics across
                          solves.
    ``warm: (B,) bool``   per-sample validity: ``False`` rows cold-start
                          from the caller's ``z0`` with an identity inverse
                          (their ring count is masked to zero), so slot
                          eviction is a per-row flag flip — no buffer wipe.
    ``age: (B,) int32``   staleness stat: solves since the row was last
                          reset (0 = cold / just evicted).

    The carry is a plain pytree: it rides in ``TrainState``, shards via the
    same ``SolveSharding`` layout as the live solve, donates cleanly, and
    checkpoints through ``checkpoint/manager`` untouched.
    """

    z: Array
    lowrank: LowRank
    warm: Array
    age: Array

    @property
    def memory(self) -> int:
        return self.lowrank.memory


def init_solve_carry(
    batch: int,
    feat: tuple[int, ...] | int,
    memory: int,
    *,
    alpha: float = 1.0,
    dtype=jnp.float32,
    qn_dtype="bfloat16",
) -> SolveCarry:
    """An all-cold carry: every row starts from the caller's ``z0``.

    ``qn_dtype`` sets the storage dtype of the quasi-Newton U/V ring
    independently of the iterate dtype (API.md "Precision policy").  The
    default matches ``SolverConfig.qn_dtype`` so a carried solve is
    bit-identical to a carryless one; pass ``None`` to keep the ring in
    the iterate dtype.
    """
    feat = (feat,) if isinstance(feat, int) else tuple(feat)
    ring_dtype = jnp.dtype(qn_dtype) if qn_dtype is not None else dtype
    return SolveCarry(
        z=jnp.zeros((batch,) + feat, dtype),
        lowrank=LowRank.identity(batch, feat, memory, alpha=alpha,
                                 dtype=ring_dtype),
        warm=jnp.zeros((batch,), bool),
        age=jnp.zeros((batch,), jnp.int32),
    )


def reset_carry_rows(carry: SolveCarry, evict: Array) -> SolveCarry:
    """Per-sample eviction: rows where ``evict`` is True return to cold-start
    behaviour (``warm=False``, ring count zeroed — the stale slot contents
    stay in place but are masked invalid, exactly like a fresh identity)."""
    keep = ~evict
    lr = dataclasses.replace(
        carry.lowrank, count=jnp.where(keep, carry.lowrank.count, 0))
    return SolveCarry(
        z=carry.z,
        lowrank=lr,
        warm=carry.warm & keep,
        age=jnp.where(keep, carry.age, 0),
    )


def carry_state_only(carry: SolveCarry) -> SolveCarry:
    """Drop the quasi-Newton chain from a carry (ring counts zeroed), keeping
    the iterate warm.  The chain encodes curvature of the PREVIOUS problem's
    samples; when every outer step sees a fresh batch, a stale chain first
    helps then actively degrades the solve (measured: iterations grow past
    the cold count within ~10 steps), while the iterate alone transfers the
    params-driven equilibrium structure and stays reliably ahead of cold.
    """
    bsz = carry.z.shape[0]
    return dataclasses.replace(
        carry,
        lowrank=dataclasses.replace(
            carry.lowrank, count=jnp.zeros((bsz,), jnp.int32)))


def seed_carry(carry: SolveCarry, z: Array) -> SolveCarry:
    """Warm-start every row at ``z`` with a FRESH inverse (ring count zeroed).

    Used when the iterate transfers across problems of different state shape
    — e.g. a prefill equilibrium's last token seeding the first decode solve:
    the (m, B, S, d) prefill chain cannot become a (m, B, 1, d) decode chain,
    but its fixed point can still seed ``z``.
    """
    bsz = carry.z.shape[0]
    return SolveCarry(
        z=z.astype(carry.z.dtype),
        lowrank=dataclasses.replace(
            carry.lowrank, count=jnp.zeros((bsz,), jnp.int32)),
        warm=jnp.ones((bsz,), bool),
        age=jnp.zeros((bsz,), jnp.int32),
    )


def _carry_start(carry: SolveCarry | None, z0: Array, memory: int):
    """Resolve the effective start ``(z0, init_lowrank)`` from a carry.

    Warm rows start at ``carry.z`` with the carried ring chain; cold rows
    keep the caller's ``z0`` and see an empty (identity) chain via a masked
    count.  Returns ``(z0, None)`` when no carry is given.
    """
    if carry is None:
        return z0, None
    if carry.lowrank.u.shape[1:] != (z0.shape[0],) + z0.shape[1:]:
        raise ValueError(
            f"carry memory shape {carry.lowrank.u.shape} does not match "
            f"solver state {z0.shape}")
    if carry.memory != memory:
        raise ValueError(
            f"carry holds {carry.memory} ring slots but the solver is "
            f"configured with memory={memory}; rebuild the carry")
    wm = _expand(carry.warm, z0)
    z_start = jnp.where(wm, carry.z.astype(z0.dtype), z0)
    H0 = dataclasses.replace(
        carry.lowrank,
        count=jnp.where(carry.warm, carry.lowrank.count, 0))
    return z_start, H0


def _carry_out(
    carry: SolveCarry | None,
    z: Array,
    H: LowRank | None,
    entry_frozen: Array,
) -> SolveCarry | None:
    """Package the post-solve state as next call's carry.

    Rows frozen at entry (freeze-masked serving slots) are preserved
    BIT-FOR-BIT: their iterate never moved, their ring count never advanced,
    and their ``warm``/``age`` flags are left untouched.  ``H=None`` keeps
    the carried chain as-is (solvers without a reusable chain: Picard /
    Anderson z-only reuse).
    """
    if carry is None:
        return None
    lr = carry.lowrank
    if H is not None:
        lr = LowRank(
            alpha=lr.alpha,
            u=H.u.astype(lr.u.dtype),
            v=H.v.astype(lr.v.dtype),
            count=H.count,
        )
    live = ~entry_frozen
    return SolveCarry(
        z=z.astype(carry.z.dtype),
        lowrank=lr,
        warm=carry.warm | live,
        age=carry.age + live.astype(jnp.int32),
    )


class SolveSharding(NamedTuple):
    """Layout hooks threaded through a batched solve under SPMD.

    ``state``   applied to every (B, *F) iterate/carry — pins the solver
                state to the caller's activation layout (batch over the DP
                mesh axes, features optionally TP-sharded).
    ``memory``  applied to every (m, B, *F) quasi-Newton buffer — pins the
                low-rank (U, V) chain batch-sharded alongside the state, so
                ``qn_apply_multi`` runs device-local over batch and the only
                collective is the feature reduce on the coefficient block.

    ``kernels``  a context (no arguments) in which the solve traces its
                Pallas kernels per device shard
                (``kernels.ops.sharded_kernels`` of the caller's ShardCtx).

    The layout hooks default to identity; they must be cheap
    (``with_sharding_constraint`` closures). The whole-batch convergence
    reduction (``jnp.all(conv)`` in the loop condition) is the one
    unavoidable cross-device step-count collective — it is what drives early
    exit for the batched solve.
    """

    state: Callable[[Array], Array]
    memory: Callable[[Array], Array]
    kernels: Callable[[], Any] = contextlib.nullcontext


# Module-level identity hooks: a stable default object keeps jit caches warm
# for the unsharded path.
NO_SHARDING = SolveSharding(state=lambda a: a, memory=lambda a: a)


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    max_steps: int = 30
    tol: float = 1e-4
    memory: int = 30
    step_size: float = 1.0
    # residual stop criterion: ||g(z)|| < tol * max(stop_scale(z), 1)
    relative: bool = True
    eps: float = 1e-8
    # OPA (outer-problem awareness): frequency M of extra updates; 0 = off
    opa_freq: int = 0
    opa_t0: float = 1.0
    # record the residual trajectory (max_steps,) for diagnostics
    trace: bool = True
    # unroll the solver loop (python for; no early exit). Used by the dry-run:
    # XLA cost analysis counts while-loop bodies ONCE, so roofline cells lower
    # the unrolled form (DESIGN.md / EXPERIMENTS.md §Dry-run).
    unroll: bool = False
    # storage dtype of the quasi-Newton U/V ring. Coefficients/denominators
    # always accumulate in f32 (API.md "Precision policy"); bf16 halves the
    # per-iteration HBM stream at unchanged accumulate precision.
    qn_dtype: str = "bfloat16"
    # -- numerical-fault guards (API.md "Failure semantics"). guard=False
    # compiles detection out entirely: loop state and lowered HLO are the
    # pre-guard program — the baseline arm of the guard-overhead bench gate.
    # On the healthy path guard=True is bit-identical: detection only ever
    # selects already-computed values, restart damping multiplies by 1.0
    # until a fault fires, and the recovery work hides behind a lax.cond.
    guard: bool = True
    # residual > divergence_ratio * max(res0, ||z0||, eps) => DIVERGED
    # (finite blow-up; non-finite residuals are caught separately)
    divergence_ratio: float = 1e4
    # consecutive steps of norm <= stall_tol before a sample is STALLED.
    # Disabled by default (negative tol never fires): warm-started rows
    # sitting at the f32 floor legitimately take bit-zero steps, and a
    # restart there would burn the warm start for a benign plateau that
    # best-iterate tracking already handles.  Chaos tests and diagnostics
    # opt in with stall_tol=0.0 (fires only on exactly-zero steps).
    stall_patience: int = 3
    stall_tol: float = -1.0
    # faulted samples get this many in-jit recovery rounds (qN ring scrub +
    # restart from the caller's z0) before freezing with a status.  The
    # restart step scale is multiplied by restart_damping per restart;
    # default 1.0 (no damping): the fused Broyden update is only stable at
    # its full step — under-relaxation is an opt-in knob for the
    # Picard/Anderson mixing, not a qN safety net.
    restart_budget: int = 1
    restart_damping: float = 1.0


class SolveResult(NamedTuple):
    z: Array                 # (B, D) best iterate
    lowrank: LowRank         # inverse estimate H ~= J_g(z*)^{-1}
    residual: Array          # (B,) final ||g||
    n_steps: Array           # () iterations executed
    converged: Array         # (B,) bool
    trace: Array             # (max_steps, B) residual history (inf-padded)
    aux: dict
    # updated persistent state for the next solve; None unless the caller
    # passed a carry in (structure in == structure out)
    carry: SolveCarry | None = None
    # (max_steps, B) per-iteration convergence telemetry (repro.obs.tape):
    # residual norm, step size, qN-ring occupancy. Rides the solver loop
    # state; frozen samples' rows keep their init values bit-for-bit.
    tape: SolveTape | None = None
    # (B,) int32 per-sample STATUS_* code.  Guarded solves (cfg.guard) can
    # report DIVERGED/NONFINITE/STALLED from in-loop detection; unguarded
    # solves derive CONVERGED/MAX_ITERS at exit.
    status: Array | None = None


def _entry_frozen(freeze_mask: Array | None, bsz: int) -> Array:
    return jnp.zeros((bsz,), bool) if freeze_mask is None else freeze_mask


def _stop_threshold(g0_norm: Array, z_norm: Array, cfg: SolverConfig) -> Array:
    if cfg.relative:
        return cfg.tol * jnp.maximum(z_norm, 1.0)
    return jnp.full_like(g0_norm, cfg.tol)


# ---------------------------------------------------------------------------
# Broyden's good method (paper Alg. 1 with b = true)
# ---------------------------------------------------------------------------


def broyden_solve(
    g: Callable[[Array], Array],
    z0: Array,
    cfg: SolverConfig,
    *,
    init_lowrank: LowRank | None = None,
    alpha0: float = 1.0,
    sharding: SolveSharding | None = None,
    freeze_mask: Array | None = None,
    carry: SolveCarry | None = None,
) -> SolveResult:
    """Solve ``g(z) = 0`` for a batch ``z0: (B, D)``.

    Maintains ``H_n ~= J_g^{-1}`` via the Sherman–Morrison form of Broyden's
    good update:

        H_{n+1} = H_n + (s_n - H_n y_n) (s_n^T H_n) / (s_n^T H_n y_n)

    i.e. one appended rank-one pair per step:
        a_n = (s_n - H_n y_n) / (s_n^T H_n y_n),    b_n = H_n^T s_n.

    ``init_lowrank`` warm-starts the chain (the paper's *refine* strategy
    re-uses the forward chain, transposed, for the backward linear solve).

    Streaming structure (the fused hot path): the loop carries
    ``Hg = H_n @ g(z_n)`` so the direction costs nothing, and each iteration
    is exactly ONE kernel launch and ONE streaming pass over the U/V
    buffers — the fused ``LowRank.broyden_step`` computes ``H @ g(z_{n+1})``
    and ``H^T @ s_n`` together, derives the denominator ``s^T H y`` from the
    same coefficient pass, and writes the rank-one ring append in place.
    ``H @ y_n`` falls out as ``H @ g(z_{n+1}) - Hg`` (linearity), and the
    carried product is advanced to ``H_{n+1} @ g(z_{n+1})`` by a rank-one
    correction using the appended pair and the ring-evicted pair returned by
    the fused step — O(B·D), no extra U/V traffic.  The ring's storage
    dtype is ``cfg.qn_dtype`` (default bf16; coefficients accumulate f32).

    Batched serving mode: ``freeze_mask: (B,) bool`` marks samples (padding
    slots, already-served requests) as converged at entry — they never move,
    never consume qN memory, and the whole-batch ``all(conv)`` early exit
    fires as soon as every *live* sample is done.  ``sharding`` pins the
    iterate and the (U, V) memory to the caller's SPMD layout.

    Warm starts: ``carry`` (see :class:`SolveCarry`) replaces BOTH the start
    iterate and the initial inverse estimate per sample — warm rows resume
    from the previous solve's ``(z, U, V)``, cold rows fall back to
    ``z0``/identity.  The updated carry is returned in ``SolveResult.carry``.
    """
    bsz, feat = z0.shape[0], z0.shape[1:]
    sh = sharding or NO_SHARDING
    z_cold = sh.state(z0)  # pre-carry start: the guard's restart target
    z0, carry_H = _carry_start(carry, z0, cfg.memory)
    z0 = sh.state(z0)
    H0 = init_lowrank if init_lowrank is not None else carry_H
    if H0 is None:
        H0 = LowRank.identity(bsz, feat, cfg.memory, alpha=alpha0,
                              dtype=jnp.dtype(cfg.qn_dtype))
    H0 = H0.constrain(sh.memory)

    z0, gs0, bad0 = _guard_entry(cfg, carry, z0, z_cold)
    if bad0 is not None:
        # the poisoned rows' carried ring goes with the iterate: a NaN
        # slot would NaN every masked matvec (0 * NaN)
        bm = _expand(bad0, z0)[None]
        H0 = LowRank(alpha=H0.alpha,
                     u=jnp.where(bm, 0.0, H0.u).astype(H0.u.dtype),
                     v=jnp.where(bm, 0.0, H0.v).astype(H0.v.dtype),
                     count=jnp.where(bad0, 0, H0.count))

    g0 = g(z0)
    res0 = bnorm(g0)
    thresh = _stop_threshold(res0, bnorm(z0), cfg)
    div_ref = jnp.maximum(res0, bnorm(z0))  # warm-start-safe scale
    Hg0 = sh.state(H0.matvec(g0.astype(jnp.float32)))

    trace0 = jnp.full((max(cfg.max_steps, 1), bsz), jnp.inf, jnp.float32)
    tape0 = empty_tape(cfg.max_steps, bsz)

    def cond(state):
        k, conv = state[0], state[5]
        done = (conv | state[10].sick) if cfg.guard else conv
        return (k < cfg.max_steps) & ~jnp.all(done)

    def body(state):
        k, z, gz, H, Hg, conv, best_z, best_res, trace, tape = state[:10]
        gs = state[10] if cfg.guard else None
        p = -Hg
        if cfg.guard:
            p = _damped(p, gs)
            active = ~(conv | gs.sick)
        else:
            active = ~conv
        am = _expand(active, z)
        z_new = sh.state(jnp.where(am, z + cfg.step_size * p.astype(z.dtype), z))
        if _FAULT_HOOK is not None:
            z_new = _FAULT_HOOK(z_new, k, z)
        gz_new = jnp.where(am, g(z_new), gz)

        s = (z_new - z).astype(jnp.float32)
        g_new32 = gz_new.astype(jnp.float32)
        wrapped = H.count >= H.memory                 # slot being overwritten
        # THE per-step U/V stream: the fused broyden_step kernel computes
        # H @ g(z_new), H^T @ s, the denominator s^T H y, AND the guarded
        # ring append in a single launch — one pass, write included.
        H, Hg_new, b, den, upd, ev_u, ev_v = H.broyden_step(
            g_new32, s, Hg, active, cfg.eps)
        Hy = Hg_new - Hg                              # H @ (g_new - g_old)
        denom = jnp.where(jnp.abs(den) > cfg.eps, den, 1.0)

        # Advance the carried product to H_{n+1} @ g_new: add the appended
        # pair's contribution, remove the evicted pair's (storage precision,
        # so the carry tracks what matvec over the new chain would compute).
        a_st = ((s - Hy) / _expand(denom, s)).astype(H.u.dtype) \
            .astype(jnp.float32)
        b_st = b.astype(H.v.dtype).astype(jnp.float32)
        gain = a_st * _expand(bdot(b_st, g_new32), s)
        loss = ev_u.astype(jnp.float32) * _expand(
            bdot(ev_v.astype(jnp.float32), g_new32)
            * wrapped.astype(jnp.float32), s)
        Hg = Hg_new + _expand(upd.astype(jnp.float32), s) * (gain - loss)

        res = bnorm(gz_new)
        if cfg.guard:
            gs, do_rs, code, res = _guard_detect(
                gs, cfg, active, res, bnorm(s), div_ref)
            # recovery round — runtime no-op unless a fault fired this
            # iteration: scrub the restarted rows' qN ring (a non-finite
            # slot would NaN every masked matvec: 0 * NaN), re-evaluate the
            # cold residual, and put the rows back at the caller's z0 with
            # a damped step scale.
            any_rs = jnp.any(do_rs)
            rm = _expand(do_rs, z)
            rmu = rm[None]
            u2, v2 = jax.lax.cond(
                any_rs,
                lambda uv: (jnp.where(rmu, 0.0, uv[0]),
                            jnp.where(rmu, 0.0, uv[1])),
                lambda uv: uv, (H.u, H.v))
            H = LowRank(alpha=H.alpha, u=u2, v=v2,
                        count=jnp.where(do_rs, 0, H.count))
            if carry is None:
                gz_cold = g0  # cold start == entry point: reuse g(z0)
            else:
                gz_cold = jax.lax.cond(
                    any_rs, lambda t: g(z_cold), lambda t: t, gz)
            z_new = jnp.where(rm, z_cold, z_new)
            gz_new = jnp.where(rm, gz_cold, gz_new)
            Hg = jnp.where(rm, H.alpha * gz_cold.astype(jnp.float32), Hg)
            res = jnp.where(do_rs, bnorm(gz_cold), res)
        improved = res < best_res
        best_z = jnp.where(_expand(improved, z_new), z_new, best_z)
        best_res = jnp.minimum(res, best_res)
        conv = conv | (res < thresh)
        trace = trace.at[k].set(jnp.where(active, res, trace[k]))
        status_k = None if gs is None else jnp.where(do_rs, code, gs.status)
        tape = tape_record(tape, k, active, res, bnorm(s), H.count,
                           status=status_k)
        out = (k + 1, z_new, gz_new, H, Hg, conv, best_z, best_res, trace,
               tape)
        return out + (gs,) if cfg.guard else out

    conv0 = res0 < thresh
    if freeze_mask is not None:
        conv0 = conv0 | freeze_mask
    state0 = (
        jnp.int32(0), z0, g0, H0, Hg0,
        conv0, z0, res0, trace0, tape0,
    )
    if cfg.guard:
        state0 = state0 + (gs0,)
    if cfg.unroll:
        state = state0
        for _ in range(cfg.max_steps):
            state = body(state)
    else:
        state = jax.lax.while_loop(cond, body, state0)
    k, _z, _gz, H, _Hg, conv, best_z, best_res, trace, tape = state[:10]
    gs = state[10] if cfg.guard else None
    status = _exit_status(conv, gs)
    aux = {} if gs is None else {"restarts": gs.restarts, "sick": gs.sick}
    carry_out = _carry_out(carry, best_z, H, _entry_frozen(freeze_mask, bsz))
    if gs is not None and carry_out is not None:
        # sick rows hand the NEXT solve a cold start, not a faulted state
        # (healthy path: all-False evict mask selects every field bitwise)
        carry_out = reset_carry_rows(carry_out, gs.sick)
    return SolveResult(best_z, H, best_res, k, conv, trace, aux, carry_out,
                       tape, status)


# ---------------------------------------------------------------------------
# Fixed-point / Anderson (Jacobian-Free baseline forward)
# ---------------------------------------------------------------------------


def fixed_point_solve(
    f: Callable[[Array], Array],
    z0: Array,
    cfg: SolverConfig,
    *,
    damping: float = 1.0,
    sharding: SolveSharding | None = None,
    freeze_mask: Array | None = None,
    carry: SolveCarry | None = None,
) -> SolveResult:
    """Damped Picard iteration on ``z <- (1-d) z + d f(z)``; residual f(z)-z.

    Carry reuse is iterate-only (Picard keeps no quasi-Newton memory): warm
    rows start at ``carry.z``, and the carried ring buffers pass through
    untouched so the carry pytree structure stays stable across solvers.
    """
    bsz = z0.shape[0]
    sh = sharding or NO_SHARDING
    z_cold = sh.state(z0)  # pre-carry start: the guard's restart target
    if carry is not None:
        z0, _ = _carry_start(carry, z0, carry.memory)  # validates shapes
    z0 = sh.state(z0)
    z0, gs0, _bad0 = _guard_entry(cfg, carry, z0, z_cold)
    H = LowRank.identity(bsz, 1, 1, alpha=1.0)  # placeholder (JFB shares I)
    res0 = bnorm(f(z0) - z0)
    thresh = _stop_threshold(res0, bnorm(z0), cfg)
    div_ref = jnp.maximum(res0, bnorm(z0))  # warm-start-safe scale
    trace0 = jnp.full((max(cfg.max_steps, 1), bsz), jnp.inf, jnp.float32)
    tape0 = empty_tape(cfg.max_steps, bsz)
    no_qn = jnp.zeros((bsz,), jnp.int32)  # Picard keeps no qN chain

    def cond(state):
        k, conv = state[0], state[2]
        done = (conv | state[6].sick) if cfg.guard else conv
        return (k < cfg.max_steps) & ~jnp.all(done)

    def body(state):
        k, z, conv, best_res, trace, tape = state[:6]
        gs = state[6] if cfg.guard else None
        fz = f(z)
        z_pic = (1 - damping) * z + damping * fz
        if cfg.guard:
            live = conv | gs.sick
            # restart damping scales the Picard mixing factor per sample;
            # healthy rows select the original mixing expression bitwise
            d2 = _expand(damping * gs.stepscale, z)
            z_dampd = (1 - d2) * z + d2 * fz
            z_pic = jnp.where(_expand(gs.stepscale < 1.0, z), z_dampd, z_pic)
        else:
            live = conv
        z_new = sh.state(jnp.where(_expand(live, z), z, z_pic))
        if _FAULT_HOOK is not None:
            z_new = _FAULT_HOOK(z_new, k, z)
        res = bnorm(fz - z)
        step_n = bnorm(z_new - z)
        if cfg.guard:
            gs, do_rs, code, res = _guard_detect(
                gs, cfg, ~live, res, step_n, div_ref)
            z_new = jnp.where(_expand(do_rs, z), z_cold, z_new)
        trace = trace.at[k].set(jnp.where(live, trace[k], res))
        status_k = None if gs is None else jnp.where(do_rs, code, gs.status)
        tape = tape_record(tape, k, ~live, res, step_n, no_qn,
                           status=status_k)
        best_res = jnp.minimum(best_res, res)
        conv = conv | (res < thresh)
        out = (k + 1, z_new, conv, best_res, trace, tape)
        return out + (gs,) if cfg.guard else out

    conv0 = res0 < thresh
    if freeze_mask is not None:
        conv0 = conv0 | freeze_mask
    state0 = (jnp.int32(0), z0, conv0, res0, trace0, tape0)
    if cfg.guard:
        state0 = state0 + (gs0,)
    if cfg.unroll:
        state = state0
        for _ in range(cfg.max_steps):
            state = body(state)
    else:
        state = jax.lax.while_loop(cond, body, state0)
    k, z, conv, best_res, trace, tape = state[:6]
    gs = state[6] if cfg.guard else None
    carry_out = _carry_out(carry, z, None, _entry_frozen(freeze_mask, bsz))
    if gs is not None and carry_out is not None:
        carry_out = reset_carry_rows(carry_out, gs.sick)
    return SolveResult(z, H, best_res, k, conv, trace,
                       {} if gs is None else {"restarts": gs.restarts,
                                              "sick": gs.sick},
                       carry_out, tape, _exit_status(conv, gs))


def anderson_solve(
    f: Callable[[Array], Array],
    z0: Array,
    cfg: SolverConfig,
    *,
    mixing: float = 1.0,
    ridge: float = 1e-8,
    sharding: SolveSharding | None = None,
    freeze_mask: Array | None = None,
    carry: SolveCarry | None = None,
) -> SolveResult:
    """Anderson acceleration with window m = cfg.memory (type-II).

    Carry reuse is iterate-only (the Anderson residual window is rebuilt —
    it is only meaningful around the current iterate); the carried ring
    buffers pass through untouched.
    """
    bsz, feat = z0.shape[0], z0.shape[1:]
    m = min(cfg.memory, 8)
    sh = sharding or NO_SHARDING
    z_cold = sh.state(z0)  # pre-carry start: the guard's restart target
    if carry is not None:
        z0, _ = _carry_start(carry, z0, carry.memory)  # validates shapes
    z0 = sh.state(z0)
    z0, gs0, _bad0 = _guard_entry(cfg, carry, z0, z_cold)
    res0 = bnorm(f(z0) - z0)
    thresh = _stop_threshold(res0, bnorm(z0), cfg)
    div_ref = jnp.maximum(res0, bnorm(z0))  # warm-start-safe scale
    trace0 = jnp.full((max(cfg.max_steps, 1), bsz), jnp.inf, jnp.float32)

    # history buffers share the qN-memory layout: (m, B, *F), batch-sharded
    Z = sh.memory(jnp.zeros((m, bsz) + feat, z0.dtype))   # iterate history
    F = sh.memory(jnp.zeros((m, bsz) + feat, z0.dtype))   # residual history

    tape0 = empty_tape(cfg.max_steps, bsz)

    def cond(state):
        k, conv = state[0], state[4]
        done = (conv | state[7].sick) if cfg.guard else conv
        return (k < cfg.max_steps) & ~jnp.all(done)

    def body(state):
        k, z, Z, F, conv, trace, tape = state[:7]
        gs = state[7] if cfg.guard else None
        live = (conv | gs.sick) if cfg.guard else conv
        fz = f(z)
        r = fz - z
        slot = k % m
        Z = Z.at[slot].set(fz)
        F = F.at[slot].set(r)
        nk = jnp.minimum(k + 1, m)
        valid = (jnp.arange(m) < nk).astype(jnp.float32)           # (m,)
        # solve min ||sum_i w_i F_i|| s.t. sum w = 1  (normal equations)
        G = jnp.einsum("ib...,jb...->bij", F.astype(jnp.float32), F.astype(jnp.float32))
        G = G * valid[None, :, None] * valid[None, None, :]
        G = G + (ridge + (1 - valid[None, :, None] * valid[None, None, :])) * jnp.eye(m)[None]
        ones = valid[None, :].repeat(bsz, 0)
        w = jnp.linalg.solve(G, ones[..., None])[..., 0]
        w = w * valid[None, :]
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-12)
        z_and = jnp.einsum("bi,ib...->b...", w, Z.astype(jnp.float32)).astype(z.dtype)
        z_mix = (1 - mixing) * z + mixing * z_and
        if cfg.guard:
            # restart damping scales the Anderson mixing per sample;
            # healthy rows select the original expression bitwise
            mx = _expand(mixing * gs.stepscale, z)
            z_dampd = (1 - mx) * z + mx * z_and
            z_mix = jnp.where(_expand(gs.stepscale < 1.0, z), z_dampd, z_mix)
            # a rank-deficient window NaNs the per-sample weight solve
            # (e.g. right after a restart scrub, when the z_cold mixture
            # reproduces itself and consecutive slots hold DUPLICATE
            # residual columns — identical columns sit beyond the f32
            # reach of the ridge).  Those rows take the plain Picard step
            # until the window regains diversity; healthy rows select
            # their own already-computed mixing result bit-identically.
            mix_ok = jnp.all(jnp.isfinite(z_mix.reshape(bsz, -1)), axis=-1)
            z_mix = jnp.where(_expand(mix_ok, z), z_mix, fz)
        z_new = sh.state(jnp.where(_expand(live, z), z, z_mix))
        if _FAULT_HOOK is not None:
            z_new = _FAULT_HOOK(z_new, k, z)
        res = bnorm(r)
        step_n = bnorm(z_new - z)
        if cfg.guard:
            gs, do_rs, code, res = _guard_detect(
                gs, cfg, ~live, res, step_n, div_ref)
            # restart: put the row back at the cold start AND scrub its
            # history window — a poisoned F row would otherwise NaN the
            # per-sample mixing solve for up to m more iterations.  The
            # scrubbed slots get Z=z_cold, F=0: identical nonzero sentinels
            # would make the Gram matrix rank-deficient beyond f32's reach
            # of the ridge (the mixing solve then returns garbage and the
            # row re-faults, burning the restart budget), while F=0 reduces
            # those slots to exactly ridge*I — well-conditioned, and the
            # mixture of z_cold entries they select is the restart iterate
            # itself until fresh residuals overwrite the window.
            rm = _expand(do_rs, z)
            rmu = rm[None]
            Z, F = jax.lax.cond(
                jnp.any(do_rs),
                lambda t: (jnp.where(rmu, z_cold[None].astype(t[0].dtype),
                                     t[0]),
                           jnp.where(rmu, jnp.asarray(0.0, t[1].dtype),
                                     t[1])),
                lambda t: t, (Z, F))
            z_new = jnp.where(rm, z_cold, z_new)
        trace = trace.at[k].set(jnp.where(live, trace[k], res))
        # qn_count reports the Anderson window fill (per-sample once live)
        status_k = None if gs is None else jnp.where(do_rs, code, gs.status)
        tape = tape_record(tape, k, ~live, res, step_n,
                           jnp.broadcast_to(nk, (bsz,)), status=status_k)
        conv = conv | (res < thresh)
        out = (k + 1, z_new, Z, F, conv, trace, tape)
        return out + (gs,) if cfg.guard else out

    conv0 = res0 < thresh
    if freeze_mask is not None:
        conv0 = conv0 | freeze_mask
    state0 = (jnp.int32(0), z0, Z, F, conv0, trace0, tape0)
    if cfg.guard:
        state0 = state0 + (gs0,)
    state = jax.lax.while_loop(cond, body, state0)
    k, z, Z, F, conv, trace, tape = state[:7]
    gs = state[7] if cfg.guard else None
    H = LowRank.identity(bsz, 1, 1, alpha=1.0)
    final_res = bnorm(f(z) - z)
    if cfg.guard:
        # a sick row's iterate may be non-finite; report +inf, not NaN
        final_res = jnp.where(gs.sick, jnp.inf, final_res)
    carry_out = _carry_out(carry, z, None, _entry_frozen(freeze_mask, bsz))
    if gs is not None and carry_out is not None:
        carry_out = reset_carry_rows(carry_out, gs.sick)
    return SolveResult(z, H, final_res, k, conv, trace,
                       {} if gs is None else {"restarts": gs.restarts,
                                              "sick": gs.sick},
                       carry_out, tape, _exit_status(conv, gs))


# ---------------------------------------------------------------------------
# Adjoint Broyden with OPA (paper §2.3, Thm 4)
# ---------------------------------------------------------------------------


def adjoint_broyden_solve(
    g: Callable[[Array], Array],
    z0: Array,
    cfg: SolverConfig,
    *,
    outer_grad: Callable[[Array], Array] | None = None,
    sigma_from_step: bool = False,  # secant direction: step instead of residual
    sharding: SolveSharding | None = None,
    freeze_mask: Array | None = None,
    carry: SolveCarry | None = None,
) -> SolveResult:
    """Adjoint Broyden: secant ``sigma^T B_{n+1} = sigma^T J_g(z_{n+1})``.

    Maintains BOTH chains exactly (B as ``alpha I + sum sigma_i w_i^T`` and
    H = B^{-1} via Sherman–Morrison), since the update coefficient needs
    ``sigma^T B`` — cheap on the B-chain — while steps need ``H g``.

    OPA: every ``cfg.opa_freq`` steps an extra update is applied with
    ``sigma = H^T dL/dz(z_n)`` (Eq. 8), which is exactly the direction the
    hypergradient (3) consumes. Requires ``outer_grad``.

    Carry reuse is iterate-only: warm-starting H without B would break the
    ``H = B^{-1}`` invariant the update coefficients rely on, so the chains
    are rebuilt each solve.  The new H chain IS packaged into the returned
    carry (the SHINE estimate keeps flowing to consumers), but its count is
    what this solve built, not a continuation.
    """
    bsz, feat = z0.shape[0], z0.shape[1:]
    sh = sharding or NO_SHARDING
    z_cold = sh.state(z0)  # pre-carry start: the guard's restart target
    z0, _ = _carry_start(carry, z0, cfg.memory)  # validates; H not reused
    z0 = sh.state(z0)
    z0, gs0, _bad0 = _guard_entry(cfg, carry, z0, z_cold)
    B = LowRank.identity(bsz, feat, cfg.memory, alpha=1.0, dtype=jnp.float32)
    H = LowRank.identity(bsz, feat, cfg.memory, alpha=1.0, dtype=jnp.float32)
    B, H = B.constrain(sh.memory), H.constrain(sh.memory)

    g0 = g(z0)
    res0 = bnorm(g0)
    thresh = _stop_threshold(res0, bnorm(z0), cfg)
    div_ref = jnp.maximum(res0, bnorm(z0))  # warm-start-safe scale
    trace0 = jnp.full((max(cfg.max_steps, 1), bsz), jnp.inf, jnp.float32)
    tape0 = empty_tape(cfg.max_steps, bsz)

    def update_chains(B, H, z_new, sigma, active):
        # sigma^T J at z_new via VJP; sigma^T B via the B-chain (rmatvec).
        _, vjp = jax.vjp(g, z_new)
        sJT = vjp(sigma.astype(z_new.dtype))[0].astype(jnp.float32)
        sB = B.rmatvec(sigma)
        ss = bdot(sigma, sigma)
        safe = ss > cfg.eps
        w_row = (sJT - sB) / _expand(jnp.where(safe, ss, 1.0), sJT)
        # H update: H <- H - (H sigma)(w^T H) / (1 + w^T H sigma).
        # H sigma and w^T H batch through one fused U/V stream.
        Hs, wH = H.matvec_multi((sigma, w_row), (False, True))
        den = 1.0 + bdot(w_row, Hs)
        safe = safe & (jnp.abs(den) > cfg.eps)
        a = -Hs / _expand(jnp.where(safe, den, 1.0), Hs)
        B = B.append(sigma, w_row, active & safe)
        H = H.append(a, wH, active & safe)
        return B, H

    def cond(state):
        k, conv = state[0], state[5]
        done = (conv | state[8].sick) if cfg.guard else conv
        return (k < cfg.max_steps) & ~jnp.all(done)

    def body(state):
        k, z, gz, B, H, conv, trace, tape = state[:8]
        gs = state[8] if cfg.guard else None
        active = ~(conv | gs.sick) if cfg.guard else ~conv
        am = _expand(active, z)
        p = -H.matvec(gz.astype(jnp.float32))
        if cfg.guard:
            p = _damped(p, gs)
        z_new = sh.state(jnp.where(am, z + cfg.step_size * p.astype(z.dtype), z))
        if _FAULT_HOOK is not None:
            z_new = _FAULT_HOOK(z_new, k, z)
        gz_new = jnp.where(am, g(z_new), gz)

        if sigma_from_step:
            sigma = (z_new - z).astype(jnp.float32)
        else:
            sigma = gz_new.astype(jnp.float32)
        B2, H2 = update_chains(B, H, z_new, sigma, active)

        if outer_grad is not None and cfg.opa_freq > 0:
            def do_opa(BH):
                B_, H_ = BH
                w = outer_grad(z_new).astype(jnp.float32)
                sigma_e = H_.rmatvec(w)  # v_n = (dL/dz B^{-1})^T   (Eq. 8)
                return update_chains(B_, H_, z_new, sigma_e, active)
            B2, H2 = jax.lax.cond(
                (k % cfg.opa_freq) == cfg.opa_freq - 1,
                do_opa, lambda BH: BH, (B2, H2),
            )

        res = bnorm(gz_new)
        if cfg.guard:
            gs, do_rs, code, res = _guard_detect(
                gs, cfg, active, res, bnorm(z_new - z), div_ref)
            # recovery round (runtime no-op unless a fault fired): scrub
            # BOTH chains for the restarted rows — the H = B^{-1} invariant
            # only holds if they reset together — and go back to the cold
            # start with a damped step scale.
            any_rs = jnp.any(do_rs)
            rm = _expand(do_rs, z)
            rmu = rm[None]
            (bu, bv), (hu, hv) = jax.lax.cond(
                any_rs,
                lambda t: (
                    (jnp.where(rmu, 0.0, t[0][0]),
                     jnp.where(rmu, 0.0, t[0][1])),
                    (jnp.where(rmu, 0.0, t[1][0]),
                     jnp.where(rmu, 0.0, t[1][1]))),
                lambda t: t, ((B2.u, B2.v), (H2.u, H2.v)))
            B2 = LowRank(alpha=B2.alpha, u=bu, v=bv,
                         count=jnp.where(do_rs, 0, B2.count))
            H2 = LowRank(alpha=H2.alpha, u=hu, v=hv,
                         count=jnp.where(do_rs, 0, H2.count))
            if carry is None:
                gz_cold = g0  # cold start == entry point: reuse g(z0)
            else:
                gz_cold = jax.lax.cond(
                    any_rs, lambda t: g(z_cold), lambda t: t, gz)
            z_new = jnp.where(rm, z_cold, z_new)
            gz_new = jnp.where(rm, gz_cold, gz_new)
            res = jnp.where(do_rs, bnorm(gz_cold), res)
        trace = trace.at[k].set(jnp.where(active, res, trace[k]))
        status_k = None if gs is None else jnp.where(do_rs, code, gs.status)
        tape = tape_record(tape, k, active, res, bnorm(z_new - z), H2.count,
                           status=status_k)
        conv = conv | (res < thresh)
        out = (k + 1, z_new, gz_new, B2, H2, conv, trace, tape)
        return out + (gs,) if cfg.guard else out

    conv0 = res0 < thresh
    if freeze_mask is not None:
        conv0 = conv0 | freeze_mask
    state0 = (jnp.int32(0), z0, g0, B, H, conv0, trace0, tape0)
    if cfg.guard:
        state0 = state0 + (gs0,)
    state = jax.lax.while_loop(cond, body, state0)
    k, z, gz, B, H, conv, trace, tape = state[:8]
    gs = state[8] if cfg.guard else None
    final_res = bnorm(gz)
    if cfg.guard:
        final_res = jnp.where(gs.sick, jnp.inf, final_res)
    aux = {"B": B}
    if gs is not None:
        aux.update(restarts=gs.restarts, sick=gs.sick)
    carry_out = _carry_out(carry, z, H, _entry_frozen(freeze_mask, bsz))
    if gs is not None and carry_out is not None:
        carry_out = reset_carry_rows(carry_out, gs.sick)
    return SolveResult(z, H, final_res, k, conv, trace, aux, carry_out,
                       tape, _exit_status(conv, gs))


# ---------------------------------------------------------------------------
# (L)BFGS with OPA extra secant pairs (paper Alg. LBFGS, Thm 3)
# ---------------------------------------------------------------------------


class LBFGSMemory(NamedTuple):
    s: Array     # (m, D)
    y: Array     # (m, D)
    rho: Array   # (m,)
    count: Array  # () int32 — total pairs ever stored (ring)


def lbfgs_two_loop_multi(
    mem: LBFGSMemory,
    vs: tuple[Array, ...] | list[Array],
    gamma: Array | float = 1.0,
) -> tuple[Array, ...]:
    """Apply the LBFGS inverse-Hessian estimate H to K vectors in ONE pass
    over the (m, D) s/y memory (each ring pair is read once and contracted
    against all K carried vectors — the L-BFGS analogue of the fused
    ``qn_apply_multi`` stream; H is symmetric so there is no transposed
    variant)."""
    m = mem.s.shape[0]
    n = jnp.minimum(mem.count, m)
    # iterate newest -> oldest: ring order
    order_new_to_old = (mem.count - 1 - jnp.arange(m)) % m

    def first_loop(carry, i):
        q, alphas = carry                                  # (K, D), (m, K)
        idx = order_new_to_old[i]
        valid = i < n
        alpha = jnp.where(valid, mem.rho[idx] * (q @ mem.s[idx]), 0.0)  # (K,)
        q = q - alpha[:, None] * jnp.where(valid, mem.y[idx], 0.0)[None, :]
        return (q, alphas.at[i].set(alpha)), None

    q0 = jnp.stack([v.astype(jnp.float32) for v in vs])
    kk = q0.shape[0]
    (q, alphas), _ = jax.lax.scan(
        first_loop, (q0, jnp.zeros((m, kk), jnp.float32)), jnp.arange(m)
    )
    r = gamma * q

    def second_loop(r, i):
        j = m - 1 - i
        idx = order_new_to_old[j]
        valid = j < n
        beta = jnp.where(valid, mem.rho[idx] * (r @ mem.y[idx]), 0.0)  # (K,)
        r = r + (alphas[j] - beta)[:, None] * \
            jnp.where(valid, mem.s[idx], 0.0)[None, :]
        return r, None

    r, _ = jax.lax.scan(second_loop, r, jnp.arange(m))
    return tuple(r[k] for k in range(kk))


def lbfgs_two_loop(mem: LBFGSMemory, v: Array, gamma: Array | float = 1.0) -> Array:
    """Apply the LBFGS inverse-Hessian estimate H to v (two-loop recursion).

    This is THE SHINE operation for the bi-level setting: sharing H with the
    hypergradient instead of running a fresh CG/Newton solve.  Single-RHS
    view of ``lbfgs_two_loop_multi``.
    """
    return lbfgs_two_loop_multi(mem, (v,), gamma)[0]


def _mem_push(mem: LBFGSMemory, s: Array, y: Array, accept: Array) -> LBFGSMemory:
    sy = jnp.dot(s, y)
    ok = accept & (sy > 1e-12)
    slot = mem.count % mem.s.shape[0]
    s_new = jnp.where(ok, s, mem.s[slot])
    y_new = jnp.where(ok, y, mem.y[slot])
    rho_new = jnp.where(ok, 1.0 / jnp.maximum(sy, 1e-12), mem.rho[slot])
    return LBFGSMemory(
        s=mem.s.at[slot].set(s_new),
        y=mem.y.at[slot].set(y_new),
        rho=mem.rho.at[slot].set(rho_new),
        count=mem.count + ok.astype(jnp.int32),
    )


class LBFGSResult(NamedTuple):
    z: Array
    memory: LBFGSMemory
    grad_norm: Array
    n_steps: Array
    converged: Array
    trace: Array
    # (max_steps,) scalar-problem convergence tape (repro.obs.tape)
    tape: SolveTape | None = None
    # () int32 STATUS_* code (scalar problem: one status for the solve)
    status: Array | None = None


def lbfgs_solve(
    grad_fn: Callable[[Array], Array],
    z0: Array,                       # (D,)
    cfg: SolverConfig,
    *,
    value_fn: Callable[[Array], Array] | None = None,
    dg_dtheta: Callable[[Array], Array] | None = None,  # OPA direction source
    max_ls: int = 20,
    mem0: LBFGSMemory | None = None,
) -> LBFGSResult:
    """L-BFGS minimization via its gradient ``grad_fn`` (= g_theta of Eq. 2).

    ``mem0`` warm-starts the secant ring memory — the HOAG outer loop passes
    the previous outer iterate's memory so both the inner solve AND the
    SHINE inverse estimate (the two-loop recursion the hypergradient shares)
    resume instead of rebuilding curvature from scratch.  Stale pairs from
    the previous hyperparameter wash out of the ring as new pairs land.

    Line search: backtracking Armijo on ``value_fn`` when given, else fixed
    unit step (Thm 3 remark covers alpha_n = 1 near the solution).

    OPA (cfg.opa_freq = M > 0, requires ``dg_dtheta``): every M steps an extra
    secant pair ``(e_n, g(z+e_n) - g(z))`` with
    ``e_n = t_n H_n dg/dtheta|_{z_n}`` is pushed into the same ring memory the
    two-loop recursion reads — improving H exactly in the direction the
    hypergradient needs. t_n = ||s_{n-1}|| (summable by superlinearity).
    """
    dim = z0.shape[0]
    m = cfg.memory
    if mem0 is None:
        mem0 = LBFGSMemory(
            s=jnp.zeros((m, dim), jnp.float32),
            y=jnp.zeros((m, dim), jnp.float32),
            rho=jnp.zeros((m,), jnp.float32),
            count=jnp.int32(0),
        )
    elif mem0.s.shape != (m, dim):
        raise ValueError(
            f"mem0 holds {mem0.s.shape} but the solver needs ({m}, {dim})")
    g0 = grad_fn(z0)
    gn0 = jnp.linalg.norm(g0)
    trace0 = jnp.full((max(cfg.max_steps, 1),), jnp.inf, jnp.float32)
    tape0 = empty_tape(cfg.max_steps, batch=None)

    def cond(state):
        k, done = state[0], state[5]
        if cfg.guard:
            done = done | state[8].sick
        return (k < cfg.max_steps) & ~done

    def line_search(z, p, gz, fz):
        """Backtracking Armijo; returns step length alpha."""
        gp = jnp.dot(gz, p)

        def ls_cond(carry):
            alpha, it = carry
            fa = value_fn(z + alpha * p)
            armijo = fa <= fz + 1e-4 * alpha * gp
            return (~armijo) & (it < max_ls)

        def ls_body(carry):
            alpha, it = carry
            return alpha * 0.5, it + 1

        alpha, _ = jax.lax.while_loop(ls_cond, ls_body, (jnp.float32(1.0), 0))
        return alpha

    def body(state):
        k, z, gz, mem, t_prev, done, trace, tape = state[:8]
        gs = state[8] if cfg.guard else None
        gamma = _lbfgs_gamma(mem)
        p = -lbfgs_two_loop(mem, gz, gamma)
        if value_fn is not None:
            fz = value_fn(z)
            alpha = line_search(z, p, gz, fz)
        else:
            alpha = jnp.float32(cfg.step_size)
        if cfg.guard:
            alpha = jnp.where(gs.stepscale < 1.0, gs.stepscale * alpha, alpha)
        z_new = z + alpha * p
        g_new = grad_fn(z_new)
        s = (z_new - z).astype(jnp.float32)
        y = (g_new - gz).astype(jnp.float32)
        mem = _mem_push(mem, s, y, jnp.bool_(True))

        if dg_dtheta is not None and cfg.opa_freq > 0:
            def do_opa(mem):
                t_n = jnp.minimum(jnp.linalg.norm(s), cfg.opa_t0)
                d = dg_dtheta(z_new).astype(jnp.float32)
                e = t_n * lbfgs_two_loop(mem, d, _lbfgs_gamma(mem))
                y_hat = (grad_fn(z_new + e) - g_new).astype(jnp.float32)
                return _mem_push(mem, e, y_hat, jnp.bool_(True))
            mem = jax.lax.cond(
                (k % cfg.opa_freq) == cfg.opa_freq - 1, do_opa, lambda m_: m_, mem
            )

        gn = jnp.linalg.norm(g_new)
        if cfg.guard:
            # scalar problem: the body only runs while live, so the sample
            # is unconditionally "active" for detection purposes
            gs, do_rs, code, gn = _guard_detect(
                gs, cfg, jnp.bool_(True), gn, jnp.linalg.norm(s), gn0)
            mem = jax.lax.cond(
                do_rs,
                lambda mm: LBFGSMemory(jnp.zeros_like(mm.s),
                                       jnp.zeros_like(mm.y),
                                       jnp.zeros_like(mm.rho),
                                       jnp.int32(0)),
                lambda mm: mm, mem)
            z_new = jnp.where(do_rs, z0.astype(jnp.float32), z_new)
            g_new = jnp.where(do_rs, g0.astype(jnp.float32), g_new)
            gn = jnp.where(do_rs, gn0, gn)
        trace = trace.at[k].set(gn)
        status_k = None if gs is None else jnp.where(do_rs, code, gs.status)
        tape = tape_record(tape, k, jnp.bool_(True), gn, jnp.linalg.norm(s),
                           jnp.minimum(mem.count, m), status=status_k)
        done = gn < cfg.tol
        out = (k + 1, z_new, g_new, mem, jnp.linalg.norm(s), done, trace,
               tape)
        return out + (gs,) if cfg.guard else out

    state0 = (jnp.int32(0), z0.astype(jnp.float32), g0.astype(jnp.float32),
              mem0, jnp.float32(cfg.opa_t0), gn0 < cfg.tol, trace0, tape0)
    if cfg.guard:
        state0 = state0 + (_guard_init(None),)
    state = jax.lax.while_loop(cond, body, state0)
    k, z, gz, mem, _, done, trace, tape = state[:8]
    gs = state[8] if cfg.guard else None
    final_gn = jnp.linalg.norm(gz)
    if cfg.guard:
        final_gn = jnp.where(gs.sick, jnp.inf, final_gn)
    return LBFGSResult(z, mem, final_gn, k, done, trace, tape,
                       _exit_status(done, gs))


def _lbfgs_gamma(mem: LBFGSMemory) -> Array:
    """Standard H0 scaling gamma = s'y / y'y of the newest pair."""
    m = mem.s.shape[0]
    has = mem.count > 0
    idx = (mem.count - 1) % m
    sy = jnp.dot(mem.s[idx], mem.y[idx])
    yy = jnp.dot(mem.y[idx], mem.y[idx])
    return jnp.where(has & (yy > 1e-12), jnp.maximum(sy, 1e-12) / jnp.maximum(yy, 1e-12), 1.0)
