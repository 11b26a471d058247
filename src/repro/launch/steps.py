"""Jit-able step builders shared by the trainer, server and dry-run.

Everything the dry-run lowers at production shapes is built here — the
SINGLE source of the ``TrainState`` shape, its shardings, and the train
step; ``runtime.trainer.Trainer`` jits exactly these builders, so the
launched training/serving steps and the dry-run/roofline artifacts are the
same functions by construction.

Persistent solve state: for DEQ models the :class:`TrainState` carries a
:class:`repro.implicit.SolveCarry` — the previous step's equilibrium and
quasi-Newton chain warm-start the next step's forward solve.  The carry is
donated with the rest of the state, sharded via the same layout as the live
solve (state batch-sharded, (U, V) memory pinned alongside), and rides
through checkpoint save/restore untouched.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, TrainConfig
from repro.core.lowrank import LowRank
from repro.core.solvers import SolveCarry, carry_state_only
from repro.models import lm
from repro.obs import metrics as obs_metrics
from repro.optim.optimizers import (
    OptState,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    make_schedule,
    sgdm_update,
)
from repro.parallel.sharding import (
    ShardCtx,
    named_sharding_tree,
    spec_tree,
    zero1_spec_tree,
)

Pytree = Any

# logical axes of the DEQ-LM solver state; the qN memory prepends "qn_mem"
# (mirrors models/lm._apply_deq and implicit.solve_sharding)
_CARRY_STATE_AXES = ("batch", "seq_res", "embed_act")


class TrainState(NamedTuple):
    step: jax.Array
    params: Pytree
    opt: OptState
    # persistent solve state (DEQ models; None otherwise) — the warm-start
    # carry threaded across train steps
    carry: SolveCarry | None = None
    # consecutive non-finite-update skips (None when skip_nonfinite is off);
    # the trainer reads it at the per-interval metrics fetch and rolls back
    # to the last checkpoint once it passes tcfg.skip_budget
    skips: jax.Array | None = None


def train_carry_enabled(cfg: ModelConfig, tcfg: TrainConfig) -> bool:
    """Whether the train step threads a persistent solve carry.

    Requires a DEQ model, ``tcfg.deq_carry != "off"``, no gradient
    accumulation (microbatches slice the batch axis, so one carry cannot
    follow all slices), and a family whose solver-state sequence length
    equals ``tcfg.seq_len`` (vlm prepends image tokens of data-dependent
    length).  ``tcfg.deq_carry`` further selects "state" (iterate-only
    reuse, the fresh-batch default) vs "full" (iterate + chain, for
    repeated-batch regimes).
    """
    if tcfg.deq_carry not in ("state", "full", "off"):
        raise ValueError(
            f"deq_carry={tcfg.deq_carry!r}; expected state | full | off")
    return bool(cfg.deq.enabled) and tcfg.deq_carry != "off" \
        and tcfg.grad_accum == 1 and cfg.family != "vlm"


# ---------------------------------------------------------------------------
# shardings / structs
# ---------------------------------------------------------------------------


def param_shardings(cfg: ModelConfig, ctx: ShardCtx):
    decl = lm.model_decl(cfg)
    if ctx.mesh is None:
        return jax.tree_util.tree_map(
            lambda d: None, decl, is_leaf=lambda x: hasattr(x, "axes"))
    return named_sharding_tree(spec_tree(decl, ctx.rules), ctx.mesh)


def param_structs(cfg: ModelConfig, ctx: ShardCtx) -> Pytree:
    """ShapeDtypeStruct tree (with shardings) for the parameter pytree."""
    decl = lm.model_decl(cfg)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    shard = param_shardings(cfg, ctx)
    return jax.tree_util.tree_map(
        lambda d, s: jax.ShapeDtypeStruct(d.shape, dt, sharding=s),
        decl, shard, is_leaf=lambda x: hasattr(x, "axes") and hasattr(x, "init"))


def carry_shardings(cfg: ModelConfig, ctx: ShardCtx) -> SolveCarry | None:
    """Sharding tree for the train-state solve carry: the iterate rides the
    activation layout, the (U, V) ring memory is pinned batch-sharded next
    to it (same rules the live solve uses via ``SolveSharding``)."""
    if ctx.mesh is None:
        return None
    ns = lambda axes: NamedSharding(ctx.mesh, ctx.rules.spec(axes))
    vec = ns(("batch",))
    mem = ns(("qn_mem",) + _CARRY_STATE_AXES)
    return SolveCarry(
        z=ns(_CARRY_STATE_AXES),
        lowrank=LowRank(alpha=NamedSharding(ctx.mesh, P()), u=mem, v=mem,
                        count=vec),
        warm=vec,
        age=vec,
    )


def state_shardings(cfg: ModelConfig, tcfg: TrainConfig, ctx: ShardCtx):
    """TrainState sharding tree: params TP-sharded/DP-replicated; moments
    additionally sharded over "data" when ZeRO-1 is on; the solve carry (if
    enabled) batch-sharded like the live solve."""
    if ctx.mesh is None:
        return None
    decl = lm.model_decl(cfg)
    pshard = named_sharding_tree(spec_tree(decl, ctx.rules), ctx.mesh)
    zsize = ctx.mesh.shape.get("data", 0) if ctx.mesh is not None else 0
    ospec = zero1_spec_tree(decl, ctx.rules, zero_size=zsize) if tcfg.zero1 \
        else spec_tree(decl, ctx.rules)
    oshard = named_sharding_tree(ospec, ctx.mesh)
    scalar = NamedSharding(ctx.mesh, P())
    return TrainState(
        step=scalar,
        params=pshard,
        opt=OptState(step=scalar, mu=oshard,
                     nu=jax.tree_util.tree_map(lambda s: s, oshard)),
        carry=(carry_shardings(cfg, ctx)
               if train_carry_enabled(cfg, tcfg) else None),
        skips=(scalar if tcfg.skip_nonfinite else None),
    )


def train_state_structs(cfg: ModelConfig, tcfg: TrainConfig, ctx: ShardCtx) -> TrainState:
    """ShapeDtypeStruct TrainState (no allocation) for lowering."""
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    decl = lm.model_decl(cfg)
    shard = state_shardings(cfg, tcfg, ctx)

    def sds(d, s, dtype):
        return jax.ShapeDtypeStruct(d.shape, dtype, sharding=s)

    is_decl = lambda x: hasattr(x, "axes") and hasattr(x, "init")
    if shard is None:
        none = jax.tree_util.tree_map(lambda d: None, decl, is_leaf=is_decl)
        shard = TrainState(None, none,
                           OptState(None, none, jax.tree_util.tree_map(lambda s: s, none)))
    params = jax.tree_util.tree_map(lambda d, s: sds(d, s, dt), decl, shard.params,
                                    is_leaf=is_decl)
    mu = jax.tree_util.tree_map(lambda d, s: sds(d, s, jnp.float32), decl, shard.opt.mu,
                                is_leaf=is_decl)
    nu = jax.tree_util.tree_map(lambda d, s: sds(d, s, jnp.float32), decl, shard.opt.nu,
                                is_leaf=is_decl)
    scalar = lambda dtype: jax.ShapeDtypeStruct(
        (), dtype, sharding=(shard.step if shard.step is not None else None))
    carry = None
    if train_carry_enabled(cfg, tcfg):
        csh = shard.carry  # SolveCarry of NamedSharding, or None off-mesh
        b, s, d, m = (tcfg.global_batch, tcfg.seq_len, cfg.d_model,
                      cfg.deq.memory)
        mem_sh = csh.lowrank.u if csh is not None else None
        vec = lambda dtype: jax.ShapeDtypeStruct(
            (b,), dtype, sharding=(csh.warm if csh is not None else None))
        carry = SolveCarry(
            z=jax.ShapeDtypeStruct((b, s, d), dt,
                                   sharding=(csh.z if csh is not None else None)),
            lowrank=LowRank(
                alpha=jax.ShapeDtypeStruct(
                    (), jnp.float32,
                    sharding=(csh.lowrank.alpha if csh is not None else None)),
                u=jax.ShapeDtypeStruct((m, b, s, d), dt, sharding=mem_sh),
                v=jax.ShapeDtypeStruct((m, b, s, d), dt, sharding=mem_sh),
                count=vec(jnp.int32),
            ),
            warm=vec(jnp.bool_),
            age=vec(jnp.int32),
        )
    return TrainState(scalar(jnp.int32), params,
                      OptState(scalar(jnp.int32), mu, nu), carry,
                      scalar(jnp.int32) if tcfg.skip_nonfinite else None)


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------


def build_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    ctx: ShardCtx,
    *,
    loss_fn: Callable | None = None,
) -> Callable:
    """(state, batch) -> (state, metrics): grads (+accumulation) -> clip ->
    AdamW/SGDM with the tcfg schedule. The canonical production train step.

    When the state carries a :class:`SolveCarry` (DEQ models, see
    ``train_carry_enabled``) the default loss threads it into the forward
    solve and the updated carry rides back into the new state — consecutive
    steps warm-start from the previous equilibrium.  A custom ``loss_fn``
    keeps the legacy ``(params, batch)`` signature and leaves the carry
    untouched.
    """
    if loss_fn is None:
        def loss_with_carry(p, b, c):
            return lm.loss_fn(p, b, cfg, ctx, z_loss=tcfg.z_loss, carry=c)
    else:
        def loss_with_carry(p, b, c):  # legacy signature: carry not threaded
            return loss_fn(p, b)
    sched = make_schedule(tcfg)

    def grads_of(params, batch, carry):
        return jax.value_and_grad(loss_with_carry, has_aux=True)(
            params, batch, carry)

    def train_step(state: TrainState, batch: dict):
        params = state.params
        new_carry = state.carry
        if tcfg.grad_accum > 1:
            k = tcfg.grad_accum

            def micro(b, i):
                return jax.tree_util.tree_map(
                    lambda a: a.reshape((k, a.shape[0] // k) + a.shape[1:])[i], b
                )

            def acc_fn(carry, i):
                gacc, laux = carry
                (l, _aux), g = grads_of(params, micro(batch, i), None)
                gacc = jax.tree_util.tree_map(jnp.add, gacc, g)
                return (gacc, laux + l), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (gsum, lsum), _ = jax.lax.scan(
                acc_fn, (zeros, jnp.float32(0.0)), jnp.arange(k)
            )
            grads = jax.tree_util.tree_map(lambda g: g / k, gsum)
            loss, aux = lsum / k, {}
        else:
            carry_in = state.carry
            if carry_in is not None and tcfg.deq_carry == "state":
                # fresh-batch regime: reuse the iterate, rebuild the chain
                carry_in = carry_state_only(carry_in)
            (loss, aux), grads = grads_of(params, batch, carry_in)
            if isinstance(aux, dict):
                new_carry = aux.pop("solve_carry", new_carry)

        grads, gnorm = clip_by_global_norm(grads, tcfg.clip_norm)
        lr = sched(state.step)
        if tcfg.optimizer == "sgdm":
            new_params, opt = sgdm_update(
                grads, state.opt, params, lr, weight_decay=tcfg.weight_decay)
        else:
            new_params, opt = adamw_update(
                grads, state.opt, params, lr, weight_decay=tcfg.weight_decay)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        if isinstance(aux, dict):
            metrics.update({k: v for k, v in aux.items() if jnp.ndim(v) == 0})
        new_state = TrainState(state.step + 1, new_params, opt, new_carry,
                               state.skips)
        if tcfg.skip_nonfinite:
            # graceful degradation: a non-finite loss or gradient norm
            # rejects the WHOLE update (params / optimizer state / solve
            # carry keep their pre-step values) via a traced select — no
            # host sync on the hot path.  The consecutive-skip count rides
            # the state; the trainer reads it at its once-per-interval
            # metrics fetch and rolls back to the last checkpoint once it
            # passes tcfg.skip_budget.
            ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            keep = lambda new, old: jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n, o), new, old)
            prev_skips = state.skips if state.skips is not None \
                else jnp.zeros((), jnp.int32)
            new_state = TrainState(
                state.step + 1,
                keep(new_params, params),
                keep(opt, state.opt),
                keep(new_carry, state.carry) if new_carry is not None else None,
                jnp.where(ok, 0, prev_skips + 1).astype(jnp.int32),
            )
            metrics["update_skipped"] = (~ok).astype(jnp.float32)
            metrics["consec_skips"] = new_state.skips.astype(jnp.float32)
            obs_metrics.emit_scalar("train_update_skips_total",
                                    (~ok).astype(jnp.float32), kind="counter")
        return new_state, metrics

    return train_step


def init_train_state(cfg: ModelConfig, tcfg: TrainConfig, ctx: ShardCtx,
                     seed: int | None = None) -> TrainState:
    seed = tcfg.seed if seed is None else seed
    with_carry = train_carry_enabled(cfg, tcfg)

    def init(key):
        params = lm.init_params(cfg, key)
        carry = (lm.deq_solve_carry(cfg, tcfg.global_batch, tcfg.seq_len)
                 if with_carry else None)
        skips = jnp.zeros((), jnp.int32) if tcfg.skip_nonfinite else None
        return TrainState(jnp.zeros((), jnp.int32), params,
                          adamw_init(params), carry, skips)

    key = jax.random.PRNGKey(seed)
    shard = state_shardings(cfg, tcfg, ctx)
    if shard is not None:
        return jax.jit(init, out_shardings=shard)(key)
    return jax.jit(init)(key)


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------


def build_prefill(cfg: ModelConfig, ctx: ShardCtx, max_len: int) -> Callable:
    def prefill_step(params, batch):
        return lm.prefill(params, batch, cfg, ctx, max_len)
    return prefill_step


def build_decode_step(cfg: ModelConfig, ctx: ShardCtx) -> Callable:
    def decode_step(params, caches, tokens, cache_index):
        return lm.decode_step(params, caches, tokens, cache_index, cfg, ctx)
    return decode_step
