"""Distributed training loop: pjit'd train_step with ZeRO-1 sharded optimizer
state, microbatched gradient accumulation, checkpoint/restart, preemption
handling and straggler reporting.

The step function, the ``TrainState`` shape (including the persistent
solve carry for DEQ models), and all shardings come from
``repro.launch.steps`` — the single source both this trainer and the
dry-run lower, so "the same functions by construction" is literally true.
This module owns only the RUNTIME concerns: jit/donation, the step loop,
checkpointing, preemption, and straggler watching.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ModelConfig, TrainConfig
from repro.implicit import ESTIMATORS, SOLVERS
from repro.launch import steps
from repro.launch.steps import TrainState  # re-export (legacy import path)
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.parallel.sharding import ShardCtx
from repro.runtime.ft import PreemptionGuard, StragglerWatchdog

__all__ = ["Trainer", "TrainState"]


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        tcfg: TrainConfig,
        ctx: ShardCtx,
        *,
        loss_fn: Callable | None = None,
    ):
        self.cfg, self.tcfg, self.ctx = cfg, tcfg, ctx
        if cfg.deq.enabled:
            # fail fast (with the registered options listed) before jit
            SOLVERS.get(cfg.deq.solver)
            ESTIMATORS.get(cfg.deq.backward)
        if ctx.mesh is not None:
            # fail fast before jit: the batched fixed-point solve (and plain
            # DP) shards the batch over the DP axes; an indivisible batch
            # would error deep inside GSPMD with an opaque message
            dp = ctx.axis_size("batch")
            if dp > 1 and tcfg.global_batch % dp != 0:
                raise ValueError(
                    f"global_batch={tcfg.global_batch} not divisible by the "
                    f"data-parallel mesh extent {dp} (axes behind 'batch')"
                )
        self.loss_fn = loss_fn
        if loss_fn is not None:
            # a custom loss keeps the legacy (params, batch) signature and
            # cannot thread the solve carry — don't allocate/checkpoint one
            # that could never be updated
            tcfg = dataclasses.replace(tcfg, deq_carry="off")
        self._tcfg_eff = tcfg
        self.state_sharding = steps.state_shardings(cfg, tcfg, ctx)
        step_fn = steps.build_train_step(cfg, tcfg, ctx, loss_fn=loss_fn)
        if self.state_sharding is not None:
            self._train_step = jax.jit(
                step_fn,
                in_shardings=(self.state_sharding, None),
                out_shardings=(self.state_sharding, None),
                donate_argnums=(0,),
            )
        else:
            self._train_step = jax.jit(step_fn, donate_argnums=(0,))
        self.watchdog = StragglerWatchdog(n_hosts=max(jax.process_count(), 1))
        self.ckpt = (
            CheckpointManager(
                tcfg.checkpoint_dir, keep=tcfg.keep_checkpoints,
                # lean mode drops the (m, B, S, d) u/v carry ring — restore
                # zero-fills it back (fill_missing_prefixes below), which is
                # the identity inverse
                omit_prefixes=((".carry.lowrank.u", ".carry.lowrank.v")
                               if tcfg.checkpoint_lean else ()),
            )
            if tcfg.checkpoint_dir else None
        )

    # ------------------------------------------------------------------

    def init_state(self, seed: int | None = None) -> TrainState:
        return steps.init_train_state(self.cfg, self._tcfg_eff, self.ctx,
                                      seed=seed)

    def restore_or_init(self) -> TrainState:
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            template = jax.eval_shape(lambda: self.init_state())
            # pre-carry checkpoints lack .carry leaves; zero-fill == the
            # cold carry, so old runs resume with a cold warm-start state
            # .skips joins .carry as forward-compatible state: pre-guard
            # checkpoints lack it and zero == "no consecutive skips"
            _, state, _ = self.ckpt.restore(
                template, shardings=self.state_sharding,
                fill_missing_prefixes=(".carry", ".skips"),
            )
            return state
        return self.init_state()

    def _rollback(self, at_step: int) -> TrainState:
        """Past the consecutive-skip budget every recent update was rejected
        (persistently non-finite loss/grads) — the run is wedged.  Restore
        the last checkpoint (or re-init when none exists), loudly, and zero
        the skip counter so the resumed run gets a full fresh budget."""
        obs_metrics.default_registry().counter("train_rollbacks_total").inc()
        budget = self.tcfg.skip_budget
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            fresh = self.restore_or_init()
            print(f"step {at_step}: {budget}+ consecutive non-finite updates "
                  f"— rolled back to checkpoint step {int(fresh.step)}")
        else:
            fresh = self.init_state()
            print(f"step {at_step}: {budget}+ consecutive non-finite updates "
                  f"and no checkpoint — re-initialized from scratch")
        if fresh.skips is not None:
            fresh = fresh._replace(skips=jnp.zeros((), jnp.int32))
        return fresh

    def run(
        self,
        batches: Iterator[dict],
        *,
        steps: int | None = None,
        log_every: int = 10,
        on_metrics: Callable[[int, dict], None] | None = None,
    ) -> TrainState:
        state = self.restore_or_init()
        start = int(state.step)
        steps = steps if steps is not None else self.tcfg.steps
        host = max(jax.process_index(), 0)

        t_sync = time.perf_counter()
        n_since = 0
        with PreemptionGuard() as guard:
            for i in range(start, steps):
                with obs_tracing.span("data", step=i + 1):
                    batch = next(batches)
                with obs_tracing.span("train_step", step=i + 1):
                    state, metrics = self._train_step(state, batch)
                    if obs_tracing.enabled():
                        # tracing is an opted-in diagnostic mode: flush the
                        # step's phase_done callbacks so the in-jit phases
                        # nest inside this host span (costs one sync/step,
                        # paid ONLY while tracing)
                        jax.block_until_ready(metrics)
                n_since += 1
                if (i + 1) % log_every == 0 or i + 1 == steps:
                    # the interval's ONE host sync: wait for the step's
                    # outputs, then a single device_get of the metrics tree
                    # — the steps in between dispatched back-to-back with
                    # no blocking fetch on the hot path
                    jax.block_until_ready((state, metrics))
                    now = time.perf_counter()
                    metrics = {k: float(v)
                               for k, v in jax.device_get(metrics).items()}
                    # this sync point drains every step since the last one,
                    # so the honest per-step time is the interval average
                    dt = (now - t_sync) / max(n_since, 1)
                    metrics["step_time_s"] = dt
                    t_sync, n_since = now, 0
                    self.watchdog.record(host, dt)
                    self.watchdog.publish_metrics()
                    if (self.tcfg.skip_nonfinite and
                            metrics.get("consec_skips", 0.0)
                            >= self.tcfg.skip_budget):
                        state = self._rollback(i + 1)
                    if on_metrics:
                        on_metrics(i + 1, metrics)
                    print(
                        f"step {i+1:5d} loss={metrics['loss']:.4f} "
                        f"gnorm={metrics['grad_norm']:.3f} "
                        f"lr={metrics['lr']:.2e} {dt*1e3:.0f}ms"
                    )
                if self.ckpt and self.tcfg.checkpoint_every and (
                    (i + 1) % self.tcfg.checkpoint_every == 0
                ):
                    with obs_tracing.span("checkpoint", step=i + 1):
                        self.ckpt.save(i + 1, state)
                    # keep checkpoint wall time out of the per-step average
                    t_sync, n_since = time.perf_counter(), 0
                if guard.should_exit:
                    if self.ckpt:
                        self.ckpt.save(i + 1, state)
                        self.ckpt.wait()
                    print(f"preempted at step {i+1}; state saved; exiting 0")
                    break
        if self.ckpt:
            self.ckpt.wait()
        return state
