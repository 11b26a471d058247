"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the Trainer (checkpoint/restart, preemption handling, straggler
watchdog) on any assigned architecture — the published config, a reduced
``--smoke`` config (runs on a CPU), or the DEQ/SHINE form of either
(``--deq``).  ``--mesh DxM`` trains on a (data=D, model=M) mesh over the
devices present (a host's chips, or forced CPU devices in a rehearsal).
``main(argv)`` also serves in-process callers: it returns the final state
and the logged metrics of every logged step.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs.base import TrainConfig
from repro.configs.registry import ARCHS, get_config, smoke_config
from repro.configs.shapes import SHAPES, make_ctx
from repro.data.pipeline import make_lm_batch_iterator
from repro.implicit import ESTIMATORS, SOLVERS
from repro.launch.compile_cache import use_persistent_cache
from repro.launch.mesh import make_device_mesh, parse_mesh
from repro.models import lm
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.parallel.sharding import ShardCtx
from repro.runtime.trainer import Trainer


def main(argv: list[str] | None = None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="minicpm-2b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--deq", action="store_true",
                    help="DEQ/SHINE form: weight-tied fixed-point backbone")
    ap.add_argument("--backward", default=None, choices=ESTIMATORS.names(),
                    help="DEQ backward cotangent estimator")
    ap.add_argument("--solver", default=None, choices=SOLVERS.names(),
                    help="DEQ forward fixed-point solver")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="train on a (data=D, model=M) mesh over the "
                         "devices present")
    ap.add_argument("--log-every", type=int, default=10,
                    help="fetch and print metrics every this many steps")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default="",
                    help="write a metrics-registry JSON snapshot here after "
                         "the run (enables the jit metrics bridge)")
    ap.add_argument("--metrics-prom-out", default="",
                    help="write (and periodically refresh, every 10s) a "
                         "Prometheus text-format exposition of the metrics "
                         "registry here (enables the jit metrics bridge)")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace JSON of the run here "
                         "(enables span tracing)")
    ap.add_argument("--checkpoint-lean", action="store_true",
                    help="omit the u/v quasi-Newton carry ring from "
                         "checkpoints (restore zero-fills it)")
    ap.add_argument("--qn-dtype", default=None,
                    choices=("bfloat16", "float32"),
                    help="storage dtype of the quasi-Newton U/V ring "
                         "(default bf16; coefficients accumulate f32)")
    ap.add_argument("--no-guard", action="store_true",
                    help="compile the numerical-fault guards out of the "
                         "solvers (the pre-guard program; see API.md "
                         "'Failure semantics')")
    ap.add_argument("--skip-budget", type=int, default=None,
                    help="consecutive non-finite-update skips tolerated "
                         "before rolling back to the last checkpoint")
    args = ap.parse_args(argv)
    use_persistent_cache()

    # observability switches are trace-time gates: enable BEFORE the first
    # jit trace so the compiled programs carry the instrumentation
    if args.metrics_out or args.metrics_prom_out:
        obs_metrics.set_enabled(True)
    if args.trace_out:
        obs_tracing.set_enabled(True)
    flusher = (obs_metrics.PromFlusher(args.metrics_prom_out).start()
               if args.metrics_prom_out else None)

    cfg = smoke_config(args.arch, deq=args.deq) if args.smoke \
        else get_config(args.arch, deq=args.deq)
    if args.backward or args.solver or args.qn_dtype or args.no_guard:
        deq = cfg.deq
        if args.backward:
            deq = dataclasses.replace(deq, backward=args.backward)
        if args.solver:
            deq = dataclasses.replace(deq, solver=args.solver)
        if args.qn_dtype:
            deq = dataclasses.replace(deq, qn_dtype=args.qn_dtype)
        if args.no_guard:
            deq = dataclasses.replace(deq, guard=False)
        cfg = dataclasses.replace(cfg, deq=deq)

    if args.mesh:
        mesh = make_device_mesh(parse_mesh(args.mesh), ("data", "model"))
        ctx = make_ctx(cfg, mesh, SHAPES["train_4k"])
    else:
        ctx = ShardCtx.for_mesh(None)

    tcfg = TrainConfig(
        steps=args.steps, global_batch=args.batch, seq_len=args.seq,
        lr=args.lr, grad_accum=args.grad_accum, seed=args.seed,
        schedule=cfg.schedule,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        checkpoint_lean=args.checkpoint_lean,
        qn_dtype=args.qn_dtype or cfg.deq.qn_dtype,
        zero1=(ctx.mesh is not None),
        **({"skip_budget": args.skip_budget}
           if args.skip_budget is not None else {}),
    )

    print(f"arch={cfg.name} params={lm.param_count(cfg)/1e6:.1f}M "
          f"deq={cfg.deq.enabled} devices={jax.device_count()}")
    trainer = Trainer(cfg, tcfg, ctx)
    batches = make_lm_batch_iterator(cfg, ctx, args.batch, args.seq,
                                     seed=args.seed)
    history: list[tuple[int, dict]] = []
    state = trainer.run(batches, steps=args.steps, log_every=args.log_every,
                        on_metrics=lambda i, m: history.append((i, m)))
    batches.close()
    print(f"finished at step {int(state.step)}")

    if args.metrics_out:
        obs_metrics.default_registry().write_json(args.metrics_out)
        print(f"metrics snapshot -> {args.metrics_out}")
    if flusher is not None:
        flusher.stop()
        print(f"prometheus exposition -> {args.metrics_prom_out}")
    if args.trace_out:
        obs_tracing.write(args.trace_out)
        print(f"chrome trace -> {args.trace_out}")
    return state, history


if __name__ == "__main__":
    main()
