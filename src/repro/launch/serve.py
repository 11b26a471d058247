"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Spins up the fixed-slot continuous-batching loop (runtime/serving.py) at the
architecture's published width (``--smoke``: the reduced same-family config
a CPU runs) and drains a synthetic request stream.  ``main(argv)`` returns
the drained requests to in-process callers.

``--mesh DxM`` runs the loop sharded (decode rules: batch over "data",
sequence-sharded KV over "model") over the devices present.  On a CPU set it
together with ``--force-devices N`` (which must win the race with jax
backend initialization, so it is applied before any device query).
"""

from __future__ import annotations

import argparse
import os
import time


def main(argv: list[str] | None = None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--deq", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="run sharded on a (data=D, model=M) mesh")
    ap.add_argument("--force-devices", type=int, default=0,
                    help="forced host CPU device count (CPU multi-device "
                         "rehearsal; must be >= D*M)")
    ap.add_argument("--carry-max-age", type=int, default=None,
                    help="DEQ carry staleness bound: evict per-slot solve "
                         "state older than this many solves")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="DEQ only: cross-request prefix carry cache — seed "
                         "each prefill solve from the longest cached prompt "
                         "prefix instead of cold-starting")
    ap.add_argument("--prefix-cache-slots", type=int, default=32,
                    help="prefix-cache capacity (entries); 0 = always-miss "
                         "cold accounting arm")
    ap.add_argument("--prefix-block", type=int, default=4,
                    help="prefix-cache publication granularity: entries are "
                         "stored at multiples of this many tokens (plus the "
                         "full prompt length)")
    ap.add_argument("--prefix-max-age", type=int, default=None,
                    help="prefix-cache staleness bound: evict entries not "
                         "republished within this many cache operations")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="synthetic prompt stream: all prompts share this "
                         "many leading tokens (exercises the prefix cache); "
                         "0 = fully random prompts")
    ap.add_argument("--qn-dtype", default=None,
                    choices=("bfloat16", "float32"),
                    help="storage dtype of the quasi-Newton U/V ring "
                         "(default bf16; coefficients accumulate f32)")
    ap.add_argument("--no-guard", action="store_true",
                    help="compile the numerical-fault guards out of the "
                         "DEQ solves (disables per-request fault "
                         "detection / cold retry; see API.md 'Failure "
                         "semantics')")
    ap.add_argument("--pipeline", default="async",
                    choices=("async", "sync"),
                    help="serving pipeline: 'async' (default) overlaps "
                         "waves through the completion queue with "
                         "device-resident caches and zero blocking host "
                         "syncs in steady state; 'sync' is the blocking "
                         "wave-at-a-time loop")
    ap.add_argument("--async-depth", type=int, default=2,
                    help="async pipeline: max in-flight waves before "
                         "admission/dispatch waits for the oldest to land")
    ap.add_argument("--reorder", action="store_true",
                    help="prefix-aware admission: stable-sort queued "
                         "requests by matched prefix key so prompts "
                         "sharing a cached prefix land in one wave")
    ap.add_argument("--reorder-age-bound", type=int, default=8,
                    help="fairness bound for --reorder: a request passed "
                         "over this many admission rounds is admitted "
                         "FIFO ahead of any grouping")
    ap.add_argument("--metrics-out", default="",
                    help="write a metrics-registry JSON snapshot here after "
                         "the drain (enables the jit metrics bridge)")
    ap.add_argument("--metrics-prom-out", default="",
                    help="write (and periodically refresh, every 10s) a "
                         "Prometheus text-format exposition of the metrics "
                         "registry here (enables the jit metrics bridge)")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace JSON of the drain here "
                         "(enables span tracing)")
    args = ap.parse_args(argv)

    if args.force_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.force_devices}").strip()

    import jax
    import numpy as np

    from repro.configs.registry import ARCHS, get_config, smoke_config
    from repro.launch.compile_cache import use_persistent_cache
    from repro.launch.mesh import make_device_mesh, parse_mesh
    from repro.models import lm
    from repro.obs import metrics as obs_metrics
    from repro.obs import tracing as obs_tracing
    from repro.parallel.sharding import DECODE_RULES, ShardCtx
    from repro.runtime.serving import Request, ServeLoop

    use_persistent_cache()
    # trace-time gates: enable before the loop's first jit trace
    if args.metrics_out or args.metrics_prom_out:
        obs_metrics.set_enabled(True)
    if args.trace_out:
        obs_tracing.set_enabled(True)
    flusher = (obs_metrics.PromFlusher(args.metrics_prom_out).start()
               if args.metrics_prom_out else None)

    if args.arch not in ARCHS:
        raise SystemExit(f"unknown arch {args.arch!r}; have {sorted(ARCHS)}")
    cfg = smoke_config(args.arch, deq=args.deq) if args.smoke \
        else get_config(args.arch, deq=args.deq)
    if args.qn_dtype or args.no_guard:
        import dataclasses
        deq = cfg.deq
        if args.qn_dtype:
            deq = dataclasses.replace(deq, qn_dtype=args.qn_dtype)
        if args.no_guard:
            deq = dataclasses.replace(deq, guard=False)
        cfg = dataclasses.replace(cfg, deq=deq)
    if cfg.family == "audio":
        raise SystemExit("encoder-only arch: no autoregressive serving")
    if args.mesh:
        mesh = make_device_mesh(parse_mesh(args.mesh), ("data", "model"))
        ctx = ShardCtx.for_mesh(mesh, DECODE_RULES)
    else:
        ctx = ShardCtx.for_mesh(None)
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))

    loop = ServeLoop(params, cfg, ctx, slots=args.slots, max_len=args.max_len,
                     carry_max_age=args.carry_max_age,
                     prefix_cache=args.prefix_cache,
                     prefix_cache_slots=args.prefix_cache_slots,
                     prefix_block=args.prefix_block,
                     prefix_max_age=args.prefix_max_age,
                     pipeline=args.pipeline, async_depth=args.async_depth,
                     reorder=args.reorder,
                     reorder_age_bound=args.reorder_age_bound)
    rng = np.random.default_rng(args.seed)
    if args.shared_prefix:
        # overlapping-prefix stream: one shared base + fixed-length random
        # tails, so waves coalesce at one prompt length and later requests
        # hit the prefixes published by earlier ones
        base = rng.integers(2, cfg.vocab_size, size=args.shared_prefix).tolist()
        prompts = [base + rng.integers(2, cfg.vocab_size, size=4).tolist()
                   for _ in range(args.requests)]
    else:
        prompts = [
            rng.integers(2, cfg.vocab_size,
                         size=int(rng.integers(4, 12))).tolist()
            for _ in range(args.requests)
        ]
    reqs = [
        Request(uid=i, prompt=prompts[i], max_new_tokens=args.max_new_tokens)
        for i in range(args.requests)
    ]
    t0 = time.perf_counter()
    loop.drain(reqs)
    dt = time.perf_counter() - t0
    tokens = sum(len(r.out) for r in reqs)
    print(f"arch={cfg.name} served {len(reqs)} requests, {tokens} tokens "
          f"in {dt:.2f}s ({tokens/dt:.1f} tok/s)")
    for r in reqs[:4]:
        print(f"  req {r.uid}: prompt[{len(r.prompt)}] -> {r.out}")
    cache = loop.prefix if loop.prefix is not None else loop.prefix_store
    if cache is not None:
        st = cache.stats()
        print(f"prefix cache: {st['hits']}/{st['lookups']} lookups hit, "
              f"{st['entries']} entries ({st['tokens']} tokens) held, "
              f"evictions={st['evictions']}; prefill iters "
              f"{loop.prefill_iters:.0f} total, {loop.saved_iters:.0f} saved")
    if args.pipeline == "async":
        syncs = sum(
            m["value"]
            for m in obs_metrics.default_registry().snapshot()["metrics"]
            if m["name"] == "host_syncs_total")
        print(f"async pipeline: {syncs:.0f} blocking host syncs recorded")

    if args.metrics_out:
        obs_metrics.default_registry().write_json(args.metrics_out)
        print(f"metrics snapshot -> {args.metrics_out}")
    if flusher is not None:
        flusher.stop()
        print(f"prometheus exposition -> {args.metrics_prom_out}")
    if args.trace_out:
        obs_tracing.write(args.trace_out)
        print(f"chrome trace -> {args.trace_out}")
    return reqs


if __name__ == "__main__":
    main()
