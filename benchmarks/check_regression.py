"""Kernel perf-regression gate: fresh bench vs the committed baseline.

``python -m benchmarks.check_regression`` re-measures the interpret-safe
kernel sweep (``benchmarks.bench_kernels.run``) on this host, persists the
fresh record next to the baseline (``BENCH_kernels.fresh.json`` — the
committed ``BENCH_kernels.json`` is never overwritten by the gate), and
fails (exit 1) when, for any (op, shape, impl) row present in the baseline:

  * the row disappeared from the fresh record (coverage shrank), or
  * ``bytes_moved`` GREW on a fused op (``qn_apply_multi*`` /
    ``lowrank_append``) — the analytic streaming model is
    hardware-independent, so any growth is a real fusion regression, or
  * ``n_iters`` GREW on a warm-start row (``warm_start[*]``) beyond a
    +1-iteration slack — the solver's iteration count on fixed seeds is
    deterministic like the byte model, so growth means the carried solve
    state stopped paying for itself, or
  * a ``serve_pipeline[*]`` row's fresh ``throughput_ratio`` fell below
    the 1.3x acceptance floor or its ``host_syncs`` count is nonzero —
    both arms of that bench run on the SAME host, so these gate
    absolutely with no baseline-host calibration, or
  * ``wall_ms`` exceeds ``ratio * host_scale * baseline + slack``.  Wall
    time IS hardware-dependent (the baseline is committed from one machine,
    CI re-measures on another), so the gate self-calibrates: with >= 3
    comparable rows, the MEDIAN fresh/baseline wall ratio is taken as the
    host-speed factor (clamped to [1, 4] — only slowdowns are corrected,
    and never more than 4x) and divided out before gating.  A uniformly
    slower runner therefore stays green, while ONE op blowing up relative
    to the fleet still trips the 1.3x ratio.  The absolute slack (default
    0.25 ms) keeps sub-millisecond rows from flaking on jitter — these are
    CPU oracle timings of ops whose real target is the TPU kernel, so the
    gate is a trajectory tripwire, not a microbenchmark.

``--fresh PATH`` compares a pre-measured record instead of re-running;
``--update-baseline`` rewrites the committed baseline from the fresh
measurement (use after an intentional perf change, and commit the diff).

The live-measurement mode (no ``--fresh``) additionally runs the
**observability-overhead gate**: the same jitted implicit solve+grad is
compiled with the obs bridge off and on (fresh jit closures each mode —
the gates are trace-time), timed in interleaved off/on pairs, and the
cleanest pairwise delta must keep the instrumented wall within
``--obs-ratio`` (default 1.05) of the uninstrumented one plus a small
absolute slack.  Real instrumentation cost is present in EVERY call so
the min pair still sees it, while a host contention burst would have to
contaminate every pair to fake a failure.  This keeps "telemetry is
~free" an enforced invariant, not a hope.  ``--skip-obs-overhead``
disables it; ``--obs-overhead`` runs ONLY it.

Live mode also runs the **guard-overhead gate** with the identical
methodology: the same probe compiled with the numerical-fault guards off
(``ForwardConfig.guard=False`` — the pre-guard program) and on, gated at
``--guard-ratio`` (default 1.05, the ISSUE's <= 5% wall budget) plus
slack.  ``--skip-guard-overhead`` disables it; ``--guard-overhead`` runs
ONLY it.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
from pathlib import Path

BASELINE = Path("results/benchmarks/BENCH_kernels.json")
FRESH = Path("results/benchmarks/BENCH_kernels.fresh.json")
FUSED_OPS = ("qn_apply_multi", "lowrank_append", "broyden_step")
# iteration counts are deterministic on fixed seeds, but the last iteration
# can flip on platform reduction-order wobble — allow one
ITER_SLACK = 1

# the machine-readable record keeps the same fields benchmarks/run.py writes
KEEP = ("op", "shape", "impl", "wall_ms", "bytes_moved", "unfused_bytes",
        "uv_traffic_ratio", "n_iters", "cold_iters", "iters_ratio",
        "sync_wall_ms", "tok_s", "sync_tok_s", "throughput_ratio",
        "host_syncs", "max_abs_err")

# serving-pipeline acceptance floor (ISSUE 9): async-vs-sync same-host
# throughput ratio — hardware-independent of the baseline, gated directly
MIN_TPUT_RATIO = 1.3


def _key(row: dict) -> tuple:
    return (row["op"], row["shape"], row["impl"])


def measure() -> list[dict]:
    from benchmarks import bench_kernels

    rows = bench_kernels.run()
    return [{k: r[k] for k in KEEP if k in r} for r in rows]


def _host_scale(base: list[dict], fresh_by: dict) -> float:
    """Median fresh/baseline wall ratio = the host-speed factor (see module
    docstring).  1.0 when fewer than 3 comparable rows exist — a single-row
    record must not calibrate away its own regression."""
    ratios = []
    for b in base:
        f = fresh_by.get(_key(b))
        bw = b.get("wall_ms")
        fw = f.get("wall_ms") if f else None
        if bw and fw:
            ratios.append(fw / bw)
    if len(ratios) < 3:
        return 1.0
    ratios.sort()
    mid = len(ratios) // 2
    med = ratios[mid] if len(ratios) % 2 else (ratios[mid - 1] + ratios[mid]) / 2
    return min(max(med, 1.0), 4.0)


def compare(base: list[dict], fresh: list[dict], *, wall_ratio: float,
            wall_slack_ms: float) -> int:
    fresh_by = {_key(r): r for r in fresh}
    scale = _host_scale(base, fresh_by)
    if scale != 1.0:
        print(f"note host-speed calibration: this host measures "
              f"{scale:.2f}x the baseline host (median over rows); wall "
              "limits scaled accordingly")
    bad = 0
    for b in base:
        k = _key(b)
        f = fresh_by.get(k)
        tag = f"{k[0]} {k[1]} [{k[2]}]"
        if f is None:
            print(f"FAIL {tag}: row missing from fresh record")
            bad += 1
            continue
        fused = any(k[0].startswith(p) for p in FUSED_OPS)
        if b.get("bytes_moved") is not None and f.get("bytes_moved") is not None:
            if f["bytes_moved"] > b["bytes_moved"]:
                level = "FAIL" if fused else "warn"
                print(f"{level} {tag}: bytes_moved {b['bytes_moved']} -> "
                      f"{f['bytes_moved']}"
                      + ("" if fused else " (unfused op: not gating)"))
                bad += fused
        if b.get("n_iters") is not None and f.get("n_iters") is not None:
            if f["n_iters"] > b["n_iters"] + ITER_SLACK:
                print(f"FAIL {tag}: n_iters {b['n_iters']} -> {f['n_iters']} "
                      f"(warm-start regression; slack +{ITER_SLACK})")
                bad += 1
        # serving-pipeline rows: both arms ran on THIS host, so the ratio
        # and the zero-blocking-sync invariant gate absolutely, with no
        # baseline-host calibration
        if (b.get("throughput_ratio") is not None
                and f.get("throughput_ratio") is not None):
            if f["throughput_ratio"] < MIN_TPUT_RATIO:
                print(f"FAIL {tag}: throughput_ratio "
                      f"{f['throughput_ratio']} < acceptance floor "
                      f"{MIN_TPUT_RATIO} (baseline {b['throughput_ratio']})")
                bad += 1
        if b.get("host_syncs") is not None and f.get("host_syncs") is not None:
            if f["host_syncs"] != 0:
                print(f"FAIL {tag}: {f['host_syncs']} blocking host syncs "
                      "recorded during the async drain (must be 0)")
                bad += 1
        bw, fw = b.get("wall_ms"), f.get("wall_ms")
        if bw is not None and fw is not None:
            limit = wall_ratio * scale * bw + wall_slack_ms
            if fw > limit:
                print(f"FAIL {tag}: wall {bw}ms -> {fw}ms "
                      f"(> {wall_ratio}x * {scale:.2f} host scale "
                      f"+ {wall_slack_ms}ms slack)")
                bad += 1
        err = f.get("max_abs_err")
        if err is not None and err > 10 * max(b.get("max_abs_err") or 0.0, 1e-3):
            print(f"warn {tag}: max_abs_err {b.get('max_abs_err')} -> {err}")
    extra = sorted(set(fresh_by) - {_key(b) for b in base})
    for k in extra:
        print(f"note new row {k[0]} {k[1]} [{k[2]}] (not in baseline — "
              "refresh with --update-baseline to start gating it)")
    print(f"check_regression: {len(base)} baseline rows, {bad} violations")
    return 1 if bad else 0


def measure_obs_overhead(reps: int = 5) -> dict:
    """Paired wall times of one jitted implicit solve+grad, obs off vs on.

    The work is pinned (tol=0 -> the forward always runs max_steps, the
    backward budget is fixed), so the only delta between the two modes is
    the instrumentation itself: the debug-callback bridge planted by
    ``record_solve``/``record_backward`` (the span tracer plants nothing in
    a program: its phases are ``jax.named_scope`` names, metadata only).
    Fresh jit closures per mode — the gate is trace-time."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.implicit import (BackwardConfig, ForwardConfig, ImplicitConfig,
                                implicit_fixed_point)
    from repro.obs import metrics as obs_metrics

    # The instrumentation cost is a FIXED per-solve-call amount (a handful
    # of host callbacks: solve record, backward record —
    # ~3-4 ms of host Python on this class of machine), independent of the
    # solve size.  Size the probe like a real train step (~100 ms+), where
    # that fixed cost is the same <5% it is in production; a tiny probe
    # would gate the callback dispatch constant, not the ratio.
    B, D = 8, 2048
    cfg = ImplicitConfig(
        forward=ForwardConfig(max_steps=30, tol=0.0),
        backward=BackwardConfig(estimator="shine"),
        memory=8,
    )
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.normal(size=(D, D)) / (2 * np.sqrt(D)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)

    def f(params, xx, z):
        return jnp.tanh(xx + z @ params)

    def compiled(enable: bool):
        # the gates are trace-time: the enabled state at COMPILE decides
        # whether the program carries callbacks, regardless of later flips
        obs_metrics.set_enabled(enable)

        def loss(params, xx):
            z, _ = implicit_fixed_point(f, params, xx, jnp.zeros_like(xx), cfg)
            return jnp.sum(z * z)

        g = jax.jit(jax.grad(loss))
        jax.block_until_ready(g(W, x))  # compile outside the timing
        return g

    def once(g) -> float:
        t0 = time.perf_counter()
        jax.block_until_ready(g(W, x))
        return (time.perf_counter() - t0) * 1e3

    was_m = obs_metrics.enabled()
    try:
        g_off = compiled(False)
        g_on = compiled(True)
        for _ in range(2):  # warm both past first-call effects
            once(g_off), once(g_on)
        # interleaved PAIRS, gated on the cleanest pair: real overhead is
        # present in every call, so the min pairwise delta still sees it,
        # while a contention burst has to contaminate every single pair
        # to fake a failure
        offs, deltas = [], []
        for _ in range(reps):
            off = once(g_off)
            on = once(g_on)
            offs.append(off)
            deltas.append(on - off)
    finally:
        obs_metrics.set_enabled(was_m)
    base = min(offs)
    return {"baseline_ms": base,
            "instrumented_ms": base + max(min(deltas), 0.0)}


def check_obs_overhead(*, ratio: float, slack_ms: float, reps: int) -> int:
    m = measure_obs_overhead(reps=reps)
    limit = ratio * m["baseline_ms"] + slack_ms
    ok = m["instrumented_ms"] <= limit
    print(f"obs-overhead: uninstrumented {m['baseline_ms']:.2f}ms, "
          f"instrumented {m['instrumented_ms']:.2f}ms, limit {limit:.2f}ms "
          f"({ratio}x + {slack_ms}ms) -> {'ok' if ok else 'FAIL'}")
    return 0 if ok else 1


def measure_guard_overhead(reps: int = 5) -> dict:
    """Paired wall times of one jitted implicit solve+grad, fault guards
    off vs on (``ForwardConfig.guard`` — a trace-time gate, exactly like
    the obs switches: guard=False lowers the pre-guard program).

    Same methodology as :func:`measure_obs_overhead`: pinned work
    (tol=0 -> full max_steps both modes), fresh jit closures per mode,
    interleaved off/on pairs gated on the cleanest pairwise delta.  The
    guard's steady-state cost is a few elementwise selects + one fused
    reduction per iteration riding an already-bandwidth-bound loop, so
    the ISSUE's <= 5% wall budget is enforced here, not assumed."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.implicit import (BackwardConfig, ForwardConfig, ImplicitConfig,
                                implicit_fixed_point)
    from repro.obs import metrics as obs_metrics

    B, D = 8, 2048
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.normal(size=(D, D)) / (2 * np.sqrt(D)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)

    def f(params, xx, z):
        return jnp.tanh(xx + z @ params)

    def compiled(guard: bool):
        cfg = ImplicitConfig(
            forward=ForwardConfig(max_steps=30, tol=0.0, guard=guard),
            backward=BackwardConfig(estimator="shine"),
            memory=8,
        )

        def loss(params, xx):
            z, _ = implicit_fixed_point(f, params, xx, jnp.zeros_like(xx), cfg)
            return jnp.sum(z * z)

        g = jax.jit(jax.grad(loss))
        jax.block_until_ready(g(W, x))  # compile outside the timing
        return g

    def once(g) -> float:
        t0 = time.perf_counter()
        jax.block_until_ready(g(W, x))
        return (time.perf_counter() - t0) * 1e3

    # isolate the guard delta: the obs bridge must not ride either arm
    was_m = obs_metrics.enabled()
    obs_metrics.set_enabled(False)
    try:
        g_off = compiled(False)
        g_on = compiled(True)
        for _ in range(2):
            once(g_off), once(g_on)
        offs, deltas = [], []
        for _ in range(reps):
            off = once(g_off)
            on = once(g_on)
            offs.append(off)
            deltas.append(on - off)
    finally:
        obs_metrics.set_enabled(was_m)
    base = min(offs)
    return {"baseline_ms": base,
            "guarded_ms": base + max(min(deltas), 0.0)}


def check_guard_overhead(*, ratio: float, slack_ms: float, reps: int) -> int:
    m = measure_guard_overhead(reps=reps)
    limit = ratio * m["baseline_ms"] + slack_ms
    ok = m["guarded_ms"] <= limit
    print(f"guard-overhead: unguarded {m['baseline_ms']:.2f}ms, "
          f"guarded {m['guarded_ms']:.2f}ms, limit {limit:.2f}ms "
          f"({ratio}x + {slack_ms}ms) -> {'ok' if ok else 'FAIL'}")
    return 0 if ok else 1


class _Tee(io.TextIOBase):
    """Mirror writes to several text streams (stdout + the report buffer)."""

    def __init__(self, *streams):
        self._streams = streams

    def write(self, s):
        for st in self._streams:
            st.write(s)
        return len(s)

    def flush(self):
        for st in self._streams:
            st.flush()


def _append_summary(path: Path, body: str, status: int) -> None:
    """Append a markdown regression report (GitHub step-summary flavoured)."""
    verdict = "PASS" if status == 0 else "FAIL"
    path.parent.mkdir(parents=True, exist_ok=True)
    with path.open("a") as fh:
        fh.write(f"## Bench regression gate: {verdict}\n\n```\n{body}```\n")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", type=Path, default=BASELINE)
    ap.add_argument("--fresh", type=Path, default=None,
                    help="compare this record instead of re-measuring")
    ap.add_argument("--write-fresh", type=Path, default=FRESH)
    ap.add_argument("--wall-ratio", type=float, default=1.3)
    ap.add_argument("--wall-slack-ms", type=float, default=0.25)
    ap.add_argument("--update-baseline", action="store_true")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="run ONLY the observability-overhead gate")
    ap.add_argument("--skip-obs-overhead", action="store_true",
                    help="skip the overhead gate in live-measurement mode")
    ap.add_argument("--obs-ratio", type=float, default=1.05)
    ap.add_argument("--obs-slack-ms", type=float, default=2.0)
    ap.add_argument("--obs-reps", type=int, default=5)
    ap.add_argument("--guard-overhead", action="store_true",
                    help="run ONLY the fault-guard-overhead gate")
    ap.add_argument("--skip-guard-overhead", action="store_true",
                    help="skip the guard-overhead gate in live mode")
    ap.add_argument("--guard-ratio", type=float, default=1.05)
    ap.add_argument("--guard-slack-ms", type=float, default=2.0)
    ap.add_argument("--guard-reps", type=int, default=5)
    ap.add_argument("--summary", type=Path, default=None,
                    help="append a markdown PASS/FAIL report of the gate's "
                         "output to this file (point it at "
                         "$GITHUB_STEP_SUMMARY in CI)")
    args = ap.parse_args()

    if args.summary is None:
        return _run(args)
    buf = io.StringIO()
    with contextlib.redirect_stdout(_Tee(sys.stdout, buf)):
        status = _run(args)
    _append_summary(args.summary, buf.getvalue(), status)
    return status


def _run(args) -> int:
    if args.obs_overhead:
        return check_obs_overhead(ratio=args.obs_ratio,
                                  slack_ms=args.obs_slack_ms,
                                  reps=args.obs_reps)
    if args.guard_overhead:
        return check_guard_overhead(ratio=args.guard_ratio,
                                    slack_ms=args.guard_slack_ms,
                                    reps=args.guard_reps)

    if not args.baseline.exists():
        print(f"check_regression: baseline {args.baseline} missing -> FAIL "
              "(regenerate with `python -m benchmarks.run --only kernels` "
              "and commit it)")
        return 1
    base = json.loads(args.baseline.read_text())

    live = args.fresh is None
    if not live:
        fresh = json.loads(args.fresh.read_text())
    else:
        fresh = measure()
        args.write_fresh.parent.mkdir(parents=True, exist_ok=True)
        args.write_fresh.write_text(json.dumps(fresh, indent=2))
        print(f"# wrote {args.write_fresh} ({len(fresh)} rows)")

    if args.update_baseline:
        args.baseline.write_text(json.dumps(fresh, indent=2))
        print(f"# baseline {args.baseline} updated — commit the diff")
        return 0

    bad = compare(base, fresh, wall_ratio=args.wall_ratio,
                  wall_slack_ms=args.wall_slack_ms)
    if live and not args.skip_obs_overhead:
        bad |= check_obs_overhead(ratio=args.obs_ratio,
                                  slack_ms=args.obs_slack_ms,
                                  reps=args.obs_reps)
    if live and not args.skip_guard_overhead:
        bad |= check_guard_overhead(ratio=args.guard_ratio,
                                    slack_ms=args.guard_slack_ms,
                                    reps=args.guard_reps)
    return bad


if __name__ == "__main__":
    sys.exit(main())
