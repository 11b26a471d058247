#!/usr/bin/env bash
# Tier-1 test runner: one command locally and in CI.
#
#   ./test.sh              tier-1 suite, gated on tests/baseline_failures.txt
#                          (exit 0 iff no failure OUTSIDE the recorded
#                          baseline — "no worse than seed", machine-checked)
#   ./test.sh kernels      interpret-mode Pallas kernel sweep only: every
#                          pallas_interpret parametrization in
#                          tests/test_kernels.py, so the TPU code path is
#                          exercised on CPU (extra pytest args pass through)
#   ./test.sh obs          observability rehearsals only — exactly what the
#                          CI observability job runs: a real train run, a
#                          serve drain, and a prefix-cache serve drain over
#                          overlapping prompts, each with
#                          --metrics-out/--trace-out; validates the
#                          snapshots (schema, non-empty traces, >= 1
#                          prefix-cache hit) under results/obs/
#   ./test.sh ci           what CI runs, reproducible offline: tier-1 suite
#                          + kernel sweep (both emitting JUnit XML under
#                          results/junit/) + the bench perf-regression gate
#                          (benchmarks/check_regression.py, including the
#                          observability-overhead gate) + the roofline
#                          report with its qN bytes-accounting gate
#                          (benchmarks/roofline.py) + the obs rehearsals
#                          (./test.sh obs) — no network, no installs
#   ./test.sh chaos        numerical-fault chaos suite (tests/test_chaos.py):
#                          all five injected fault classes — non-finite
#                          iterate, diverging solve, corrupted qN ring,
#                          poisoned prefix-cache entry, SIGTERM preemption —
#                          must be detected, contained, and recovered; the
#                          injected-fault metrics snapshot lands at
#                          results/chaos/metrics.json (CI uploads it), and
#                          the guard-overhead gate enforces the <= 5% wall
#                          budget of the always-on guards
#   ./test.sh lint         ruff when available, else a dependency-free
#                          compileall pass (the container has no linter)
#   ./test.sh tests/x.py   pass any pytest args through (ungated)
set -euo pipefail
cd "$(dirname "$0")"

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# force the host CPU platform: tests must not try to grab an accelerator,
# and multi-device tests spawn subprocesses that set their own flags.
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

run_gated() {
  # pytest + baseline gate: known failures don't fail the build, NEW ones do
  local junit="$1"; shift
  mkdir -p results/junit
  set +e
  python -m pytest --junitxml="$junit" "$@"
  local code=$?
  set -e
  python tests/check_baseline.py --junit "$junit" \
    --baseline tests/baseline_failures.txt --pytest-exit "$code"
}

run_obs() {
  # observability rehearsals: a real train run and real serve drains must
  # produce a metrics snapshot + a Perfetto-loadable trace.  ONE function
  # for local runs and the CI observability job, so they cannot diverge.
  mkdir -p results/obs
  python -m repro.launch.train --smoke --deq --steps 2 --batch 2 --seq 16 \
    --metrics-out results/obs/train_metrics.json \
    --trace-out results/obs/train_trace.json
  python -m repro.launch.serve --smoke --deq --requests 6 --slots 2 \
    --max-new-tokens 4 --carry-max-age 3 \
    --metrics-out results/obs/serve_metrics.json \
    --trace-out results/obs/serve_trace.json
  # prefix-cache drain: overlapping prompts (6 shared tokens) through the
  # cross-request prefix carry cache — the snapshot must record hits
  python -m repro.launch.serve --smoke --deq --requests 6 --slots 2 \
    --max-new-tokens 4 --prefix-cache --prefix-cache-slots 8 \
    --shared-prefix 6 \
    --metrics-out results/obs/serve_prefix_metrics.json \
    --trace-out results/obs/serve_prefix_trace.json
  # async-pipeline drain: device-resident caches + wave overlap + reorder;
  # the validator asserts host_syncs_total == 0 (the steady state never
  # blocks on unready device data) and a parseable Prometheus exposition
  python -m repro.launch.serve --smoke --deq --requests 8 --slots 2 \
    --max-new-tokens 4 --pipeline async --prefix-cache \
    --prefix-cache-slots 8 --shared-prefix 6 --reorder \
    --metrics-out results/obs/serve_async_metrics.json \
    --metrics-prom-out results/obs/serve_async_metrics.prom
  python - <<'EOF'
import json
for p in ("results/obs/train_metrics.json", "results/obs/serve_metrics.json",
          "results/obs/serve_prefix_metrics.json"):
    snap = json.load(open(p))
    assert snap["schema"] == "repro.obs.metrics/v1" and snap["metrics"], p
for p in ("results/obs/train_trace.json", "results/obs/serve_trace.json",
          "results/obs/serve_prefix_trace.json"):
    tr = json.load(open(p))
    assert tr["traceEvents"], p
snap = json.load(open("results/obs/serve_prefix_metrics.json"))
hits = sum(m["value"]
           for m in snap["metrics"]
           if m["name"] == "prefix_cache_lookups_total"
           and m["labels"].get("outcome") in ("hit", "partial"))
assert hits >= 1, "prefix-cache drain recorded no hits"
asnap = json.load(open("results/obs/serve_async_metrics.json"))
syncs = sum(m["value"] for m in asnap["metrics"]
            if m["name"] == "host_syncs_total")
assert syncs == 0, f"async drain recorded {syncs} blocking host syncs"
assert any(m["name"] == "serve_ttft_ms" and m["count"]
           for m in asnap["metrics"]), "async drain recorded no TTFT"
prom = open("results/obs/serve_async_metrics.prom").read()
assert "# TYPE serve_ttft_ms histogram" in prom, "prom exposition broken"
assert 'serve_ttft_ms_bucket{le="+Inf"}' in prom, "prom +Inf bucket missing"
print(f"obs: artifacts validated (results/obs/), prefix-cache hits={hits:.0f},"
      f" async host_syncs=0")
EOF
}

case "${1:-}" in
  "")
    run_gated results/junit/tier1.xml -q
    ;;
  kernels)
    shift
    mkdir -p results/junit
    exec python -m pytest -q tests/test_kernels.py "$@"
    ;;
  obs)
    shift
    run_obs
    ;;
  ci)
    shift
    run_gated results/junit/tier1.xml -q
    mkdir -p results/junit
    python -m pytest -q tests/test_kernels.py \
      --junitxml=results/junit/kernels.xml
    python -m benchmarks.check_regression
    # roofline report + qN bytes-accounting gate: trace-time stream counters
    # must match the analytic dtype-aware byte model exactly (bf16 ring =
    # half the f32 U/V bytes); report lands at
    # results/benchmarks/ROOFLINE_report.json (CI uploads it as an artifact)
    python -m benchmarks.roofline
    run_obs
    echo "ci: tier-1 + kernel sweep + bench gates + obs rehearsals all green"
    ;;
  chaos)
    shift
    mkdir -p results/chaos results/junit
    CHAOS_METRICS_OUT=results/chaos/metrics.json \
      python -m pytest -q tests/test_chaos.py \
      --junitxml=results/junit/chaos.xml "$@"
    python -m benchmarks.check_regression --guard-overhead
    python - <<'EOF'
import json
snap = json.load(open("results/chaos/metrics.json"))
assert snap["schema"] == "repro.obs.metrics/v1" and snap["metrics"]
names = {m["name"] for m in snap["metrics"]}
assert "solve_failures_total" in names, "no injected solve faults recorded"
print(f"chaos: all fault classes contained; metrics snapshot at "
      f"results/chaos/metrics.json ({len(snap['metrics'])} series)")
EOF
    ;;
  lint)
    shift
    if command -v ruff >/dev/null 2>&1; then
      ruff check src tests benchmarks
    else
      python -m compileall -q src tests benchmarks
      echo "lint: compileall clean (ruff unavailable — full lint runs in CI)"
    fi
    ;;
  *)
    exec python -m pytest -q "$@"
    ;;
esac
