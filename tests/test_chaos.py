"""Chaos suite: every numerical-fault class is injected deterministically
(`runtime.faultinject`) and must be detected, contained, and recovered.

Five fault classes (ISSUE 10 acceptance):
  1. non-finite iterate        — FaultPlan("nonfinite") inside the solver loop
  2. diverging solve           — FaultPlan("diverge"), finite residual blow-up
  3. corrupted qN ring         — corrupt_carry_ring on a warm SolveCarry
  4. poisoned prefix-cache     — poison_prefix_entry / poison_prefix_store_slot
  5. SIGTERM preemption        — subprocess train run killed mid-loop

Cross-cutting invariants:
  * co-batched healthy samples/requests are bit-identical to a fault-free run
  * guard=True with no fault is bit-identical (logits AND gradients) to
    guard=False — detection only selects already-computed values
  * faults land in metrics (solve_failures_total, serve_request_faults_total,
    prefix_cache_evictions_total{reason="poisoned"}, ...)

Run via ``./test.sh chaos`` — it points CHAOS_METRICS_OUT at
results/chaos/metrics.json so the injected-fault counters are archived.
"""

import dataclasses
import json
import os
import re
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.solvers import (
    STATUS_CONVERGED,
    STATUS_DIVERGED,
    STATUS_NONFINITE,
    STATUS_STALLED,
    SolverConfig,
    anderson_solve,
    broyden_solve,
    fixed_point_solve,
    init_solve_carry,
)
from repro.implicit import (BackwardConfig, ForwardConfig, ImplicitConfig,
                            implicit_fixed_point)
from repro.obs import metrics as obs_metrics
from repro.runtime import faultinject
from repro.runtime.faultinject import FaultPlan

D = 24
BSZ = 3


def _linear_g(seed: int = 0):
    """Contractive batched root problem g(z) = A z - b with known z*."""
    rng = np.random.default_rng(seed)
    A = jnp.asarray(np.eye(D) + 0.1 * rng.normal(size=(D, D)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(BSZ, D)), jnp.float32)

    def g(z):
        return z @ A.T - b

    z_star = jnp.linalg.solve(A, b.T).T
    return g, z_star


def _counter(name, **labels):
    total = 0.0
    for m in obs_metrics.default_registry().snapshot()["metrics"]:
        if m["name"] == name and all(
                m.get("labels", {}).get(k) == v for k, v in labels.items()):
            total += m["value"]
    return total


@pytest.fixture(autouse=True, scope="module")
def _dump_metrics_snapshot():
    """Archive the registry after the module so ``./test.sh chaos`` can
    upload the injected-fault counters as a CI artifact."""
    yield
    out = os.environ.get("CHAOS_METRICS_OUT")
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(obs_metrics.default_registry().snapshot(), f, indent=2)


# ---------------------------------------------------------------------------
# class 1+2: in-solver iterate faults (non-finite / diverging)
# ---------------------------------------------------------------------------


CFG = SolverConfig(max_steps=40, tol=1e-5, memory=40)


@pytest.mark.parametrize("kind,code", [("nonfinite", STATUS_NONFINITE),
                                       ("diverge", STATUS_DIVERGED)])
def test_transient_fault_recovers_with_sticky_status(kind, code):
    g, z_star = _linear_g()
    ref = broyden_solve(g, jnp.zeros_like(z_star), CFG)
    with faultinject.inject(FaultPlan(kind, sample=1, step=2, duration=1)):
        res = broyden_solve(g, jnp.zeros_like(z_star), CFG)
    st = np.asarray(res.status)
    # transient fault: the in-jit restart recovers the row to the true root,
    # but the status stays sticky so callers can still see the fault
    assert st[1] == code
    assert np.all(np.isfinite(np.asarray(res.z)))
    assert float(res.residual[1]) < 1e-3
    # healthy co-batched rows are bit-identical to the fault-free run
    for i in (0, 2):
        assert st[i] == STATUS_CONVERGED
        np.testing.assert_array_equal(np.asarray(res.z[i]),
                                      np.asarray(ref.z[i]))


@pytest.mark.parametrize("kind,code", [("nonfinite", STATUS_NONFINITE),
                                       ("diverge", STATUS_DIVERGED)])
def test_persistent_fault_freezes_with_finite_best_iterate(kind, code):
    g, z_star = _linear_g()
    with faultinject.inject(FaultPlan(kind, sample=0, step=2)):
        res = broyden_solve(g, jnp.zeros_like(z_star), CFG)
    st = np.asarray(res.status)
    assert st[0] == code
    # the returned iterate is the best pre-fault one — always finite
    assert np.all(np.isfinite(np.asarray(res.z)))
    assert st[1] == STATUS_CONVERGED and st[2] == STATUS_CONVERGED


def test_fixed_point_and_anderson_detect_nonfinite():
    g, z_star = _linear_g()

    def f(z):  # fixed-point form z = f(z)
        return z - 0.5 * g(z)

    cfg = SolverConfig(max_steps=60, tol=1e-6, memory=5)
    with faultinject.inject(FaultPlan("nonfinite", sample=2, step=3,
                                      duration=1)):
        r_fp = fixed_point_solve(f, jnp.zeros_like(z_star), cfg)
        r_ad = anderson_solve(f, jnp.zeros_like(z_star), cfg)
    for r in (r_fp, r_ad):
        assert np.asarray(r.status)[2] == STATUS_NONFINITE
        assert np.all(np.isfinite(np.asarray(r.z)))


def test_stall_detection_opt_in():
    g, z_star = _linear_g()
    cfg = dataclasses.replace(CFG, stall_tol=0.0, stall_patience=3)
    with faultinject.inject(FaultPlan("stall", sample=1, step=2)):
        res = broyden_solve(g, jnp.zeros_like(z_star), cfg)
    assert np.asarray(res.status)[1] == STATUS_STALLED
    assert np.all(np.isfinite(np.asarray(res.z)))


def test_solver_faults_hit_metrics():
    g, z_star = _linear_g()
    cfg = ImplicitConfig(forward=ForwardConfig(max_steps=30, tol=1e-6),
                         backward=BackwardConfig(estimator="shine"),
                         memory=30)

    def f(params, x, z):
        return z - 0.5 * (z @ params.T - x)

    rng = np.random.default_rng(3)
    W = jnp.asarray(np.eye(D) + 0.1 * rng.normal(size=(D, D)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(BSZ, D)), jnp.float32)
    was = obs_metrics.enabled()
    obs_metrics.set_enabled(True)
    before = _counter("solve_failures_total")
    try:
        with faultinject.inject(FaultPlan("nonfinite", sample=0, step=2)):
            z, _ = implicit_fixed_point(f, W, x, jnp.zeros_like(x), cfg)
            jax.block_until_ready(z)
    finally:
        obs_metrics.set_enabled(was)
    assert _counter("solve_failures_total") > before


# ---------------------------------------------------------------------------
# class 3: corrupted quasi-Newton ring (host-state carry corruption)
# ---------------------------------------------------------------------------


def test_corrupted_carry_ring_detected_and_recovered():
    g, z_star = _linear_g()
    carry = init_solve_carry(BSZ, D, CFG.memory)
    warm = broyden_solve(g, jnp.zeros_like(z_star), CFG, carry=carry).carry

    # the next solve targets a SHIFTED problem (a new batch, as in
    # training) — the warm iterate is a good start but not converged, so
    # the first quasi-Newton direction actually consumes the ring
    shift = jnp.asarray(np.random.default_rng(9).normal(
        size=z_star.shape) * 0.5, jnp.float32)

    def g2(z):
        return g(z) - shift

    ref = broyden_solve(g2, jnp.zeros_like(z_star), CFG, carry=warm)
    assert int(ref.n_steps) > 0

    bad = faultinject.corrupt_carry_ring(warm, rows=[1])
    res = broyden_solve(g2, jnp.zeros_like(z_star), CFG, carry=bad)
    st = np.asarray(res.status)
    # the corrupted row recovers from a cold restart to the true root
    assert np.all(np.isfinite(np.asarray(res.z)))
    assert float(res.residual[1]) < 1e-3
    assert st[1] >= STATUS_DIVERGED  # NONFINITE from the poisoned direction
    # healthy warm rows are bit-identical to the uncorrupted carried solve
    for i in (0, 2):
        np.testing.assert_array_equal(np.asarray(res.z[i]),
                                      np.asarray(ref.z[i]))
    # the carry handed back is clean: a follow-up solve stays healthy
    nxt = broyden_solve(g2, jnp.zeros_like(z_star), CFG, carry=res.carry)
    assert np.all(np.isfinite(np.asarray(nxt.z)))
    assert float(jnp.max(nxt.residual)) < 1e-3


def test_poisoned_warm_iterate_contained_at_entry():
    """A NaN carried-in iterate (not the ring — the z itself) must be
    repaired before it poisons res0/div_ref/best-iterate tracking."""
    g, z_star = _linear_g()
    carry = init_solve_carry(BSZ, D, CFG.memory)
    warm = broyden_solve(g, jnp.zeros_like(z_star), CFG, carry=carry).carry
    z = np.array(warm.z)
    z[1] = np.nan
    bad = dataclasses.replace(warm, z=jnp.asarray(z))
    res = broyden_solve(g, jnp.zeros_like(z_star), CFG, carry=bad)
    assert np.asarray(res.status)[1] == STATUS_NONFINITE
    assert np.all(np.isfinite(np.asarray(res.z)))
    assert float(res.residual[1]) < 1e-3


# ---------------------------------------------------------------------------
# guards-on / guards-off bit-identity on the healthy path
# ---------------------------------------------------------------------------


def test_guard_bit_identical_without_faults():
    g, z_star = _linear_g()

    def f(z):  # contractive fixed-point form for the Picard solver
        return z - 0.5 * g(z)

    for solve, fn in ((broyden_solve, g), (fixed_point_solve, f)):
        on = solve(fn, jnp.zeros_like(z_star), CFG)
        off = solve(fn, jnp.zeros_like(z_star),
                    dataclasses.replace(CFG, guard=False))
        np.testing.assert_array_equal(np.asarray(on.z), np.asarray(off.z))
        np.testing.assert_array_equal(np.asarray(on.residual),
                                      np.asarray(off.residual))


def test_guard_bit_identical_gradients():
    rng = np.random.default_rng(5)
    W = jnp.asarray(np.eye(D) + 0.1 * rng.normal(size=(D, D)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(BSZ, D)), jnp.float32)

    def f(params, xx, z):
        return z - 0.5 * (z @ params.T - xx)

    grads = {}
    for guard in (True, False):
        cfg = ImplicitConfig(
            forward=ForwardConfig(max_steps=25, tol=1e-6, guard=guard),
            backward=BackwardConfig(estimator="shine"), memory=25)

        def loss(params):
            z, _ = implicit_fixed_point(f, params, x, jnp.zeros_like(x), cfg)
            return jnp.sum(z * z)

        grads[guard] = jax.grad(loss)(W)
    np.testing.assert_array_equal(np.asarray(grads[True]),
                                  np.asarray(grads[False]))


# ---------------------------------------------------------------------------
# class 4: poisoned prefix-cache entry (serving isolation)
# ---------------------------------------------------------------------------


def _serve_setup():
    from repro.configs.registry import smoke_config
    from repro.models import lm
    from repro.parallel.sharding import ShardCtx

    cfg = smoke_config("minicpm-2b", deq=True)
    cfg = dataclasses.replace(
        cfg, num_layers=2, d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
        vocab_size=128, head_dim=16, dtype="float32",
        deq=dataclasses.replace(cfg.deq, max_steps=60, tol=1e-5, memory=16))
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    params["deq_blocks"] = jax.tree_util.tree_map(
        lambda a: a * 0.3, params["deq_blocks"])
    return cfg, params, ShardCtx.for_mesh(None)


@pytest.mark.slow
def test_poisoned_prefix_entry_sync_retry_and_isolation():
    from repro.runtime.serving import Request, ServeLoop

    cfg, params, ctx = _serve_setup()
    rng = np.random.default_rng(7)
    base = rng.integers(2, 128, size=8).tolist()
    pA = base + rng.integers(2, 128, size=4).tolist()
    pB = rng.integers(2, 128, size=12).tolist()

    ref = ServeLoop(params, cfg, ctx, slots=2, max_len=64, eos_id=-1,
                    prefix_cache=True, prefix_cache_slots=16)
    rB0 = Request(uid=0, prompt=list(pB), max_new_tokens=4)
    ref.drain([rB0])

    loop = ServeLoop(params, cfg, ctx, slots=2, max_len=64, eos_id=-1,
                     prefix_cache=True, prefix_cache_slots=16)
    loop.drain([Request(uid=1, prompt=list(pA), max_new_tokens=2)])
    assert len(loop.prefix) > 0
    for key in list(loop.prefix._entries):
        faultinject.poison_prefix_entry(loop.prefix, key)

    f0 = _counter("serve_request_faults_total")
    e0 = _counter("prefix_cache_evictions_total", reason="poisoned")
    rA = Request(uid=2, prompt=list(pA), max_new_tokens=4)
    rB = Request(uid=3, prompt=list(pB), max_new_tokens=4)
    loop.drain([rA, rB])

    assert rA.done and rB.done
    # poisoned request: detected at prefill, cold-retried once, succeeded
    assert rA.retried and rA.error is None and len(rA.out) == 4
    # healthy co-batched request bit-identical to the fault-free run
    assert rB.out == rB0.out
    assert _counter("serve_request_faults_total") - f0 >= 1
    assert _counter("prefix_cache_evictions_total",
                    reason="poisoned") - e0 >= 1


@pytest.mark.slow
def test_poisoned_prefix_store_async_retry():
    from repro.runtime.serving import Request, ServeLoop

    cfg, params, ctx = _serve_setup()
    rng = np.random.default_rng(11)
    pA = (rng.integers(2, 128, size=8).tolist()
          + rng.integers(2, 128, size=4).tolist())

    loop = ServeLoop(params, cfg, ctx, slots=2, max_len=64, eos_id=-1,
                     pipeline="async", prefix_cache=True,
                     prefix_cache_slots=8)
    loop.drain([Request(uid=1, prompt=list(pA), max_new_tokens=2)])
    assert len(loop.prefix_store) > 0
    for slot in {e.slot for e in loop.prefix_store._entries.values()}:
        faultinject.poison_prefix_store_slot(loop.prefix_store, slot)

    f0 = _counter("serve_request_faults_total")
    rA = Request(uid=2, prompt=list(pA), max_new_tokens=4)
    loop.drain([rA])
    assert rA.done and rA.retried and rA.epoch == 1
    assert rA.error is None and len(rA.out) == 4
    assert _counter("serve_request_faults_total") - f0 >= 1


# ---------------------------------------------------------------------------
# class 5: SIGTERM preemption (subprocess e2e — also satellite (c))
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sigterm_preemption_writes_final_checkpoint(tmp_path):
    ckdir = tmp_path / "ck"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [os.path.join(repo, "src")]
    if os.environ.get("PYTHONPATH"):
        paths.append(os.environ["PYTHONPATH"])
    # the launcher would keep a persistent compile cache: not in tests
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false",
               PYTHONPATH=os.pathsep.join(paths))
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.train", "--smoke", "--deq",
         "--steps", "500", "--batch", "2", "--seq", "16",
         "--checkpoint-dir", str(ckdir), "--checkpoint-every", "100"],
        env=env, cwd=repo,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    # wait until training is demonstrably mid-loop (first step logged),
    # then preempt
    deadline = time.time() + 300
    started = False
    while time.time() < deadline:
        if any(p.startswith("step_") for p in
               (os.listdir(ckdir) if ckdir.exists() else [])):
            started = True
            break
        if proc.poll() is not None:
            break
        time.sleep(0.5)
    if not started:
        out = proc.communicate()[0]
        pytest.fail(f"training never reached a checkpoint:\n{out[-2000:]}")
    proc.send_signal(signal.SIGTERM)
    out = proc.communicate(timeout=240)[0]
    assert proc.returncode == 0, f"non-zero exit after SIGTERM:\n{out[-2000:]}"
    assert "preempted at step" in out
    steps = sorted(int(p.split("_")[1]) for p in os.listdir(ckdir)
                   if p.startswith("step_") and not p.endswith(".tmp"))
    assert steps, "no checkpoint written"
    # the preemption save lands at the interrupted step, not a multiple of
    # checkpoint_every (unless SIGTERM raced the periodic save exactly)
    m = re.search(r"preempted at step (\d+)", out)
    assert int(m.group(1)) == steps[-1]
