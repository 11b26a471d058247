"""Observability subsystem tests: solver convergence tapes, the metrics
registry + jit bridge, Chrome-trace span tracing, serving telemetry, the
CarryCache staleness policy and checkpoint-lean saves.

The tape tests pin the two invariants the subsystem is built on: the tape
never perturbs the solve (inert under jit/vmap, frozen cells bit-for-bit
at their init values) and it faithfully records convergence (monotone
nonincreasing residuals on a contractive map).  The bridge/tracing tests
exercise the trace-time gating: instrumentation only exists in programs
traced while the switch is on.
"""

import dataclasses
import json
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import smoke_config
from repro.core.solvers import (
    SolverConfig,
    broyden_solve,
    fixed_point_solve,
    init_solve_carry,
)
from repro.implicit import (
    CarryCache,
    ForwardConfig,
    ImplicitConfig,
    implicit_fixed_point,
)
from repro.models import lm
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.obs.tape import empty_tape, tape_residual_series, tape_summary
from repro.parallel.sharding import ShardCtx
from repro.runtime.serving import Request, ServeLoop

CTX = ShardCtx.for_mesh(None)


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with the gates off and a fresh registry/
    recorder — the obs-off default must hold for the rest of the suite."""
    obs_metrics.set_enabled(False)
    obs_tracing.set_enabled(False)
    obs_metrics.default_registry().reset()
    obs_tracing.clear()
    yield
    obs_metrics.set_enabled(False)
    obs_tracing.set_enabled(False)
    obs_metrics.default_registry().reset()
    obs_tracing.clear()


# ---------------------------------------------------------------------------
# solve tape
# ---------------------------------------------------------------------------


def test_tape_monotone_nonincreasing_on_contraction():
    """Picard on a linear contraction: residual shrinks by the contraction
    factor every step, and the tape must record exactly that."""
    f = lambda z: 0.5 * z + 1.0
    z0 = jnp.zeros((3, 6))
    res = fixed_point_solve(f, z0, SolverConfig(max_steps=40, tol=1e-8))
    series = tape_residual_series(res.tape.residual)
    assert len(series) >= 5
    assert all(b <= a * (1 + 1e-5) for a, b in zip(series, series[1:]))
    summ = tape_summary(res.tape)
    assert summ["n_iters"] == len(series)
    assert summ["final_residual"] == series[-1]
    # picard keeps no quasi-Newton chain
    assert summ["qn_occupancy_max"] == 0


def test_tape_records_qn_occupancy_and_step_norm():
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.normal(size=(8, 8)) / 6.0, jnp.float32)
    g = lambda z: z @ A - z + 1.0
    res = broyden_solve(g, jnp.zeros((2, 8)),
                        SolverConfig(max_steps=20, tol=1e-9, memory=20))
    k = int(res.n_steps)
    tape = res.tape
    # ring occupancy grows 1, 2, ... with the Broyden chain
    counts = np.asarray(tape.qn_count[:k, 0])
    assert counts[0] == 1 and (np.diff(counts) >= 0).all()
    assert (np.asarray(tape.step_norm[:k]) > 0).all()


def test_tape_frozen_cells_stay_at_init_bit_for_bit():
    """Cells past the executed iterations keep the exact init values: the
    residual-inf padding IS the per-sample step count encoding."""
    f = lambda z: 0.25 * z + 3.0
    cfg = SolverConfig(max_steps=50, tol=1e-6)
    res = fixed_point_solve(f, jnp.zeros((2, 4)), cfg)
    k = int(res.n_steps)
    assert k < 50
    init = empty_tape(50, 2)
    np.testing.assert_array_equal(np.asarray(res.tape.residual[k:]),
                                  np.asarray(init.residual[k:]))
    np.testing.assert_array_equal(np.asarray(res.tape.step_norm[k:]),
                                  np.asarray(init.step_norm[k:]))
    np.testing.assert_array_equal(np.asarray(res.tape.qn_count[k:]),
                                  np.asarray(init.qn_count[k:]))


def test_tape_inert_under_jit_no_retrace_and_vmap_consistent():
    traces = []

    def f(z):
        traces.append(1)
        return 0.5 * z + 1.0

    cfg = SolverConfig(max_steps=30, tol=1e-7)
    solve = jax.jit(lambda z0: fixed_point_solve(f, z0, cfg))
    r1 = solve(jnp.zeros((2, 5)))
    n_traces = len(traces)
    r2 = solve(jnp.ones((2, 5)))  # same shape: cached program, no retrace
    assert len(traces) == n_traces
    assert np.isfinite(np.asarray(r2.tape.residual)).sum() > 0

    # vmap over a leading axis reproduces the unvmapped tape slice-for-slice
    z0s = jnp.stack([jnp.zeros((2, 5)), jnp.ones((2, 5))])
    vres = jax.vmap(lambda z0: fixed_point_solve(f, z0, cfg).tape)(z0s)
    ref = fixed_point_solve(f, jnp.zeros((2, 5)), cfg).tape
    np.testing.assert_array_equal(np.asarray(vres.residual[0]),
                                  np.asarray(ref.residual))
    np.testing.assert_array_equal(np.asarray(vres.qn_count[0]),
                                  np.asarray(ref.qn_count))


def test_tape_never_changes_the_solution():
    """The tape rides the loop state but must not feed back: solutions and
    step counts are identical to what the legacy trace already recorded."""
    rng = np.random.default_rng(1)
    A = jnp.asarray(rng.normal(size=(10, 10)) / 8.0, jnp.float32)
    g = lambda z: z @ A - z + 0.5
    res = broyden_solve(g, jnp.zeros((3, 10)),
                        SolverConfig(max_steps=30, tol=1e-8, memory=30))
    # the tape's residual buffer and the legacy trace agree where recorded
    np.testing.assert_allclose(np.asarray(res.tape.residual),
                               np.asarray(res.trace), rtol=1e-6)


# ---------------------------------------------------------------------------
# metrics registry + jit bridge
# ---------------------------------------------------------------------------


def test_registry_basics_and_snapshot_schema():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("c", {"k": "a"}).inc()
    reg.counter("c", {"k": "a"}).inc(2)
    reg.gauge("g").set(4.5)
    reg.histogram("h").observe(3.0)
    reg.series("s").record([1.0, 0.5])
    assert reg.value("c", {"k": "a"}) == 3
    snap = reg.snapshot()
    assert snap["schema"] == "repro.obs.metrics/v1"
    kinds = {m["name"]: m["kind"] for m in snap["metrics"]}
    assert kinds == {"c": "counter", "g": "gauge", "h": "histogram",
                     "s": "series"}
    h = next(m for m in snap["metrics"] if m["name"] == "h")
    assert h["count"] == 1 and h["mean"] == 3.0
    json.dumps(snap)  # must be JSON-able as-is
    with pytest.raises(TypeError):
        reg.gauge("c", {"k": "a"})  # kind mismatch on the same key


def test_metrics_bridge_lands_from_inside_jit():
    obs_metrics.set_enabled(True)
    reg = obs_metrics.default_registry()
    cfg = ImplicitConfig(forward=ForwardConfig(max_steps=15, tol=1e-6),
                         memory=8)

    def f(params, x, z):
        return jnp.tanh(x + 0.5 * z)

    # unique feature width => this trace cannot reuse a cached program
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 37)), jnp.float32)

    @jax.jit
    def run(x):
        z, stats = implicit_fixed_point(f, None, x, jnp.zeros_like(x), cfg)
        return z

    jax.block_until_ready(run(x))
    assert reg.value("solves_total", {"phase": "forward"}) == 1
    series = reg.get("solve_residual_tape", {"phase": "forward"})
    assert series is not None and len(series.last) >= 1
    # residuals decrease on this contraction
    assert series.last[-1] < series.last[0]

    # and a second call only increments the counters
    jax.block_until_ready(run(x + 1.0))
    assert reg.value("solves_total", {"phase": "forward"}) == 2


def test_metrics_bridge_off_means_zero_residue():
    """With the gate off at trace time, the compiled program carries no
    callback: enabling AFTERWARDS must not make the cached program emit."""
    reg = obs_metrics.default_registry()
    cfg = ImplicitConfig(forward=ForwardConfig(max_steps=10, tol=1e-5),
                         memory=4)

    def f(params, x, z):
        return 0.5 * z + x

    run = jax.jit(lambda x: implicit_fixed_point(
        f, None, x, jnp.zeros_like(x), cfg)[0])
    x = jnp.ones((2, 23))
    jax.block_until_ready(run(x))          # traced with the gate OFF
    obs_metrics.set_enabled(True)
    jax.block_until_ready(run(x + 1.0))    # cached: still silent
    assert reg.value("solves_total", {"phase": "forward"}) is None


def test_emit_scalar_kinds():
    obs_metrics.set_enabled(True)
    reg = obs_metrics.default_registry()

    @jax.jit
    def f(v):
        obs_metrics.emit_scalar("es_gauge", v)
        obs_metrics.emit_scalar("es_count", v, kind="counter")
        obs_metrics.emit_scalar("es_hist", v, kind="histogram")
        return v * 2

    jax.block_until_ready(f(jnp.float32(3.0)))
    jax.block_until_ready(f(jnp.float32(5.0)))
    assert reg.value("es_gauge") == 5.0
    assert reg.value("es_count") == 8.0
    assert reg.get("es_hist").count == 2


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------


def test_chrome_trace_schema_and_nesting():
    obs_tracing.set_enabled(True)
    with obs_tracing.span("outer", step=1):
        with obs_tracing.span("inner"):
            pass
        jax.block_until_ready(jax.jit(lambda v: v * 2)(jnp.ones((5,))))
    obs_tracing.instant("tick")

    trace = obs_tracing.default_recorder().to_chrome_trace()
    json.dumps(trace)
    ev = trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"
    for e in ev:
        assert "name" in e and "ph" in e and "pid" in e
        if e["ph"] != "M":
            assert isinstance(e["ts"], float) and e["ts"] >= 0
    begins = [e for e in ev if e["ph"] == "B"]
    ends = [e for e in ev if e["ph"] == "E"]
    assert len(begins) == len(ends) == 2
    assert [e["ph"] for e in ev if e["ph"] != "M"] == ["B", "B", "E", "E",
                                                       "i"]
    # the inner span nests in the outer one's window
    at = {(e["name"], e["ph"]): e["ts"] for e in ev if e["ph"] in "BE"}
    assert at["outer", "B"] <= at["inner", "B"] <= at["inner", "E"] \
        <= at["outer", "E"]
    assert next(e for e in begins if e["name"] == "outer")["args"] == {
        "step": 1}
    # metadata events name the process/thread for Perfetto
    assert {e["name"] for e in ev if e["ph"] == "M"} == {
        "process_name", "thread_name"}


def test_tracing_disabled_is_silent():
    with obs_tracing.span("ghost"):
        with obs_tracing.span("phantom", step=2):
            pass
        obs_tracing.instant("nope")
    assert obs_tracing.default_recorder().events() == []


def _host_events(logdir) -> list:
    """(name, stats) of every event on the host planes of the profiler
    trace written under ``logdir``."""
    from pathlib import Path

    from jax.profiler import ProfileData

    (pb,) = Path(logdir).rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(pb))
    return [(ev.name, dict(ev.stats)) for plane in pd.planes
            if plane.name.startswith("/host") for line in plane.lines
            for ev in line.events]


@pytest.mark.parametrize("recording", [False, True])
def test_span_lands_in_profiler_trace(tmp_path, recording):
    """A span is a profiler annotation whether or not the Chrome-trace
    recorder is on: it appears on the host plane with its arguments."""
    obs_tracing.set_enabled(recording)
    with jax.profiler.trace(str(tmp_path)):
        with obs_tracing.span("obs_probe_span", step=7):
            jax.block_until_ready(jnp.ones((3,)) * 2)
    hits = [st for name, st in _host_events(tmp_path)
            if name == "obs_probe_span"]
    assert hits == [{"step": 7}]
    assert len(obs_tracing.default_recorder().events()) == (
        2 if recording else 0)


# ---------------------------------------------------------------------------
# serving telemetry
# ---------------------------------------------------------------------------


def _tiny_cfg():
    cfg = smoke_config("minicpm-2b")
    return dataclasses.replace(
        cfg, num_layers=2, d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
        vocab_size=128, head_dim=16)


def test_serving_histograms_count_each_request_exactly_once():
    cfg = _tiny_cfg()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    loop = ServeLoop(params, cfg, CTX, slots=2, max_len=64, eos_id=-1)
    reqs = [Request(uid=i, prompt=[3 + i, 4, 5], max_new_tokens=3)
            for i in range(5)]
    loop.drain(reqs)
    assert all(r.done for r in reqs)

    reg = obs_metrics.default_registry()
    assert reg.value("serve_requests_submitted") == 5
    assert reg.value("serve_requests_completed") == 5
    ttft = reg.get("serve_ttft_ms")
    assert ttft.count == 5 and ttft.min >= 0
    # every generated token lands once: 3 per request, 1 of which comes
    # from prefill (so 2 decode-tick observations each)
    assert reg.value("serve_tokens_total") == 10
    assert reg.get("serve_token_ms").count == 10
    # legacy attributes stay in lockstep with the registry mirror
    assert reg.value("serve_prefill_calls") == loop.prefill_calls
    assert reg.value("serve_prefill_requests") == loop.prefill_requests == 5


# ---------------------------------------------------------------------------
# CarryCache staleness policy
# ---------------------------------------------------------------------------


def test_carry_cache_staleness_evicts_old_rows():
    make_cold = lambda: init_solve_carry(3, (4,), 2)
    cc = CarryCache(make_cold, 3, max_age=2)
    reg = obs_metrics.default_registry()

    aged = dataclasses.replace(
        cc.carry,
        warm=jnp.asarray([True, True, True]),
        age=jnp.asarray([1, 2, 5], jnp.int32),
    )
    cc.update(aged)
    # only the row past max_age resets; at the bound survives
    assert cc.evictions_by_reason["stale"] == 1
    assert reg.value("carry_evictions_total", {"reason": "stale"}) == 1
    warm = np.asarray(cc.carry.warm)
    assert warm.tolist() == [True, True, False]
    assert int(np.asarray(cc.carry.age)[2]) == 0

    # ownership / release eviction reasons keep their own counters
    cc.lease(0, "req-a")
    cc.release(0)
    assert cc.evictions_by_reason["ownership"] == 1
    assert cc.evictions_by_reason["release"] == 1
    assert reg.value("carry_evictions_total", {"reason": "release"}) == 1


def test_carry_cache_rejects_bad_max_age():
    make_cold = lambda: init_solve_carry(2, (4,), 2)
    with pytest.raises(ValueError):
        CarryCache(make_cold, 2, max_age=0)


def test_carry_cache_no_staleness_without_max_age():
    make_cold = lambda: init_solve_carry(2, (4,), 2)
    cc = CarryCache(make_cold, 2)
    aged = dataclasses.replace(
        cc.carry, warm=jnp.asarray([True, True]),
        age=jnp.asarray([100, 100], jnp.int32))
    cc.update(aged)
    assert cc.evictions_by_reason["stale"] == 0
    assert np.asarray(cc.carry.warm).all()


# ---------------------------------------------------------------------------
# checkpoint-lean mode
# ---------------------------------------------------------------------------


class _LR(NamedTuple):
    u: jax.Array
    v: jax.Array


class _Carry(NamedTuple):
    z: jax.Array
    lowrank: _LR


class _State(NamedTuple):
    w: jax.Array
    carry: _Carry


def test_checkpoint_lean_omits_ring_and_restore_zero_fills(tmp_path):
    state = _State(
        w=jnp.arange(6.0).reshape(2, 3),
        carry=_Carry(
            z=jnp.ones((2, 3)),
            lowrank=_LR(u=jnp.full((4, 2, 3), 7.0),
                        v=jnp.full((4, 2, 3), 9.0)),
        ),
    )
    reg = obs_metrics.default_registry()
    mgr = CheckpointManager(str(tmp_path), async_save=False,
                            omit_prefixes=(".carry.lowrank.u",
                                           ".carry.lowrank.v"))
    mgr.save(1, state)

    # the ring bytes were counted and the manifest records the omission
    omitted = reg.value("checkpoint_bytes_omitted")
    assert omitted == 2 * 4 * 2 * 3 * 4  # two f32 (4,2,3) leaves
    assert reg.value("checkpoint_leaves_omitted") == 2
    manifest = json.load(open(tmp_path / "step_1" / "manifest.json"))
    assert manifest["omitted"]["bytes"] == omitted
    assert not any(k.startswith(".carry.lowrank")
                   for k in manifest["keys"])

    # restore zero-fills the omitted ring, everything else roundtrips
    template = jax.tree_util.tree_map(jnp.zeros_like, state)
    step, restored, _ = mgr.restore(
        template, fill_missing_prefixes=(".carry",))
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored.w),
                                  np.asarray(state.w))
    np.testing.assert_array_equal(np.asarray(restored.carry.z),
                                  np.asarray(state.carry.z))
    assert (np.asarray(restored.carry.lowrank.u) == 0).all()
    assert (np.asarray(restored.carry.lowrank.v) == 0).all()


def test_checkpoint_full_mode_unchanged(tmp_path):
    state = _State(
        w=jnp.arange(6.0).reshape(2, 3),
        carry=_Carry(z=jnp.ones((2, 3)),
                     lowrank=_LR(u=jnp.full((4, 2, 3), 7.0),
                                 v=jnp.full((4, 2, 3), 9.0))),
    )
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, state)
    template = jax.tree_util.tree_map(jnp.zeros_like, state)
    _, restored, _ = mgr.restore(template)
    np.testing.assert_array_equal(np.asarray(restored.carry.lowrank.u),
                                  np.asarray(state.carry.lowrank.u))
