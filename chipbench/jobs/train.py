"""The training job: the jitted, donated train step of the repository's
``Trainer`` at the mix's batch and sequence length, driven back to back.

Set-up builds one train state from the seed's weights and drives it through
the first ``checked_steps`` steps (the readings the check compares); the
window then continues the same state.  The window dispatches steps with at
most ``in_flight`` unfinished, and ends with ``block_until_ready``; the
rate is every token of every step dispatched over the whole window.

Where the mix names a mesh, the weights, the state and each batch are made
in the layouts the program's sharded path gives them (its ``Trainer``'s),
so that no chip holds the whole model or optimizer state, and the
reference is spread over the same chips.
"""

from __future__ import annotations

import collections
import gc
import time

import jax
import jax.numpy as jnp

from chipbench import model_ref, program, traffic
from chipbench import trace as trace_mod
from chipbench.harness import memory_peak
from chipbench.runctx import Run
from chipbench.weights import key_from_seed, make_params

# the program's modules (importable once ``program`` has set the path)
from repro.configs.base import TrainConfig  # noqa: E402
from repro.launch.steps import TrainState  # noqa: E402
from repro.optim.optimizers import adamw_init  # noqa: E402
from repro.runtime.trainer import Trainer  # noqa: E402

B1 = 0.9  # the program's AdamW first-moment decay


def train_config(mix: dict, cfg) -> TrainConfig:
    """The program's training settings for the mix; on a mesh with ZeRO-1,
    as ``launch/train.py --mesh`` sets it."""
    return TrainConfig(
        steps=10 ** 9, global_batch=mix["batch"], seq_len=mix["seq"],
        lr=mix["lr"], warmup_steps=mix["warmup_steps"],
        schedule=mix["schedule"], z_loss=mix["z_loss"],
        clip_norm=mix["clip_norm"], weight_decay=mix["weight_decay"],
        deq_carry=mix["deq_carry"], qn_dtype=cfg.deq.qn_dtype,
        zero1=traffic.mesh_shape(mix) is not None)


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(a.astype(jnp.float32).ravel())
                      for a in jax.tree_util.tree_leaves(tree)])


@jax.jit
def _change_norms(new, old):
    return _leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, old))


def placed(fn, shardings):
    """``fn`` jitted, its outputs laid out as ``shardings`` (None: where
    JAX puts them, on one device)."""
    if shardings is None:
        return jax.jit(fn)
    return jax.jit(fn, out_shardings=shardings)


def build(run: Run, step_wrapper=None):
    """The jitted step, the state after the checked steps, and the
    program's readings of them."""
    spec, mix = run.spec, run.mix
    cfg = program.model_config(spec)
    tcfg = train_config(mix, cfg)
    ctx = program.ctx(cfg, mix, run.devices)
    trainer = Trainer(cfg, tcfg, ctx)
    pshard = None if ctx.mesh is None else program.param_shardings(cfg, ctx)
    params = make_params(spec, run.seed, shardings=pshard)
    program.check_layout(cfg, params)
    step = trainer._train_step
    if step_wrapper is not None:
        step = step_wrapper(step)

    def initial_state(p):
        return TrainState(jnp.zeros((), jnp.int32), p, adamw_init(p),
                          program.lm.deq_solve_carry(cfg, mix["batch"],
                                                     mix["seq"]),
                          jnp.zeros((), jnp.int32))

    state = placed(initial_state, trainer.state_sharding)(params)
    del params
    bshard = program.batch_sharding(ctx)
    batch_at = placed(traffic.train_batch_fn(mix, spec.vocab),
                      None if bshard is None else (bshard, bshard))
    data_key = jax.random.fold_in(key_from_seed(run.seed), 1)

    def feed(i):
        tokens, targets = batch_at(data_key, i)
        return {"tokens": tokens, "targets": targets}

    readings = {"loss": [], "grad": None, "outside": None, "change": None,
                "deq_steps": [], "skipped": []}
    for i in range(mix["checked_steps"]):
        state, met = step(state, feed(i))
        readings["loss"].append(float(met["loss"]))
        readings["deq_steps"].append(float(met.get("deq_steps", -1.0)))
        readings["skipped"].append(float(met.get("update_skipped", 0.0)))
        if i == 0:
            readings["grad"] = [float(x) / (1.0 - B1)
                                for x in _leaf_norms(state.opt.mu)]
            readings["outside"] = {
                k: m / (1.0 - B1)
                for k, m in model_ref.outside_group(state.opt.mu).items()}
    p0 = make_params(spec, run.seed, shardings=pshard)
    readings["change"] = [float(x) for x in _change_norms(state.params, p0)]
    del p0
    return step, state, feed, readings, cfg


def window(run: Run, step, state, feed, first: int, seconds: float,
           traced: bool):
    """Dispatch steps for ``seconds`` of host time, then wait for all of
    them.  Returns the state, steps run, the window's length, and the
    per-step metrics."""
    in_flight = run.mix["in_flight"]
    pending: collections.deque = collections.deque()
    mets = []
    i = first
    capture = trace_mod.Capture() if traced else None
    if capture:
        capture.start()
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("data"):
            batch = feed(i)
        with jax.profiler.TraceAnnotation("train_step"):
            state, met = step(state, batch)
        mets.append(met)
        pending.append(met["loss"])
        i += 1
        if len(pending) > in_flight:
            with jax.profiler.TraceAnnotation("sync"):
                pending.popleft().block_until_ready()
        if time.perf_counter() - t0 >= seconds:
            break
    with jax.profiler.TraceAnnotation("sync"):
        jax.block_until_ready((state, mets[-1]))
    t1 = time.perf_counter()
    if capture:
        run.reduced_trace = capture.stop(t0, t1)
    return state, i - first, t1 - t0, mets


def reference_readings(run: Run, precision: str, rows: slice | None = None,
                       backward: str | None = None):
    """The plain reference over the first steps, on the same weights and
    batches (both made here from the seed), with the configured backward
    unless ``backward`` names another."""
    spec, mix = run.spec, run.mix
    batch_at = jax.jit(traffic.train_batch_fn(mix, spec.vocab))
    data_key = jax.random.fold_in(key_from_seed(run.seed), 1)
    batches = []
    for i in range(mix["checked_steps"]):
        tokens, targets = batch_at(data_key, i)
        if rows is not None:
            tokens, targets = tokens[rows], targets[rows]
        batches.append((tokens, targets))
    tcfg = {k: mix[k] for k in ("lr", "warmup_steps", "z_loss", "clip_norm",
                                "weight_decay")}
    tcfg.update(b1=B1, b2=0.95, eps=1e-8)
    return model_ref.train_readings(
        lambda shardings: make_params(spec, run.seed, shardings=shardings),
        batches, spec, tcfg, precision, backward or spec.backward,
        run.devices)


def gaps(prog: dict, ref: dict) -> dict:
    """The readings of a run against the reference's:

    * ``first_loss_gap``, ``loss_gap``: the first step's relative loss gap,
      and the worst step's;
    * ``grad_gap``, ``change_gap``: the worst leaf's gap of norms (first
      gradient; change over the steps), against the larger of that leaf's
      reference norm and the median leaf's.  Leaves whose reference
      gradient is under a thousandth of the median leaf's are left out;
    * ``grad_angle``: the worst, over the leaves outside the DEQ group, of
      one less the cosine between the two first gradients."""
    import numpy as np

    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    g_ref = np.asarray(ref["grad"])
    keep = g_ref >= 1e-3 * np.median(g_ref)

    def worst(p, r):
        p, r = np.asarray(p)[keep], np.asarray(r)[keep]
        base = np.maximum(r, np.median(r))
        return float(np.max(np.abs(p - r) / base))

    def angle(p, r):
        p, r = p.astype(np.float64).ravel(), r.astype(np.float64).ravel()
        den = np.linalg.norm(p) * np.linalg.norm(r)
        return 1.0 - float(p @ r / den) if den > 0 else 1.0

    first = abs(prog["loss"][0] - ref["loss"][0]) / abs(ref["loss"][0])
    angles = {k: angle(prog["outside"][k], ref["outside"][k])
              for k in ref["outside"]}
    return {"first_loss_gap": float(first), "loss_gap": float(loss),
            "grad_gap": worst(prog["grad"], ref["grad"]),
            "change_gap": worst(prog["change"], ref["change"]),
            "grad_angle": max(angles.values()), "angle_by_leaf": angles,
            "leaves_left_out": int((~keep).sum())}


def judge(run: Run, g: dict) -> None:
    """Hold each reading that the cell's limits name to its limit; record
    the others."""
    run.counters["not_compared"] = {
        k: v for k, v in g.items() if k not in run.limits}
    for name in run.limits:
        run.check(name, g[name])


def run(run: Run, compiles, step_wrapper=None) -> None:
    mix, spec = run.mix, run.spec
    step, state, feed, readings, cfg = build(run, step_wrapper)
    first = mix["checked_steps"]
    tokens_per_step = mix["batch"] * mix["seq"]
    seconds = mix["trace_seconds"] if run.trace else run.seconds
    run.setup_end = time.time()
    compiles.active = True
    state, n, dt, mets = window(run, step, state, feed, first, seconds,
                                run.trace)
    compiles.active = False
    deq = [float(m.get("deq_steps", jnp.nan)) for m in mets]
    skipped = sum(float(m.get("update_skipped", 0.0)) for m in mets)
    run.window_s = dt
    run.attempted, run.failed = n, int(skipped)
    run.end_to_end["train_tokens_per_s"] = n * tokens_per_step / dt
    run.counters.update(
        steps=n, tokens_per_step=tokens_per_step, deq_steps=deq,
        compiles_in_window=compiles.count)
    run.memory_peak_bytes = memory_peak(run.devices)
    del state, step, mets
    gc.collect()
    jax.clear_caches()
    ref = reference_readings(run, "f32")
    judge(run, gaps(readings, ref))
