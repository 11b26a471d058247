#!/usr/bin/env python3
"""Bring-up check of the DEQ language model on a TPU, at the published width
of ``minicpm-2b`` (d=2304, 36 heads x 64, d_ff=5760, vocab 122753; DEQ form:
4 weight-tied blocks, Broyden forward, ``shine_fallback`` backward), with
random weights made from a seed.

    python chip_smoke.py              # one chip: device, kernels, train, serve
    python chip_smoke.py --chips 4    # four chips: one sharded DEQ train step
                                      # on a (data=2, model=2) mesh against the
                                      # same step on one chip

Every phase runs in this one process (a chip belongs to one process) and
prints its own lines; any failed check exits non-zero.  The last line of a
passing run is ``{"ok": true, "device": {...}}``.  Times printed here are
smoke timings of a three-step run, not benchmarks.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "minicpm-2b"
TRAIN_ARGS = ["--arch", ARCH, "--deq", "--backward", "shine_fallback",
              "--batch", "8", "--seq", "512", "--log-every", "1"]
SERVE_ARGS = ["--arch", ARCH, "--deq", "--requests", "8", "--slots", "4",
              "--max-len", "512", "--max-new-tokens", "8", "--prefix-cache",
              "--prefix-cache-slots", "4",
              # 196 shared tokens + 4 own: 200-token prompts (not a multiple
              # of the 128-row attention block) that hit the prefix store
              "--shared-prefix", "196"]
# tests/test_kernels.py tolerances: bf16 operands, and the normalized error
# of the fused Broyden step
BF16_TOL = dict(rtol=2e-2, atol=2e-2)
BROYDEN_TOL = 2e-2


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def phase_device():
    import jax

    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform!r}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"[device] jax {jax.__version__} jaxlib "
          f"{importlib.metadata.version('jaxlib')} libtpu {libtpu}; "
          f"{len(jax.devices())} x {dev.device_kind}", flush=True)
    return dev


# ---------------------------------------------------------------------------
# kernels: every op through ops.* with automatic dispatch vs its oracle
# ---------------------------------------------------------------------------


def _allclose(got, want, rtol, atol) -> tuple[bool, float]:
    import jax.numpy as jnp

    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    excess = jnp.max(jnp.abs(got - want) - (atol + rtol * jnp.abs(want)))
    return bool(excess <= 0.0), float(jnp.max(jnp.abs(got - want)))


def _normalized_err(got, want) -> float:
    import jax.numpy as jnp

    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / (1.0 + jnp.max(jnp.abs(want))))


def _run_kernel(name, fn, oracle, args, compare):
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{name}: no Pallas kernel (tpu_custom_call) in the compiled program")
    got = compiled(*args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(oracle)(*args)
    outs = list(zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)))
    check(len(outs) == len(jax.tree_util.tree_leaves(want)),
          f"{name}: output structure differs from the oracle")
    worst = 0.0
    for g, w in outs:
        check(g.shape == w.shape and g.dtype == w.dtype,
              f"{name}: output {g.shape} {g.dtype} vs oracle {w.shape} "
              f"{w.dtype}")
        ok, err = compare(g, w)
        worst = max(worst, err)
        check(ok, f"{name}: differs from its oracle (max abs err {err:.3e})")
    print(f"[kernels] {name}: tpu_custom_call present, max err vs oracle "
          f"{worst:.3e}", flush=True)


def phase_kernels(cfg) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))
    normal = lambda shape, dt=jnp.float32, scale=1.0: (
        scale * jax.random.normal(next(keys), shape)).astype(dt)
    close = lambda g, w: _allclose(g, w, **BF16_TOL)
    m, b, d = cfg.deq.memory, 8, cfg.d_model
    for label, feat in (("decode", d), ("train", 512 * d)):
        shape = (m, b, feat)
        # U/V scaled so H's coefficients are O(1), as in a live ring
        u = normal(shape, jnp.bfloat16, feat ** -0.5)
        v = normal(shape, jnp.bfloat16, feat ** -0.5)
        x, s, hy, hg = (normal((b, feat)) for _ in range(4))
        alpha = jnp.float32(0.7)
        count = jax.random.randint(next(keys), (b,), 0, 2 * m)
        slot = (count % m).astype(jnp.int32)
        mask = (jnp.arange(m)[:, None] < jnp.minimum(count, m)[None, :]
                ).astype(jnp.float32)
        active = (jnp.arange(b) % 4 != 3).astype(jnp.float32)
        _run_kernel(
            f"qn_apply[{label} {shape}]",
            lambda u, v, x, mk: ops.qn_apply(u, v, x, alpha, mk),
            lambda u, v, x, mk: ref.qn_apply_ref(u, v, x, alpha, mk),
            (u, v, x, mask), close)
        xs = jnp.stack([x, s])
        _run_kernel(
            f"qn_apply_multi[{label} {shape}]",
            lambda u, v, xs, mk: ops.qn_apply_multi(u, v, xs, alpha, mk,
                                                    (False, True)),
            lambda u, v, xs, mk: ref.qn_apply_multi_ref(u, v, xs, alpha, mk,
                                                        (False, True)),
            (u, v, xs, mask), close)
        inv_den = normal((b,))
        _run_kernel(
            f"lowrank_append[{label} {shape}]",
            lambda u, v, s, hy, x, i, sl, up: ops.lowrank_append(
                u, v, s, hy, x, i, sl, up),
            ref.lowrank_append_ref,
            (u, v, s, hy, x, inv_den, slot, active), close)
        _run_kernel(
            f"broyden_step[{label} {shape}]",
            lambda u, v, x, s, hg, mk, sl, ac: ops.broyden_step(
                u, v, x, s, hg, alpha, mk, sl, ac, 1e-8),
            lambda u, v, x, s, hg, mk, sl, ac: ref.broyden_step_ref(
                u, v, x, s, hg, alpha, mk, sl, ac, 1e-8),
            (u, v, x, s, hg, mask, slot, active),
            lambda g, w: (_normalized_err(g, w) < BROYDEN_TOL,
                          _normalized_err(g, w)))
        del u, v, x, s, hy, hg, xs

    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    for seq in (512, 300):
        q, k, vv = (normal((b, seq, n, hd), jnp.bfloat16)
                    for n in (h, kv, kv))
        kv_len = jax.random.randint(next(keys), (b,), seq // 2, seq + 1)
        _run_kernel(
            f"attention[causal ({b}, {seq}, {h}, {hd}), kv_length]",
            lambda q, k, v, ln: ops.attention(q, k, v, causal=True,
                                              kv_length=ln),
            lambda q, k, v, ln: ref.attention_ref(q, k, v, causal=True,
                                                  kv_length=ln),
            (q, k, vv, kv_len), close)
    t = 512
    q = normal((b, h, hd), jnp.bfloat16)
    k, vv = (normal((b, t, kv, hd), jnp.bfloat16) for _ in range(2))
    kv_len = jax.random.randint(next(keys), (b,), 1, t + 1)
    _run_kernel(f"decode_attention[q ({b}, {h}, {hd}), cache T={t}]",
                ops.decode_attention, ref.decode_attention_ref,
                (q, k, vv, kv_len), close)
    x = normal((b, 512, d), jnp.bfloat16)
    w = normal((d,), jnp.bfloat16)
    _run_kernel(f"rmsnorm[({b}, 512, {d})]",
                lambda x, w: ops.rmsnorm(x, w, cfg.norm_eps),
                lambda x, w: ref.rmsnorm_ref(x, w, cfg.norm_eps),
                (x, w), close)


# ---------------------------------------------------------------------------
# train / serve through the launchers
# ---------------------------------------------------------------------------


def _rollbacks() -> float:
    from repro.obs import metrics as obs_metrics

    return obs_metrics.default_registry().counter(
        "train_rollbacks_total").value


def _train(argv: list[str]):
    """Run the training launcher; fail on any non-finite loss, skipped
    update or rollback.  Returns ``(state, history)``."""
    from repro.launch import train

    before = _rollbacks()
    state, history = train.main(argv)
    check(len(history) > 0, "the trainer logged no step")
    for step, met in history:
        check(math.isfinite(met["loss"]) and math.isfinite(met["grad_norm"]),
              f"step {step}: non-finite loss {met['loss']} / grad norm "
              f"{met['grad_norm']}")
        check(met.get("update_skipped", 0.0) == 0.0
              and met.get("consec_skips", 0.0) == 0.0,
              f"step {step}: the update was skipped as non-finite")
    check(_rollbacks() == before, "the trainer rolled back")
    return state, history


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def phase_train(cfg, dev) -> None:
    _, history = _train(TRAIN_ARGS + ["--steps", "3"])
    first = history[0][1]["loss"]
    expect = math.log(cfg.vocab_size)
    check(abs(first - expect) <= 1.5,
          f"first loss {first:.4f} is not within 1.5 of ln(V) = {expect:.4f}")
    for step, met in history:
        print(f"[train] step {step}: loss {met['loss']:.6f} grad_norm "
              f"{met['grad_norm']:.6f} deq_steps {met.get('deq_steps', -1):.0f}"
              f" smoke timing {met['step_time_s'] * 1e3:.1f} ms/step"
              f"{' (includes compilation)' if step == 1 else ''}",
              flush=True)
    print(f"[train] 3 finite steps at d={cfg.d_model}, no skip, no rollback;"
          f" peak_bytes_in_use {_peak_bytes(dev)}", flush=True)


def phase_serve(dev) -> None:
    from repro.launch import serve
    from repro.obs import metrics as obs_metrics

    reqs = serve.main(SERVE_ARGS)
    want = int(SERVE_ARGS[SERVE_ARGS.index("--max-new-tokens") + 1])
    check(len(reqs) == int(SERVE_ARGS[SERVE_ARGS.index("--requests") + 1]),
          "the serving loop returned the wrong number of requests")
    for r in reqs:
        check(r.error is None, f"request {r.uid} failed: {r.error}")
        check(not r.retried, f"request {r.uid} needed a cold retry")
        check(len(r.out) == want,
              f"request {r.uid} returned {len(r.out)} of {want} tokens")
    syncs = sum(m["value"]
                for m in obs_metrics.default_registry().snapshot()["metrics"]
                if m["name"] == "host_syncs_total")
    check(syncs == 0, f"{syncs} blocking host syncs in the async drain")
    print(f"[serve] {len(reqs)} requests x {want} tokens, prompts of "
          f"{len(reqs[0].prompt)} tokens, no error, 0 host syncs; "
          f"peak_bytes_in_use {_peak_bytes(dev)}", flush=True)


def phase_four_chips(cfg) -> None:
    """One DEQ train step on a (data=2, model=2) mesh vs the same step (same
    batch, same seed) on one chip."""
    import gc

    import jax

    check(jax.device_count() >= 4,
          f"--chips 4 needs four devices, have {jax.device_count()}")
    argv = TRAIN_ARGS + ["--steps", "1"]
    state, hist = _train(argv + ["--mesh", "2x2"])
    sharded = hist[0][1]
    ring = state.carry.lowrank.u
    ring_dev = {s.device.id: s.data.nbytes for s in ring.addressable_shards}
    peaks = {d.id: _peak_bytes(d) for d in jax.devices()[:4]}
    del state, ring
    gc.collect()
    state, hist = _train(argv)
    single = hist[0][1]
    ring_one = state.carry.lowrank.u.nbytes
    del state
    for key in ("loss", "grad_norm"):
        a, b = sharded[key], single[key]
        print(f"[4 chips] {key}: mesh 2x2 {a:.6f} vs one chip {b:.6f}",
              flush=True)
        check(math.isclose(a, b, rel_tol=BF16_TOL["rtol"]),
              f"{key} on the mesh differs from one chip beyond bf16 tolerance")
    print(f"[4 chips] deq_steps: mesh {sharded.get('deq_steps', -1):.0f}, "
          f"one chip {single.get('deq_steps', -1):.0f}", flush=True)
    for dev_id in sorted(ring_dev):
        print(f"[4 chips] device {dev_id}: qN ring U {ring_dev[dev_id]} bytes "
              f"(one chip: {ring_one}), peak_bytes_in_use {peaks[dev_id]}",
              flush=True)
        check(ring_dev[dev_id] * 2 <= ring_one,
              f"device {dev_id} holds more than half of the ring: the ring "
              "is not batch-sharded")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-step comparison on four "
                         "chips")
    args = ap.parse_args(argv)
    try:
        dev = phase_device()
        import jax

        from repro.configs.registry import get_config
        from repro.launch.compile_cache import use_persistent_cache

        print(f"[device] compilation cache: {use_persistent_cache()}",
              flush=True)
        cfg = get_config(ARCH, deq=True)
        if args.chips == 4:
            phase_four_chips(cfg)
        else:
            phase_kernels(cfg)
            phase_train(cfg, dev)
            phase_serve(dev)
    except SmokeFailure as e:
        print(f"FAIL: {e}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
