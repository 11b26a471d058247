"""Language-model assembly for all assigned architecture families.

A model is a list of *stack groups*; each group is ``count`` blocks of one
kind, stored stacked (leading ``layers`` axis) so the same parameter tree
serves three execution modes:

  * scan    — ``lax.scan`` over the stacked params (+remat): training default
  * unroll  — python loop (dry-run costing mode: XLA counts loop bodies once,
              so roofline numbers must come from unrolled HLO; DESIGN.md)
  * deq     — the paper's technique: a weight-tied group of ``deq.num_blocks``
              blocks is solved to a fixed point with SHINE-family backward

Families:
  dense/audio/vlm : uniform attn+MLP blocks (audio = encoder-only, stub
                    frame embeddings; vlm = stub patch embeddings + decoder)
  moe             : first_k dense blocks then attn+MoE blocks
  hybrid (zamba2) : units of (attn_every Mamba2 blocks + one SHARED attention
                    block — the shared block is weight-tied across units)
  ssm (xlstm)     : units of (slstm_every-1 mLSTM + 1 sLSTM)
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.lowrank import LowRank
from repro.kernels import ops as kernel_ops
from repro.implicit import (
    ImplicitConfig,
    SolveCarry,
    batched_solve,
    implicit_fixed_point,
    init_solve_carry,
    seed_carry,
)
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (
    cross_entropy,
    embed_decl,
    embed_tokens,
    lm_logits,
    mlp,
    mlp_decl,
    norm_decl,
    rmsnorm,
)
from repro.parallel.sharding import ParamDecl, ShardCtx, init_tree

Array = jax.Array


# ---------------------------------------------------------------------------
# Stack structure
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StackGroup:
    kind: str       # attn_mlp | attn_moe | zamba_unit | xlstm_unit
    count: int      # number of repetitions (stacked/scanned)


def stack_groups(cfg: ModelConfig) -> list[StackGroup]:
    if cfg.family in ("dense", "audio", "vlm"):
        return [StackGroup("attn_mlp", cfg.num_layers)]
    if cfg.family == "moe":
        g = []
        if cfg.moe.first_k_dense:
            g.append(StackGroup("attn_mlp", cfg.moe.first_k_dense))
        g.append(StackGroup("attn_moe", cfg.num_layers - cfg.moe.first_k_dense))
        return g
    if cfg.family == "hybrid":
        period = cfg.ssm.attn_every or cfg.num_layers
        assert cfg.num_layers % period == 0, (cfg.num_layers, period)
        return [StackGroup("zamba_unit", cfg.num_layers // period)]
    if cfg.family == "ssm":
        period = cfg.xlstm.slstm_every
        assert cfg.num_layers % period == 0, (cfg.num_layers, period)
        return [StackGroup("xlstm_unit", cfg.num_layers // period)]
    raise ValueError(cfg.family)


def _stack_decl(decl: Any, count: int) -> Any:
    """Prepend a stacked `layers` axis to every ParamDecl in a tree."""
    return jax.tree_util.tree_map(
        lambda d: ParamDecl((count,) + d.shape, ("layers",) + d.axes,
                            init=d.init, scale=d.scale, dtype=d.dtype),
        decl,
        is_leaf=lambda x: isinstance(x, ParamDecl),
    )


def _attn_decl(cfg: ModelConfig) -> dict:
    return attn.mla_decl(cfg) if cfg.attn_type == "mla" else attn.gqa_decl(cfg)


def _unit_decl(cfg: ModelConfig, kind: str) -> dict:
    if kind == "attn_mlp":
        ff = cfg.moe.dense_d_ff if (cfg.family == "moe" and cfg.moe.dense_d_ff) else cfg.d_ff
        return {
            "ln1": norm_decl(cfg.d_model), "attn": _attn_decl(cfg),
            "ln2": norm_decl(cfg.d_model), "mlp": mlp_decl(cfg, d_ff=ff),
        }
    if kind == "attn_moe":
        return {
            "ln1": norm_decl(cfg.d_model), "attn": _attn_decl(cfg),
            "ln2": norm_decl(cfg.d_model), "moe": moe_mod.moe_decl(cfg),
        }
    if kind == "zamba_unit":
        return {
            "mamba": _stack_decl(
                {"ln": norm_decl(cfg.d_model), "m": ssm_mod.mamba2_decl(cfg)},
                cfg.ssm.attn_every,
            ),
        }
    if kind == "xlstm_unit":
        n_m = cfg.xlstm.slstm_every - 1
        return {
            "mlstm": _stack_decl(
                {"ln": norm_decl(cfg.d_model), "m": xlstm_mod.mlstm_decl(cfg)}, n_m
            ),
            "slstm": {"ln": norm_decl(cfg.d_model), "s": xlstm_mod.slstm_decl(cfg)},
        }
    raise ValueError(kind)


def model_decl(cfg: ModelConfig) -> dict:
    decl: dict[str, Any] = {"embed": embed_decl(cfg), "final_norm": norm_decl(cfg.d_model)}
    if cfg.family == "audio":
        # classifier head over the real (unpadded) class inventory
        decl["embed"] = {
            "embedding": ParamDecl((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
                                   init="normal", scale=0.02),
            "lm_head": ParamDecl((cfg.d_model, cfg.padded_vocab), ("embed", "vocab")),
        }
    if cfg.deq.enabled:
        decl["deq_blocks"] = _stack_decl(_unit_decl(cfg, _deq_kind(cfg)), cfg.deq.num_blocks)
    else:
        for i, grp in enumerate(stack_groups(cfg)):
            decl[f"group{i}"] = _stack_decl(_unit_decl(cfg, grp.kind), grp.count)
    if cfg.family == "hybrid":
        decl["shared_attn"] = {
            "ln1": norm_decl(cfg.d_model), "attn": _attn_decl(cfg),
            "ln2": norm_decl(cfg.d_model), "mlp": mlp_decl(cfg),
        }
    return decl


def _deq_kind(cfg: ModelConfig) -> str:
    return {"dense": "attn_mlp", "audio": "attn_mlp", "vlm": "attn_mlp",
            "moe": "attn_moe", "hybrid": "zamba_unit", "ssm": "xlstm_unit"}[cfg.family]


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    return init_tree(model_decl(cfg), key, dtype=dtype)


def param_count(cfg: ModelConfig) -> int:
    decl = model_decl(cfg)
    leaves = jax.tree_util.tree_leaves(
        decl, is_leaf=lambda x: isinstance(x, ParamDecl)
    )
    return sum(int(functools.reduce(lambda a, b: a * b, d.shape, 1)) for d in leaves)


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def _apply_attention(p, x, cfg, ctx, positions, cache, cache_index):
    fn = attn.mla_attention if cfg.attn_type == "mla" else attn.gqa_attention
    return fn(p, x, cfg, ctx, positions, cache, cache_index)


def apply_unit(
    kind: str,
    params: dict,
    x: Array,
    cfg: ModelConfig,
    ctx: ShardCtx,
    positions: Array,
    cache: Any = None,
    cache_index: Array | None = None,
    shared: dict | None = None,
):
    """One stack unit. Returns (x, new_cache, aux_losses)."""
    aux = {"moe_aux": jnp.float32(0.0), "moe_z": jnp.float32(0.0)}

    # SP gather point: block inputs are pinned full-seq (a no-op layout when
    # SP is off); block outputs are pinned seq_res (the reduce-scatter
    # point). Without explicit pins GSPMD bounces between layouts inside the
    # block (~30 boundary crossings/layer measured — EXPERIMENTS.md §Perf A6).
    def gathered(h):
        return ctx.constrain(h, ("batch", "seq", "embed_act"))

    if kind in ("attn_mlp", "attn_moe"):
        a_out, new_kv = _apply_attention(
            params["attn"], gathered(rmsnorm(params["ln1"], x, cfg.norm_eps)),
            cfg, ctx, positions, cache, cache_index,
        )
        x = x + a_out
        h = gathered(rmsnorm(params["ln2"], x, cfg.norm_eps))
        if kind == "attn_mlp":
            x = x + mlp(params["mlp"], h, cfg, ctx)
        else:
            m_out, m_aux = moe_mod.moe_block(params["moe"], h, cfg, ctx)
            x = x + m_out
            aux = {k: aux[k] + m_aux[k] for k in aux}
        return x, new_kv, aux

    if kind == "zamba_unit":
        n_m = cfg.ssm.attn_every
        m_caches = []
        for j in range(n_m):
            pj = jax.tree_util.tree_map(lambda a: a[j], params["mamba"])
            cj = None if cache is None else jax.tree_util.tree_map(
                lambda a: a[j], cache["mamba"]
            )
            out, mc = ssm_mod.mamba2_block(
                pj["m"], gathered(rmsnorm(pj["ln"], x, cfg.norm_eps)),
                cfg, ctx, cj
            )
            x = x + out
            m_caches.append(mc)
        # shared (weight-tied) attention block
        a_out, new_kv = _apply_attention(
            shared["attn"], gathered(rmsnorm(shared["ln1"], x, cfg.norm_eps)),
            cfg, ctx,
            positions, None if cache is None else cache["attn"], cache_index,
        )
        x = x + a_out
        x = x + mlp(shared["mlp"],
                    gathered(rmsnorm(shared["ln2"], x, cfg.norm_eps)), cfg, ctx)
        new_cache = None
        if cache is not None:
            stacked = jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), *m_caches
            )
            new_cache = {"mamba": stacked, "attn": new_kv}
        return x, new_cache, aux

    if kind == "xlstm_unit":
        n_m = cfg.xlstm.slstm_every - 1
        m_caches = []
        for j in range(n_m):
            pj = jax.tree_util.tree_map(lambda a: a[j], params["mlstm"])
            cj = None if cache is None else jax.tree_util.tree_map(
                lambda a: a[j], cache["mlstm"]
            )
            out, mc = xlstm_mod.mlstm_block(
                pj["m"], gathered(rmsnorm(pj["ln"], x, cfg.norm_eps)),
                cfg, ctx, cj
            )
            x = x + out
            m_caches.append(mc)
        sp = params["slstm"]
        out, sc = xlstm_mod.slstm_block(
            sp["s"], gathered(rmsnorm(sp["ln"], x, cfg.norm_eps)), cfg, ctx,
            None if cache is None else cache["slstm"],
        )
        x = x + out
        new_cache = None
        if cache is not None:
            new_cache = {
                "mlstm": jax.tree_util.tree_map(lambda *a: jnp.stack(a), *m_caches),
                "slstm": sc,
            }
        return x, new_cache, aux

    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Stack application (scan / unroll / deq)
# ---------------------------------------------------------------------------


def _remat_wrap(fn, cfg: ModelConfig, train: bool):
    if not train or cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def deq_solve_carry(cfg: ModelConfig, batch: int, seq: int) -> SolveCarry:
    """An all-cold persistent solve state for the DEQ group's ``(B, S, d)``
    activations — thread it through ``loss_fn``/``decode_step`` to warm-start
    consecutive solves (train steps, decode tokens)."""
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    return init_solve_carry(batch, (seq, cfg.d_model), cfg.deq.memory,
                            dtype=dtype, qn_dtype=cfg.deq.qn_dtype)


def apply_stack(
    params: dict,
    x: Array,
    cfg: ModelConfig,
    ctx: ShardCtx,
    positions: Array,
    caches: dict | None = None,
    cache_index: Array | None = None,
    train: bool = True,
    active: Array | None = None,
    carry: SolveCarry | None = None,
):
    """Runs all stack groups. Returns (x, new_caches, aux).

    ``active: (B,) bool`` (serving only) freezes inactive batch slots in the
    DEQ fixed-point solve — they pay no solver iterations.  ``carry``
    warm-starts the DEQ solve from the previous outer call (train step /
    decode token); the updated carry comes back under ``aux["solve_carry"]``.
    """
    aux = {"moe_aux": jnp.float32(0.0), "moe_z": jnp.float32(0.0)}

    if cfg.deq.enabled:
        return _apply_deq(params, x, cfg, ctx, positions, caches, cache_index,
                          train, active, carry)

    shared = params.get("shared_attn")
    new_caches: dict = {}
    for i, grp in enumerate(stack_groups(cfg)):
        gp = params[f"group{i}"]
        gcache = None if caches is None else caches[f"group{i}"]

        def body(xc, layer_params, layer_cache):
            x2, nc, aux_l = apply_unit(
                grp.kind, layer_params, xc, cfg, ctx, positions,
                layer_cache, cache_index, shared,
            )
            # Residual-stream layout between blocks: seq-sharded under SP
            # rules (Megatron sequence parallelism), replicated otherwise.
            x2 = ctx.constrain(x2, ("batch", "seq_res", "embed_act"))
            return x2, nc, aux_l

        wrapped = _remat_wrap(body, cfg, train)

        if cfg.scan_layers and grp.count > 1:
            if gcache is None:
                def scan_nc(xc, lp):
                    x2, _, aux_l = wrapped(xc, lp, None)
                    return x2, aux_l

                x, aux_s = jax.lax.scan(scan_nc, x, gp)
                ncaches = None
            else:
                def scan_c(xc, inp):
                    lp, lc = inp
                    x2, ncache, aux_l = wrapped(xc, lp, lc)
                    return x2, (ncache, aux_l)

                x, (ncaches, aux_s) = jax.lax.scan(scan_c, x, (gp, gcache))
            aux = {k: aux[k] + jnp.sum(aux_s[k]) for k in aux}
        else:
            ncaches_list = []
            for j in range(grp.count):
                lp = jax.tree_util.tree_map(lambda a: a[j], gp)
                lc = None if gcache is None else jax.tree_util.tree_map(
                    lambda a: a[j], gcache
                )
                x, nc, aux_l = wrapped(x, lp, lc)
                ncaches_list.append(nc)
                aux = {k: aux[k] + aux_l[k] for k in aux}
            ncaches = None
            if gcache is not None:
                ncaches = jax.tree_util.tree_map(
                    lambda *a: jnp.stack(a), *ncaches_list
                )
        new_caches[f"group{i}"] = ncaches
    return x, (new_caches if caches is not None else None), aux


def _apply_deq(params, x_emb, cfg, ctx, positions, caches, cache_index, train,
               active=None, carry=None):
    """The paper's technique at LM scale: weight-tied block group solved to a
    fixed point, with SHINE-family backward (cfg.deq.backward).

    ``carry`` threads the persistent solve state through the call: the
    previous train step's (or previous decode token's) equilibrium and qN
    chain seed this solve, and the updated carry returns in
    ``aux["solve_carry"]`` (stop-gradient'ed — warm starts never perturb
    the implicit gradient).

    State formulation (input injection): the equilibrium stream solves

        z* = x + C(z*),   C(z) = blocks(z) - z

    i.e. the injection rides OUTSIDE the weight-tied block contributions C.
    The previous form ``z = blocks(z + x)`` has Jacobian ``I + J_C`` — its
    root system ``g = -x - C(z+x)`` is singular whenever ``J_C`` is small
    (any near-init model), the fixed points degenerate into a scale ray
    (rmsnorm makes C scale-invariant) and every solve escapes to infinity.
    With injection outside, ``J_f = J_C`` — contractive exactly when the
    blocks are weakly coupled, so equilibria exist, solves genuinely
    converge, and a carried equilibrium is meaningful across steps/tokens.
    """
    d = cfg.deq
    kind = _deq_kind(cfg)
    shared = params.get("shared_attn")

    # single-array state: implicit_fixed_point keeps (B, S, d) unflattened,
    # so TP-sharded activations stay sharded through the solver; under a
    # mesh these axes also pin the solver's quasi-Newton (U, V) memory
    # batch-sharded next to the state (sharded batched solve)
    state_axes = ("batch", "seq_res", "embed_act")
    deq_cfg = ImplicitConfig.from_strings(
        solver=d.solver, max_steps=d.max_steps, tol=d.tol, memory=d.memory,
        backward=d.backward, refine_steps=d.refine_steps,
        backward_max_steps=d.backward_max_steps, unroll=d.unroll,
        qn_dtype=d.qn_dtype, guard=d.guard,
    )

    # IMPORTANT: everything traced must flow through the custom_vjp's
    # differentiable args, never through f's closure (tracer leak otherwise).
    p_all = {"blocks": params["deq_blocks"]}
    if shared is not None:
        p_all["shared"] = shared

    if caches is None:
        def f(p, xin, z):
            x_in, pos = xin
            h = z
            for j in range(d.num_blocks):
                pj = jax.tree_util.tree_map(lambda a: a[j], p["blocks"])
                h, _, _ = apply_unit(kind, pj, h, cfg, ctx, pos,
                                     None, None, p.get("shared"))
            return ctx.constrain(x_in + (h - z),
                                 ("batch", "seq_res", "embed_act"))

        # cold start AT the injection: f(x) = x + C(x) is one free Picard
        # step, and the solve stays input-anchored even when a random-init
        # C is not yet contractive (best-iterate tracking then returns a
        # stream-shaped state rather than collapsing to zero)
        z0 = x_emb
        out = implicit_fixed_point(f, p_all, (x_emb, positions), z0,
                                   deq_cfg, ctx=ctx, state_axes=state_axes,
                                   carry=carry)
        z_star, stats = out[0], out[1]
        aux = {"moe_aux": jnp.float32(0.0), "moe_z": jnp.float32(0.0),
               "deq_residual": jnp.mean(stats.residual),
               "deq_steps": stats.n_steps.astype(jnp.float32)}
        if stats.status is not None:
            aux["deq_status"] = stats.status  # (B,) solve-health codes
        if carry is not None:
            aux["solve_carry"] = out[2]
        return z_star, None, aux

    # decode/prefill with cache: solve the fixed point of the new token(s)
    # against the frozen cache, then refresh the cache once at z*.
    def f_dec(p, xin, z):
        x_in, pos, cch, cidx = xin
        h = z
        for j in range(d.num_blocks):
            pj = jax.tree_util.tree_map(lambda a: a[j], p["blocks"])
            cj = jax.tree_util.tree_map(lambda a: a[j], cch["deq"])
            h, _, _ = apply_unit(kind, pj, h, cfg, ctx, pos, cj,
                                 cidx, p.get("shared"))
        return x_in + (h - z)

    z0 = x_emb
    if active is not None:
        # serving: freeze inactive slots in the batched solve (no backward
        # pass exists at decode time, so the inference engine applies)
        out = batched_solve(
            f_dec, p_all, (x_emb, positions, caches, cache_index), z0,
            deq_cfg, valid=active, ctx=ctx, state_axes=state_axes,
            carry=carry,
        )
    else:
        out = implicit_fixed_point(
            f_dec, p_all, (x_emb, positions, caches, cache_index), z0, deq_cfg,
            ctx=ctx, state_axes=state_axes, carry=carry,
        )
    z_star, stats = out[0], out[1]
    # one more pass to materialize the updated caches at the fixed point
    # (the state IS the block-input stream under input injection)
    h = z_star
    new_list = []
    for j in range(d.num_blocks):
        pj = jax.tree_util.tree_map(lambda a: a[j], params["deq_blocks"])
        cj = jax.tree_util.tree_map(lambda a: a[j], caches["deq"])
        h, nc, _ = apply_unit(kind, pj, h, cfg, ctx, positions, cj,
                              cache_index, shared)
        new_list.append(nc)
    new_caches = {"deq": jax.tree_util.tree_map(lambda *a: jnp.stack(a), *new_list)}
    aux = {"moe_aux": jnp.float32(0.0), "moe_z": jnp.float32(0.0),
           "deq_residual": jnp.mean(stats.residual),
           "deq_steps": stats.n_steps.astype(jnp.float32)}
    if stats.status is not None:
        aux["deq_status"] = stats.status  # (B,) solve-health codes
    if carry is not None:
        aux["solve_carry"] = out[2]
    return z_star, new_caches, aux


# ---------------------------------------------------------------------------
# Full model: forward / loss / prefill / decode
# ---------------------------------------------------------------------------


def _kernels_on_mesh(fn):
    """Trace ``fn``'s Pallas kernels per device shard of its ``ctx``
    argument's mesh (kernels.ops.sharded_kernels)."""
    sig = inspect.signature(fn)

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        ctx = sig.bind(*args, **kwargs).arguments["ctx"]
        with kernel_ops.sharded_kernels(ctx):
            return fn(*args, **kwargs)
    return wrapped


def _input_embedding(params, batch: dict, cfg: ModelConfig, ctx: ShardCtx):
    """Token/frontend embedding. Returns (x (B,S,d), positions (B,S))."""
    if cfg.family == "audio":
        x = batch["embeds"].astype(
            jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        )
        x = ctx.constrain(x, ("batch", "seq", "embed_act"))
        b, s = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        return x, pos
    tok = embed_tokens(params["embed"], batch["tokens"], cfg, ctx)
    if cfg.family == "vlm" and "image_embeds" in batch:
        img = batch["image_embeds"].astype(tok.dtype)
        x = jnp.concatenate([img, tok], axis=1)
    else:
        x = tok
    b, s = x.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    return x, pos


@_kernels_on_mesh
def forward(params, batch: dict, cfg: ModelConfig, ctx: ShardCtx,
            train: bool = True, carry: SolveCarry | None = None):
    """Full-sequence forward. Returns (logits, aux).

    ``carry`` warm-starts the DEQ solve; the updated state comes back under
    ``aux["solve_carry"]`` (see :func:`deq_solve_carry`)."""
    x, pos = _input_embedding(params, batch, cfg, ctx)
    x, _, aux = apply_stack(params, x, cfg, ctx, pos, train=train,
                            carry=carry)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params["embed"], x, cfg, ctx)
    return logits, aux


def loss_fn(params, batch: dict, cfg: ModelConfig, ctx: ShardCtx,
            z_loss: float = 1e-4, carry: SolveCarry | None = None):
    logits, aux = forward(params, batch, cfg, ctx, train=True, carry=carry)
    targets = batch["targets"]
    if cfg.family == "vlm" and "image_embeds" in batch:
        n_img = batch["image_embeds"].shape[1]
        logits = logits[:, n_img:]
    loss, metrics = cross_entropy(logits, targets, z_loss)
    loss = loss + cfg.moe.aux_weight * aux["moe_aux"] + cfg.moe.z_weight * aux["moe_z"]
    metrics.update({k: v for k, v in aux.items()})
    metrics["loss"] = loss
    return loss, metrics


# ---- serving --------------------------------------------------------------


def _unit_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    if kind in ("attn_mlp", "attn_moe"):
        return attn.mla_cache_shape(cfg, batch, max_len) if cfg.attn_type == "mla" \
            else attn.gqa_cache_shape(cfg, batch, max_len)
    if kind == "zamba_unit":
        m = jax.tree_util.tree_map(
            lambda a: jnp.stack([a] * cfg.ssm.attn_every),
            ssm_mod.mamba2_cache_shape(cfg, batch),
        )
        return {"mamba": m, "attn": attn.gqa_cache_shape(cfg, batch, max_len)}
    if kind == "xlstm_unit":
        n_m = cfg.xlstm.slstm_every - 1
        ml = jax.tree_util.tree_map(
            lambda a: jnp.stack([a] * n_m), xlstm_mod.mlstm_cache_shape(cfg, batch)
        )
        return {"mlstm": ml, "slstm": xlstm_mod.slstm_cache_shape(cfg, batch)}
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    if cfg.deq.enabled:
        unit = _unit_cache(cfg, _deq_kind(cfg), batch, max_len)
        return {"deq": jax.tree_util.tree_map(
            lambda a: jnp.stack([a] * cfg.deq.num_blocks), unit)}
    caches = {}
    for i, grp in enumerate(stack_groups(cfg)):
        unit = _unit_cache(cfg, grp.kind, batch, max_len)
        caches[f"group{i}"] = jax.tree_util.tree_map(
            lambda a: jnp.stack([a] * grp.count), unit
        )
    return caches


def prefix_seed_carry(cfg: ModelConfig, batch: int, seq: int,
                      snapshots: list) -> tuple[SolveCarry, Array]:
    """Assemble a PREFILL-shaped carry from per-row prefix-cache snapshots.

    ``snapshots``: one entry per batch row — ``None`` for a cache miss
    (the row stays cold, bit-identical to a carryless prefill) or a host
    tuple ``(z, u, v, count)`` with ``z: (L, d)`` the cached prefix
    equilibrium and ``u/v: (m, L, d)`` the donor's quasi-Newton ring over
    the prefix positions (``None``/``count=0`` for an iterate-only seed).
    Suffix positions (``>= L``) are zero here; :func:`prefill` overwrites
    them with the live ``x_emb`` so the suffix still cold-starts AT the
    injection, and the zero-padded ring pairs act as identity on the
    suffix subspace.  Returns ``(carry, prefix_len)`` where ``prefix_len:
    (B,) int32`` is per-row ``L`` (0 for misses).
    """
    if len(snapshots) != batch:
        raise ValueError(f"{len(snapshots)} snapshots for batch {batch}")
    tmpl = deq_solve_carry(cfg, batch, seq)
    m = tmpl.memory
    z = np.zeros(tmpl.z.shape, tmpl.z.dtype)
    u = np.zeros(tmpl.lowrank.u.shape, tmpl.lowrank.u.dtype)
    v = np.zeros(tmpl.lowrank.v.shape, tmpl.lowrank.v.dtype)
    count = np.zeros((batch,), np.int32)
    warm = np.zeros((batch,), bool)
    plen = np.zeros((batch,), np.int32)
    for i, snap in enumerate(snapshots):
        if snap is None:
            continue
        sz, su, sv, sc = snap
        sz = np.asarray(sz)
        length = sz.shape[0]
        if length > seq:
            raise ValueError(f"snapshot row {i}: prefix {length} > seq {seq}")
        warm[i] = True
        plen[i] = length
        z[i, :length] = sz.astype(z.dtype)
        if su is not None and sv is not None and sc:
            su, sv = np.asarray(su), np.asarray(sv)
            if su.shape[0] != m:
                raise ValueError(
                    f"snapshot row {i}: ring memory {su.shape[0]} != {m}")
            u[:, i, :length] = su.astype(u.dtype)
            v[:, i, :length] = sv.astype(v.dtype)
            count[i] = min(int(sc), m)
    carry = SolveCarry(
        z=jnp.asarray(z),
        lowrank=dataclasses.replace(
            tmpl.lowrank, u=jnp.asarray(u), v=jnp.asarray(v),
            count=jnp.asarray(count)),
        warm=jnp.asarray(warm),
        age=tmpl.age,
    )
    return carry, jnp.asarray(plen)


def prefix_gather_carry(cfg: ModelConfig, batch: int, seq: int,
                        arrays, slot_ids: Array,
                        prefix_len: Array) -> tuple[SolveCarry, Array]:
    """Assemble a PREFILL-shaped carry by GATHERING device-store rows.

    The traced twin of :func:`prefix_seed_carry` for the device-resident
    prefix cache (:class:`repro.implicit.DevicePrefixStore`): ``arrays``
    are the store's slot arrays, ``slot_ids: (B,) int32`` the donor rows
    and ``prefix_len: (B,) int32`` the matched lengths (0 = miss: the row
    comes out cold, bit-identical to a carryless prefill).  Runs INSIDE
    the jitted prefill program — no snapshot ever touches the host.

    Positions past the matched length carry stale donor-tail data in the
    store (one donor row serves every block-boundary length); they are
    masked here exactly like the host assembly zero-pads: ``z`` to zero
    (:func:`prefill` overwrites it with the live ``x_emb``) and the ring
    pairs to zero (identity inverse on the suffix subspace).
    """
    if not cfg.deq.enabled:
        raise ValueError("prefix_gather_carry requires cfg.deq.enabled")
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    z_s, u_s, v_s, c_s = arrays
    if u_s.shape[0] != cfg.deq.memory:
        raise ValueError(
            f"store ring memory {u_s.shape[0]} != cfg {cfg.deq.memory}")
    if z_s.shape[1] < seq:
        raise ValueError(f"store seq {z_s.shape[1]} < prompt seq {seq}")
    pmask = (jnp.arange(seq, dtype=jnp.int32)[None, :]
             < prefix_len[:, None])[..., None]
    zeros = jnp.zeros((), dtype)
    z = jnp.where(pmask, z_s[slot_ids, :seq].astype(dtype), zeros)
    u = jnp.where(pmask[None], u_s[:, slot_ids, :seq],
                  jnp.zeros((), u_s.dtype))
    v = jnp.where(pmask[None], v_s[:, slot_ids, :seq],
                  jnp.zeros((), v_s.dtype))
    warm = prefix_len > 0
    count = jnp.where(warm, c_s[slot_ids], 0).astype(jnp.int32)
    carry = SolveCarry(
        z=z,
        lowrank=LowRank(alpha=jnp.asarray(1.0, jnp.float32),
                        u=u, v=v, count=count),
        warm=warm,
        age=jnp.zeros((batch,), jnp.int32),
    )
    return carry, prefix_len


@_kernels_on_mesh
def prefill(params, batch: dict, cfg: ModelConfig, ctx: ShardCtx,
            max_len: int, carry: SolveCarry | None = None,
            prefix_carry: SolveCarry | None = None,
            prefix_len: Array | None = None,
            return_status: bool = False):
    """Encode a prompt; returns (logits, caches, lengths).

    ``carry`` must be a DECODE-shaped carry (``deq_solve_carry(cfg, B, 1)``):
    the prefill solve itself runs cold (its (B, S, d) state is a different
    problem), but the last token's equilibrium SEEDS the carry so the first
    decode step warm-starts — token-to-token reuse begins at token 0.  With
    a carry the return is ``(logits, caches, lengths, carry)``.

    ``prefix_carry`` + ``prefix_len`` (DEQ only) seed the PREFILL solve
    itself from a cross-request prefix-cache snapshot (see
    :func:`prefix_seed_carry`): warm rows start at
    ``where(pos < prefix_len, cached_z, x_emb)`` with the cached ring
    chain, cold rows are bit-identical to a carryless prefill.
    ``prefix_len`` is traced, so one compiled program serves every match
    length.  The return gains ``(solve_carry, deq_steps)`` — the converged
    prefill carry (for publication back to the index) and the solver's
    step count (iteration accounting).

    ``return_status`` appends the forward solve's per-sample health codes
    (``deq_status: (B,) int32``, ``core.solvers.STATUS_*``; all-zeros when
    the model is not a guarded DEQ) — the serving loop's containment
    signal for per-request error status / cold retry / poisoned-prefix
    eviction.
    """
    x, pos = _input_embedding(params, batch, cfg, ctx)
    b = x.shape[0]
    caches = init_cache(cfg, b, max_len)
    idx0 = jnp.zeros((b,), jnp.int32)
    solve_carry = None
    if prefix_carry is not None:
        if not cfg.deq.enabled:
            raise ValueError("prefix_carry requires cfg.deq.enabled")
        if prefix_len is None:
            raise ValueError("prefix_carry requires prefix_len")
        # live suffix positions start at the injection (x_emb), cached
        # prefix positions at the donor equilibrium — assembled inside the
        # jitted program so match lengths never retrace
        pmask = (pos < prefix_len[:, None])[..., None]
        solve_carry = dataclasses.replace(
            prefix_carry,
            z=jnp.where(pmask, prefix_carry.z.astype(x.dtype), x))
    x, caches, aux = apply_stack(
        params, x, cfg, ctx, pos, caches, idx0, train=False,
        carry=solve_carry,
    )
    # for the DEQ path, the stack output IS the equilibrium z*
    z_last = x[:, -1:, :]
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params["embed"], x, cfg, ctx)
    lengths = jnp.full((b,), x.shape[1], jnp.int32)
    out = (logits, caches, lengths)
    if carry is not None:
        out = out + (seed_carry(carry, z_last),)
    if prefix_carry is not None:
        out = out + (aux["solve_carry"], aux["deq_steps"])
    if return_status:
        out = out + (aux.get("deq_status", jnp.zeros((b,), jnp.int32)),)
    return out


@_kernels_on_mesh
def decode_step(params, caches, tokens: Array, cache_index: Array,
                cfg: ModelConfig, ctx: ShardCtx, active: Array | None = None,
                carry: SolveCarry | None = None, return_steps: bool = False,
                return_status: bool = False):
    """One decode step. tokens: (B,), cache_index: (B,). Returns
    (logits (B, V), new caches).  ``active: (B,) bool`` lets the serving
    loop freeze finished/empty slots inside the DEQ fixed-point solve.

    ``carry`` threads the token-to-token solve state: the equilibrium (and
    quasi-Newton chain) at token *t* seeds token *t+1* — steady-state decode
    then converges in a fraction of the cold iteration count.  With a carry
    the return is ``(logits, caches, carry)``.

    ``return_steps`` appends the solver's step count (``deq_steps``, 0.0
    for non-DEQ models) so the serving pipeline can thread iteration
    accounting through its completion queue instead of re-fetching aux.
    ``return_status`` then appends the per-sample solve-health codes
    (``deq_status: (B,) int32``; zeros for non-DEQ/unguarded models).
    """
    batch = {"tokens": tokens[:, None]}
    x = embed_tokens(params["embed"], batch["tokens"], cfg, ctx)
    pos = cache_index[:, None]
    x, caches, aux = apply_stack(
        params, x, cfg, ctx, pos, caches, cache_index, train=False,
        active=active, carry=carry,
    )
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params["embed"], x, cfg, ctx)
    out = ((logits[:, 0], caches) if carry is None
           else (logits[:, 0], caches, aux.get("solve_carry", carry)))
    if return_steps:
        out = out + (aux.get("deq_steps", jnp.float32(0.0)),)
    if return_status:
        out = out + (aux.get("deq_status",
                             jnp.zeros((tokens.shape[0],), jnp.int32)),)
    return out
