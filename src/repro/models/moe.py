"""Fine-grained MoE (DeepSeek family): shared experts + routed top-k experts
with expert parallelism over the "model" mesh axis.

EP scheme (DESIGN.md §4.1): activations entering the block are TP-replicated,
so inside a shard_map over the mesh each device (a) routes all of its DP-shard
tokens, (b) argsort-buckets the subset destined for its *own* E/tp experts up
to a fixed capacity, (c) runs its experts, and (d) contributes its partial
output to the SAME psum a dense TP FFN would issue. The dispatch collective
therefore degenerates into the reduce TP already pays — no all-to-all on the
baseline path.

Static shapes throughout: capacity C = ceil(T*top_k/E * cf) rounded to 8;
tokens beyond capacity are dropped (dropless up to cf, standard).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import mlp, mlp_decl
from repro.parallel.sharding import ParamDecl, ShardCtx

Array = jax.Array


def moe_decl(cfg: ModelConfig) -> dict:
    d, m = cfg.d_model, cfg.moe
    eff = m.expert_d_ff
    decl = {
        "router": ParamDecl((d, m.num_experts), ("embed", None), init="normal",
                            scale=0.02, dtype=jnp.float32),
        "wi_g": ParamDecl((m.num_experts, d, eff), ("expert", "embed", "expert_mlp")),
        "wi_u": ParamDecl((m.num_experts, d, eff), ("expert", "embed", "expert_mlp")),
        "wo": ParamDecl((m.num_experts, eff, d), ("expert", "expert_mlp", "embed")),
    }
    if m.num_shared:
        decl["shared"] = mlp_decl(cfg, d_ff=m.num_shared * eff)
    return decl


def _route(x: Array, router_w: Array, cfg: ModelConfig):
    """Returns (top-k indices (T,k), top-k gates (T,k), aux losses)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w)
    scores = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(scores, m.top_k)
    if m.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss + router z-loss
    density = jnp.mean(
        jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32), axis=(0, 1)
    )
    mean_prob = jnp.mean(scores, axis=0)
    aux = m.num_experts * jnp.sum(density * mean_prob)
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return idx, gates.astype(x.dtype), aux, zloss


def _expert_bucket(idx: Array, gates: Array, expert_id: int, capacity: int):
    """Select up to `capacity` tokens routed to `expert_id`.

    Returns (token_positions (C,), gate (C,), valid (C,)).
    """
    t = idx.shape[0]
    hit = idx == expert_id                       # (T, k)
    sel = hit.any(-1)                            # (T,)
    gate = jnp.where(hit, gates, 0.0).sum(-1)    # (T,)
    # stable order: first-come-first-served up to capacity
    order = jnp.where(sel, jnp.cumsum(sel.astype(jnp.int32)) - 1, t + 1)
    perm = jnp.argsort(jnp.where(sel, order, t + 1))[:capacity]
    valid = sel[perm]
    return perm, gate[perm], valid


def _moe_local(x: Array, params: dict, cfg: ModelConfig, n_local: int,
               first_expert: Array, capacity: int):
    """Compute this device's experts on its token shard. x: (T, d)."""
    idx, gates, aux, zloss = _route(x, params["router"], cfg)
    out = jnp.zeros_like(x)
    for j in range(n_local):
        e = first_expert + j
        perm, gate, valid = _expert_bucket(idx, gates, e, capacity)
        xg = x[perm] * valid[:, None].astype(x.dtype)
        g = jnp.einsum("cd,df->cf", xg, params["wi_g"][j].astype(x.dtype))
        u = jnp.einsum("cd,df->cf", xg, params["wi_u"][j].astype(x.dtype))
        h = jax.nn.silu(g) * u
        y = jnp.einsum("cf,fd->cd", h, params["wo"][j].astype(x.dtype))
        out = out.at[perm].add(y * (gate * valid.astype(x.dtype))[:, None])
    return out, aux, zloss


def moe_block(params: dict, x: Array, cfg: ModelConfig, ctx: ShardCtx
              ) -> tuple[Array, dict]:
    """x: (B, S, d) -> (out, {"moe_aux", "moe_z"})."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(b * s, d)

    mesh = ctx.mesh
    ep = ctx.axis_size("expert_act") if mesh is not None else 1
    if mesh is None or ep == 1:
        cap = _capacity(b * s, m)
        out, aux, zloss = _moe_local(
            xf, params, cfg, m.num_experts, jnp.int32(0), cap
        )
    else:
        n_local = m.num_experts // ep
        dp_axes = tuple(
            a for a in ("pod", "data") if a in mesh.axis_names
        )
        tokens_local = (b * s) // max(1, math.prod(mesh.shape[a] for a in dp_axes))
        cap = _capacity(tokens_local, m)
        ep_axis = ctx.rules.physical("expert_act")

        def shard_fn(xs, ps):
            first = jax.lax.axis_index(ep_axis) * n_local
            local_p = {
                "router": ps["router"],
                "wi_g": ps["wi_g"], "wi_u": ps["wi_u"], "wo": ps["wo"],
            }
            o, aux, zl = _moe_local(xs, local_p, cfg, n_local, first, cap)
            o = jax.lax.psum(o, ep_axis)
            aux = jax.lax.pmean(aux, ep_axis)
            zl = jax.lax.pmean(zl, ep_axis)
            return o, aux, zl

        batch_spec = ctx.rules.spec(("batch", None))
        pspecs = {
            "router": P(),
            "wi_g": ctx.rules.spec(("expert_act", None, None)),
            "wi_u": ctx.rules.spec(("expert_act", None, None)),
            "wo": ctx.rules.spec(("expert_act", None, None)),
        }
        routed = {k: params[k] for k in ("router", "wi_g", "wi_u", "wo")}
        out, aux, zloss = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(batch_spec, pspecs),
            out_specs=(batch_spec, P(), P()),
            check_vma=False,
        )(xf, routed)

    out = out.reshape(b, s, d)
    if "shared" in params:
        out = out + mlp(params["shared"], x, cfg, ctx)
    out = ctx.constrain(out, ("batch", "seq_res", "embed_act"))
    return out, {"moe_aux": aux, "moe_z": zloss}


def _capacity(tokens: int, m) -> int:
    cap = int(math.ceil(tokens * m.top_k / m.num_experts * m.capacity_factor))
    return max(8, (cap + 7) // 8 * 8)
