"""Logical-axis sharding vocabulary and the single-source-of-truth parameter
declaration system.

Every model module declares its parameters once, as a pytree of
:class:`ParamDecl` (shape + logical axis names + initializer). From that one
declaration we derive
  * the initialized parameter pytree (``init_tree``),
  * the ``PartitionSpec`` pytree for any mesh/rule-set (``spec_tree``),
  * the ZeRO-1 optimizer-state specs (``zero1_spec``).

Logical axis names are mapped to physical mesh axes by a
:class:`ShardingRules` table, so the same model code serves the 1-device CPU
smoke tests, the (data=16, model=16) single-pod mesh and the
(pod=2, data=16, model=16) multi-pod mesh without modification.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

Pytree = Any

# ---------------------------------------------------------------------------
# Logical -> physical rules
# ---------------------------------------------------------------------------

# Value is a mesh axis name, a tuple of mesh axis names, or None (replicated).
RuleValue = Any


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Mapping from logical axis names to physical mesh axes."""

    table: Mapping[str, RuleValue]

    def physical(self, logical: str | None) -> RuleValue:
        if logical is None:
            return None
        if logical not in self.table:
            raise KeyError(f"unknown logical axis {logical!r}")
        return self.table[logical]

    def spec(self, axes: Sequence[str | None]) -> P:
        """PartitionSpec for a tensor whose dims carry these logical names."""
        phys = [self.physical(a) for a in axes]
        # A mesh axis may appear at most once in a PartitionSpec; later
        # occurrences degrade to replicated (this happens e.g. when a small
        # tensor uses "model" on two dims).
        seen: set[str] = set()
        out = []
        for p in phys:
            names = (p,) if isinstance(p, str) else tuple(p or ())
            if any(n in seen for n in names):
                out.append(None)
                continue
            seen.update(names)
            out.append(p)
        return P(*out)

    def replace(self, **updates: RuleValue) -> "ShardingRules":
        new = dict(self.table)
        new.update(updates)
        return ShardingRules(new)


def _base_table(**overrides: RuleValue) -> Mapping[str, RuleValue]:
    table: dict[str, RuleValue] = {
        # --- activations ---
        "batch": ("pod", "data"),  # global batch, DP over pods x data
        "seq": None,               # query/sequence axis of activations
        "seq_res": None,           # residual-stream seq axis (SP shards this)
        "kv_seq": None,            # KV-cache length axis
        "embed_act": None,         # activation d_model axis
        "heads_act": "model",      # per-head activation axis (TP)
        "kv_heads_act": "model",   # KV heads of activations (None if indivisible)
        "mlp_act": "model",        # d_ff activation axis
        "vocab_act": "model",      # logits vocab axis
        "expert_act": "model",     # per-expert token buffers
        "ssm_heads_act": "model",  # SSM / mLSTM heads
        # --- weights ---
        "embed": None,             # d_model axis of weights (replicated; ZeRO-1
        #                            shards the *optimizer* over "data")
        "vocab": "model",
        "heads": "model",          # flattened (num_heads * head_dim) axis
        "kv": "model",             # flattened (num_kv_heads * head_dim) axis
        "mlp": "model",
        "expert": "model",         # expert-parallel axis of expert stacks
        "expert_mlp": None,        # intra-expert d_ff (EP already on "model")
        "layers": None,            # stacked-layer leading axis
        "ssm_inner": "model",      # SSM inner/head axis of weights
        "ssm_heads": "model",      # per-head SSM params (A, D, dt bias)
        "ssm_state": None,
        "conv": None,
        "lora": None,              # MLA low-rank bottleneck axes
        "qn_mem": None,            # quasi-Newton memory axis
        "flat": None,              # flattened DEQ feature axis
        "scale": None,
    }
    table.update(overrides)
    return table


# Training / prefill: shard batch, replicate sequence.
TRAIN_RULES = ShardingRules(_base_table())

# Training with Megatron-style sequence parallelism: the residual-stream
# activations between blocks are seq-sharded over "model" (all-gather into
# each block, reduce-scatter out — GSPMD derives both from the constraints).
TRAIN_SP_RULES = ShardingRules(_base_table(seq_res="model"))

# Decode: batch over DP axes; the KV cache's sequence axis is sharded over
# "model" (sequence-sharded KV: each chip holds a context slice and computes
# partial attention, combined by GSPMD's softmax all-reduce). This is the
# only layout that fits a 32k cache when kv_heads < tp (internlm2, pixtral)
# or kv_heads % tp != 0 (minicpm's 36).
#
# Attention heads are REPLICATED here on purpose: "model" is owned by the
# cache's T axis, and a second owner (q heads) forces GSPMD to all-gather
# the full cache every layer (measured: 2 GB/layer/token on internlm2 —
# EXPERIMENTS.md §Perf iteration B1). Each chip computes all heads against
# its context slice; the combine is one small (B, d) all-reduce.
DECODE_RULES = ShardingRules(_base_table(
    kv_seq="model", heads_act=None, kv_heads_act=None))

# Prefill: writes the decode-layout (T-sharded) cache, but attention itself
# is compute-bound and stays head-sharded; the attention consumes the
# PRE-write (seq-replicated, head-sharded) k/v so the only cross-layout cost
# is the one cache-write reshard per layer (models/attention.py).
PREFILL_RULES = ShardingRules(_base_table(kv_seq="model"))

# Long-context decode (batch=1): context parallelism — the KV cache / SSM
# sequence axis is sharded over the DP axes instead of the batch.
LONG_CONTEXT_RULES = ShardingRules(
    _base_table(
        batch=None,
        kv_seq=("pod", "data"),
        seq=None,
    )
)


def rules_for_mesh(rules: ShardingRules, mesh: Mesh | None) -> ShardingRules:
    """Drop references to mesh axes that don't exist (e.g. no "pod" axis)."""
    if mesh is None:
        return ShardingRules({k: None for k in rules.table})
    names = set(mesh.axis_names)

    def fix(v: RuleValue) -> RuleValue:
        if v is None:
            return None
        if isinstance(v, str):
            return v if v in names else None
        kept = tuple(a for a in v if a in names)
        if not kept:
            return None
        # unwrap 1-tuples so specs compare equal to the plain-string form
        return kept[0] if len(kept) == 1 else kept

    return ShardingRules({k: fix(v) for k, v in rules.table.items()})


# ---------------------------------------------------------------------------
# Parameter declarations
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamDecl:
    """Single-source-of-truth declaration of one parameter tensor."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "fan_in"  # fan_in | zeros | ones | normal | truncated
    scale: float = 1.0
    dtype: Any = jnp.float32

    def __post_init__(self) -> None:
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")

    def initialize(self, key: jax.Array) -> jax.Array:
        shape, dtype = self.shape, self.dtype
        if self.init == "zeros":
            return jnp.zeros(shape, dtype)
        if self.init == "ones":
            return jnp.ones(shape, dtype)
        if self.init == "normal":
            return (self.scale * jax.random.normal(key, shape)).astype(dtype)
        if self.init in ("fan_in", "truncated"):
            # fan-in = product of all dims except the last output dim
            fan_in = max(1, math.prod(shape[:-1])) if len(shape) > 1 else shape[0]
            std = self.scale / math.sqrt(fan_in)
            x = jax.random.truncated_normal(key, -2.0, 2.0, shape) * std
            return x.astype(dtype)
        raise ValueError(f"unknown init {self.init!r}")


def _is_decl(x: Any) -> bool:
    return isinstance(x, ParamDecl)


def init_tree(decls: Pytree, key: jax.Array, dtype: Any | None = None) -> Pytree:
    """Initialize a parameter pytree from a declaration pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(decls, is_leaf=_is_decl)
    keys = jax.random.split(key, max(1, len(leaves)))
    out = []
    for d, k in zip(leaves, keys):
        arr = d.initialize(k)
        if dtype is not None and jnp.issubdtype(arr.dtype, jnp.floating):
            arr = arr.astype(dtype)
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def spec_tree(decls: Pytree, rules: ShardingRules) -> Pytree:
    """PartitionSpec pytree matching the declaration pytree."""
    return jax.tree_util.tree_map(
        lambda d: rules.spec(d.axes), decls, is_leaf=_is_decl
    )


def shape_tree(decls: Pytree, dtype: Any | None = None) -> Pytree:
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype or d.dtype),
        decls,
        is_leaf=_is_decl,
    )


def named_sharding_tree(specs: Pytree, mesh: Mesh) -> Pytree:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def zero1_spec(decl: ParamDecl, rules: ShardingRules, zero_axis: str = "data",
               zero_size: int = 0) -> P:
    """ZeRO-1 optimizer-state spec: additionally shard the largest replicated
    dim of the parameter over ``zero_axis`` when divisible.

    Parameters themselves stay TP-sharded and DP-replicated (cheap compute
    path); only the optimizer moments/master weights pay the extra shard.
    ``zero_size`` (the mesh size of ``zero_axis``) gates divisibility; 0
    skips the check (single-device tests).
    """
    base = rules.spec(decl.axes)
    entries = list(base) + [None] * (len(decl.shape) - len(base))
    used = set()
    for e in entries:
        for n in (e,) if isinstance(e, str) else tuple(e or ()):
            used.add(n)
    if zero_axis in used:
        return base
    # find largest dim that is currently replicated and divisible
    zdim = -1
    best = 0
    for i, (dim, e) in enumerate(zip(decl.shape, entries)):
        divisible = zero_size <= 1 or dim % zero_size == 0
        if e is None and dim > best and divisible:
            zdim, best = i, dim
    if zdim < 0:
        return base
    entries[zdim] = zero_axis
    return P(*entries)


def zero1_spec_tree(decls: Pytree, rules: ShardingRules, zero_axis: str = "data",
                    zero_size: int = 0) -> Pytree:
    return jax.tree_util.tree_map(
        lambda d: zero1_spec(d, rules, zero_axis, zero_size), decls,
        is_leaf=_is_decl
    )


# ---------------------------------------------------------------------------
# Shard context threaded through model code
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh + rules carried through model ``apply`` functions.

    With ``mesh=None`` (CPU unit tests) every call is a no-op, so model code
    is identical across environments.
    """

    mesh: Mesh | None = None
    rules: ShardingRules = TRAIN_RULES

    @staticmethod
    def for_mesh(mesh: Mesh | None, rules: ShardingRules = TRAIN_RULES) -> "ShardCtx":
        return ShardCtx(mesh=mesh, rules=rules_for_mesh(rules, mesh))

    def constrain(self, x: jax.Array, axes: Sequence[str | None]) -> jax.Array:
        if self.mesh is None or self.mesh.empty:
            return x
        spec = self.rules.spec(axes)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def sharding(self, axes: Sequence[str | None]) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.rules.spec(axes))

    def axis_size(self, logical: str) -> int:
        """Product of physical mesh axis sizes behind a logical axis."""
        if self.mesh is None:
            return 1
        phys = self.rules.physical(logical)
        if phys is None:
            return 1
        names = (phys,) if isinstance(phys, str) else phys
        return int(np.prod([self.mesh.shape[n] for n in names]))


NULL_CTX = ShardCtx(mesh=None, rules=ShardingRules({k: None for k in _base_table()}))
