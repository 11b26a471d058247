"""Weights made from a seed, on the device, in one jitted call.

The tree has the layout the DEQ language model takes (``embed``,
``final_norm``, ``deq_blocks`` stacked over the group's blocks).  Matrices
are truncated normals scaled by ``1/sqrt(fan_in)``; the matrices of the DEQ
group are further scaled by the configuration's ``deq_weight_scale`` so that
the fixed-point map is contractive, as a trained DEQ is.  Rows of the
embedding (and columns of an untied head) past the published vocabulary are
zero, so no padded id is ever the best token.

The values depend on the seed alone: laid out over several devices, each
device makes its own part, and the weights are the same bits as on one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.spec import ModelSpec


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    seed = int(seed)
    if seed < 0 or seed >= 2 ** 63:
        raise ValueError(f"seed {seed} outside [0, 2**63)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _matrix(key, shape, fan_in, scale, dtype):
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (w * (scale / fan_in ** 0.5)).astype(dtype)


def _make(spec: ModelSpec, key: jax.Array, dtype) -> dict:
    d, nb, ff = spec.d, spec.blocks, spec.d_ff
    ad, kvd = spec.heads * spec.head_dim, spec.kv_heads * spec.head_dim
    vp, s = spec.padded_vocab, spec.deq_weight_scale
    keys = iter(jax.random.split(key, 16))
    real = (jnp.arange(vp) < spec.vocab)[:, None]
    emb = 0.02 * jax.random.normal(next(keys), (vp, d), jnp.float32)
    embed = {"embedding": jnp.where(real, emb, 0.0).astype(dtype)}
    if not spec.tied:
        head = _matrix(next(keys), (vp, d), d, 1.0, jnp.float32)
        embed["lm_head"] = jnp.where(real, head, 0.0).T.astype(dtype)
    ones = jnp.ones((nb, d), dtype)
    blocks = {
        "ln1": {"scale": ones},
        "attn": {
            "wq": _matrix(next(keys), (nb, d, ad), d, s, dtype),
            "wk": _matrix(next(keys), (nb, d, kvd), d, s, dtype),
            "wv": _matrix(next(keys), (nb, d, kvd), d, s, dtype),
            "wo": _matrix(next(keys), (nb, ad, d), ad, s, dtype),
        },
        "ln2": {"scale": ones},
        "mlp": {
            "wi_g": _matrix(next(keys), (nb, d, ff), d, s, dtype),
            "wi_u": _matrix(next(keys), (nb, d, ff), d, s, dtype),
            "wo": _matrix(next(keys), (nb, ff, d), ff, s, dtype),
        },
    }
    return {"embed": embed, "final_norm": {"scale": jnp.ones((d,), dtype)},
            "deq_blocks": blocks}


@functools.lru_cache(maxsize=None)
def _maker(spec: ModelSpec, dtype, layout=None):
    """One jitted maker per configuration, type and layout (a tree
    definition and its shardings, flat, so that it can key the cache)."""
    make = functools.partial(_make, spec, dtype=dtype)
    if layout is None:
        return jax.jit(make)
    return jax.jit(make, out_shardings=jax.tree_util.tree_unflatten(*layout))


def make_params(spec: ModelSpec, seed: int, dtype=jnp.bfloat16,
                shardings=None) -> dict:
    """The configuration's weights for ``seed``: on the default device, or
    laid out as ``shardings`` says (a tree of shardings, one per leaf)."""
    layout = None
    if shardings is not None:
        leaves, treedef = jax.tree_util.tree_flatten(shardings)
        layout = (treedef, tuple(leaves))
    return _maker(spec, jnp.dtype(dtype), layout)(key_from_seed(seed))
