"""Compile the Pallas kernels of the main path for a TPU v5e, at the widths
of ``minicpm-2b`` (d=2304, 36 heads x 64), without a chip: the TPU compiler
works on a described ``v5e:2x2`` topology.  A kernel the chip's compiler
refuses (unaligned block, too much VMEM, unpartitionable) fails here.

Nothing runs, so these tests say nothing about results (the interpret-mode
sweeps in test_kernels.py do) or times.  The topology is described inside a
fixture, never at import: only one process may load the TPU library, and
every test worker imports every test file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.parallel.sharding import ShardCtx

M, B, D, HEADS, HD = 8, 8, 2304, 36, 64
RING = {"decode": (M, B, D), "train": (M, B, 512 * D)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    return Mesh(np.asarray(topo.devices[:4]).reshape(2, 2), ("data", "model"))


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ring_args(sh, shape):
    m, b, d = shape
    bf = jnp.bfloat16
    return dict(u=_sds(sh, shape, bf), v=_sds(sh, shape, bf),
                x=_sds(sh, (b, d)), mask=_sds(sh, (m, b)),
                slot=_sds(sh, (b,), jnp.int32), vec=_sds(sh, (b,)))


@pytest.mark.parametrize("width", sorted(RING))
def test_qn_apply_compiles(one_chip, width):
    a = _ring_args(one_chip, RING[width])
    txt = _compiled_text(
        lambda u, v, x, mk: ops.qn_apply(u, v, x, 0.7, mk, impl="pallas"),
        a["u"], a["v"], a["x"], a["mask"])
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("width", sorted(RING))
def test_qn_apply_multi_compiles(one_chip, width):
    a = _ring_args(one_chip, RING[width])
    xs = _sds(one_chip, (2,) + a["x"].shape)
    txt = _compiled_text(
        lambda u, v, xs, mk: ops.qn_apply_multi(u, v, xs, 0.7, mk,
                                                (False, True), impl="pallas"),
        a["u"], a["v"], xs, a["mask"])
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("width", sorted(RING))
def test_lowrank_append_compiles(one_chip, width):
    a = _ring_args(one_chip, RING[width])
    txt = _compiled_text(
        lambda u, v, s, hy, b, i, sl, up: ops.lowrank_append(
            u, v, s, hy, b, i, sl, up, impl="pallas"),
        a["u"], a["v"], a["x"], a["x"], a["x"], a["vec"], a["slot"], a["vec"])
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("width", sorted(RING))
def test_broyden_step_compiles(one_chip, width):
    a = _ring_args(one_chip, RING[width])
    txt = _compiled_text(
        lambda u, v, g, s, hg, mk, sl, ac: ops.broyden_step(
            u, v, g, s, hg, 1.0, mk, sl, ac, 1e-8, impl="pallas"),
        a["u"], a["v"], a["x"], a["x"], a["x"], a["mask"], a["slot"],
        a["vec"])
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("seq,kv_seq,heads,lengths", [
    pytest.param(512, 512, HEADS, True, id="512"),
    pytest.param(300, 300, HEADS, True, id="300"),
    # the train cell's exact call: whole-sequence blocks, no kv_length
    pytest.param(512, 512, HEADS, False, id="512-train-cell"),
    # a serving prefill past the whole-sequence cap: flash tiles, clamped
    # causal kv copies
    pytest.param(1152, 1152, HEADS, True, id="1152-tiled"),
    # chunked prefill: 128 new rows against a 1152-row kv axis
    pytest.param(128, 1152, HEADS, True, id="128-chunk-of-1152"),
    # a 4-way heads split: 9 local heads have no lane-dense divisor
    pytest.param(512, 512, HEADS // 4, True, id="512-9-heads")])
def test_flash_attention_compiles(one_chip, seq, kv_seq, heads, lengths):
    q = _sds(one_chip, (B, seq, heads, HD), jnp.bfloat16)
    kv = _sds(one_chip, (B, kv_seq, heads, HD), jnp.bfloat16)
    lens = [_sds(one_chip, (B,), jnp.int32)] if lengths else []
    txt = _compiled_text(
        lambda q, k, v, *ln: ops.attention(q, k, v, causal=True,
                                           kv_length=ln[0] if ln else None,
                                           impl="pallas"), q, kv, kv, *lens)
    assert "tpu_custom_call" in txt


def test_decode_attention_compiles(one_chip):
    q = _sds(one_chip, (B, HEADS, HD), jnp.bfloat16)
    kv = _sds(one_chip, (B, 512, HEADS, HD), jnp.bfloat16)
    lens = _sds(one_chip, (B,), jnp.int32)
    txt = _compiled_text(
        lambda q, k, v, ln: ops.decode_attention(q, k, v, ln, impl="pallas"),
        q, kv, kv, lens)
    assert "tpu_custom_call" in txt


def test_rmsnorm_compiles(one_chip):
    x = _sds(one_chip, (B, 512, D), jnp.bfloat16)
    w = _sds(one_chip, (D,), jnp.bfloat16)
    txt = _compiled_text(
        lambda x, w: ops.rmsnorm(x, w, 1e-5, impl="pallas"), x, w)
    assert "tpu_custom_call" in txt


def test_sharded_broyden_step_compiles_batch_local(mesh_2x2):
    """On a (data=2, model=2) mesh the fused step runs per batch shard: the
    kernel is in the program and each chip holds half of the ring."""
    ring = NamedSharding(mesh_2x2, P(None, "data"))
    rows = NamedSharding(mesh_2x2, P("data"))
    shape = RING["train"]
    bf = jnp.bfloat16
    args = (_sds(ring, shape, bf), _sds(ring, shape, bf),
            _sds(rows, shape[1:]), _sds(rows, shape[1:]),
            _sds(rows, shape[1:]), _sds(ring, shape[:2]),
            _sds(rows, shape[1:2], jnp.int32), _sds(rows, shape[1:2]))
    with ops.sharded_kernels(ShardCtx.for_mesh(mesh_2x2)):
        compiled = jax.jit(
            lambda u, v, g, s, hg, mk, sl, ac: ops.broyden_step(
                u, v, g, s, hg, 1.0, mk, sl, ac, 1e-8, impl="pallas"),
        ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    new_u = compiled.output_shardings[0]
    assert new_u.shard_shape(shape) == (M, B // 2, shape[2])
