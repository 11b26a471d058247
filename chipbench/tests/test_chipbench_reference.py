"""The plain reference's solver and backward: Broyden's method as the
configuration states it finds the fixed point, one row's solve does not
depend on the others (so the reference may run rows in chunks), and the
exact backward gives the implicit gradient."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _tiny import tiny_spec
from chipbench import model_ref
from chipbench.weights import make_params


def linear_problem(rows=3, shape=(4, 5), seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    n = int(np.prod(shape))
    a = 0.6 * jax.random.orthogonal(k1, n)
    c = jax.random.normal(k2, (rows,) + shape)
    f = lambda z: (z.reshape(rows, n) @ a.T).reshape(z.shape) + c
    exact = jnp.linalg.solve(jnp.eye(n) - a, c.reshape(rows, n).T).T
    return f, c, exact.reshape(c.shape)


def test_broyden_finds_the_fixed_point():
    f, c, exact = linear_problem()
    spec = dataclasses.replace(tiny_spec(), max_steps=40, tol=1e-7)
    z, u, v, count = model_ref.broyden(lambda z: z - f(z), c, spec)
    np.testing.assert_allclose(z, exact, atol=1e-4)
    assert u.shape == (spec.memory,) + c.shape and int(count.min()) > 0


def test_rows_solve_alone():
    f, c, _ = linear_problem()
    spec = tiny_spec()
    together = model_ref.broyden(lambda z: z - f(z), c, spec)[0]
    for r in range(c.shape[0]):
        def g_row(z, r=r):
            full = c.at[r].set(z[0])
            return (full - f(full))[r:r + 1]
        alone = model_ref.broyden(g_row, c[r:r + 1], spec)[0]
        np.testing.assert_allclose(alone[0], together[r], atol=1e-5)


def test_inverse_estimate_is_the_identity_before_any_update():
    x = jnp.arange(6.0).reshape(2, 3)
    u = jnp.ones((4, 2, 3))
    np.testing.assert_array_equal(
        model_ref._apply(u, u, jnp.zeros(2, jnp.int32), x), x)


@pytest.mark.parametrize("backward", ["exact", "shine_fallback"])
def test_backward_against_unrolled_iteration(backward):
    spec = dataclasses.replace(tiny_spec(), d=32, heads=2, kv_heads=2,
                               head_dim=16, d_ff=64, vocab=257,
                               max_steps=40, tol=1e-6)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    make_params(spec, 3))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 257)
    pos = model_ref._positions(2, 8)
    x = params["embed"]["embedding"][tokens]
    w = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def implicit(pb):
        z = model_ref._deq(spec, "f32", backward)(pb, x, x)
        return jnp.sum(z * w)

    def unrolled(pb):
        z = x
        for _ in range(60):
            z = model_ref.fixed_point_map(pb, x, z, pos, spec, "f32")
        return jnp.sum(z * w)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(implicit)(params["deq_blocks"])
        want = jax.grad(unrolled)(params["deq_blocks"])
        assert abs(implicit(params["deq_blocks"])
                   - unrolled(params["deq_blocks"])) < 1e-3 * abs(
                       unrolled(params["deq_blocks"]))
    gap = max(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
              for a, b in zip(jax.tree_util.tree_leaves(got),
                              jax.tree_util.tree_leaves(want)))
    if backward == "exact":
        assert gap < 1e-3
    else:
        # SHINE's estimate departs from the implicit gradient by design,
        # but stays a usable direction
        assert 1e-3 < gap < 1.0
