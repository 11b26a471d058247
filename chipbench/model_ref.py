"""Plain reference of the DEQ language model the benchmark runs.

Straightforward ``jax.numpy`` in float32 (matrix products at ``highest``
precision), with no kernel, cache, batching or solver code of the program:
the equilibrium of the weight-tied block group is found by the solver the
configuration states (Broyden's good method, written out plainly, one
solve per row), and the training gradient by the backward it states
(SHINE with its fallback: the solve's own inverse estimate applied to the
loss cotangent).  ``backward="exact"`` gives the exact implicit gradient
instead, its adjoint found by fixed-point iteration.  It imports nothing of
the program.  ``precision="fp8"`` is the control: every matrix product
takes its operands through float8 (e4m3, one scale per tensor), the next
precision below the bfloat16 the configuration states.

On several devices the training reference spreads its parameters, gradients
and moments over them by a rule of its own (``layout``); the mathematics
and its precision are the same as on one.

The model (as the program runs it; departures from the published models
are listed in each configuration file):

    x      = E[tokens]
    block  : h += Attn(rmsnorm(h)); h += SwiGLU(rmsnorm(h))   (pre-norm)
    F(z)   = x + blocks(z) - z       (the group's fixed-point map)
    z*     = F(z*)
    logits = rmsnorm(z*) @ E^T   (or an untied head), over the padded vocab

Attention is causal, rotary (rotate-halves) on q and k, scaled by
``head_dim ** -0.5``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from chipbench.spec import ModelSpec

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _q(a, precision):
    """The operand as the precision holds it.  fp8 rounds the value (one
    scale per tensor) and passes gradients straight through, so the
    backward pass sees the rounded operands but no rounded cotangent."""
    if precision == "f32":
        return a
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    scale = jax.lax.stop_gradient(scale)
    rounded = (a / scale).astype(FP8).astype(F32) * scale
    return a + jax.lax.stop_gradient(rounded - a)


def _mm(eq, a, b, precision):
    return jnp.einsum(eq, _q(a, precision), _q(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos[..., None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, h, pos, spec: ModelSpec, precision):
    b, s, _ = h.shape
    hn, kvn, hd = spec.heads, spec.kv_heads, spec.head_dim
    q = _mm("bsd,de->bse", h, p["wq"], precision).reshape(b, s, hn, hd)
    k = _mm("bsd,de->bse", h, p["wk"], precision).reshape(b, s, kvn, hd)
    v = _mm("bsd,de->bse", h, p["wv"], precision).reshape(b, s, kvn, hd)
    q, k = rope(q, pos, spec.rope_theta), rope(k, pos, spec.rope_theta)
    k = jnp.repeat(k, hn // kvn, axis=2)
    v = jnp.repeat(v, hn // kvn, axis=2)
    scores = _mm("bqhd,bkhd->bhqk", q, k, precision) * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", probs, v, precision).reshape(b, s, hn * hd)
    return _mm("bse,ed->bsd", o, p["wo"], precision)


def swiglu(p, h, precision):
    g = _mm("bsd,df->bsf", h, p["wi_g"], precision)
    u = _mm("bsd,df->bsf", h, p["wi_u"], precision)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["wo"], precision)


def block(p, h, pos, spec: ModelSpec, precision):
    h = h + attention(p["attn"], rmsnorm(h, p["ln1"]["scale"], spec.norm_eps),
                      pos, spec, precision)
    return h + swiglu(p["mlp"], rmsnorm(h, p["ln2"]["scale"], spec.norm_eps),
                      precision)


def blocks(pb, z, pos, spec: ModelSpec, precision):
    # recomputed in the backward pass, which then keeps only block inputs
    step = jax.checkpoint(block, static_argnums=(3, 4))
    h = z
    for j in range(spec.blocks):
        h = step(jax.tree_util.tree_map(lambda a: a[j], pb), h, pos, spec,
                 precision)
    return h


def fixed_point_map(pb, x, z, pos, spec, precision):
    return x + blocks(pb, z, pos, spec, precision) - z


def picard(fn, z0, tol, max_iter):
    """Iterate ``z <- fn(z)`` until ``||fn(z) - z|| <= tol * ||z||``
    (whole tensor) or ``max_iter``; returns ``(z, relative residual,
    iterations)``."""

    def cond(c):
        _, res, it = c
        return (res > tol) & (it < max_iter)

    def body(c):
        z, _, it = c
        z2 = fn(z)
        res = jnp.linalg.norm(z2 - z) / jnp.maximum(jnp.linalg.norm(z2), 1e-30)
        return z2, res, it + 1

    return jax.lax.while_loop(cond, body, (z0, jnp.float32(jnp.inf), 0))


SOLVE_TOL = 1e-6
SOLVE_MAX_ITER = 200


def logits_of(params, z, spec: ModelSpec, precision):
    h = rmsnorm(z, params["final_norm"]["scale"], spec.norm_eps)
    if spec.tied:
        return _mm("bsd,vd->bsv", h, params["embed"]["embedding"], precision)
    return _mm("bsd,dv->bsv", h, params["embed"]["lm_head"], precision)


def _positions(b, s):
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))


# ---------------------------------------------------------------------------
# training: the configured solver and backward, loss, the optimizer
# ---------------------------------------------------------------------------

FALLBACK_RATIO = 1.3  # SHINE's fallback test (Ramzi et al. 2022, sec. 3)
DEN_EPS = 1e-8        # a Broyden pair with |s^T H y| at or under it is not kept


def _bnorm(a):
    return jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim))))


def _per_row(v, a):
    return v.reshape((-1,) + (1,) * (a.ndim - 1))


def _apply(u, v, count, x, transpose=False):
    """``H x`` (or ``H^T x``) for ``H = I + sum_i u_i v_i^T``, per row, over
    the ring slots ``i < count``."""
    valid = (jnp.arange(u.shape[0])[:, None] < count[None]).astype(F32)
    left, right = (v, u) if transpose else (u, v)
    coef = jnp.sum(right * x[None], axis=tuple(range(2, x.ndim + 1))) * valid
    return x + jnp.einsum("mb,mb...->b...", coef, left,
                          precision=jax.lax.Precision.HIGHEST)


def broyden(gfn, z0, spec: ModelSpec):
    """Broyden's good method on ``g(z) = 0``, one solve per row, as the
    configuration states it: ``H_0 = I``, step ``-H g``, the rank-one update
    ``H += (s - H y)(s^T H) / (s^T H y)`` kept in a ring of ``memory``
    pairs, a row done once ``||g|| < tol * max(||z_0||, 1)``, at most
    ``max_steps`` steps, and the row's best iterate returned.  Returns
    ``(z, u, v, count)``: the iterate and the inverse estimate."""
    m = spec.memory
    u = jnp.zeros((m,) + z0.shape, F32)
    v = jnp.zeros_like(u)
    count = jnp.zeros(z0.shape[0], jnp.int32)
    g0 = gfn(z0)
    res0 = _bnorm(g0)
    thresh = spec.tol * jnp.maximum(_bnorm(z0), 1.0)

    def body(_, c):
        z, g, u, v, count, done, best_z, best_res = c
        live = ~done
        z2 = jnp.where(_per_row(live, z), z - _apply(u, v, count, g), z)
        g2 = jnp.where(_per_row(live, z), gfn(z2), g)
        s, hy = z2 - z, _apply(u, v, count, g2 - g)
        den = jnp.sum(s * hy, axis=tuple(range(1, z.ndim)))
        keep = live & (jnp.abs(den) > DEN_EPS)
        a = (s - hy) / _per_row(jnp.where(keep, den, 1.0), s)
        b = _apply(u, v, count, s, transpose=True)
        slot = (jnp.arange(m)[:, None] == (count % m)[None]) & keep[None]
        slot = slot.reshape(slot.shape + (1,) * (z.ndim - 1))
        u, v = jnp.where(slot, a[None], u), jnp.where(slot, b[None], v)
        count = count + keep.astype(jnp.int32)
        res = _bnorm(g2)
        better = res < best_res
        best_z = jnp.where(_per_row(better, z), z2, best_z)
        return (z2, g2, u, v, count, done | (res < thresh), best_z,
                jnp.minimum(res, best_res))

    c = jax.lax.fori_loop(0, spec.max_steps, body,
                          (z0, g0, u, v, count, res0 < thresh, z0, res0))
    return c[6], c[2], c[3], c[4]


def _deq(spec, precision, backward):
    """``z*`` of the group from the start ``z0``, differentiable in
    ``(pb, x)``.  ``backward="shine_fallback"`` is the configured
    estimator: the adjoint is the forward solve's inverse estimate applied
    to the loss cotangent, ``H^T w``, or ``w`` itself where that has a norm
    over ``FALLBACK_RATIO * ||w||``.  ``"exact"`` solves the adjoint
    ``w = g + J^T w`` by fixed-point iteration (the implicit gradient)."""

    def solve_fwd(pb, x, z0):
        pos = _positions(*x.shape[:2])
        if spec.solver != "broyden":
            raise ValueError(f"no reference of solver {spec.solver!r}")
        return broyden(
            lambda z: z - fixed_point_map(pb, x, z, pos, spec, precision),
            z0, spec)

    @jax.custom_vjp
    def solve(pb, x, z0):
        return solve_fwd(pb, x, z0)[0]

    def fwd(pb, x, z0):
        z, u, v, count = solve_fwd(pb, x, z0)
        return z, (pb, x, z, u, v, count)

    def bwd(res, g):
        pb, x, z, u, v, count = res
        pos = _positions(*x.shape[:2])
        _, vjp = jax.vjp(
            lambda pb_, x_, z_: fixed_point_map(pb_, x_, z_, pos, spec,
                                                precision), pb, x, z)
        if backward == "exact":
            w, _, _ = picard(lambda w: g + vjp(w)[2], g, SOLVE_TOL,
                             SOLVE_MAX_ITER)
        elif backward == "shine_fallback":
            w = _apply(u, v, count, g, transpose=True)
            bad = _bnorm(w) > FALLBACK_RATIO * _bnorm(g)
            w = jnp.where(_per_row(bad, g), g, w)
        else:
            raise ValueError(f"no reference of backward {backward!r}")
        dpb, dx, _ = vjp(w)
        return dpb, dx, jnp.zeros_like(z)

    solve.defvjp(fwd, bwd)
    return solve


def loss_sums(params, tokens, targets, z0, spec: ModelSpec, z_loss: float,
              precision="f32", backward="shine_fallback"):
    """Summed next-token cross-entropy plus ``z_loss * sum(lse**2)``, over
    the padded vocabulary as the program computes it (the mean divides by
    the token count), with the solve started at ``z0``; returns the loss
    and ``z*``."""
    x = params["embed"]["embedding"][tokens]
    z = _deq(spec, precision, backward)(params["deq_blocks"], x, z0)

    @jax.checkpoint
    def row(zr, tr):
        lg = logits_of(params, zr[None], spec, precision)[0]
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tr[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold), jnp.sum(lse * lse)

    nll, zz = jax.lax.map(lambda a: row(*a), (z, targets))
    return jnp.sum(nll) + z_loss * jnp.sum(zz), z


def lr_at(step: int, tcfg: dict) -> float:
    """The warm-up of the configured schedule (the steps compared all lie
    inside it: ``step < warmup_steps``)."""
    if step >= tcfg["warmup_steps"]:
        raise ValueError("the reference covers the warm-up steps only")
    return tcfg["lr"] * min(1.0, (step + 1) / max(tcfg["warmup_steps"], 1))


ROWS_PER_CHUNK = 2
CHIPS = "chips"  # the one axis of the reference's mesh


def mesh_over(devices) -> Mesh | None:
    """A one-axis mesh over ``devices``; None for one device."""
    if devices is None or len(devices) <= 1:
        return None
    return Mesh(np.asarray(devices), (CHIPS,))


def leaf_sharding(shape, mesh: Mesh) -> NamedSharding:
    """The reference's rule: a leaf of two or more dimensions is split along
    its largest axis that the device count divides (the last of equals);
    any other leaf is replicated."""
    n = mesh.devices.size
    axes = [i for i, d in enumerate(shape) if d % n == 0] \
        if len(shape) >= 2 else []
    spec = [None] * len(shape)
    if axes:
        spec[max(axes, key=lambda i: (shape[i], i))] = CHIPS
    return NamedSharding(mesh, PartitionSpec(*spec))


def layout(tree, mesh: Mesh):
    """``leaf_sharding`` of every leaf of ``tree`` (arrays or shapes)."""
    return jax.tree_util.tree_map(lambda a: leaf_sharding(a.shape, mesh),
                                  tree)


def _pin(tree, mesh: Mesh | None):
    """``tree`` held to the reference's layout inside a jitted function;
    unchanged on one device."""
    if mesh is None:
        return tree
    return jax.tree_util.tree_map(
        lambda a: jax.lax.with_sharding_constraint(
            a, leaf_sharding(a.shape, mesh)), tree)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _grad(params, tokens, targets, z0, spec, z_loss, precision, backward,
          mesh):
    """Mean loss, its gradient and ``z*``, the gradient summed over chunks
    of rows (rows are independent sequences and solves) so that the
    activations of few rows are live.  ``z0`` is the start of each row's
    solve: the embedding, or the last step's ``z*`` (the configured warm
    start from the iterate).  ``params`` come as stored (bfloat16) and
    are worked on in float32; on a ``mesh`` the float32 copy, each chunk's
    gradient and their sum keep the reference's layout."""
    params = _pin(jax.tree_util.tree_map(lambda a: a.astype(F32), params),
                  mesh)
    n, rows = targets.size, targets.shape[0]
    chunk = min(ROWS_PER_CHUNK, rows)

    def body(i, acc):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk)
        (val, z), g = jax.value_and_grad(loss_sums, has_aux=True)(
            params, sl(tokens), sl(targets), sl(z0), spec, z_loss, precision,
            backward)
        return (acc[0] + val / n,
                _pin(jax.tree_util.tree_map(lambda a, b: a + b / n, acc[1],
                                            _pin(g, mesh)), mesh),
                jax.lax.dynamic_update_slice_in_dim(acc[2], z, i * chunk, 0))

    zeros = _pin(jax.tree_util.tree_map(jnp.zeros_like, params), mesh)
    return jax.lax.fori_loop(0, rows // chunk, body,
                             (jnp.float32(0), zeros, jnp.zeros_like(z0)))


@functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 2, 3))
def _adam(params, grads, mu, nu, tcfg_items, lr, t):
    tcfg = dict(tcfg_items)
    b1, b2, eps, wd = tcfg["b1"], tcfg["b2"], tcfg["eps"], tcfg["weight_decay"]
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    clip = jnp.minimum(1.0, tcfg["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree_util.tree_map(lambda g: g * clip, grads)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def upd(p, g, m, v):
        p = p.astype(F32)
        m2 = b1 * m + (1 - b1) * g
        v2 = b2 * v + (1 - b2) * g * g
        delta = (m2 / c1) / (jnp.sqrt(v2 / c2) + eps)
        if p.ndim >= 2:
            delta = delta + wd * p
        # parameters are stored in the configured bfloat16: returned as
        # such, so that no compiler may keep the update's excess precision
        return (p - lr * delta).astype(jnp.bfloat16), m2, v2

    out = jax.tree_util.tree_map(upd, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t3: t3[i], out, is_leaf=lambda a: isinstance(a, tuple))
    return pick(0), grads, pick(1), pick(2), gnorm


def leaf_norms(tree) -> list[float]:
    return [float(jnp.linalg.norm(a.astype(F32).ravel()))
            for a in jax.tree_util.tree_leaves(tree)]


def outside_group(tree) -> dict:
    """The leaves outside the DEQ group (embedding, head, final norm), by
    path, as float32 arrays on the host."""
    import numpy as np

    return {jax.tree_util.keystr(path): np.asarray(a, np.float32)
            for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]
            if jax.tree_util.keystr(path[:1]) != "['deq_blocks']"}


def train_readings(make_params0, batches, spec: ModelSpec, tcfg: dict,
                   precision="f32", backward="shine_fallback",
                   devices=None) -> dict:
    """Follow the first ``len(batches)`` optimizer steps from the weights
    ``make_params0(shardings)`` makes (made again at the end, for the
    change, so they are not held during the steps).  The first step's
    solves start at the embedding, each later one at the last step's
    ``z*``.  Over several ``devices`` the parameters, gradients and moments
    are spread by ``layout`` (``shardings`` is that layout; None on one
    device).

    Returns the loss of each step, the norm of each leaf of the first
    (clipped) gradient and the leaves of it outside the DEQ group, and the
    norm of each leaf's change over all the steps."""
    mesh = mesh_over(devices)
    shardings = None if mesh is None else layout(
        jax.eval_shape(lambda: make_params0(None)), mesh)
    params = make_params0(shardings)
    if mesh is None:
        mu = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, F32), params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, mu)
    else:
        mu, nu = (jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, F32, device=a.sharding), params)
            for _ in range(2))
    items = tuple(sorted((k, v) for k, v in tcfg.items()
                         if isinstance(v, (int, float))))
    losses, first_grad, outside, z = [], None, None, None
    with jax.default_matmul_precision("highest"):
        for i, (tokens, targets) in enumerate(batches):
            if z is None:
                z = params["embed"]["embedding"][tokens].astype(F32)
            lval, grads, z = _grad(params, tokens, targets, z, spec,
                                   tcfg["z_loss"], precision, backward, mesh)
            params, clipped, mu, nu, _ = _adam(
                params, grads, mu, nu, items, lr_at(i, tcfg),
                float(i + 1))
            losses.append(float(lval))
            if first_grad is None:
                first_grad = leaf_norms(clipped)
                outside = outside_group(clipped)
            del grads, clipped
        del mu, nu, z
        change = [float(_diff_norm(a, b)) for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(make_params0(shardings)))]
    return {"loss": losses, "grad": first_grad, "outside": outside,
            "change": change}


@jax.jit
def _diff_norm(a, b):
    return jnp.linalg.norm((a.astype(F32) - b.astype(F32)).ravel())
