"""The plain reference: a per-tenant Adam in ``jax.numpy``, one tensor at a
time.

It imports nothing of the system under test.  It replays a tenant's whole
run from the seed -- the initial parameters and the two gradient trees
the tenant pushed in turn -- tensor by tensor, on the chip where the
tenant's trees were made, after the measured window has closed and the
service's state is freed.  Adam is elementwise, so a tensor replayed
alone gives what the whole tree gives; the replay then holds the moments
of one tensor, not of the tenant.

``dtype=float32`` is the reference; ``dtype=bfloat16`` is the control,
the same arithmetic one precision below the configuration's.  A tenant
with one gradient tree pushes it every step; with two, in turn.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

PARAM_SCALE, GRAD_SCALE = 0.05, 0.01


def seed_key(seed: int):
    """A key from any whole seed: ``PRNGKey`` keeps only the low 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def tree_key(seed: int, tenant: int, what: int):
    """Key of one tree: ``what`` 0 and 1 are the tenant's gradient trees,
    ``2 + a`` its initial parameters on its ``a``-th arrival."""
    return jax.random.fold_in(jax.random.fold_in(seed_key(seed), tenant),
                              what)


def tree_maker(inventory, scale):
    """Jitted ``key -> {tensor: (n,) float32 normal * scale}``: one device
    program per model, so a tenant's tree is made in one call."""
    names = [name for name, _ in inventory]
    offs = [0]
    for _, n in inventory:
        offs.append(offs[-1] + int(n))

    @jax.jit
    def make(key):
        flat = jax.random.normal(key, (offs[-1],), jnp.float32) * scale
        return {name: flat[offs[i]:offs[i + 1]]
                for i, name in enumerate(names)}

    return make


def _adam_steps(p, g0, g1, n, *, lr, b1, b2, eps, dtype):
    """``n`` textbook Adam steps from zero moments, step t taking g0 when t
    is odd and g1 when it is even.  The step's coefficients are worked out
    in float32 and then cast to ``dtype``, the precision of every array."""
    f32 = jnp.float32
    one, b1, b2 = f32(1.0), f32(b1), f32(b2)

    def step(t, carry, g):
        p, mu, nu = carry
        tt = jnp.asarray(t, f32)
        c1 = one - b1 ** tt
        c2 = one - b2 ** tt
        k1, k1c, k2, k2c, lr_, eps_ = (
            jnp.asarray(x, dtype)
            for x in (b1, one - b1, b2, one - b2, f32(lr), f32(eps)))
        c1, c2 = c1.astype(dtype), c2.astype(dtype)
        mu = jax.tree_util.tree_map(lambda m, x: k1 * m + k1c * x, mu, g)
        nu = jax.tree_util.tree_map(lambda v, x: k2 * v + k2c * x * x, nu, g)
        p = jax.tree_util.tree_map(
            lambda w, m, v: w - lr_ * (m / c1) / (jnp.sqrt(v / c2) + eps_),
            p, mu, nu)
        return p, mu, nu

    def pair(i, carry):
        carry = step(2 * i + 1, carry, g0)
        return step(2 * i + 2, carry, g1)

    cast = lambda t: jax.tree_util.tree_map(lambda x: x.astype(dtype), t)  # noqa: E731
    p, g0, g1 = cast(p), cast(g0), cast(g1)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
    carry = jax.lax.fori_loop(0, n // 2, pair, (p, zeros, zeros))
    carry = jax.lax.cond(n % 2 == 1,
                         lambda c: step(n, c, g0), lambda c: c, carry)
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), carry[0])


_replay = jax.jit(_adam_steps,
                  static_argnames=("lr", "b1", "b2", "eps", "dtype"))


@jax.jit
def _gap_parts(pulled, ref, init):
    """(max |pulled - ref|, max |ref - init|) over one tensor."""
    return (jnp.max(jnp.abs(pulled.reshape(-1) - ref)),
            jnp.max(jnp.abs(ref - init)))


def replay(opt, init, grads, steps, dtype=jnp.float32):
    """The parameters after ``steps`` updates, replayed: of one tensor, or
    of a whole tree, which gives the same numbers tensor by tensor."""
    g0, g1 = grads[0], grads[-1]
    return _replay(init, g0, g1, jnp.int32(steps),
                   lr=float(opt["lr"]), b1=float(opt["b1"]),
                   b2=float(opt["b2"]), eps=float(opt["eps"]), dtype=dtype)


def gap_parts(pulled, ref, init) -> Tuple[float, float]:
    """The two maxima of :func:`gap` over one tensor, on the host."""
    err, moved = jax.device_get(_gap_parts(pulled, ref, init))
    return float(err), float(moved)


def gap_of(parts) -> float:
    """:func:`gap` from every tensor's :func:`gap_parts`."""
    parts = list(parts)
    return max(e for e, _ in parts) / max(m for _, m in parts)


def gap(pulled, ref, init) -> float:
    """The number compared: the widest gap between a pulled parameter and
    the reference's, as a share of the largest distance the reference
    moved any parameter from its start.  Rounding drifts by about the
    same share of each step, so the number stays put as a faster service
    fits more steps into the window; a lost, doubled or altered update
    moves it by a large share of one step."""
    return gap_of(gap_parts(pulled[k], ref[k], init[k]) for k in ref)
