"""SEAFL's server aggregation, Eq. (4)-(8), written out plainly.

For K buffered client models w_k with data sizes n_k and staleness
tau_k = t - t_k, against the current global g:

    gamma_k = alpha * beta / (tau_k + beta)                       Eq. (4)
    s_k     = mu * (clip(cos(w_k - g, g), -1, 1) + 1) / 2         Eq. (5)
    p_k     = (n_k / sum n) (gamma_k + s_k) / sum_j (...)         Eq. (6)
    g'      = (1 - theta) g + theta sum_k p_k w_k                 Eq. (7)-(8)

Every reduction is an elementwise product and a sum in the given dtype:
float32 for the reference, bfloat16 for the control.

:func:`replay_round` computes one round over flat vectors of any length in
P-blocks of ``block`` elements, split over a mesh where one is given: each
device reads its own part of the global and of the pool block by block, the
(K,) sums are added over the devices, and each device mixes its own part of
the new global.  No array of K rows of the whole length is made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 1 << 24


def partials(g, rows, dtype=jnp.float32):
    """The sums Eq. (5) needs: per row (w_k - g).g and (w_k - g).(w_k - g),
    and g.g."""
    g = g.astype(dtype)
    delta = rows.astype(dtype) - g[None, :]
    return (jnp.sum(delta * g[None, :], axis=1),
            jnp.sum(delta * delta, axis=1), jnp.sum(g * g))


def weights_of(dot, dd, gg, sizes, staleness, hyper, dtype=jnp.float32):
    """Eq. (4)-(6) from the sums of :func:`partials`."""
    cos = dot / jnp.sqrt(dd * gg)
    gamma = (hyper["alpha"] * hyper["beta"]
             / (staleness.astype(dtype) + hyper["beta"]))
    s = hyper["mu"] * (jnp.clip(cos, -1, 1) + 1) / 2
    n = sizes.astype(dtype) / jnp.sum(sizes.astype(dtype))
    p = n * (gamma + s)
    return p / jnp.sum(p)


def weights(g, rows, sizes, staleness, hyper, dtype=jnp.float32):
    """Eq. (4)-(6): the K aggregation weights."""
    return weights_of(*partials(g, rows, dtype), sizes, staleness, hyper,
                      dtype)


def mix(g, rows, p, theta, dtype=jnp.float32):
    """Eq. (7)-(8) in ``dtype``, returned in float32."""
    mixed = jnp.sum(p[:, None] * rows.astype(dtype), axis=0)
    return ((1 - theta) * g.astype(dtype) + theta * mixed).astype(jnp.float32)


def aggregate(g, rows, sizes, staleness, hyper, dtype=jnp.float32):
    """Eq. (4)-(8): (new global in float32, weights in float32)."""
    p = weights(g, rows, sizes, staleness, hyper, dtype)
    return mix(g, rows, p, hyper["theta"], dtype), p.astype(jnp.float32)


def aggregate_trees(g, models, sizes, staleness, hyper, dtype=jnp.float32):
    """Eq. (4)-(8) over parameter trees (dicts of arrays): the cosine sums
    over every leaf.  Returns (new global tree, weights)."""
    flat = lambda t: jnp.concatenate(  # noqa: E731
        [x.astype(dtype).ravel() for x in jax.tree.leaves(t)])
    gf = flat(g)
    rows = jnp.stack([flat(m) for m in models])
    p = weights(gf, rows, sizes, staleness, hyper, dtype)
    theta = hyper["theta"]
    new = jax.tree.map(
        lambda gl, *ms: ((1 - theta) * gl.astype(dtype) + theta * sum(
            p[i] * m.astype(dtype) for i, m in enumerate(ms))
        ).astype(jnp.float32), g, *models)
    return new, p.astype(jnp.float32)


def over_blocks(n: int, body, carry, block: int = BLOCK):
    """``carry = body(start, size, carry)`` over [0, n) in blocks of
    ``block``: the whole blocks in a loop, then the tail."""
    size = min(block, n)
    whole, tail = divmod(n, size)
    carry = lax.fori_loop(
        0, whole, lambda i, c: body(i * size, size, c), carry)
    return body(whole * size, tail, carry) if tail else carry


def _cut(x, start, size):
    """Elements [start, start + size) of the flat vector ``x``."""
    return lax.dynamic_slice_in_dim(x, start, size)


def _local_round(g, pool, idx, sizes, staleness, hyper, dtype, block,
                 total):
    k = idx.shape[0]

    def rows(start, size):
        return jnp.stack([_cut(r, start, size) for r in pool])[idx]

    def sums(start, size, acc):
        part = partials(_cut(g, start, size), rows(start, size), dtype)
        return tuple(a + b for a, b in zip(acc, part))

    zero = (jnp.zeros((k,), dtype), jnp.zeros((k,), dtype),
            jnp.zeros((), dtype))
    dot, dd, gg = total(over_blocks(g.shape[0], sums, zero, block))
    p = weights_of(dot, dd, gg, sizes, staleness, hyper, dtype)

    def new(start, size, out):
        blk = mix(_cut(g, start, size), rows(start, size), p,
                  hyper["theta"], dtype)
        return lax.dynamic_update_slice_in_dim(out, blk, start, axis=0)

    out = over_blocks(g.shape[0], new, jnp.zeros(g.shape, jnp.float32),
                      block)
    return out, p.astype(jnp.float32)


def replay_round(hyper, dtype=jnp.float32, mesh=None, block=BLOCK):
    """A jitted Eq. (4)-(8) round ``(g, pool, idx, sizes, staleness) ->
    (new global, weights)``: ``pool`` is a tuple of flat client models and
    the round's rows are ``pool[idx]``, read block by block.  With
    ``mesh``, ``g``, each pool row and the new global are split along P
    over all its devices."""
    def local(total):
        return lambda g, pool, idx, sz, tau: _local_round(
            g, pool, idx, sz, tau, hyper, dtype, block, total)

    if mesh is None:
        return jax.jit(local(lambda x: x))
    from jax.sharding import PartitionSpec as P

    ax = tuple(mesh.axis_names)
    return jax.jit(jax.shard_map(
        local(lambda x: lax.psum(x, ax)), mesh=mesh,
        in_specs=(P(ax), P(ax), P(), P(), P()),
        out_specs=(P(ax), P()), check_vma=False))


def _local_gap(a, b, block, total):
    def widest(start, size, acc):
        ab, bb = _cut(a, start, size), _cut(b, start, size)
        return (jnp.maximum(acc[0], jnp.max(jnp.abs(ab - bb))),
                jnp.maximum(acc[1], jnp.max(jnp.abs(ab))),
                jnp.maximum(acc[2], jnp.max(jnp.abs(bb))))

    zero = jnp.zeros((), jnp.float32)
    return total(over_blocks(a.shape[0], widest, (zero,) * 3, block))


def widest_gap(mesh=None, block=BLOCK):
    """A jitted ``(a, b) -> (max |a - b|, max |a|, max |b|)`` over two flat
    float32 vectors, block by block, split as :func:`replay_round` splits
    the global."""
    if mesh is None:
        return jax.jit(lambda a, b: _local_gap(a, b, block, lambda x: x))
    from jax.sharding import PartitionSpec as P

    ax = tuple(mesh.axis_names)
    return jax.jit(jax.shard_map(
        lambda a, b: _local_gap(a, b, block, lambda x: lax.pmax(x, ax)),
        mesh=mesh, in_specs=(P(ax), P(ax)), out_specs=P(),
        check_vma=False))
