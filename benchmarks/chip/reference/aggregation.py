"""SEAFL's server aggregation, Eq. (4)-(8), written out plainly.

For K buffered client models w_k with data sizes n_k and staleness
tau_k = t - t_k, against the current global g:

    gamma_k = alpha * beta / (tau_k + beta)                       Eq. (4)
    s_k     = mu * (clip(cos(w_k - g, g), -1, 1) + 1) / 2         Eq. (5)
    p_k     = (n_k / sum n) (gamma_k + s_k) / sum_j (...)         Eq. (6)
    g'      = (1 - theta) g + theta sum_k p_k w_k                 Eq. (7)-(8)

Every reduction is an elementwise product and a sum in the given dtype:
float32 for the reference, bfloat16 for the control.
"""
from __future__ import annotations

import jax.numpy as jnp


def weights(g, rows, sizes, staleness, hyper, dtype=jnp.float32):
    """Eq. (4)-(6): the K aggregation weights."""
    g = g.astype(dtype)
    rows = rows.astype(dtype)
    delta = rows - g[None, :]
    dot = jnp.sum(delta * g[None, :], axis=1)
    cos = dot / jnp.sqrt(jnp.sum(delta * delta, axis=1) * jnp.sum(g * g))
    gamma = (hyper["alpha"] * hyper["beta"]
             / (staleness.astype(dtype) + hyper["beta"]))
    s = hyper["mu"] * (jnp.clip(cos, -1, 1) + 1) / 2
    n = sizes.astype(dtype) / jnp.sum(sizes.astype(dtype))
    p = n * (gamma + s)
    return p / jnp.sum(p)


def aggregate(g, rows, sizes, staleness, hyper, dtype=jnp.float32):
    """Eq. (4)-(8): (new global in float32, weights in float32)."""
    p = weights(g, rows, sizes, staleness, hyper, dtype)
    mixed = jnp.sum(p[:, None] * rows.astype(dtype), axis=0)
    theta = hyper["theta"]
    new = (1 - theta) * g.astype(dtype) + theta * mixed
    return new.astype(jnp.float32), p.astype(jnp.float32)


def aggregate_trees(g, models, sizes, staleness, hyper, dtype=jnp.float32):
    """Eq. (4)-(8) over parameter trees (dicts of arrays): the cosine sums
    over every leaf.  Returns (new global tree, weights)."""
    import jax

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
