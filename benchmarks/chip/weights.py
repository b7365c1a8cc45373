"""Weights made by the benchmark from ``--seed``, on the device, in one
jitted call, in float32 (the type the server and clients hold)."""
from __future__ import annotations

import math


def key(seed: int):
    """A PRNG key from all bits of ``seed`` (``PRNGKey`` alone keeps only
    the low 32, so seeds 2**32 apart would collide)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def normal_all(scale: float):
    """Every leaf ~ N(0, scale^2): a global near init scale."""
    return lambda path, shape: ("normal", scale)


def fan_in(path: str, shape) -> tuple:
    """Kernels ``.../w`` ~ N(0, 1/fan_in), biases ``.../b`` zero, norm
    scales ``.../scale`` one."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "w":
        return ("normal", 1.0 / math.sqrt(math.prod(shape[:-1])))
    if leaf == "scale":
        return ("const", 1.0)
    if leaf == "b":
        return ("const", 0.0)
    raise KeyError(f"no initialisation rule for leaf {path!r}")


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def init(shapes, seed: int, rule):
    """A tree shaped like ``shapes`` (ShapeDtypeStructs) whose leaf ``i``
    follows ``rule(path, shape)``: ("normal", std) or ("const", value)."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(rule(_path(kp), tuple(s.shape)), tuple(s.shape), s.dtype)
             for kp, s in flat]

    @jax.jit
    def make(k):
        leaves = []
        for i, ((kind, v), shape, dtype) in enumerate(specs):
            if kind == "normal":
                leaves.append(v * jax.random.normal(jax.random.fold_in(k, i),
                                                    shape, dtype))
            else:
                leaves.append(jnp.full(shape, v, dtype))
        return jax.tree.unflatten(treedef, leaves)

    return make(key(seed))


def flatten(tree):
    """The leaves of ``tree`` in tree order, raveled and joined in f32."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: jnp.concatenate(
        [x.astype(jnp.float32).ravel() for x in jax.tree.leaves(t)]))(tree)


def unflatten(flat, shapes):
    """The inverse of ``flatten``: ``flat`` cut into the leaves of
    ``shapes`` (ShapeDtypeStructs), in tree order and in their dtypes."""
    import jax

    leaves, treedef = jax.tree.flatten(shapes)
    out, at = [], 0
    for s in leaves:
        n = math.prod(s.shape)
        out.append(flat[at:at + n].reshape(s.shape).astype(s.dtype))
        at += n
    return jax.tree.unflatten(treedef, out)
