"""Weights made by the benchmark from ``--seed``, on the device, in one
jitted call, in float32 (the type the server and clients hold).

Where a mesh is installed (``repro.sharding.axis_rules``, as ``run.py``
does for a cell on several chips), every array comes out of its program
already split over the mesh (:func:`sharding`); with none, it lands whole
on the default device as before.  The values are the same bits either way:
JAX's partitionable threefry draws each element from its index alone.
"""
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


def sharding(shape, axis: int | None = None):
    """Where an array of ``shape`` lives over the installed mesh: split
    along ``axis`` over all the mesh's devices, or, with ``axis`` None,
    along the first axis that the device count divides, and replicated
    where none does.  None where no mesh is installed.

    JAX splits an array only evenly at a program's boundary, so a named
    ``axis`` that the device count does not divide is an error."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.sharding import current_rules

    mesh = current_rules().mesh
    if mesh is None:
        return None
    n = mesh.devices.size
    if axis is None:
        axis = next((i for i, d in enumerate(shape) if d % n == 0), None)
    elif shape[axis] % n:
        raise ValueError(f"axis {axis} of {tuple(shape)} does not split "
                         f"evenly over {n} devices")
    spec = [None] * len(shape)
    if axis is not None:
        spec[axis] = tuple(mesh.axis_names)
    return NamedSharding(mesh, PartitionSpec(*spec))


def placed(fn, out_sharding=None, **kw):
    """``jax.jit(fn)`` whose output lands in ``out_sharding`` (a sharding
    or a tree of them) where that is not None."""
    import jax

    if out_sharding is not None:
        kw["out_shardings"] = out_sharding
    return jax.jit(fn, **kw)


def init(shapes, seed: int, rule):
    """A tree shaped like ``shapes`` (ShapeDtypeStructs) whose leaf ``i``
    follows ``rule(path, shape)``: ("normal", std) or ("const", value)."""
    return maker(shapes, rule)(key(seed))


def maker(shapes, rule):
    """The one jitted program of :func:`init`: ``key -> tree``."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(rule(_path(kp), tuple(s.shape)), tuple(s.shape), s.dtype)
             for kp, s in flat]
    where = [sharding(shape) for _, shape, _ in specs]

    def make(k):
        leaves = []
        for i, ((kind, v), shape, dtype) in enumerate(specs):
            if kind == "normal":
                leaves.append(v * jax.random.normal(jax.random.fold_in(k, i),
                                                    shape, dtype))
            else:
                leaves.append(jnp.full(shape, v, dtype))
        return jax.tree.unflatten(treedef, leaves)

    out = None if where[0] is None else jax.tree.unflatten(treedef, where)
    return placed(make, out)


def flatten(tree):
    """The leaves of ``tree`` in tree order, raveled and joined in f32,
    split along P over the installed mesh.

    On one device this is one jitted join.  Over a mesh, each device's part
    is written piece by piece from the leaves it overlaps, each leaf
    brought whole to that device in turn: one program that joins split
    leaves into a split vector gathers the whole f32 vector on every device
    (14.99 GB a chip at MiniCPM-2B's P over four v5e chips, by XLA's
    memory analysis)."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree.leaves(tree)
    p = sum(x.size for x in leaves)
    where = sharding((p,), 0)
    if where is None:
        return jax.jit(lambda t: jnp.concatenate(
            [x.astype(jnp.float32).ravel() for x in jax.tree.leaves(t)]))(
            tree)
    parts = []
    for dev, (cut,) in where.addressable_devices_indices_map((p,)).items():
        part = jnp.zeros(cut.stop - cut.start, jnp.float32, device=dev)
        at = 0
        for x in leaves:
            a, b = max(at, cut.start), min(at + x.size, cut.stop)
            if a < b:
                part = piece_program()(part, jax.device_put(x, dev),
                                       a - at, a - cut.start, b - a)
            at += x.size
        parts.append(part)
    return jax.make_array_from_single_device_arrays((p,), where, parts)


def _write_piece(part, leaf, src, dst, n: int):
    import jax.numpy as jnp
    from jax import lax

    from benchmarks.chip.reference.aggregation import over_blocks

    flat = leaf.ravel()

    def put(at, size, out):
        piece = lax.dynamic_slice_in_dim(flat, src + at, size)
        return lax.dynamic_update_slice_in_dim(
            out, piece.astype(jnp.float32), dst + at, 0)

    return over_blocks(n, put, part)


def piece_program():
    """The jitted ``(part, leaf, src, dst, n) -> part`` of :func:`flatten`
    over a mesh: ``part[dst:dst + n] = leaf.ravel()[src:src + n]`` in f32,
    in place of the donated ``part``, on the device that holds both."""
    import jax

    if "fn" not in _PIECE:
        _PIECE["fn"] = jax.jit(_write_piece, static_argnums=4,
                               donate_argnums=0)
    return _PIECE["fn"]


_PIECE: dict = {}


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
