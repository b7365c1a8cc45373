"""The harness's own programs at MiniCPM-2B's packed P, compiled for a
described ``v5e:2x2`` with no chip attached: the seeded tree and the
pieces of its flat f32 vector, a pool row, one reference round (a pool of
2, K = 4) and the gap of two globals, each split over a four-chip ``pod``
mesh.  ``memory_analysis`` (arguments, outputs not aliased to a donated
argument, and temporaries together) bounds the bytes each program holds on
one chip; a whole f32 P-vector (10.9 GB) on one chip would break every
bound.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around the compiles (a compile for a
described chip cannot be read back).  The tests skip only where the TPU
compiler is not installed; a topology that cannot be described is a
failure, so the budgets never pass by not running.  Only one process at a
time may load the TPU compiler: under several pytest workers this file and
``tests/test_tpu_compile.py`` may each go to one, which passes only with
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` set for the test run; in one process both
pass as they are.
"""
import importlib.util
import os
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.chip import models, weights  # noqa: E402
from benchmarks.chip.drivers import server  # noqa: E402
from benchmarks.chip.reference import aggregation as ref  # noqa: E402

MINICPM = {"name": "minicpm-2b", "builder": "lm", "arch": "minicpm-2b",
           "packed_params": 2_725_173_504}
GB = 1e9
HYPER = {"alpha": 0.6, "mu": 0.4, "beta": 4.0, "theta": 0.9}


@pytest.fixture(scope="module")
def mesh():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        if importlib.util.find_spec("libtpu") is None:
            pytest.skip("the TPU compiler (libtpu) is not installed")
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield Mesh(np.asarray(desc.devices), ("pod",))
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def rules(mesh):
    from repro.sharding import axis_rules

    with axis_rules(mesh):
        yield mesh


def _sds(shape, dtype, axis=None, replicated=False):
    from jax.sharding import NamedSharding, PartitionSpec

    where = (NamedSharding(weights.sharding((1,)).mesh, PartitionSpec())
             if replicated else weights.sharding(shape, axis))
    return jax.ShapeDtypeStruct(shape, dtype, sharding=where)


def _per_chip(compiled) -> float:
    m = compiled.memory_analysis()
    print(f"{m.argument_size_in_bytes / GB:.3f} GB arguments, "
          f"{m.output_size_in_bytes / GB:.3f} outputs, "
          f"{m.temp_size_in_bytes / GB:.3f} temporaries, "
          f"{m.alias_size_in_bytes / GB:.3f} aliased")
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) / GB


def _key():
    return _sds((2,), jnp.uint32, replicated=True)


def test_seeded_tree_and_flat_vector_fit_a_chip(rules):
    """``weights.init``'s one program, and the largest piece that
    ``weights.flatten`` writes into one chip's part of the flat vector,
    with the whole leaf it is cut from on that chip."""
    from jax.sharding import SingleDeviceSharding

    shapes = models.param_shapes(MINICPM)
    init = weights.maker(shapes, weights.normal_all(0.02)).lower(
        _key()).compile()
    p = MINICPM["packed_params"]
    part = p // 4
    big = max(jax.tree.leaves(shapes), key=lambda s: s.size)
    chip = SingleDeviceSharding(rules.devices.flat[0])
    piece = weights.piece_program().lower(
        jax.ShapeDtypeStruct((part,), jnp.float32, sharding=chip),
        jax.ShapeDtypeStruct(big.shape, big.dtype, sharding=chip),
        0, 0, min(big.size, part)).compile()
    assert weights.sharding((p,), 0).shard_shape((p,)) == (part,)
    assert _per_chip(init) < 6 and _per_chip(piece) < 6


def test_pool_row_fits_a_chip(rules):
    p = MINICPM["packed_params"]
    step = server.step_program(weights.sharding((p,), 0)).lower(
        _sds((p,), jnp.float32, 0), _key(),
        _sds((), jnp.float32, replicated=True)).compile()
    assert _per_chip(step) < 6


def test_reference_round_and_gap_fit_a_chip(rules):
    """One reference round over a pool of 2 with K = 4, and the widest gap
    of two globals."""
    p = MINICPM["packed_params"]
    g = _sds((p,), jnp.float32, 0)
    k = _sds((4,), jnp.float32, replicated=True)
    rnd = ref.replay_round(HYPER, jnp.float32, rules).lower(
        g, (g, g), _sds((4,), jnp.int32, replicated=True), k, k).compile()
    gap = ref.widest_gap(rules).lower(g, g).compile()
    assert _per_chip(rnd) < 12 and _per_chip(gap) < 6
