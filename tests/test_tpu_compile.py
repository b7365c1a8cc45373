"""v5e AOT compiles of the server aggregation kernels at real widths.

Compiles, for a described ``v5e:2x2`` topology and with no chip attached,
every ``seafl_agg`` entry point the server calls, at whisper-tiny's packed
P (not a multiple of ``block_p``, so the padding path is in each program):
the delta-free seafl path, fedavg and fedbuff at K = 4 and fedasync at
K = 1, for f32 and bf16 buffers, plus the slot-sharded aggregation over a
4-chip 'pod' mesh.  Each compiled program must hold a ``tpu_custom_call``:
Mosaic refuses kernels here that interpret mode runs happily.

The topology is described inside a fixture, never at import, and the
tests stay in this one file: only one process at a time may load the TPU
compiler's library.  The persistent compilation cache is off around the
compiles (a compile for a described chip cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding,
)

from repro.kernels.seafl_agg import ops

# ParamPacker size of the published whisper-tiny (pinned against the
# config in tests/test_bringup.py)
P_WHISPER_TINY = 56_437_248


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                          # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _entry_args(name, dtype, sharding, k=4):
    """(jitted body, abstract args) of one server aggregation entry point."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    g = sds((P_WHISPER_TINY,), jnp.float32)
    stacked = sds((k, P_WHISPER_TINY), dtype)
    vec, scalar = sds((k,), jnp.float32), sds((), jnp.float32)
    return {
        "seafl_aggregate_flat_from_params": (
            ops._seafl_aggregate_flat_from_params_jit,
            (g, stacked, vec, vec, scalar, scalar, scalar, scalar)),
        "fedavg_aggregate_flat": (ops._fedavg_aggregate_flat_jit,
                                  (g, stacked, vec)),
        "fedbuff_aggregate_flat": (ops._fedbuff_aggregate_flat_jit,
                                   (g, stacked, scalar)),
        "fedasync_aggregate_flat": (
            ops._fedasync_aggregate_flat_jit,
            (g, sds((P_WHISPER_TINY,), dtype), scalar)),
    }[name]


# kernels per program: the seafl path runs the partials and the mix
_CALLS = {"seafl_aggregate_flat_from_params": 2, "fedavg_aggregate_flat": 1,
          "fedbuff_aggregate_flat": 1, "fedasync_aggregate_flat": 1}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(_CALLS))
def test_server_aggregation_compiles_for_v5e(one_chip, name, dtype):
    body, args = _entry_args(name, dtype, one_chip)
    assert P_WHISPER_TINY % 2048 != 0          # the _pad_to path is compiled
    hlo = body.lower(*args, interpret=False).compile().as_text()
    assert hlo.count("tpu_custom_call") == _CALLS[name]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_pod_sharded_aggregation_compiles_for_four_v5e(topo, dtype):
    """The (K, P) buffer split P('pod', None) over four chips: the kernels
    run per shard in shard_map, the mix is psum-ed, and the buffer is
    never gathered onto one chip."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("pod",))
    replicated = NamedSharding(mesh, PartitionSpec())
    body, args = _entry_args("seafl_aggregate_flat_from_params", dtype,
                             replicated)
    buf = jax.ShapeDtypeStruct(args[1].shape, dtype, sharding=NamedSharding(
        mesh, PartitionSpec("pod", None)))
    slots = ops.slot_sharding_of(buf)
    assert slots is not None
    compiled = body.lower(args[0], buf, *args[2:], interpret=False,
                          slot_sharding=slots).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 2
    assert "all-reduce" in hlo
    assert "all-gather" not in hlo
    # each chip holds the global and its share of the buffer, less than
    # the global plus the whole buffer that one chip holds unsharded
    whole = (4 + 4 * jnp.dtype(dtype).itemsize) * P_WHISPER_TINY
    assert compiled.memory_analysis().argument_size_in_bytes < whole
