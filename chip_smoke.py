#!/usr/bin/env python3
"""Bring-up smoke run of the SEAFL trainer and its fused aggregation
kernels on a TPU, at whisper-tiny's published widths (arXiv:2212.04356).

    python3 chip_smoke.py             # one chip: kernel, trainer, wire phases
    python3 chip_smoke.py --chips 4   # slot-sharded aggregation over a
                                      # 4-chip 'pod' mesh vs one device

Phases (one process, which holds the chip; nothing runs in a child):

  kernel   every server aggregation entry point (seafl delta-free, fedavg,
           fedbuff at K=4; fedasync at K=1) compiled with interpret=False
           for f32 and bf16 buffers at whisper-tiny's packed P, checked for
           a ``tpu_custom_call`` and against ``kernels/seafl_agg/ref.py``
           under ``default_matmul_precision("highest")``;
  trainer  ``algorithm=seafl``: 4 clients, concurrency 2, buffer 2, 3
           aggregation rounds through ``launch.train.build_lm_fl`` and
           ``FLSimulation``, default wire;
  wire     the same with ``algorithm=seafl2``, int8 uplink and topk:0.1
           downlink (codecs, top-k, ingest scatters, dispatch ring);
  sharded  (``--chips 4`` only, and nothing else then) the delta-free
           aggregation with the (K, P) buffer placed ``P('pod', None)`` by
           ``sharding.shard_update_buffer``, against one device.

Weights are random, from a fixed seed.  Wall times and peak bytes are
printed for information: this is a smoke run, not a measurement.  Any
failed check raises, so the script exits non-zero; without a TPU it exits
non-zero before any phase.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels.seafl_agg import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import DEVICE_PEAKS  # noqa: E402
from repro.launch.train import build_lm_fl, device_line  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.runtime.simulator import FLSimulation, SimConfig  # noqa: E402

ARCH = "whisper-tiny"
K = 4
SEAFL_HYPER = (3.0, 1.0, 10.0, 0.8)       # alpha, mu, beta, theta
FEDBUFF_ETA = 0.5
FEDASYNC_STALENESS = 2.0

# Kernel vs ref.py.  Both read the same buffer values and accumulate in
# f32, so only the summation order differs, for f32 and bf16 buffers
# alike: the new global within 1e-5 of max|ref|, the weights within 1e-5.
GLOBAL_RTOL = 1e-5
WEIGHT_ATOL = 1e-5
# The Eq. (5) partials inside the seafl path sum over all of P, block by
# block in the kernel and in XLA's own order in ref.py: the cosine within
# 1e-4 absolutely, the squared norms within 1e-4 relatively.
PARTIALS_TOL = 1e-4


def require_tpu():
    """The device gate: the TPU devices JAX runs on, or a RuntimeError."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX runs on {devs[0].platform!r}; this "
                           "smoke run never carries on without the chip")
    return devs


def packed_size(arch: str = ARCH) -> int:
    """P: the parameter count ``ParamPacker`` packs for ``arch``."""
    model = build_model(get_config(arch))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def agg_inputs(p: int, k: int, dtype, seed: int = 0):
    """A global near init scale and k client rows a small step away."""
    @jax.jit
    def make(key):
        kg, kd = jax.random.split(key)
        g = 0.02 * jax.random.normal(kg, (p,), jnp.float32)
        rows = g[None, :] + 1e-3 * jax.random.normal(kd, (k, p), jnp.float32)
        return g, rows.astype(dtype)

    g, stacked = make(jax.random.PRNGKey(seed))
    sizes = jnp.asarray(np.arange(1, k + 1) * 10.0, jnp.float32)
    stale = jnp.asarray(np.arange(k), jnp.float32)
    return g, stacked, sizes, stale


def _fedavg_ref(g, stacked, sizes):
    n = sizes / jnp.sum(sizes)
    return ref.weighted_agg_ref(n, stacked, g, 1.0), n


def _fedbuff_ref(g, stacked, eta):
    uniform = jnp.full((stacked.shape[0],), 1.0 / stacked.shape[0])
    return ref.weighted_agg_ref(uniform, stacked, g, eta), uniform


def _fedasync_ref(g, client, staleness):
    alpha = 0.6 * (1.0 + staleness) ** -0.5
    return ref.weighted_agg_ref(jnp.ones((1,)), client[None], g, alpha)


def agg_cases(g, stacked, sizes, stale):
    """name -> (jitted body, args, public entry point, reference).  The
    reference takes the same args and returns what the entry point
    returns: (global, weights), or the global alone for fedasync."""
    hyper = tuple(jnp.float32(x) for x in SEAFL_HYPER)
    return {
        "seafl_aggregate_flat_from_params": (
            ops._seafl_aggregate_flat_from_params_jit,
            (g, stacked, sizes, stale, *hyper),
            ops.seafl_aggregate_flat_from_params,
            ref.seafl_aggregate_flat_from_params_ref),
        "fedavg_aggregate_flat": (
            ops._fedavg_aggregate_flat_jit, (g, stacked, sizes),
            ops.fedavg_aggregate_flat, _fedavg_ref),
        "fedbuff_aggregate_flat": (
            ops._fedbuff_aggregate_flat_jit,
            (g, stacked, jnp.float32(FEDBUFF_ETA)),
            ops.fedbuff_aggregate_flat, _fedbuff_ref),
        "fedasync_aggregate_flat": (
            ops._fedasync_aggregate_flat_jit,
            (g, stacked[0], jnp.float32(FEDASYNC_STALENESS)),
            ops.fedasync_aggregate_flat, _fedasync_ref),
    }


def agg_errors(out, want) -> tuple[float, float]:
    """(max|global - ref| / max|ref|, max|weights - ref weights|)."""
    if isinstance(want, tuple):
        (out_g, out_w), (want_g, want_w) = out, want
        w_err = float(jnp.max(jnp.abs(out_w - want_w)))
    else:
        out_g, want_g, w_err = out, want, 0.0
    g_err = float(jnp.max(jnp.abs(out_g.astype(jnp.float32)
                                  - want_g.astype(jnp.float32)))
                  / jnp.max(jnp.abs(want_g.astype(jnp.float32))))
    return g_err, w_err


def check_errors(label: str, g_err: float, w_err: float) -> None:
    if not (g_err <= GLOBAL_RTOL and w_err <= WEIGHT_ATOL):
        raise AssertionError(
            f"{label}: global rel err {g_err:.3e} (tol {GLOBAL_RTOL:.0e}), "
            f"weight abs err {w_err:.3e} (tol {WEIGHT_ATOL:.0e})")


def partials_errors(stacked, g, interpret: bool) -> tuple[float, float]:
    """(max cosine abs err, max squared-norm rel err) of the delta-free
    Eq. (5) partials kernel against ref.py."""
    got = ops.similarity_partials_from_params(stacked, g, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.similarity_partials_from_params_ref)(stacked, g)

    def cos(x):
        return x[:, 0] / jnp.sqrt(x[:, 1] * x[:, 2])

    norms = jnp.abs(got[:, 1:3] - want[:, 1:3]) / want[:, 1:3]
    return (float(jnp.max(jnp.abs(cos(got) - cos(want)))),
            float(jnp.max(norms)))


def kernel_phase(p: int, interpret: bool = False) -> None:
    """Every server aggregation entry point at (K, p), f32 and bf16."""
    worst: dict[str, tuple[float, float]] = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        dt = jnp.dtype(dtype).name
        inputs = agg_inputs(p, K, dtype)
        cos_err, norm_err = partials_errors(inputs[1], inputs[0], interpret)
        print(f"[kernel] similarity_partials_from_params {dt} K={K} P={p} "
              f"cos_abs_err={cos_err:.3e} norm_rel_err={norm_err:.3e} "
              f"(tol {PARTIALS_TOL:.0e})", flush=True)
        if not (cos_err <= PARTIALS_TOL and norm_err <= PARTIALS_TOL):
            raise AssertionError(f"partials {dt}: cos {cos_err:.3e}, "
                                 f"norms {norm_err:.3e}")
        for name, (body, args, entry, want) in agg_cases(*inputs).items():
            t0 = time.perf_counter()
            hlo = body.lower(*args, interpret=interpret).compile().as_text()
            calls = hlo.count("tpu_custom_call")
            if not interpret and calls == 0:
                raise AssertionError(f"{name} {dt}: no tpu_custom_call in "
                                     "the compiled program")
            out = jax.block_until_ready(entry(*args, interpret=interpret))
            with jax.default_matmul_precision("highest"):
                expect = jax.jit(want)(*args)
            g_err, w_err = agg_errors(out, expect)
            k = args[1].shape[0] if args[1].ndim == 2 else 1
            print(f"[kernel] {name} {dt} K={k} P={p} "
                  f"tpu_custom_call={calls} global_rel_err={g_err:.3e} "
                  f"weight_abs_err={w_err:.3e} "
                  f"wall_s={time.perf_counter() - t0:.3f}", flush=True)
            check_errors(f"{name} {dt}", g_err, w_err)
            prev = worst.get(name, (0.0, 0.0))
            worst[name] = (max(prev[0], g_err), max(prev[1], w_err))
        del inputs
    for name, (g_err, w_err) in worst.items():
        print(f"[kernel] worst {name}: global_rel_err={g_err:.3e} "
              f"weight_abs_err={w_err:.3e} (tol {GLOBAL_RTOL:.0e} / "
              f"{WEIGHT_ATOL:.0e})", flush=True)


def trainer_phase(label: str, *, smoke: bool = False, rounds: int = 3,
                  **fl_kw) -> None:
    """``rounds`` aggregations of 4 whisper-tiny clients through the
    trainer's own builder and simulator."""
    t0 = time.perf_counter()
    _, server, clients, eval_fn = build_lm_fl(
        ARCH, smoke=smoke, n_clients=4, concurrency=2, buffer_size=2,
        seq_len=64, **fl_kw)
    sim = FLSimulation(server, clients, SimConfig(seed=0), eval_fn=eval_fn,
                       eval_every=1)
    print(f"[{label}] P={server.packer.size} "
          f"setup_wall_s={time.perf_counter() - t0:.3f}", flush=True)
    for r in range(1, rounds + 1):
        t0 = time.perf_counter()
        sim.run(max_rounds=r)
        jax.block_until_ready(server.global_flat)
        rec = sim.history[-1] if sim.history else {}
        ce = -rec["acc"] if "acc" in rec else float("nan")
        print(f"[{label}] round={server.round} heldout_ce={ce:.4f} "
              f"wall_s={time.perf_counter() - t0:.3f} (smoke run, not a "
              "measurement)", flush=True)
    ces = [-h["acc"] for h in sim.history if "acc" in h]
    if server.round < rounds:
        raise AssertionError(f"{label}: {server.round} of {rounds} rounds")
    if server.total_aggregations < 1:
        raise AssertionError(f"{label}: no aggregation happened")
    if not ces or not all(np.isfinite(ces)):
        raise AssertionError(f"{label}: held-out CE not finite: {ces}")
    print(f"[{label}] rounds={server.round} "
          f"aggregations={server.total_aggregations} "
          f"heldout_ce={ces[-1]:.4f} uplink_bytes={server.bytes_uploaded} "
          f"downlink_bytes={server.bytes_downloaded} "
          f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}", flush=True)


def sharded_phase(p: int, devices, interpret: bool = False) -> None:
    """Delta-free aggregation with the buffer slot-sharded over a 'pod'
    mesh of ``devices``, against the same aggregation on one device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.sharding import axis_rules, shard_update_buffer

    mesh = Mesh(np.asarray(devices), ("pod",))
    for dtype in (jnp.float32, jnp.bfloat16):
        dt = jnp.dtype(dtype).name
        g, stacked, sizes, stale = agg_inputs(p, K, dtype)
        hyper = tuple(jnp.float32(x) for x in SEAFL_HYPER)
        one = jax.block_until_ready(ops.seafl_aggregate_flat_from_params(
            g, stacked, sizes, stale, *hyper, interpret=interpret))
        with axis_rules(mesh):
            buf = shard_update_buffer(stacked)
        slots = ops.slot_sharding_of(buf)
        if slots is None:
            raise AssertionError(f"buffer not slot-sharded: {buf.sharding}")
        g_rep = jax.device_put(g, NamedSharding(mesh, PartitionSpec()))
        args = (g_rep, buf, sizes, stale, *hyper)
        hlo = ops._seafl_aggregate_flat_from_params_jit.lower(
            *args, interpret=interpret, slot_sharding=slots).compile() \
            .as_text()
        calls = hlo.count("tpu_custom_call")
        if not interpret and calls == 0:
            raise AssertionError(f"sharded {dt}: no tpu_custom_call")
        t0 = time.perf_counter()
        out = jax.block_until_ready(ops.seafl_aggregate_flat_from_params(
            *args, interpret=interpret))
        wall = time.perf_counter() - t0
        g_err, w_err = agg_errors(out, one)
        print(f"[sharded] seafl_aggregate_flat_from_params {dt} K={K} P={p} "
              f"buffer={buf.sharding.spec} devices={len(devices)} "
              f"tpu_custom_call={calls} "
              f"all-reduce={hlo.count('all-reduce(')} "
              f"all-gather={hlo.count('all-gather(')} "
              f"vs_one_device: global_rel_err={g_err:.3e} "
              f"weight_abs_err={w_err:.3e} wall_s={wall:.3f}", flush=True)
        check_errors(f"sharded {dt}", g_err, w_err)
        with jax.default_matmul_precision("highest"):
            expect = jax.jit(ref.seafl_aggregate_flat_from_params_ref)(
                g, stacked, sizes, stale, *SEAFL_HYPER)
        g_err, w_err = agg_errors(one, expect)
        print(f"[sharded] one device vs ref.py {dt}: "
              f"global_rel_err={g_err:.3e} weight_abs_err={w_err:.3e}",
              flush=True)
        check_errors(f"one device {dt}", g_err, w_err)
    print(f"[sharded] peak_bytes_in_use per device: "
          f"{[peak_bytes(d) for d in devices]}", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the slot-sharded aggregation over a "
                         "4-chip 'pod' mesh and its one-device comparison")
    args = ap.parse_args(argv)
    devs = require_tpu()
    if len(devs) < args.chips:
        raise RuntimeError(f"--chips {args.chips} but JAX sees {len(devs)}")
    print(f"[smoke] {device_line()} compile_cache={enable_compile_cache()} "
          f"peaks_known={devs[0].device_kind in DEVICE_PEAKS}",
          flush=True)
    p = packed_size()
    if args.chips == 4:
        sharded_phase(p, devs[:4])
    else:
        kernel_phase(p)
        trainer_phase("trainer", algorithm="seafl")
        trainer_phase("wire", algorithm="seafl2", compression="int8",
                      dispatch_compression="topk:0.1")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
