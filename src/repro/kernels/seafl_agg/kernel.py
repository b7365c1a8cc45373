"""Fused SEAFL aggregation kernels (the paper's server hot path, TPU-native).

Two memory-bound passes over the K-slot update buffer:

  1. similarity_partials — per-update partial reductions (Delta_k . w_g,
     ||Delta_k||^2, ||w_g||^2) for the Eq. (5) cosine terms, fused so the
     buffer is read from HBM exactly once (arithmetic intensity ~3 flops /
     2 bytes -> firmly bandwidth-bound; fusing the three reductions is the
     whole win).

  2. weighted_agg — fused Eq. (7) + Eq. (8):
     out = (1 - theta) * w_g + theta * sum_k p_k * w_k
     again one HBM pass over the buffer instead of K+2 (the PLATO/GPU
     reference does a Python loop of K state-dict traversals).

Blocks are (K, BP) tiles: the whole K axis lives in VMEM (K <= 64 in any
sane config; 64 x 2048 x 4B = 512 KiB), parameter axis is tiled at BP.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sim_kernel(d_ref, g_ref, out_ref):
    """Grid (nP,).  d:(K,BP) g:(1,BP) out:(K,4) accumulated across blocks."""
    i = pl.program_id(0)
    d = d_ref[...].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    dot = jnp.sum(d * g[None, :], axis=1)      # (K,)
    dsq = jnp.sum(d * d, axis=1)               # (K,)
    gsq = jnp.broadcast_to(jnp.sum(g * g), dot.shape)
    part = jnp.stack([dot, dsq, gsq, jnp.zeros_like(dot)], axis=1)  # (K,4)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += part


def similarity_partials_call(deltas, global_flat, block_p=2048,
                             interpret=True):
    """deltas: (K, P) ; global_flat: (P,) ; P % block_p == 0.
    Returns (K, 4) f32: [:,0]=dot, [:,1]=|d|^2, [:,2]=|g|^2."""
    K, P = deltas.shape
    grid = (P // block_p,)
    return pl.pallas_call(
        _sim_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, block_p), lambda i: (0, i)),
            pl.BlockSpec((1, block_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((K, 4), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, 4), jnp.float32),
        interpret=interpret,
    )(deltas, global_flat[None, :])


def _sim_from_params_kernel(w_ref, g_ref, out_ref):
    """Delta-free Eq. (5) partials.  Grid (nP,).  w:(K,BP) g:(1,BP) out:(K,4).

    Delta_k = w_k - w_g is formed blockwise in VMEM and never materialised in
    HBM: the (K, P) buffer stores client params only, so the aggregation's
    buffer-resident bytes (and the bytes streamed to build a delta buffer)
    are halved versus the explicit-delta path.  The partial sums accumulate
    exactly across blocks because every term is a sum over the P axis.
    """
    i = pl.program_id(0)
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    d = w - g[None, :]
    dot = jnp.sum(d * g[None, :], axis=1)      # (K,)  Delta_k . w_g
    dsq = jnp.sum(d * d, axis=1)               # (K,)  ||Delta_k||^2
    gsq = jnp.broadcast_to(jnp.sum(g * g), dot.shape)
    part = jnp.stack([dot, dsq, gsq, jnp.zeros_like(dot)], axis=1)  # (K,4)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += part


def similarity_partials_from_params_call(params, global_flat, block_p=2048,
                                         interpret=True):
    """params: (K, P) client weights; global_flat: (P,); P % block_p == 0.
    Returns (K, 4) f32 delta partials [dot, |d|^2, |g|^2] with no delta
    buffer in HBM (zero-padding is exact: d = 0 - 0 in padded lanes)."""
    K, P = params.shape
    grid = (P // block_p,)
    return pl.pallas_call(
        _sim_from_params_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, block_p), lambda i: (0, i)),
            pl.BlockSpec((1, block_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((K, 4), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, 4), jnp.float32),
        interpret=interpret,
    )(params, global_flat[None, :])


def _agg_kernel(coef_ref, w_ref, p_ref, g_ref, out_ref):
    """Grid (nP,).  coef:(2,) SMEM [a, b]  w:(K,1) p:(K,BP) g:(1,BP)
    out:(1,BP) = a * g + b * sum_k w_k p_k.

    The K-mix is a broadcast-multiply and a sublane sum on the VPU, in f32:
    a (1,K)@(K,BP) product would fill one MXU row in K and Mosaic refuses
    the 1-D (K,)@(K,BP) form outright."""
    a = coef_ref[0]
    b = coef_ref[1]
    w = w_ref[...].astype(jnp.float32)                     # (K, 1)
    p = p_ref[...].astype(jnp.float32)                     # (K, BP)
    g = g_ref[...].astype(jnp.float32)                     # (1, BP)
    mix = jnp.sum(w * p, axis=0, keepdims=True)            # (1, BP)
    out_ref[...] = (a * g + b * mix).astype(out_ref.dtype)


def weighted_agg_call(weights, stacked, global_flat, theta,
                      block_p=2048, interpret=True, global_coef=None,
                      out_dtype=None):
    """weights:(K,) stacked:(K,P) global:(P,) -> (P,) fused Eq.(7)+(8):
    ``(1 - theta) * global + theta * weights @ stacked``.

    ``global_coef`` replaces the ``1 - theta`` on the global term (the
    slot-sharded path gives it to one shard only, so a sum of the shards'
    outputs counts the global once); ``out_dtype`` defaults to the
    global's dtype."""
    K, P = stacked.shape
    grid = (P // block_p,)
    theta = jnp.asarray(theta, jnp.float32)
    a = 1.0 - theta if global_coef is None else jnp.asarray(global_coef,
                                                             jnp.float32)
    coef = jnp.stack([a, theta])
    out_dtype = global_flat.dtype if out_dtype is None else out_dtype
    out = pl.pallas_call(
        _agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, block_p), lambda i: (0, i)),
            pl.BlockSpec((1, block_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, P), out_dtype),
        interpret=interpret,
    )(coef, weights.astype(jnp.float32)[:, None], stacked,
      global_flat[None, :])
    return out[0]
