"""jit'd public wrappers: padding, weight math, end-to-end fused aggregation.

This module is the single flat-buffer aggregation engine behind every server
algorithm (seafl / seafl2 / fedbuff / fedavg / fedasync): SEAFL's Eq. (4)-(8)
adaptive rule plus the baselines' weight rules, all expressed as one fused
``weighted_aggregate`` HBM pass over the (K, P) buffer.  The delta-free
entry point (``seafl_aggregate_flat_from_params``) recovers the Eq. (5)
cosine terms directly from client params, so no delta buffer ever exists.

Tuned routing (opt-in): each public wrapper accepts ``tuned=`` — a plan
dict from ``runtime/autotune.py`` (``{'use_oracle': bool, 'block_p':
int}``).  ``use_oracle`` dispatches to a jitted XLA twin built on
``ref.py`` (the per-entry-point fallback for backends where the Pallas
kernel loses or fails to lower); otherwise the swept ``block_p`` is
applied.  Without ``tuned`` (or with ``tuned=None``) the call is
byte-for-byte the untuned path — the ``autotune='off'`` bit-identity pin.

Slot-sharded buffers: a Mosaic kernel cannot be partitioned by XLA, so when
the (K, P) buffer's slot axis is split over mesh axes (the 'pod' placement
of ``sharding.shard_update_buffer``) the kernels run per shard inside
``shard_map``.  Each shard computes the Eq. (5) partials of its own rows,
and the weighted mix becomes a per-shard (P,) partial sum that is
``psum``-ed over the slot axes: the buffer is never gathered onto one chip.
The public wrappers read the placement off the buffer and pass it to the
jitted bodies as the static ``slot_sharding``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.aggregation import (
    SeaflHyper, cosine_from_partials, seafl_weights,
)
from repro.kernels import INTERPRET
from repro.kernels.seafl_agg import ref as _ref
from repro.kernels.seafl_agg.kernel import (
    similarity_partials_call, similarity_partials_from_params_call,
    weighted_agg_call,
)

def slot_sharding_of(stacked) -> Optional[NamedSharding]:
    """The placement of a (K, P) buffer whose slot axis is split over mesh
    axes, else None (one device, replicated, or a tracer)."""
    if isinstance(stacked, jax.core.Tracer) or len(stacked.shape) != 2:
        return None
    sh = getattr(stacked, "sharding", None)
    if not isinstance(sh, NamedSharding):
        return None
    spec = tuple(sh.spec) + (None, None)
    if spec[1] is not None:
        raise NotImplementedError(
            f"buffer sharded along its parameter axis ({sh.spec}); only the "
            "slot axis may be sharded")
    if spec[0] is None:
        return None
    return NamedSharding(sh.mesh, PartitionSpec(spec[0], None))


def _route(jit_body, oracle_body, *args, **kw):
    """Dispatch one public entry point through its tuning plan.

    ``tuned=None`` (the default everywhere) leaves args, kwargs, and the
    callee untouched — identical dispatch to the pre-autotune tree — except
    that a slot-sharded buffer (``args[1]``) selects the per-shard path."""
    tuned = kw.pop("tuned", None)
    if tuned:
        if tuned.get("use_oracle"):
            kw.pop("block_p", None)
            kw.pop("interpret", None)
            return oracle_body(*args, **kw)
        bp = tuned.get("block_p")
        if bp:
            kw.setdefault("block_p", int(bp))
    slots = slot_sharding_of(args[1]) if len(args) > 1 else None
    if slots is not None:
        kw["slot_sharding"] = slots
    return jit_body(*args, **kw)


def _pad_to(x, m, axis=-1):
    n = x.shape[axis]
    pad = (-n) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _partials(call, stacked, global_flat, block_p, interpret, slot_sharding):
    """Run one Eq. (5) partials kernel, per slot shard when the buffer is
    slot-sharded (each shard reduces its own rows; zero-padding is exact)."""
    def local(s, g):
        return call(_pad_to(s, block_p, axis=1), _pad_to(g, block_p, axis=0),
                    block_p=block_p, interpret=interpret)

    if slot_sharding is None:
        return local(stacked, global_flat)
    rows = slot_sharding.spec[0]
    return jax.shard_map(
        local, mesh=slot_sharding.mesh,
        in_specs=(PartitionSpec(rows, None), PartitionSpec()),
        out_specs=PartitionSpec(rows, None), check_vma=False,
    )(stacked, global_flat)


@partial(jax.jit, static_argnames=("block_p", "interpret", "slot_sharding"))
def similarity_partials(deltas, global_flat, block_p=2048, interpret=INTERPRET,
                        slot_sharding=None):
    """(K, P), (P,) -> (K, 4) partial reductions (zero-padding is exact)."""
    return _partials(similarity_partials_call, deltas, global_flat, block_p,
                     interpret, slot_sharding)


@partial(jax.jit, static_argnames=("block_p", "interpret", "slot_sharding"))
def similarity_partials_from_params(stacked, global_flat, block_p=2048,
                                    interpret=INTERPRET, slot_sharding=None):
    """Delta-free Eq. (5) partials from client params (K, P) directly."""
    return _partials(similarity_partials_from_params_call, stacked,
                     global_flat, block_p, interpret, slot_sharding)


@partial(jax.jit, static_argnames=("block_p", "interpret", "slot_sharding"))
def weighted_aggregate(weights, stacked, global_flat, theta,
                       block_p=2048, interpret=INTERPRET, slot_sharding=None):
    """(1 - theta) * global + theta * weights @ stacked, in one pass over
    the buffer; per slot shard plus a ``psum`` when it is slot-sharded."""
    P = global_flat.shape[0]
    if slot_sharding is None:
        out = weighted_agg_call(weights, _pad_to(stacked, block_p, axis=1),
                                _pad_to(global_flat, block_p, axis=0), theta,
                                block_p=block_p, interpret=interpret)
        return out[:P]
    rows = slot_sharding.spec[0]

    def local(w, s, g, th):
        # the global term rides on the first shard only, so the psum of
        # the shards' (P,) outputs counts it once
        first = jax.lax.axis_index(rows) == 0
        part = weighted_agg_call(
            w, _pad_to(s, block_p, axis=1), _pad_to(g, block_p, axis=0), th,
            block_p=block_p, interpret=interpret,
            global_coef=jnp.where(first, 1.0 - th, 0.0),
            out_dtype=jnp.float32)
        return jax.lax.psum(part, rows)

    out = jax.shard_map(
        local, mesh=slot_sharding.mesh,
        in_specs=(PartitionSpec(rows), PartitionSpec(rows, None),
                  PartitionSpec(), PartitionSpec()),
        out_specs=PartitionSpec(), check_vma=False,
    )(weights.astype(jnp.float32), stacked, global_flat,
      jnp.asarray(theta, jnp.float32))
    return out[:P].astype(global_flat.dtype)


# XLA-oracle twins of the raw entry points: the same math via ref.py,
# jitted.  These are what the autotuner times against the Pallas path and
# what tuned routing dispatches to when the kernel loses on a backend.
_similarity_partials_oracle = jax.jit(_ref.similarity_partials_ref)
_similarity_partials_from_params_oracle = jax.jit(
    _ref.similarity_partials_from_params_ref)
_weighted_aggregate_oracle = jax.jit(_ref.weighted_agg_ref)


def _seafl_weights_flat(cos, data_sizes, staleness, alpha, mu, beta,
                        use_importance=True, use_staleness=True):
    """Eq. (4)+(6) via the single weight-rule implementation in
    core.aggregation (the hyper scalars may be tracers; SeaflHyper is just
    the container seafl_weights expects)."""
    hyper = SeaflHyper(alpha=alpha, mu=mu, beta=beta,
                       use_importance=use_importance,
                       use_staleness=use_staleness)
    return seafl_weights(data_sizes, staleness, cos, hyper)


@partial(jax.jit, static_argnames=("use_importance", "use_staleness",
                                   "block_p", "interpret", "slot_sharding"))
def _seafl_aggregate_flat_jit(global_flat, stacked_params, stacked_deltas,
                         data_sizes, staleness, alpha, mu, beta, theta,
                         use_importance=True, use_staleness=True,
                         block_p=2048, interpret=INTERPRET,
                         slot_sharding=None):
    """Fully fused flat-buffer SEAFL aggregation (Eqs. 4-8), explicit deltas.

    Two HBM passes total: one over the deltas (partials), one over the
    params (weighted mix).  Returns (new_global (P,), weights (K,)).
    """
    part = similarity_partials(stacked_deltas, global_flat,
                               block_p=block_p, interpret=interpret,
                               slot_sharding=slot_sharding)
    cos = cosine_from_partials(part[:, 0], part[:, 1], part[:, 2])
    p = _seafl_weights_flat(cos, data_sizes, staleness, alpha, mu, beta,
                            use_importance, use_staleness)
    new_global = weighted_aggregate(p, stacked_params, global_flat, theta,
                                    block_p=block_p, interpret=interpret,
                                    slot_sharding=slot_sharding)
    return new_global, p


@partial(jax.jit, static_argnames=("use_importance", "use_staleness"))
def _seafl_aggregate_flat_oracle(global_flat, stacked_params, stacked_deltas,
                                 data_sizes, staleness, alpha, mu, beta,
                                 theta, use_importance=True,
                                 use_staleness=True):
    """XLA twin of ``_seafl_aggregate_flat_jit``: ref partials + the same
    weight rule + ref weighted mix (parity <=1e-6 by tests)."""
    part = _ref.similarity_partials_ref(stacked_deltas, global_flat)
    cos = cosine_from_partials(part[:, 0], part[:, 1], part[:, 2])
    p = _seafl_weights_flat(cos, data_sizes, staleness, alpha, mu, beta,
                            use_importance, use_staleness)
    return _ref.weighted_agg_ref(p, stacked_params, global_flat, theta), p


def seafl_aggregate_flat(*args, **kw):
    """Fused flat-buffer SEAFL aggregation, explicit deltas (see the jitted
    body), routed when ``tuned=``."""
    return _route(_seafl_aggregate_flat_jit,
                  _seafl_aggregate_flat_oracle, *args, **kw)


@partial(jax.jit, static_argnames=("use_importance", "use_staleness",
                                   "block_p", "interpret", "slot_sharding"))
def _seafl_aggregate_flat_from_params_jit(global_flat, stacked_params,
                                     data_sizes, staleness,
                                     alpha, mu, beta, theta,
                                     use_importance=True, use_staleness=True,
                                     block_p=2048, interpret=INTERPRET,
                                     slot_sharding=None):
    """Delta-free fused SEAFL aggregation: the server hot path.

    The (K, P) buffer holds client params only; Delta_k = w_k - w_g is formed
    blockwise in VMEM for the Eq. (5) partials.  Two HBM passes over one
    buffer (vs. two passes over params + deltas plus the pass that *built*
    the delta buffer), so buffer-read bytes roughly halve end to end.
    Returns (new_global (P,), weights (K,)).
    """
    part = similarity_partials_from_params(stacked_params, global_flat,
                                           block_p=block_p,
                                           interpret=interpret,
                                           slot_sharding=slot_sharding)
    cos = cosine_from_partials(part[:, 0], part[:, 1], part[:, 2])
    p = _seafl_weights_flat(cos, data_sizes, staleness, alpha, mu, beta,
                            use_importance, use_staleness)
    new_global = weighted_aggregate(p, stacked_params, global_flat, theta,
                                    block_p=block_p, interpret=interpret,
                                    slot_sharding=slot_sharding)
    return new_global, p


@partial(jax.jit, static_argnames=("use_importance", "use_staleness"))
def _seafl_aggregate_flat_from_params_oracle(global_flat, stacked_params,
                                             data_sizes, staleness, alpha,
                                             mu, beta, theta,
                                             use_importance=True,
                                             use_staleness=True):
    """XLA twin of the delta-free server hot path."""
    part = _ref.similarity_partials_from_params_ref(stacked_params,
                                                    global_flat)
    cos = cosine_from_partials(part[:, 0], part[:, 1], part[:, 2])
    p = _seafl_weights_flat(cos, data_sizes, staleness, alpha, mu, beta,
                            use_importance, use_staleness)
    return _ref.weighted_agg_ref(p, stacked_params, global_flat, theta), p


def seafl_aggregate_flat_from_params(*args, **kw):
    """Delta-free fused SEAFL aggregation: the server hot path (see the
    jitted body), routed when ``tuned=``."""
    return _route(_seafl_aggregate_flat_from_params_jit,
                  _seafl_aggregate_flat_from_params_oracle, *args, **kw)


# ---------------------------------------------------------------------------
# Baseline weight rules on the same engine (paper §VI comparison set).
# Every algorithm is one fused (1-theta)*g + theta*(w @ buffer) pass.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("block_p", "interpret", "slot_sharding"))
def _fedavg_aggregate_flat_jit(global_flat, stacked_params, data_sizes,
                               block_p=2048, interpret=INTERPRET,
                               slot_sharding=None):
    """FedAvg: w_{t+1} = sum_k (n_k/n) w_k  (theta = 1 drops the old global)."""
    n = data_sizes.astype(jnp.float32)
    w = n / jnp.maximum(jnp.sum(n), 1.0)
    new_global = weighted_aggregate(w, stacked_params, global_flat,
                                    jnp.float32(1.0), block_p=block_p,
                                    interpret=interpret,
                                    slot_sharding=slot_sharding)
    return new_global, w


@jax.jit
def _fedavg_aggregate_flat_oracle(global_flat, stacked_params, data_sizes):
    n = data_sizes.astype(jnp.float32)
    w = n / jnp.maximum(jnp.sum(n), 1.0)
    return _ref.weighted_agg_ref(w, stacked_params, global_flat,
                                 jnp.float32(1.0)), w


def fedavg_aggregate_flat(*args, **kw):
    return _route(_fedavg_aggregate_flat_jit,
                  _fedavg_aggregate_flat_oracle, *args, **kw)


@partial(jax.jit, static_argnames=("block_p", "interpret", "slot_sharding"))
def _fedbuff_aggregate_flat_jit(global_flat, stacked_params, eta_g,
                                block_p=2048, interpret=INTERPRET,
                                slot_sharding=None):
    """FedBuff, delta-free: w_t + eta_g mean_k(w_k - w_t)
    == (1 - eta_g) w_t + eta_g mean_k w_k  (uniform weights)."""
    K = stacked_params.shape[0]
    w = jnp.full((K,), 1.0 / K, jnp.float32)
    new_global = weighted_aggregate(w, stacked_params, global_flat,
                                    jnp.asarray(eta_g, jnp.float32),
                                    block_p=block_p, interpret=interpret,
                                    slot_sharding=slot_sharding)
    return new_global, w


@jax.jit
def _fedbuff_aggregate_flat_oracle(global_flat, stacked_params, eta_g):
    K = stacked_params.shape[0]
    w = jnp.full((K,), 1.0 / K, jnp.float32)
    return _ref.weighted_agg_ref(w, stacked_params, global_flat,
                                 jnp.asarray(eta_g, jnp.float32)), w


def fedbuff_aggregate_flat(*args, **kw):
    return _route(_fedbuff_aggregate_flat_jit,
                  _fedbuff_aggregate_flat_oracle, *args, **kw)


@partial(jax.jit, static_argnames=("block_p", "interpret"))
def _fedasync_aggregate_flat_jit(global_flat, client_flat, staleness,
                                 alpha0=0.6, a=0.5, block_p=2048,
                                 interpret=INTERPRET):
    """FedAsync: immediate K=1 mixing at the poly-discounted rate
    alpha_t = alpha0 (1+s)^-a (theta = alpha_t on the same fused pass)."""
    alpha = (jnp.asarray(alpha0, jnp.float32)
             * (1.0 + jnp.asarray(staleness, jnp.float32)) ** (-a))
    return weighted_aggregate(jnp.ones((1,), jnp.float32), client_flat[None],
                              global_flat, alpha, block_p=block_p,
                              interpret=interpret)


@jax.jit
def _fedasync_aggregate_flat_oracle(global_flat, client_flat, staleness,
                                    alpha0=0.6, a=0.5):
    alpha = (jnp.asarray(alpha0, jnp.float32)
             * (1.0 + jnp.asarray(staleness, jnp.float32)) ** (-a))
    return _ref.weighted_agg_ref(jnp.ones((1,), jnp.float32),
                                 client_flat[None], global_flat, alpha)


def fedasync_aggregate_flat(*args, **kw):
    return _route(_fedasync_aggregate_flat_jit,
                  _fedasync_aggregate_flat_oracle, *args, **kw)
