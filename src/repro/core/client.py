"""Client-side local training (Algorithm 1/2 ClientUpdate).

Paper-faithful: E epochs of mini-batch SGD at learning rate eta.  The
function is jit'd *per epoch* so SEAFL²'s partial training ("finish the
current epoch, upload immediately") maps to calling it e' < E times — the
interruption point is decided by the event simulator / scheduler, exactly as
the server NOTIFY message does in Algorithm 2.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.telemetry import NULL

PyTree = Any


def make_epoch_fn(loss_fn: Callable, lr: float | None = None):
    """Returns jit'd epoch(params, data, lr) scanning SGD over batches.

    loss_fn(params, batch) -> (loss, metrics); data: dict of arrays with
    leading (n_batches, batch_size, ...) (pre-batched client shard).
    """

    @jax.jit
    def epoch(params, data, lr_):
        def step(p, batch):
            (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
            p = jax.tree.map(lambda w, gr: w - lr_ * gr.astype(w.dtype), p, g)
            return p, l

        params, losses = jax.lax.scan(step, params, data)
        return params, jnp.mean(losses)

    if lr is None:
        return epoch
    return lambda params, data, lr_=lr: epoch(params, data, lr_)


class Client:
    """A simulated FL device: holds a data shard, trains on demand.

    Training is *lazy*: the simulator only materialises the local update when
    the upload event fires, at which point the number of completed epochs
    (E, or fewer after a SEAFL² notification) is known.
    """

    def __init__(self, cid: int, data: dict, epoch_fn, n_samples: int,
                 batch_size: int, seed: int = 0):
        self.cid = cid
        self.data = data                      # {x: (n,...), y: (n,)} host arrays
        self.n_samples = int(n_samples)
        self.batch_size = int(batch_size)
        self.epoch_fn = epoch_fn
        self._rng = np.random.default_rng(seed * 100_003 + cid)

    def _epoch_batches(self) -> dict:
        n = self.n_samples
        bs = min(self.batch_size, n)
        nb = max(1, n // bs)
        idx = self._rng.permutation(n)[: nb * bs].reshape(nb, bs)
        return jax.tree.map(lambda a: a[idx], self.data)

    def local_train(self, params: PyTree, n_epochs: int, lr: float):
        """Run n_epochs of SGD; returns (new_params, mean_loss).  The
        ``client.train`` span's own time is the wait for the last epoch's
        loss."""
        with NULL.span("client.train"):
            loss = jnp.float32(0.0)
            for _ in range(max(1, n_epochs)):
                with NULL.span("client.batches"):
                    batches = self._epoch_batches()
                with NULL.span("client.epoch"):
                    params, loss = self.epoch_fn(params, batches, lr)
            return params, float(loss)
