"""K-slot update buffer (Algorithm 1 'Server stores received updates').

Host-side metadata + one preallocated ``(K, P)`` device buffer.  Client
updates arrive over the chunked uplink transport (runtime/transport.py) and
land in a reserved slot with donated dynamic-updates: a whole upload's
decoded windows in one program that joins them, adds the delta base where
the wire is delta-coded, and writes the row in place — no per-aggregation
``tree_stack``, no stored delta pytrees, no host-side (P,) staging vector
(the Eq. (5) cosine terms are recovered delta-free by kernels/seafl_agg).

Two storage modes (``dtype``): f32 slots, or bf16 slots at half the HBM —
the seafl_agg kernels accumulate in f32 either way, so bf16 storage costs
~3 decimal digits on the stored params, not on the reductions.

The leading K axis is placed over the 'pod' mesh axis when one is active
(``sharding.DEFAULT_RULES['buffer']`` via ``shard_update_buffer``): cohort
updates stay resident on the pod that produced them and aggregation becomes
a sharded reduction over the slot axis.

Slot protocol (slots are *physical rows*, decoupled from commit order so
concurrent streams may finish — or die — in any order):
  ``reserve(meta) -> slot``    claim a free row (grows past K under SEAFL
                               sync-wait spill);
  ``write_range(slot, off, v)``  donated write into that row of one array,
                               of a sequence of in-order windows, or of
                               ``DeltaWindows`` (windows plus their base),
                               as one device program;
  ``write_batch(items)``       one donated scatter landing many queued
                               (slot, start, vals) chunk writes at once —
                               the IngestBatcher flush path;
  ``commit(slot)``             the upload completed; the slot joins the
                               committed sequence (arrival order);
  ``release(slot)``            the upload died mid-stream; the row returns
                               to the free pool.
``add`` keeps the legacy monolithic one-call write on top of the same
protocol.  ``stacked_flat`` is a zero-copy slice whenever the committed rows
are contiguous from 0 (the common, single-stream case) and a gather
otherwise.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass, field
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.telemetry import Telemetry, of as _tel_of
from repro.sharding import shard_update_buffer


# Windows joined per in-place row write inside ``_write_range``: fewer,
# longer writes into the (8, 128)-tiled buffer cost less, while joining the
# whole row first costs a (P,) temporary and more device time (one f32
# upload at whisper-tiny's P on a TPU v5e: 10.2 ms joined whole, 7.7 ms a
# window at a time, 7.2 ms in joins of 16).
_WRITE_GROUP = 16


@partial(jax.jit, donate_argnums=(0,))
def _write_range(buf: jnp.ndarray, slot, start, parts: tuple,
                 base: Optional[jnp.ndarray]):
    """In-place (donated) write of one contiguous run into row ``slot`` from
    ``start``: the in-order windows ``parts``, joined ``_WRITE_GROUP`` at a
    time, plus their window of ``base`` when one is given (a delta-coded
    wire), each join landed with its own in-place ``dynamic_update_slice``,
    all in one program.  Compiles once per window count and lengths."""
    off = start
    for i in range(0, len(parts), _WRITE_GROUP):
        vals = jax.lax.concatenate(parts[i:i + _WRITE_GROUP], 0)
        if base is not None:
            vals = vals + jax.lax.dynamic_slice(base, (off,), vals.shape)
        buf = jax.lax.dynamic_update_slice(
            buf, vals.astype(buf.dtype)[None, :], (slot, off))
        off = off + vals.shape[0]
    return buf


@partial(jax.jit, donate_argnums=(0,))
def _write_batch(buf: jnp.ndarray, slots: jnp.ndarray, starts: jnp.ndarray,
                 vals: jnp.ndarray):
    """One donated scatter applying a whole batch of equal-length chunk
    writes — ``vals[i]`` lands in row ``slots[i]`` at element ``starts[i]``.
    The sequential fori_loop keeps same-slot writes in enqueue order (they
    are disjoint windows anyway) and fuses into a single device dispatch."""
    vals = vals.astype(buf.dtype)

    def body(i, b):
        row = jax.lax.dynamic_index_in_dim(vals, i, keepdims=True)
        return jax.lax.dynamic_update_slice(b, row, (slots[i], starts[i]))

    return jax.lax.fori_loop(0, slots.shape[0], body, buf)


@partial(jax.jit, donate_argnums=(0,))
def _merge_rows(buf: jnp.ndarray, dst: jnp.ndarray, src: jnp.ndarray,
                w_dst: jnp.ndarray, w_src: jnp.ndarray):
    """In-place (donated) sample-weighted mean of two rows into ``dst``:
    ``buf[dst] = (w_dst*buf[dst] + w_src*buf[src]) / (w_dst + w_src)`` —
    the edge-aggregation pre-combine, accumulated in f32 regardless of the
    buffer's storage dtype."""
    a = jax.lax.dynamic_index_in_dim(buf, dst, keepdims=True).astype(
        jnp.float32)
    b = jax.lax.dynamic_index_in_dim(buf, src, keepdims=True).astype(
        jnp.float32)
    merged = (w_dst * a + w_src * b) / (w_dst + w_src)
    return jax.lax.dynamic_update_slice(
        buf, merged.astype(buf.dtype), (dst, jnp.int32(0)))


@dataclass(frozen=True)
class DeltaWindows:
    """Decoded in-order windows of a delta-coded run and the flat base they
    are deltas against; :meth:`UpdateBuffer.write_range` lands their join
    plus the base's matching window."""
    parts: Sequence[jnp.ndarray]
    base: jnp.ndarray


@dataclass
class Update:
    """Per-slot host metadata (the params live in the device buffer)."""
    client_id: int
    n_samples: int
    version: int              # t_k — round at which the client got the model
    n_epochs: int             # epochs actually completed (< E under SEAFL²)
    recv_time: float = 0.0
    meta: dict = field(default_factory=dict)


class UpdateBuffer:
    """Fixed-capacity slot buffer: metadata list + (capacity, P) device array."""

    def __init__(self, capacity: int, param_size: Optional[int] = None,
                 dtype=jnp.float32, telemetry: Optional[Telemetry] = None):
        self.tel = _tel_of(telemetry)
        self.capacity = int(capacity)
        self.param_size = param_size
        self.dtype = jnp.dtype(dtype)
        self._committed: list[tuple[Update, int]] = []   # (meta, row), arrival
        self._pending: dict[int, Update] = {}            # row -> meta
        self._free: list[int] = list(range(self.capacity))  # min-heap
        self._buf: Optional[jnp.ndarray] = None
        if param_size is not None:
            self._buf = self._alloc(self.capacity, int(param_size))

    def _alloc(self, rows: int, p: int) -> jnp.ndarray:
        return shard_update_buffer(jnp.zeros((rows, p), self.dtype))

    def __len__(self) -> int:
        return len(self._committed)

    @property
    def full(self) -> bool:
        return len(self._committed) >= self.capacity

    @property
    def streaming(self) -> bool:
        """True while any reserved slot has not been committed."""
        return bool(self._pending)

    @property
    def hbm_bytes(self) -> int:
        """Allocated device bytes of the slot array (the bf16-mode metric)."""
        if self._buf is None:
            return 0
        return int(self._buf.size) * self._buf.dtype.itemsize

    # ---------------------------------------------------------- slot protocol
    def _grow(self) -> None:
        # SEAFL sync-wait can hold aggregation while updates keep landing
        # (paper §IV-B): spill past K by doubling the slot array.  A
        # pod-sharded operand must be replicated before the eager
        # concatenate (mixed-sharding concat mis-reduces the replicated
        # mesh axes), then the doubled array is re-placed.
        old = self._buf
        sh = getattr(old, "sharding", None)
        if isinstance(sh, jax.sharding.NamedSharding):
            old = jax.device_put(old, jax.sharding.NamedSharding(
                sh.mesh, jax.sharding.PartitionSpec()))
        rows = old.shape[0]
        grow = jnp.zeros((rows, self.param_size), self.dtype)
        self._buf = shard_update_buffer(jnp.concatenate([old, grow], axis=0))
        for r in range(rows, 2 * rows):
            heapq.heappush(self._free, r)
        self.tel.counter("buffer.spill_grow")
        self.tel.gauge("buffer.rows", 2 * rows)

    def reserve(self, u: Update, param_size: Optional[int] = None) -> int:
        """Claim a free slot for a streaming upload."""
        if self._buf is None:                 # lazy alloc from first update
            if param_size is None:
                raise ValueError(
                    "UpdateBuffer was built without param_size; the first "
                    "reserve() must pass param_size= (add() infers it from "
                    "the flat vector)")
            self.param_size = int(param_size)
            self._buf = self._alloc(self.capacity, self.param_size)
        if not self._free:
            self._grow()
        slot = heapq.heappop(self._free)
        self._pending[slot] = u
        return slot

    def write_range(self, slot: int, start: int, vals) -> None:
        """Donated write into row ``slot`` from element ``start``, one
        device program whatever ``vals`` holds: one array, a sequence of
        in-order windows (joined in the program), or :class:`DeltaWindows`
        (joined, then added to the base's matching window)."""
        base = None
        if isinstance(vals, DeltaWindows):
            vals, base = vals.parts, vals.base
        parts = (tuple(vals) if isinstance(vals, (list, tuple))
                 else (vals,))
        self._buf = _write_range(self._buf, np.int32(slot), np.int32(start),
                                 parts, base)

    def write_batch(self, items: list) -> None:
        """One donated scatter applying many ``(slot, start, vals)`` chunk
        writes at once — the batched-ingest hot path (IngestBatcher flushes
        land here).  All ``vals`` must share one length; the batch is padded
        to the next power of two by *repeating its last entry* (an
        idempotent duplicate write), so the jit cache holds O(log B) batch
        shapes instead of one per batch size."""
        if not items:
            return
        if len(items) == 1:
            slot, start, vals = items[0]
            self.write_range(slot, start, vals)
            return
        n = len(items)
        target = 1 << (n - 1).bit_length()
        items = items + [items[-1]] * (target - n)
        slots = jnp.asarray([s for s, _, _ in items], jnp.int32)
        starts = jnp.asarray([o for _, o, _ in items], jnp.int32)
        vals = jnp.stack([v for _, _, v in items])
        self._buf = _write_batch(self._buf, slots, starts, vals)

    def commit(self, slot: int) -> None:
        """The upload for ``slot`` completed; make it visible to readers.
        Commits may land in any order (concurrent streams)."""
        if slot not in self._pending:
            raise RuntimeError(f"slot {slot} is not a reserved slot")
        self._committed.append((self._pending.pop(slot), slot))
        self.tel.gauge("buffer.committed", len(self._committed))
        self.tel.gauge("buffer.pending", len(self._pending))

    def merge_rows(self, dst_slot: int, src_slot: int,
                   w_dst: float, w_src: float) -> None:
        """Sample-weighted in-place merge of row ``src_slot`` into row
        ``dst_slot`` (one donated device dispatch; f32 accumulation).  The
        edge-aggregation tier uses this to pre-combine a cohort's uploads
        into one resident partial — the caller owns the metadata fold
        (n_samples, contributor ids) and recycling of ``src_slot`` via
        :meth:`uncommit`."""
        self._buf = _merge_rows(self._buf, jnp.int32(dst_slot),
                                jnp.int32(src_slot), jnp.float32(w_dst),
                                jnp.float32(w_src))

    def uncommit(self, slot: int) -> Update:
        """Remove a *committed* slot from the visible sequence and recycle
        its row (the inverse of :meth:`commit`): after an edge-tier merge
        the source row's content lives on in the destination partial, so
        the row returns to the free pool.  Returns the slot's metadata."""
        for i, (u, r) in enumerate(self._committed):
            if r == slot:
                self._committed.pop(i)
                heapq.heappush(self._free, slot)
                return u
        raise RuntimeError(f"slot {slot} is not a committed slot")

    def release(self, slot: int) -> None:
        """The upload for ``slot`` died mid-stream; recycle the row."""
        if slot not in self._pending:
            raise RuntimeError(f"slot {slot} is not a reserved slot")
        self._pending.pop(slot)
        heapq.heappush(self._free, slot)

    def add(self, u: Update, flat_params: jnp.ndarray) -> None:
        """Legacy monolithic path: reserve + one full-row write + commit."""
        slot = self.reserve(u, param_size=int(flat_params.shape[0]))
        self.write_range(slot, 0, flat_params)
        self.commit(slot)

    # ----------------------------------------------------------------- reads
    def updates(self) -> list[Update]:
        return [u for u, _ in self._committed]

    def staleness(self, current_round: int) -> jnp.ndarray:
        return jnp.asarray([current_round - u.version
                            for u, _ in self._committed], jnp.float32)

    def data_sizes(self) -> jnp.ndarray:
        return jnp.asarray([u.n_samples for u, _ in self._committed],
                           jnp.float32)

    def stacked_flat(self) -> jnp.ndarray:
        """(k, P) view of the committed slots in arrival order.  Zero-copy
        slice when the rows are 0..k-1 (single-stream case); gather when
        concurrent streams committed out of order."""
        if self._buf is None:
            raise RuntimeError("UpdateBuffer is empty")
        rows = [r for _, r in self._committed]
        if rows == list(range(self._buf.shape[0])):
            return self._buf
        if rows == list(range(len(rows))):
            return self._buf[:len(rows)]
        return self._buf[jnp.asarray(rows, jnp.int32)]

    def row(self, i: int) -> jnp.ndarray:
        """(P,) view of the i-th committed update (checkpointing non-empty
        buffers)."""
        return self._buf[self._committed[i][1]]

    def drain(self) -> list[Update]:
        """Consume the committed slots; rows return to the free pool.
        Mid-stream reservations survive (their rows stay claimed)."""
        out = [u for u, _ in self._committed]
        for _, r in self._committed:
            heapq.heappush(self._free, r)
        self._committed = []
        return out

    def client_ids(self) -> list[int]:
        return [u.client_id for u, _ in self._committed]
