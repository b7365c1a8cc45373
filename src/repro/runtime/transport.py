"""Uplink transport: chunked wire format for client updates.

SEAFL's premise is that the *uplink* is the scarce resource in heterogeneous
FL, so the client->server payload is a first-class object here: a client
update is serialised as a sequence of fixed-size chunks of the flat ``(P,)``
``ParamPacker`` vector, and the server decodes each chunk straight into its
``(K, P)`` buffer slot (``IngestSession``) — no host pytree staging, no
transient delta pytree, no (P,)-sized reassembly buffer on the server.
With many uploads concurrently in flight, sessions route their chunk
writes through a shared :class:`IngestBatcher` (one donated scatter per
flush instead of one device dispatch per chunk) — committed slots stay
bit-identical to the eager path.

Chunk encode/decode itself lives in the shared codec layer
(:mod:`repro.runtime.codecs`) — one registry serving both this uplink and
the downlink dispatch (:mod:`repro.runtime.dispatch`).  Scheme summary
(``WireFormat.scheme``): ``f32`` (bit-exact raw), ``bf16`` (half-size raw),
``topk``/``int8`` (lossy *deltas* vs the dispatch base, carried with flat
error feedback).  Delta-coded schemes need the base on both ends; raw
schemes are base-free, so a freshly restored server can ingest them without
any version history.

This module keeps what is genuinely uplink-shaped: the payload object, the
client-side encoder with its EF fold, and the server-side streaming ingest
(sessions + the batched scatter queue).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.runtime.codecs import (
    CHUNK_HEADER_BYTES, DEFAULT_CHUNK_ELEMS, Chunk, FlatErrorFeedback,
    WireFormat, decode_chunk, decode_concat, encode_chunk, encode_flat,
    make_wire_format, parse_spec,
)
from repro.runtime.telemetry import Telemetry, of as _tel_of

__all__ = [
    "CHUNK_HEADER_BYTES",
    "DEFAULT_CHUNK_ELEMS",
    "Chunk",
    "WireFormat",
    "parse_spec",
    "make_wire_format",
    "encode_chunk",
    "encode_flat",
    "decode_chunk",
    "decode_concat",
    "encode_update",
    "FlatErrorFeedback",
    "UploadPayload",
    "IngestBatcher",
    "IngestSession",
]


# --------------------------------------------------------------- client side

@dataclass
class UploadPayload:
    """One client upload as it travels on the wire."""
    cid: int
    version: int                 # t_k: round the client trained from
    n_epochs: int
    scheme: str
    param_size: int
    chunks: list[Chunk] = field(default_factory=list)
    nbytes: int = 0              # total wire bytes (headers included)


def encode_update(cid: int, version: int, n_epochs: int,
                  flat_params: jnp.ndarray, fmt: WireFormat,
                  base_flat: Optional[jnp.ndarray] = None,
                  ef: Optional[FlatErrorFeedback] = None) -> UploadPayload:
    """Client-side encoder: flat params -> wire payload.

    Raw schemes (f32/bf16) ship the params themselves.  Delta-coded schemes
    (topk/int8) ship delta = params - base (+ EF residual); ``base_flat`` is
    required — the flat model the client actually holds from its last
    dispatch (the delivered reconstruction under lossy dispatch schemes) —
    and ``ef`` (if given) is updated in place with the new residual.
    """
    if fmt.delta_coded:
        if base_flat is None:
            raise ValueError(f"wire scheme {fmt.scheme} is delta-coded and "
                             "needs the dispatch-version base")
        vec = flat_params - base_flat
        if ef is not None:
            vec = ef.carry_in(vec)
    else:
        vec = flat_params
    chunks = encode_flat(vec, fmt)
    if fmt.delta_coded and ef is not None:
        ef.carry_out(vec, decode_concat(chunks, fmt))
    return UploadPayload(
        cid=cid, version=version, n_epochs=n_epochs, scheme=fmt.scheme,
        param_size=int(flat_params.shape[0]), chunks=chunks,
        nbytes=sum(c.nbytes for c in chunks))


# --------------------------------------------------------------- server side

# Auto-bypass probe: coalescing only ever loses on *large* chunks (the
# batched fori_loop scatter serialises full-width rows that the eager path
# overlaps as independent dispatches — BENCH_ingest's batch_flush_speedup
# < 1 for f32/bf16 at 64 Ki elements, > 1 for the small-row compressed
# schemes).  Tiny chunks always win by batching, so the probe only runs at
# or above this element count — which also keeps the many small-chunk unit
# tests on the deterministic batched path.
_BYPASS_MIN_ELEMS = 4096

# (chunk_elems, dtype name, flush_chunks) -> bypass?  One timing probe per
# distinct shape per process; every batcher after that reads the cache.
_bypass_probe_cache: dict[tuple, bool] = {}


def _coalescing_loses(length: int, dtype, flush_chunks: int) -> bool:
    """Cheap startup probe: time one flush-sized run of eager per-chunk
    writes against one batched scatter of the same writes on a scratch
    buffer, and report whether the batch is slower.  Both kernels are
    warmed first so the probe times steady-state dispatch, not tracing."""
    from repro.core.buffer import UpdateBuffer

    key = (int(length), jnp.dtype(dtype).name, int(flush_chunks))
    hit = _bypass_probe_cache.get(key)
    if hit is not None:
        return hit
    rows = max(2, min(int(flush_chunks), 8))
    scratch = UpdateBuffer(rows, param_size=int(length) * 2, dtype=dtype)
    vals = jnp.ones((int(length),), jnp.float32)
    items = [(i % rows, (i % 2) * int(length), vals)
             for i in range(int(flush_chunks))]
    # reserve-free scratch writes: the probe touches rows directly
    scratch.write_range(0, 0, vals)                      # warm eager jit
    scratch.write_batch(list(items))                     # warm batched jit
    jax.block_until_ready(scratch._buf)

    def eager():
        for slot, start, v in items:
            scratch.write_range(slot, start, v)
        jax.block_until_ready(scratch._buf)

    def batched():
        scratch.write_batch(list(items))
        jax.block_until_ready(scratch._buf)

    t_eager = min(_time_once(eager) for _ in range(3))
    t_batch = min(_time_once(batched) for _ in range(3))
    loses = t_batch > t_eager
    _bypass_probe_cache[key] = loses
    return loses


def _time_once(fn) -> float:
    import time
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


class IngestBatcher:
    """Double-buffered batch queue for the multi-client streaming path.

    The eager streaming path issues one donated device dispatch per wire
    chunk; with many uploads in flight (SEAFL's semi-async premise) that is
    O(fleet x chunks) dispatch overhead for writes that could land
    together.  Sessions enqueue their decoded, base-added chunk writes
    here instead; a *flush* swaps the fill queue out (the next batch
    accumulates while the flushed scatter's device work is still in flight
    — JAX dispatch is async, so the swap is the double buffer) and lands the
    whole batch with one donated scatter per chunk-length group
    (``UpdateBuffer.write_batch``).  In steady state there are at most two
    lengths: full chunks and tails.

    Correctness contract: committed slots are bit-identical to the eager
    per-chunk path (same decode, same base add, same destination windows —
    rows are disjoint across sessions and in-order within one).  The
    server flushes before any ``commit`` so readers only see flushed
    rows, and ``cancel_slot`` drops a dead upload's queued writes so a
    recycled row can never be corrupted by a stale write.
    """

    def __init__(self, buffer, flush_chunks: int = 16,
                 auto_bypass: bool = False,
                 telemetry: Optional[Telemetry] = None,
                 tuned_verdict=None):
        self.tel = _tel_of(telemetry)
        self.buffer = buffer
        self.flush_chunks = max(1, int(flush_chunks))
        self.auto_bypass = bool(auto_bypass)
        # tuned_verdict: (length, dtype, flush_chunks) -> Optional[bool],
        # the autotuner's cached bypass answer.  None (no tuner, or a cache
        # miss) falls through to the one-shot timing probe below.
        self.tuned_verdict = tuned_verdict
        self._bypass: Optional[bool] = None   # verdict, decided once
        self._fill: list[tuple[int, int, jnp.ndarray]] = []
        self.flushes = 0
        self.chunks_batched = 0
        self.chunks_bypassed = 0     # eager pass-through writes (auto-bypass)
        self.writes_issued = 0       # donated scatters actually dispatched

    @property
    def pending(self) -> int:
        return len(self._fill)

    def enqueue(self, slot: int, start: int, vals: jnp.ndarray) -> None:
        if self.auto_bypass and int(vals.shape[0]) >= _BYPASS_MIN_ELEMS:
            if self._bypass is None:
                if self.tuned_verdict is not None:
                    self._bypass = self.tuned_verdict(
                        int(vals.shape[0]), self.buffer.dtype,
                        self.flush_chunks)
                if self._bypass is None:      # tuning-cache miss -> probe
                    self._bypass = _coalescing_loses(
                        int(vals.shape[0]), self.buffer.dtype,
                        self.flush_chunks)
                self.tel.gauge("ingest.bypass_verdict",
                               1.0 if self._bypass else 0.0)
            if self._bypass:
                # eager pass-through: coalescing loses at this chunk shape
                # (probe verdict), so the write lands immediately.  Order
                # vs queued writes is safe — every (slot, window) on the
                # wire is disjoint, and same-slot chunks of one session
                # are disjoint in-order windows.
                self.buffer.write_range(slot, start, vals)
                self.chunks_bypassed += 1
                self.tel.counter("ingest.chunks_bypassed")
                return
        self._fill.append((slot, start, vals))
        if len(self._fill) >= self.flush_chunks:
            self.flush()

    def cancel_slot(self, slot: int) -> None:
        """Drop queued writes for a dead upload before its row is recycled."""
        self._fill = [w for w in self._fill if w[0] != slot]

    def flush(self) -> None:
        if not self._fill:
            return
        batch, self._fill = self._fill, []     # swap, then dispatch
        groups: dict[int, list] = {}
        for slot, start, vals in batch:
            groups.setdefault(int(vals.shape[0]), []).append(
                (slot, start, vals))
        for length in sorted(groups):
            self.buffer.write_batch(groups[length])
            self.writes_issued += 1
        self.flushes += 1
        self.chunks_batched += len(batch)
        self.tel.counter("ingest.flushes")
        self.tel.histogram("ingest.flush_chunks", len(batch))


# The delta base add's eager ops as programs of their own name, so a
# profile tells the ingest's device work from every other eager op: a
# rename, the same HLO.  (The join is ``codecs._join_chunks``.)

@partial(jax.jit, static_argnums=(1, 2))
def _ingest_base(base, start: int, end: int):
    """The delta base's ``[start, end)`` window (eager ``lax.slice``)."""
    return jax.lax.slice(base, (start,), (end,))


@jax.jit
def _ingest_add(vals, base):
    """Decoded delta plus its base window (eager ``+``)."""
    return vals + base


class IngestSession:
    """Server-side decoder for one in-flight upload.

    Each wire chunk is decoded and written straight into the reserved
    ``(K, P)`` buffer slot — with a donated dynamic-update in eager mode, or
    enqueued on the shared :class:`IngestBatcher` (one donated scatter per
    flush, coalesced across concurrent clients) in batched mode.  The server
    never stages the update as a host pytree or a transient (P,) staging
    vector.  Chunks must arrive in order (start == bytes ingested so far),
    which the sequential wire framing guarantees.
    """

    def __init__(self, buffer, slot: int, fmt: WireFormat,
                 base_flat: Optional[jnp.ndarray] = None,
                 param_size: Optional[int] = None,
                 batcher: Optional[IngestBatcher] = None,
                 telemetry: Optional[Telemetry] = None):
        if fmt.delta_coded and base_flat is None:
            raise ValueError(f"wire scheme {fmt.scheme} is delta-coded and "
                             "needs the dispatch-version base to decode")
        self.buffer = buffer
        self.slot = int(slot)
        self.fmt = fmt
        self.base = base_flat
        self.param_size = int(param_size if param_size is not None
                              else buffer.param_size)
        self.batcher = batcher
        self.tel = _tel_of(telemetry)
        self.covered = 0             # elements ingested so far (in order)
        self.nbytes = 0              # wire bytes seen

    def _check(self, chunk: Chunk, expected: int) -> None:
        if chunk.start != expected:
            raise ValueError(
                f"out-of-order chunk: start={chunk.start}, "
                f"expected {expected}")
        if chunk.start + chunk.length > self.param_size:
            raise ValueError("chunk overruns the parameter vector")

    def write(self, chunk: Chunk) -> None:
        self._check(chunk, self.covered)
        vals = decode_chunk(chunk, self.fmt)
        if self.fmt.delta_coded:
            vals = _ingest_add(vals, _ingest_base(
                self.base, chunk.start, chunk.start + chunk.length))
        if chunk.length:
            if self.batcher is not None:
                self.batcher.enqueue(self.slot, chunk.start, vals)
            else:
                self.buffer.write_range(self.slot, chunk.start, vals)
        self.covered += chunk.length
        self.nbytes += chunk.nbytes

    def write_all(self, chunks: list[Chunk]) -> None:
        """Coalesced write of one drained batch of in-order chunks.

        The sequential wire framing makes a drained batch one contiguous
        window, so instead of one donated ``dynamic_update_slice`` dispatch
        per chunk (the per-chunk overhead flagged in BENCH_ingest), the
        decoded chunks are concatenated — and the delta base added — once,
        and the whole run lands in the slot with a *single* donated write.
        Values are bit-identical to chunk-by-chunk ``write`` (same decode,
        same elementwise base add, same destination elements).

        The whole batch is validated before any state changes: a bad batch
        raises with the session untouched, so the driver's redelivery path
        (see ``finish``) can never commit a half-claimed coverage range.
        """
        start = end = self.covered
        nbytes = 0
        for chunk in chunks:
            self._check(chunk, end)
            end += chunk.length
            nbytes += chunk.nbytes
        if end > start:
            with self.tel.span("ingest.decode"):
                vals = decode_concat(chunks, self.fmt)
                if self.fmt.delta_coded:
                    vals = _ingest_add(vals,
                                       _ingest_base(self.base, start, end))
            with self.tel.span("ingest.write"):
                self.buffer.write_range(self.slot, start, vals)
        self.covered = end
        self.nbytes += nbytes

    @property
    def complete(self) -> bool:
        return self.covered == self.param_size

    def finish(self) -> int:
        """Validate full coverage; returns total wire bytes ingested."""
        if not self.complete:
            raise ValueError(
                f"incomplete upload: {self.covered}/{self.param_size} "
                "elements ingested")
        return self.nbytes
