"""Server-side policy state machine for SEAFL / SEAFL² and baselines.

Time-free: the event-driven simulator (runtime/simulator.py) and the
production cohort scheduler (launch/train.py) both drive this object, so the
paper's protocol logic exists exactly once.

Policies (paper §VI comparison set):
  fedavg   — synchronous, waits for all M selected clients
  fedasync — aggregate-on-arrival with polynomial staleness mixing
  fedbuff  — buffer K, uniform-weight delta aggregation, no staleness limit
  seafl    — buffer K + staleness limit (sync-wait) + adaptive weights (Eqs 4-8)
  seafl2   — seafl + partial-training notifications (Algorithm 2)

Hot path: every algorithm aggregates through the flat (K, P) buffer engine
(kernels/seafl_agg).  Uploads arrive over the chunked uplink transport
(runtime/transport.py): ``encode_update`` serialises the client's packed
(P,) vector into wire chunks (raw f32/bf16 or topk/int8-compressed deltas
with flat error feedback), and ``begin_ingest``/``ingest_chunk``/
``finish_ingest`` decode each chunk straight into the reserved (K, P) buffer
slot — no host pytree staging, no transient delta pytree, no (P,) reassembly
buffer; concurrent streams coalesce their chunk writes through a shared
``IngestBatcher`` (one donated scatter per flush, bit-identical commits).
Downlink dispatches go through the multicast ``DispatchSession``: delta
hits on a shared held version are encoded once and fanned out from a
bounded encode cache (runtime/dispatch.py).  The Eq. (5) cosine terms are recovered delta-free in the kernels and
model versions live in ``_history`` as flat (P,) f32 buffers, unpacked lazily
only at dispatch / eval / checkpoint boundaries.  The buffer itself can store
slots in bf16 (``FLConfig.buffer_dtype``) at half the HBM; the kernels
accumulate in f32 regardless.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field, replace as dc_replace
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import SeaflHyper
from repro.core.buffer import Update, UpdateBuffer
from repro.runtime.cohorts import CohortDispatchSession
from repro.runtime.dispatch import DispatchPayload, DispatchSession
from repro.runtime.monitor import RunMonitor
from repro.runtime.policy import DriftTracker, RatePolicy, RESYNC_MODES
from repro.runtime.scheduler import make_scheduler
from repro.runtime.telemetry import Telemetry
from repro.runtime.transport import (
    Chunk, FlatErrorFeedback, IngestBatcher, IngestSession, UploadPayload,
    encode_update as transport_encode_update, make_wire_format,
)
from repro.core.packer import ParamPacker

PyTree = Any

ALGORITHMS = ("seafl", "seafl2", "fedbuff", "fedasync", "fedavg")

BUFFER_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@dataclass(frozen=True)
class FLConfig:
    algorithm: str = "seafl"
    n_clients: int = 100
    concurrency: int = 20            # M: clients training at any time
    buffer_size: int = 10            # K
    staleness_limit: Optional[float] = 10.0   # beta; None = infinity
    alpha: float = 3.0
    mu: float = 1.0
    theta: float = 0.8
    local_epochs: int = 5            # E
    local_lr: float = 0.05
    batch_size: int = 32
    use_importance: bool = True
    use_staleness: bool = True
    importance_mode: str = "delta_vs_global"   # paper Eq. 5
    fedbuff_eta_g: float = 1.0
    fedasync_alpha0: float = 0.6
    fedasync_poly_a: float = 0.5
    # uplink wire format: None (= raw f32) | 'bf16' | 'topk:<ratio>' | 'int8'
    compression: Optional[str] = None
    chunk_elems: int = 1 << 16       # wire chunk granularity (elements)
    buffer_dtype: str = "float32"    # 'float32' | 'bfloat16' slot storage
    # downlink wire format: None keeps the legacy whole-model broadcast
    # (no wire object; the bandwidth model charges raw f32 model bytes);
    # 'f32' | 'bf16' | 'topk:<ratio>' | 'int8' serve chunked dispatch
    # payloads with per-client version tracking (runtime/dispatch.py)
    dispatch_compression: Optional[str] = None
    dispatch_history: int = 8        # global-history ring depth (versions)
    dispatch_chunk_elems: int = 1 << 16   # downlink chunk granularity
    # multicast wire engine: delta hits encode the pure ring hop once per
    # (base, target) and fan cached chunks out byte-identically; a client
    # whose accumulated EF residual exceeds dispatch_resync x |hop delta|
    # gets one personalized fold-in encode (False restores per-client
    # fold-in on every delta — the pre-multicast semantics)
    dispatch_multicast: bool = True
    dispatch_resync: float = 4.0
    # resync trigger economics (runtime/policy.py): 'norm' fires the
    # fold-in at |r| > dispatch_resync x |hop delta| (the PR 4 behaviour,
    # bit-for-bit); 'bytes' fires when the residual's projected top-k
    # re-ship size exceeds dispatch_resync x one payload's wire bytes
    dispatch_resync_mode: str = "norm"
    # drift-adaptive top-k rate policy (runtime/policy.py): 'static' keeps
    # the configured ratio; 'drift' bins the round-over-round global drift
    # norm (normalised by its own EMA) into discrete bands and dispatches
    # each round at that band's ratio.  Discrete bands keep the multicast
    # encode-cache sharing intact within a band.  The same chosen ratio
    # optionally drives uplink topk encoding (uplink_ratio_policy).
    dispatch_ratio_policy: str = "static"    # 'static' | 'drift'
    uplink_ratio_policy: str = "static"      # 'static' | 'drift'
    drift_band_edges: tuple = (0.8, 1.6)     # on x = drift / ema(drift)
    drift_band_ratios: tuple = (0.025, 0.05, 0.1)   # len(edges) + 1
    drift_ema_beta: float = 0.8
    # streaming-ingest batch queue: coalesce up to this many pending chunk
    # writes across concurrent uploads into one donated scatter per flush
    # (0 = eager, one device dispatch per chunk — the pre-batching path)
    ingest_batch_chunks: int = 16
    # batched-ingest auto-bypass: a cheap startup probe times one eager
    # chunk write against a batched flush at the actual chunk size and
    # falls back to eager pass-through where coalescing loses (large f32 /
    # bf16 chunks — BENCH_ingest's batch_flush_speedup < 1 regime), so
    # batched mode never regresses ingest throughput
    ingest_auto_bypass: bool = True
    # cohorted fleet state (runtime/cohorts.py): 'on' makes the cohort —
    # (held version, drift band) — the unit of server-side dispatch state
    # (one shared EF residual + one cached fold encode per cohort instead
    # of per client) and enables the two-tier edge-aggregation pre-combine
    # (same-version uploads merge into one (K, P) buffer slot).  'off' is
    # the per-client mode, bit-for-bit identical to the pre-cohort stack.
    cohorts: str = "off"
    # coalesce one round's personalized resync re-encodes into a single
    # batched encode pass (DispatchSession.encode_many), overlapped with
    # the cached-hop fan-out by the simulator's encode-time model
    resync_batching: bool = False
    # unified telemetry (runtime/telemetry.py): counters/gauges/histograms
    # + trace spans threaded through every layer.  Off by default with
    # pinned zero behavioral change (RNG stream, wire bytes, aggregation
    # outputs bit-identical — the cohorts='off' discipline).
    telemetry: bool = False
    # run-health monitor (runtime/monitor.py): 'on' runs the online
    # anomaly detectors (plateau, staleness blowup, straggler dominance,
    # resync storms, ...) against every round record and attaches typed
    # alerts to it.  Implies telemetry.  'off' (default) is bit-identical
    # to the monitor-free stack — same RNG stream, wire bytes, and
    # history keys (pinned in tests/test_monitor.py).
    monitor: str = "off"
    # fail-fast SLO: comma-separated severities ('warn'|'error') and/or
    # detector names; any matching alert breaches the SLO, the simulator
    # stops at the next round boundary, and launch/train.py exits
    # nonzero.  None disables the gate (alerts still record).
    slo: Optional[str] = None
    # hard budget on cumulative up+down wire bytes for the byte_budget
    # detector (None = unlimited)
    monitor_byte_budget: Optional[int] = None
    # client-selection policy (runtime/scheduler.py): every idle-pool draw
    # — start() warm-up, crash replacement, post-aggregation top-up — goes
    # through it.  'random' reproduces the legacy uniform draw
    # RNG-call-for-RNG-call (pinned bit-identical); 'stragglers_last' and
    # 'rate_staleness' rank eligible clients by predicted round time
    # (+ predicted staleness) from observed dispatch->deliver EMAs.
    scheduler: str = "random"
    # per-chip kernel tuning (runtime/autotune.py): 'off' (default) runs
    # the hardcoded block_p / chunk_elems / ingest defaults, bit-identical
    # to the untuned tree (pinned in tests/test_autotune.py).  'cache'
    # applies the winners from the user tuning cache (~/.cache) or the
    # repo-committed default table — no measurement at construction.
    # 'sweep' measures this server's actual shapes first (block-until-ready
    # sweeps over block_p / chunk_elems / ingest bypass), persists the
    # winners to the user cache, then applies them.  Tuned configs change
    # timing only, never values (parity pinned <= 1e-6).
    autotune: str = "off"
    seed: int = 0

    def hyper(self) -> SeaflHyper:
        beta = self.staleness_limit if self.staleness_limit is not None else 1e9
        return SeaflHyper(alpha=self.alpha, mu=self.mu, beta=float(beta),
                          theta=self.theta, use_importance=self.use_importance,
                          use_staleness=self.use_staleness)


@dataclass
class AggregationEvent:
    round: int
    weights: Optional[np.ndarray]
    staleness: Optional[np.ndarray]
    contributors: list[int]
    dispatch: list[int] = field(default_factory=list)
    notify: list[int] = field(default_factory=list)


class SeaflServer:
    """Holds global params (flat), buffer, version history, client activity."""

    def __init__(self, cfg: FLConfig, params: PyTree,
                 client_sizes: dict[int, int],
                 telemetry: Optional[Telemetry] = None):
        assert cfg.algorithm in ALGORITHMS, cfg.algorithm
        if cfg.buffer_dtype not in BUFFER_DTYPES:
            raise ValueError(f"buffer_dtype must be one of "
                             f"{sorted(BUFFER_DTYPES)}, got {cfg.buffer_dtype}")
        self.cfg = cfg
        if cfg.monitor not in ("off", "on"):
            raise ValueError(f"monitor must be 'off' or 'on', got "
                             f"{cfg.monitor!r}")
        # the monitor consumes telemetry (compact snapshots, sim-track
        # busy time), so monitor='on' implies an enabled registry even
        # when cfg.telemetry is False
        self.tel = (telemetry if telemetry is not None
                    else Telemetry(enabled=cfg.telemetry
                                   or cfg.monitor == "on"))
        # built eagerly so a bad SLO spec fails at construction, not
        # mid-run; never checkpointed (detectors restart cold on resume)
        self.monitor: Optional[RunMonitor] = (
            RunMonitor.from_config(cfg, self.tel)
            if cfg.monitor == "on" else None)
        # pluggable client-selection policy; like the monitor, built
        # eagerly (bad names fail at construction) and never checkpointed
        # (ranking EMAs re-warm within a few rounds on resume)
        self.scheduler = make_scheduler(cfg.scheduler, self.tel)
        self.packer = ParamPacker(params)
        self._flat = self.packer.pack(params)          # current global, (P,)
        self.round = 0
        self.wire = make_wire_format(cfg.compression, cfg.chunk_elems)
        if cfg.autotune not in ("off", "cache", "sweep"):
            raise ValueError(f"autotune must be 'off', 'cache' or 'sweep', "
                             f"got {cfg.autotune!r}")
        # per-chip tuning: resolved once at construction.  'off' keeps the
        # tuner out of every code path (self.tuning is None and nothing
        # below consults it) — the bit-identity pin.  A tuned chunk_elems
        # rebuilds the wire format, so uplink chunking itself is swept.
        self.tuning = None
        if cfg.autotune != "off":
            from repro.runtime.autotune import ServerTuning
            self.tuning = ServerTuning.build(
                cfg.autotune, p=self.packer.size, k=self._trigger_size(),
                dtype=BUFFER_DTYPES[cfg.buffer_dtype],
                scheme=self.wire.scheme, algorithm=cfg.algorithm,
                chunk_elems=cfg.chunk_elems,
                flush_chunks=cfg.ingest_batch_chunks, telemetry=self.tel)
            ce = self.tuning.chunk_elems(cfg.chunk_elems)
            if ce != self.wire.chunk_elems:
                self.wire = make_wire_format(cfg.compression, ce)
        if cfg.dispatch_resync_mode not in RESYNC_MODES:
            raise ValueError(f"dispatch_resync_mode must be one of "
                             f"{RESYNC_MODES}, got "
                             f"{cfg.dispatch_resync_mode!r}")
        if cfg.cohorts not in ("off", "on"):
            raise ValueError(f"cohorts must be 'off' or 'on', got "
                             f"{cfg.cohorts!r}")
        self._cohorts_on = cfg.cohorts == "on"
        self.dispatch: Optional[DispatchSession] = None
        if cfg.dispatch_compression is not None:
            sess_cls = (CohortDispatchSession if self._cohorts_on
                        else DispatchSession)
            self.dispatch = sess_cls(
                make_wire_format(cfg.dispatch_compression,
                                 cfg.dispatch_chunk_elems),
                cfg.dispatch_history,
                multicast=cfg.dispatch_multicast,
                resync=cfg.dispatch_resync,
                resync_mode=cfg.dispatch_resync_mode,
                telemetry=self.tel)
        # drift-adaptive rate policy: validated here so a bad band config
        # fails at construction, not mid-run
        self.rate_policy = RatePolicy.from_config(cfg)
        if cfg.dispatch_ratio_policy == "drift" and (
                self.dispatch is None
                or self.dispatch.fmt.scheme != "topk"):
            raise ValueError(
                "dispatch_ratio_policy='drift' adapts the top-k dispatch "
                "ratio and needs dispatch_compression='topk:<ratio>'")
        if cfg.uplink_ratio_policy == "drift" and self.wire.scheme != "topk":
            raise ValueError(
                "uplink_ratio_policy='drift' adapts the top-k uplink "
                "ratio and needs compression='topk:<ratio>'")
        self._drift = DriftTracker(cfg.drift_ema_beta)
        self._ratio_by_version: dict[int, float] = {}
        self._buffer_dtype = BUFFER_DTYPES[cfg.buffer_dtype]
        self.buffer = UpdateBuffer(self._trigger_size(), self.packer.size,
                                   dtype=self._buffer_dtype,
                                   telemetry=self.tel)
        self._batcher = self._make_batcher()
        # two-tier edge aggregation (cohorts='on'): same-version uploads
        # pre-combine into one resident (P,) partial per version, so the
        # buffer holds O(live versions) slots regardless of how many
        # clients uploaded this round.  The trigger then counts *uploads
        # absorbed* since the last aggregation, not committed slots.
        self._edge_slots: dict[int, tuple[int, Update]] = {}
        self._updates_since_agg = 0
        self._edge_merges_round = 0
        self._edge_merges_total = 0
        self._edge_partials_last = 0
        self.client_sizes = client_sizes
        self.active: dict[int, int] = {}         # cid -> version t_k
        self.idle: set[int] = set(client_sizes)
        self._history: dict[int, jnp.ndarray] = {0: self._flat}  # flat buffers
        self._unpack_cache: dict[int, PyTree] = {0: params}
        self._notified: set[int] = set()
        self._rng = np.random.default_rng(cfg.seed)
        self.total_aggregations = 0
        self.bytes_uploaded = 0                  # uplink wire bytes
        self.bytes_downloaded = 0                # downlink wire bytes
        self._ef: dict[int, FlatErrorFeedback] = {}
        self._ingests: dict[int, IngestSession] = {}   # cid -> mid-stream

    # ------------------------------------------------------------- plumbing
    def _make_batcher(self) -> Optional[IngestBatcher]:
        """Ingest batcher over the current buffer, tuning-aware: a cached
        bypass verdict answers without the startup probe, and the swept
        flush size replaces the configured one.  With tuning off this is
        exactly the pre-autotune construction."""
        cfg = self.cfg
        if cfg.ingest_batch_chunks <= 0:
            return None
        flush = cfg.ingest_batch_chunks
        verdict = None
        if self.tuning is not None:
            flush = self.tuning.ingest_flush_chunks(flush)
            verdict = self.tuning.ingest_verdict
        return IngestBatcher(self.buffer, flush,
                             auto_bypass=cfg.ingest_auto_bypass,
                             telemetry=self.tel, tuned_verdict=verdict)

    def _trigger_size(self) -> int:
        if self.cfg.algorithm == "fedavg":
            return self.cfg.concurrency
        if self.cfg.algorithm == "fedasync":
            return 1
        return self.cfg.buffer_size

    @property
    def params(self) -> PyTree:
        """Current global model as a pytree (dispatch/eval boundary)."""
        return self.params_at(self.round)

    @property
    def global_flat(self) -> jnp.ndarray:
        return self._flat

    def flat_at(self, version: int) -> jnp.ndarray:
        return self._history[version]

    def params_at(self, version: int) -> PyTree:
        if version not in self._unpack_cache:
            self._unpack_cache[version] = self.packer.unpack(
                self._history[version])
        return self._unpack_cache[version]

    def staleness_of(self, cid: int) -> int:
        return self.round - self.active[cid]

    def _gc_history(self):
        live = set(self.active.values()) | {self.round}
        if self.dispatch is not None and self.dispatch.fmt.delta_coded:
            # the bounded dispatch ring: keep the last `dispatch_history`
            # globals so returning clients can receive deltas against the
            # version they still hold (older holders get a full snapshot).
            # Raw dispatch schemes (f32/bf16) never read old ring versions,
            # so they pay no retention.
            live |= self.dispatch.ring_versions(self.round)
        self._history = {v: p for v, p in self._history.items() if v in live}
        self._unpack_cache = {v: p for v, p in self._unpack_cache.items()
                              if v in live}
        # chosen per-version ratios die with the versions they encode for
        self._ratio_by_version = {v: r for v, r in
                                  self._ratio_by_version.items()
                                  if v in self._history}
        if self.dispatch is not None:
            # encode-cache entries age out with the ring they index into
            self.dispatch.age_cache(self.round)

    def _sample_idle(self, k: int) -> list[int]:
        """Every idle-pool draw routes through the scheduler policy: it
        filters offline clients out (when the simulator bound an
        availability model) and ranks or samples the rest.  The default
        RandomScheduler consumes ``self._rng`` exactly like the historic
        inline draw here — the bit-identity pin in tests/test_scheduler.py
        holds this line to it."""
        return self.scheduler.select(sorted(self.idle), k, self._rng,
                                     round_=self.round)

    # ------------------------------------------------------------ lifecycle
    def start(self) -> list[int]:
        """Dispatch up to M in-flight clients (top-up, so calling it on a
        resumed or restored server never over-subscribes the fleet)."""
        cids = self._sample_idle(self.cfg.concurrency - len(self.active))
        for c in cids:
            self.mark_dispatched(c)
        return cids

    def mark_dispatched(self, cid: int):
        self.idle.discard(cid)
        self.active[cid] = self.round
        self._notified.discard(cid)

    def mark_failed(self, cid: int):
        """Client died mid-training: return a replacement dispatch if any."""
        self.active.pop(cid, None)
        self.abort_ingest(cid)           # a mid-stream upload dies with it
        if self.dispatch is not None:
            # the device lost its model state: version tracking is void and
            # its next dispatch re-requests a full snapshot
            self.dispatch.drop(cid)
        # the dead client may rejoin the idle pool later (recovery)
        repl = self._sample_idle(1)
        for c in repl:
            self.mark_dispatched(c)
        return repl

    def recover(self, cid: int):
        if cid not in self.active:
            self.idle.add(cid)

    # --------------------------------------------------------------- policy
    def _blocked_by_stale(self) -> bool:
        """SEAFL sync-wait (paper §IV-B): hold aggregation while any
        in-flight client's update would exceed the staleness limit."""
        if self.cfg.algorithm not in ("seafl", "seafl2"):
            return False
        if self.cfg.staleness_limit is None:
            return False
        return any(self.round - v >= self.cfg.staleness_limit
                   for v in self.active.values())

    def clients_to_notify(self) -> list[int]:
        """SEAFL² (Algorithm 2): in-flight clients at/over the limit get a
        NOTIFY and will upload after their current epoch."""
        if self.cfg.algorithm != "seafl2" or self.cfg.staleness_limit is None:
            return []
        out = [c for c, v in self.active.items()
               if (self.round - v) >= self.cfg.staleness_limit
               and c not in self._notified]
        self._notified.update(out)
        return out

    # ----------------------------------------------------- downlink transport
    def encode_dispatch(self, cid: int,
                        materialize: bool = True) -> DispatchPayload:
        """Serve the current global to ``cid``.

        Legacy mode (``dispatch_compression=None``): no wire object — a
        marker payload whose ``nbytes`` is the raw f32 model size, exactly
        what the pre-dispatch bandwidth model charged.  Otherwise the
        DispatchSession encodes chunked f32/bf16 snapshots or topk/int8
        deltas against the client's held ring version
        (``materialize=False`` skips building raw/full chunks whose bytes
        have a closed form — the simulator's hot path).  Tracking state is
        untouched until :meth:`deliver_dispatch` — an undelivered payload
        (crash inside the dispatch window) simply dies on the wire."""
        target = self.active.get(cid, self.round)
        if self.dispatch is None:
            return DispatchPayload(
                cid=cid, target_version=target, base_version=None,
                scheme="raw", param_size=self.packer.size, chunks=None,
                nbytes=4 * self.packer.size,
                encode_cost_bytes=4 * self.packer.size)
        ratio = None
        if self.cfg.dispatch_ratio_policy == "drift":
            ratio = self._ratio_by_version.get(target)
        with self.tel.span("dispatch.encode", cid=cid, version=target):
            return self.dispatch.encode(cid, target, self._history,
                                        materialize=materialize, ratio=ratio)

    def encode_dispatch_round(self, cids: list[int],
                              materialize: bool = True
                              ) -> tuple[list[DispatchPayload], int]:
        """Encode one aggregation round's dispatch fan-out in a single
        pass (``DispatchSession.encode_many``): cached-hop payloads fan
        out as usual while every personalized resync fold-in coalesces
        into one batched encode per wire format.  Returns ``(payloads,
        fold_cost_bytes)`` with payloads aligned to ``cids`` and
        byte-identical to sequential :meth:`encode_dispatch` calls; the
        batch's fresh-encode source cost comes back once as
        ``fold_cost_bytes`` (the simulator prices it overlapped with the
        fan-out — the resync-batching path)."""
        if self.dispatch is None:
            return ([self.encode_dispatch(c, materialize) for c in cids], 0)
        reqs = []
        for cid in cids:
            target = self.active.get(cid, self.round)
            ratio = None
            if self.cfg.dispatch_ratio_policy == "drift":
                ratio = self._ratio_by_version.get(target)
            reqs.append((cid, target, ratio))
        return self.dispatch.encode_many(reqs, self._history,
                                         materialize=materialize)

    def dispatch_ratio(self, version: Optional[int] = None) -> Optional[float]:
        """Effective top-k dispatch ratio for dispatches of ``version``
        (default: the current round): the drift band's chosen ratio when
        the adaptive policy is on, the static configured ratio for topk
        dispatch, None for non-topk schemes — what the simulator records
        in its per-round history."""
        if self.dispatch is None or self.dispatch.fmt.scheme != "topk":
            return None
        v = self.round if version is None else version
        if self.cfg.dispatch_ratio_policy == "drift":
            r = self._ratio_by_version.get(v)
            if r is not None:
                return r
        return self.dispatch.fmt.topk_ratio

    def deliver_dispatch(self, cid: int, payload: DispatchPayload) -> None:
        """The last downlink chunk reached the client: account the wire
        bytes and commit version tracking + error-feedback residual."""
        self.bytes_downloaded += payload.nbytes
        if self.dispatch is not None and payload.scheme != "raw":
            self.dispatch.deliver(payload)

    def dispatch_model(self, cid: int) -> PyTree:
        """The model ``cid`` actually holds (training-base boundary): the
        exact dispatch-version global in legacy/f32 mode, the delivered
        reconstruction under lossy dispatch.  Unpacked once, here."""
        with self.tel.span("server.dispatch_model"):
            if self.dispatch is None or cid not in self.dispatch.versions:
                return self.params_at(self.active[cid])
            held = self.dispatch.held_flat(cid, self._history)
            if held is self._history.get(self.dispatch.versions[cid]):
                # f32: cached
                return self.params_at(self.dispatch.versions[cid])
            return self.packer.unpack(held)

    # ------------------------------------------------------- uplink transport
    def encode_update(self, cid: int, client_params: PyTree,
                      n_epochs: int) -> UploadPayload:
        """Client-side encoder (simulated on the server object): pack once,
        then serialise to wire chunks per the configured WireFormat.  For
        delta-coded schemes (topk/int8) the delta is taken vs the dispatch
        version and the client's flat error-feedback residual is folded in
        and updated — per-leaf delta pytrees are never built."""
        with self.tel.span("server.encode_update"):
            version = self.active[cid]
            flat = self.packer.pack(client_params)
            wire = self.wire
            if wire.scheme == "topk":
                if self.cfg.uplink_ratio_policy == "drift":
                    # the drift band chosen for the version this client
                    # trained from also sizes its upload (same discrete-
                    # ratio set)
                    r = self._ratio_by_version.get(version)
                    if r is not None:
                        wire = dc_replace(wire, topk_ratio=r)
                if n_epochs < self.cfg.local_epochs:
                    # SEAFL² byte coupling: a notified partial-training
                    # client did n' < E epochs of work, so its update
                    # carries proportionally less signal — ship
                    # proportionally fewer bytes.  (Decode is ratio-free:
                    # topk chunks carry their own indices.)
                    wire = dc_replace(
                        wire, topk_ratio=wire.topk_ratio
                        * max(1, n_epochs) / self.cfg.local_epochs)
            base = ef = None
            if wire.delta_coded:
                base = self._uplink_base(cid, version)
                ef = self._ef.setdefault(cid, FlatErrorFeedback())
            return transport_encode_update(cid, version, n_epochs, flat,
                                           wire, base, ef)

    def _uplink_base(self, cid: int, version: int) -> jnp.ndarray:
        """The flat base a delta-coded upload is measured against.

        Under a lossy dispatch scheme the client never saw the exact
        ``ring[version]`` snapshot — it trained from the *delivered*
        reconstruction (``held = ring[version] - dispatch residual``), so
        its uplink delta must be measured against that reconstruction, and
        the server (which knows the residual exactly) decodes against the
        same base.  Using ``ring[version]`` on either end would silently
        fold the dispatch reconstruction mismatch into every upload — the
        cross-direction error-coupling bug.  Exact-dispatch modes
        (legacy/f32, or no tracking for this client) keep the snapshot."""
        if (self.dispatch is not None
                and self.dispatch.versions.get(cid) == version):
            return self.dispatch.held_flat(cid, self._history)
        return self._history[version]

    def begin_ingest(self, cid: int, version: int, n_epochs: int,
                     recv_time: float = 0.0) -> IngestSession:
        """Open a streaming ingest: reserve a buffer slot for ``cid``'s
        upload and return the session that decodes chunks into it."""
        if cid in self._ingests:
            raise RuntimeError(f"client {cid} already has an ingest open")
        base = (self._uplink_base(cid, version) if self.wire.delta_coded
                else None)
        slot = self.buffer.reserve(Update(
            client_id=cid, n_samples=self.client_sizes[cid], version=version,
            n_epochs=n_epochs, recv_time=recv_time))
        sess = IngestSession(self.buffer, slot, self.wire, base,
                             param_size=self.packer.size,
                             batcher=self._batcher, telemetry=self.tel)
        self._ingests[cid] = sess
        return sess

    def ingest_chunk(self, cid: int, chunk: Chunk) -> None:
        self._ingests[cid].write(chunk)

    def abort_ingest(self, cid: int) -> None:
        """Drop a mid-stream upload (truncated stream, dead client): the
        session is discarded and its reserved buffer slot is recycled."""
        sess = self._ingests.pop(cid, None)
        if sess is not None:
            if self._batcher is not None:
                # drop queued-but-unflushed writes so the recycled row can
                # never be corrupted by a dead client's stale chunks
                self._batcher.cancel_slot(sess.slot)
            self.buffer.release(sess.slot)

    def finish_ingest(self, cid: int,
                      recv_time: float = 0.0) -> Optional[AggregationEvent]:
        """Close the stream: validate coverage, commit the slot, account the
        wire bytes (compressed or not — the bandwidth model and the bench
        tables both need raw-f32 payloads counted), and aggregate if the
        buffer triggered.  On incomplete coverage the session stays open
        (the driver may deliver the missing chunks or ``abort_ingest``).
        Concurrent streams may finish in any order; uploads still mid-stream
        keep their reserved rows across an aggregation's drain."""
        with self.tel.span("ingest.commit"):
            sess = self._ingests[cid]
            nbytes = sess.finish()       # raises while coverage is incomplete
            del self._ingests[cid]
            self.bytes_uploaded += nbytes
            self.tel.counter("ingest.commits")
            self.tel.histogram("ingest.upload_bytes", nbytes)
            if self._batcher is not None:
                # readers only ever see flushed rows: the slot's queued
                # writes (and any co-batched neighbours) land before the
                # commit
                self._batcher.flush()
            self.buffer.commit(sess.slot)
            self._updates_since_agg += 1
            if self._cohorts_on and self.buffer.capacity > 1:
                self._edge_absorb(sess.slot)
            self.active.pop(cid, None)
            self.idle.add(cid)
            filled = (self._updates_since_agg if self._cohorts_on
                      else len(self.buffer))
            trigger = (filled >= self.buffer.capacity
                       and not self._blocked_by_stale())
        return self._aggregate(recv_time) if trigger else None

    def _edge_absorb(self, slot: int) -> None:
        """Two-tier aggregation, edge tier: fold the just-committed upload
        into its version's resident partial.

        The first upload of a version this round claims its slot as the
        version's edge partial; every later same-version upload merges into
        it as a sample-weighted mean (one donated device op) and its own
        row is uncommitted back to the free pool.  The partial's metadata
        accumulates the contributor ids (``meta['merged_cids']``) and total
        sample count, so the top-tier Eq. (4)-(8) weights see one slot per
        version carrying the cohort's combined mass — the buffer stays
        O(live versions) while the aggregation trigger still counts raw
        uploads.  Within a partial, members are n_k-weighted (plain
        sample-weighted averaging); the staleness/importance weighting
        applies at the cohort granularity — the hierarchical trade."""
        hu, _ = self.buffer._committed[-1]
        v = hu.version
        held = self._edge_slots.get(v)
        if held is None:
            self._edge_slots[v] = (slot, hu)
            return
        hslot, head = held
        self.buffer.merge_rows(hslot, slot, float(head.n_samples),
                               float(hu.n_samples))
        head.meta.setdefault("merged_cids",
                             [head.client_id]).append(hu.client_id)
        head.n_samples += hu.n_samples
        head.recv_time = hu.recv_time
        head.n_epochs = max(head.n_epochs, hu.n_epochs)
        self.buffer.uncommit(slot)
        self._edge_merges_round += 1
        self._edge_merges_total += 1

    def ingest_payload(self, payload: UploadPayload,
                       recv_time: float = 0.0) -> Optional[AggregationEvent]:
        """Atomic ingest of a whole wire payload (the simulator's deliver
        event, which ``launch/train.py`` drives too, and the legacy
        ``on_update`` land here).  The drained chunks are adjacent windows
        of one slot: each is decoded on its own, and
        ``IngestSession.write_all`` lands them all with one donated device
        program that joins them, adds the delta base on a delta-coded wire,
        and writes the slot in place."""
        with self.tel.span("ingest"):
            sess = self.begin_ingest(payload.cid, payload.version,
                                     payload.n_epochs, recv_time=recv_time)
            sess.write_all(payload.chunks)
            return self.finish_ingest(payload.cid, recv_time)

    # ----------------------------------------------------------- on_update
    def on_update(self, cid: int, client_params: PyTree, n_epochs: int,
                  recv_time: float = 0.0) -> Optional[AggregationEvent]:
        """Encode + ingest in one step (drivers without an explicit wire)."""
        payload = self.encode_update(cid, client_params, n_epochs)
        return self.ingest_payload(payload, recv_time)

    # ----------------------------------------------------------- aggregate
    def _aggregate(self, now: float) -> AggregationEvent:
        """One server aggregation, entirely on the flat (K, P) engine."""
        # deferred import: kernels.seafl_agg.ops reuses the Eq. (4)/(6)
        # weight rule from core.aggregation, so importing it at module scope
        # from here (via the repro.core package) would be circular
        from repro.kernels.seafl_agg.ops import (
            seafl_aggregate_flat_from_params, fedavg_aggregate_flat,
            fedbuff_aggregate_flat, fedasync_aggregate_flat,
        )
        cfg = self.cfg
        prev_flat = self._flat            # drift observation base
        updates = self.buffer.updates()
        staleness = np.asarray([self.round - u.version for u in updates],
                               np.float32)
        sizes = np.asarray([u.n_samples for u in updates], np.float32)
        stacked = self.buffer.stacked_flat()   # f32 or bf16 slots; kernels
        weights = None                         # accumulate in f32 either way

        # tuning plans (None with autotune='off' — the entry points then
        # dispatch byte-for-byte like the untuned tree): the baselines ride
        # the raw fused pass, seafl/seafl2 the delta-free fused hot path
        tuned_w = tuned_s = None
        if self.tuning is not None:
            tuned_w = self.tuning.agg_plan("weighted_aggregate")
            tuned_s = self.tuning.agg_plan("seafl_aggregate_flat_from_params")

        with self.tel.span("server.aggregate", round=self.round,
                           k=len(updates), algorithm=cfg.algorithm):
            if cfg.algorithm == "fedavg":
                self._flat, w = fedavg_aggregate_flat(
                    self._flat, stacked, jnp.asarray(sizes), tuned=tuned_w)
                weights = np.asarray(w)
            elif cfg.algorithm == "fedasync":
                self._flat = fedasync_aggregate_flat(
                    self._flat, stacked[0], staleness[0],
                    cfg.fedasync_alpha0, cfg.fedasync_poly_a, tuned=tuned_w)
            elif cfg.algorithm == "fedbuff":
                # fedbuff_aggregate_flat yields w_t + eta*mean(w_k - w_t);
                # true FedBuff deltas are vs each client's dispatch version,
                # so add eta*(w_t - mean_k base_k) — a tiny combination over
                # the few distinct live versions, not another (K, P) pass.
                g, k = self._flat, float(len(updates))
                mixed, w = fedbuff_aggregate_flat(g, stacked,
                                                  cfg.fedbuff_eta_g,
                                                  tuned=tuned_w)
                counts: dict[int, int] = {}
                for u in updates:
                    counts[u.version] = counts.get(u.version, 0) + 1
                base_mix = sum((n / k) * self._history[v]
                               for v, n in counts.items())
                self._flat = mixed + cfg.fedbuff_eta_g * (g - base_mix)
                weights = np.asarray(w)
            else:  # seafl / seafl2 — Eqs. (4)-(8), delta-free
                # Eq. (5) importance is measured against the *current*
                # global (the seafl_aggregate_from_params identity):
                # cos(w_k - w_t^g, w_t^g), not the dispatch-version base.
                # This is the delta-free trade the engine is built on — the
                # similarity question becomes "does this update still point
                # somewhere useful from where the model is now", and the
                # buffer never has to store deltas.
                h = cfg.hyper()
                self._flat, w = seafl_aggregate_flat_from_params(
                    self._flat, stacked, jnp.asarray(sizes),
                    jnp.asarray(staleness), h.alpha, h.mu, h.beta, h.theta,
                    use_importance=h.use_importance,
                    use_staleness=h.use_staleness, tuned=tuned_s)
                weights = np.asarray(w)

        if self.tel.enabled:
            # per-update staleness + Eq. (5) adaptive-weight distributions:
            # the histograms tests/benches cross-check against the buffer
            self.tel.counter("agg.count")
            self.tel.gauge("agg.buffer_fill", len(updates))
            self.tel.histogram_many("agg.staleness", staleness)
            if weights is not None:
                self.tel.histogram_many("agg.weight", weights)

        # an edge partial contributes every client it absorbed; plain slots
        # carry their own id (identical to buffer.client_ids() when no
        # merge happened — the cohorts='off' expression, bit-for-bit)
        contributors = [c for u in updates
                        for c in u.meta.get("merged_cids", [u.client_id])]
        self.buffer.drain()
        self._edge_partials_last = self._edge_merges_round
        self._edge_merges_round = 0
        self._edge_slots = {}
        self._updates_since_agg = 0
        self.round += 1
        self.total_aggregations += 1
        self._history[self.round] = self._flat
        if self.rate_policy.active:
            # one scalar per aggregation: the round-over-round drift norm,
            # EMA-normalised and binned into a discrete ratio band.  Chosen
            # once per target version, so every dispatch of this round
            # (and its multicast cache hops) shares the band's ratio.
            x = self._drift.observe(
                float(jnp.linalg.norm(self._flat - prev_flat)))
            self._ratio_by_version[self.round] = \
                self.rate_policy.ratio_for(x, telemetry=self.tel)
        self._gc_history()

        # contributors + top-up to M go back to training on the new model.
        # Only contributors still idle: a crash replacement (or an eager
        # scheduler top-up) may have re-dispatched a buffered contributor
        # between its delivery and this aggregation — re-dispatching it
        # again would overlap two in-flight rounds for one client.
        dispatch = [c for c in dict.fromkeys(contributors) if c in self.idle]
        if self.scheduler.reselect_contributors:
            # ranked policies: contributors returned to the idle pool at
            # ingest, so re-select the whole fan-out — the policy, not
            # delivery order, decides who trains next round (the random
            # policy keeps the legacy unconditional re-dispatch)
            dispatch = self._sample_idle(
                self.cfg.concurrency - len(self.active))
            for c in dispatch:
                self.mark_dispatched(c)
        else:
            for c in dispatch:
                self.mark_dispatched(c)
            top_up = self._sample_idle(
                self.cfg.concurrency - len(self.active))
            for c in top_up:
                self.mark_dispatched(c)
            dispatch += top_up

        return AggregationEvent(
            round=self.round, weights=weights, staleness=staleness,
            contributors=contributors, dispatch=dispatch,
            notify=self.clients_to_notify())

    # ------------------------------------------------------- fleet telemetry
    def cohort_stats(self) -> Optional[dict]:
        """Cohort-layer occupancy for the simulator's per-round history and
        the train CLI (None when ``cohorts='off'``): ``cohorts`` is the
        live cohort count in the dispatch table (0 without a dispatch
        session), ``edge_partials`` the number of edge-tier pre-combine
        merges absorbed by the round that just aggregated."""
        if not self._cohorts_on:
            return None
        return {
            "cohorts": (self.dispatch.table.n_cohorts()
                        if isinstance(self.dispatch, CohortDispatchSession)
                        else 0),
            "edge_partials": int(self._edge_partials_last),
            "edge_merges_total": int(self._edge_merges_total),
        }

    def resident_state_bytes(self) -> dict:
        """Server-resident fleet-state breakdown (the BENCH_fleet metric).

        ``server_array_bytes`` sums the *server-resident* (P,)-scaled
        device state — history ring, (K, P) buffer, dispatch residuals —
        which is what must stay ~O(cohorts + ring) as fleet size grows;
        ``tracking_entries`` counts the O(clients) *scalar* entries (held
        versions) that legitimately remain per-client.  ``client_ef_bytes``
        is reported separately: uplink error-feedback residuals live on the
        devices in a real deployment and are only simulated centrally."""
        hist = sum(int(v.size) * 4 for v in self._history.values())
        buf = int(self.buffer.hbm_bytes)
        ef = sum(int(e.residual.size) * 4 for e in self._ef.values()
                 if e.residual is not None)
        disp = cache = tracking = 0
        if self.dispatch is not None:
            tracking = len(self.dispatch.versions)
            if isinstance(self.dispatch, CohortDispatchSession):
                disp = self.dispatch.table.resident_bytes()
            else:
                disp = sum(int(r.size) * 4
                           for r in self.dispatch.residuals.values())
            for ent in self.dispatch._cache.values():
                cache += int(ent[2])
                if ent[1] is not None:
                    cache += int(ent[1].size) * 4
        return {
            "history_bytes": hist,
            "buffer_bytes": buf,
            "dispatch_residual_bytes": disp,
            "client_ef_bytes": ef,
            "encode_cache_bytes": cache,
            "tracking_entries": tracking,
            "edge_partial_slots": len(self._edge_slots),
            "server_array_bytes": hist + buf + disp,
        }

    # ------------------------------------------------------ fault tolerance
    def state_dict(self) -> dict:
        """JSON-able control state (arrays are saved separately via the
        Checkpointer).  Committed buffer slots are persisted — a checkpoint
        taken while SEAFL sync-wait is holding aggregation must not drop a
        non-empty buffer.  Uploads still mid-stream (``_ingests``) are *not*
        persisted: their clients remain listed as active, so a restored
        driver re-dispatches them and the upload is simply re-sent."""
        return {
            "round": self.round,
            "active": {str(k): int(v) for k, v in self.active.items()},
            "idle": sorted(self.idle),
            "notified": sorted(self._notified),
            "total_aggregations": self.total_aggregations,
            "bytes_uploaded": int(self.bytes_uploaded),
            "bytes_downloaded": int(self.bytes_downloaded),
            "dispatch": (self.dispatch.state_dict()
                         if self.dispatch is not None else None),
            # drift-band rate policy: the EMA float + per-live-version
            # chosen ratios — without them a restored session would
            # re-encode in-ring hops at the wrong ratio (different bytes)
            "drift": self._drift.state_dict(),
            "ratio_by_version": {str(v): float(r) for v, r in
                                 self._ratio_by_version.items()},
            "rng": self._rng.bit_generator.state,
            "history_versions": sorted(self._history),
            # a slot's meta rides along only when non-empty (edge partials
            # carry merged_cids); off-mode entries are unchanged, so PR-5
            # era checkpoints stay interchangeable with cohorts='off'
            "buffer": [
                dict({"client_id": u.client_id, "n_samples": u.n_samples,
                      "version": u.version, "n_epochs": u.n_epochs,
                      "recv_time": u.recv_time},
                     **({"meta": u.meta} if u.meta else {}))
                for u in self.buffer.updates()
            ],
            "ef_clients": sorted(c for c, ef in self._ef.items()
                                 if ef.residual is not None),
            **({
                # cohort mode: the upload counter decouples the trigger
                # from committed-slot count, and edge partials must re-link
                # to their rebuilt rows (slots are re-rowed 0..k-1 by the
                # add() rebuild, so the committed *index* is the stable id)
                "updates_since_agg": int(self._updates_since_agg),
                "edge_slots": [
                    [int(v), next(i for i, (u, _) in
                                  enumerate(self.buffer._committed)
                                  if u is hu)]
                    for v, (_, hu) in self._edge_slots.items()
                ],
            } if self._cohorts_on else {}),
            # metrics snapshot rides with the checkpoint only when telemetry
            # is on — off-mode state dicts keep their pre-telemetry shape
            **({"telemetry": self.tel.snapshot()}
               if self.tel.enabled else {}),
        }

    def checkpoint_trees(self) -> dict:
        """Arrays that must be persisted: the flat model at each live
        version, per-client error-feedback residuals (without them a restart
        under compression=topk:* silently resets error memory), and the
        committed (K, P) buffer rows (without them a checkpoint under
        sync-wait silently drops buffered updates)."""
        trees = {f"v{v}": p for v, p in self._history.items()}
        for cid, ef in self._ef.items():
            if ef.residual is not None:
                trees[f"ef{cid}"] = ef.residual
        if self.dispatch is not None:
            trees.update(self.dispatch.residual_trees())
        for i in range(len(self.buffer)):
            trees[f"slot{i}"] = self.buffer.row(i)
        return trees

    def load_state(self, state: dict, trees: dict):
        self.round = int(state["round"])
        self.active = {int(k): int(v) for k, v in state["active"].items()}
        self.idle = set(state["idle"])
        self._notified = set(state["notified"])
        self.total_aggregations = int(state["total_aggregations"])
        self.bytes_uploaded = int(state.get("bytes_uploaded", 0))
        self.bytes_downloaded = int(state.get("bytes_downloaded", 0))
        disp_state = state.get("dispatch")
        disp_trees = {k: v for k, v in trees.items()
                      if k.startswith(("dr", "cr"))}
        if disp_state is not None and self.dispatch is None:
            warnings.warn(
                "checkpoint carries dispatch version-tracking state but the "
                "restored config has dispatch_compression=None; dropping it "
                "(all clients will receive full legacy broadcasts)")
        elif self.dispatch is not None:
            if disp_state is not None and \
                    disp_state.get("scheme") != self.dispatch.fmt.scheme:
                warnings.warn(
                    f"checkpoint dispatch state was written under scheme "
                    f"'{disp_state.get('scheme')}' but the restored config "
                    f"uses '{self.dispatch.fmt.scheme}'; dropping tracking "
                    f"state (clients re-request full snapshots)")
                disp_state, disp_trees = None, {}
            if disp_state is not None and \
                    ("cohort" in disp_state) != isinstance(
                        self.dispatch, CohortDispatchSession):
                # per-client residual state cannot seed cohort tables (or
                # vice versa) — crossing modes drops tracking, so every
                # client re-requests one exact full snapshot
                warnings.warn(
                    "checkpoint dispatch state was written under the "
                    f"{'cohort' if 'cohort' in disp_state else 'per-client'}"
                    " fleet-state mode but the restored config uses "
                    f"cohorts='{self.cfg.cohorts}'; dropping tracking state "
                    "(clients re-request full snapshots)")
                disp_state, disp_trees = None, {}
            self.dispatch.load_state(disp_state or {}, disp_trees)
        self._drift = DriftTracker.from_state(state.get("drift"),
                                              self.cfg.drift_ema_beta)
        self._ratio_by_version = {
            int(k): float(v)
            for k, v in state.get("ratio_by_version", {}).items()}
        self._rng = np.random.default_rng()
        self._rng.bit_generator.state = state["rng"]
        self._history = {int(k[1:]): jnp.asarray(v)
                         for k, v in trees.items() if k.startswith("v")}
        self._flat = self._history[self.round]
        self._unpack_cache = {}
        self._ingests = {}
        self._ef = {}
        ef_keys = sorted(k for k in trees if k.startswith("ef"))
        if ef_keys and not self.wire.delta_coded:
            # restored config has no delta-coded compression: an EF residual
            # is meaningless (and would crash the next roundtrip) — drop it.
            warnings.warn(
                f"checkpoint carries {len(ef_keys)} error-feedback "
                f"residual(s) but the restored config uses wire scheme "
                f"'{self.wire.scheme}'; dropping stale residuals")
        elif ef_keys:
            for k in ef_keys:
                v = trees[k]
                # flat (P,) residuals are the native format; pre-transport
                # checkpoints stored per-leaf delta pytrees — pack them.
                residual = (self.packer.pack(v) if isinstance(v, dict)
                            else jnp.asarray(v, jnp.float32))
                self._ef[int(k[2:])] = FlatErrorFeedback(residual)
        self.buffer = UpdateBuffer(self._trigger_size(), self.packer.size,
                                   dtype=self._buffer_dtype,
                                   telemetry=self.tel)
        self._batcher = self._make_batcher()
        for i, m in enumerate(state.get("buffer", [])):
            self.buffer.add(
                Update(client_id=int(m["client_id"]),
                       n_samples=int(m["n_samples"]),
                       version=int(m["version"]),
                       n_epochs=int(m["n_epochs"]),
                       recv_time=float(m["recv_time"]),
                       meta=dict(m.get("meta", {}))),
                jnp.asarray(trees[f"slot{i}"]))
        # edge-tier state: absent in pre-cohort / off-mode checkpoints, so
        # the counter defaults to the committed-slot count (off-mode
        # equivalence) and the partial map stays empty
        self._updates_since_agg = int(state.get(
            "updates_since_agg", len(state.get("buffer", []))))
        self._edge_slots = {}
        for v, i in state.get("edge_slots", []):
            u, row = self.buffer._committed[int(i)]
            self._edge_slots[int(v)] = (row, u)
        self._edge_merges_round = 0
        self._edge_partials_last = 0
        if self.tel.enabled and "telemetry" in state:
            self.tel.load_snapshot(state["telemetry"])
