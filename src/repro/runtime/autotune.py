"""Per-chip kernel autotuner: measured sweeps + a device-keyed tuning cache.

The aggregation engine (kernels/seafl_agg), the chunk codecs
(runtime/codecs.py) and the streaming-ingest batcher (runtime/transport.py)
all carry hardcoded performance knobs — ``block_p=2048``, ``chunk_elems=
1<<16``, ``ingest_batch_chunks=16`` — chosen for a TPU v5e that this CPU
container is not.  BENCH_ingest's ``batch_flush_speedup < 1`` for f32/bf16
is the measured proof that a default can be *wrong* on the chip actually
running.  This module makes the compute layer measurement-driven:

  * ``resolve_interpret()`` (re-exported by ``repro.kernels``) decides at
    runtime whether Pallas kernels run compiled (real TPU backends) or in
    interpret mode (CPU containers) — no more hand-flipped constant;

  * per-entry-point sweeps time every ``block_p`` candidate *and* the
    XLA-oracle twin (``kernels/seafl_agg/ref.py``) with a block-until-ready
    clock, so a backend where the compiled kernel loses is routed to the
    oracle per entry point, never process-wide (on a TPU, a kernel that
    fails to lower at the default ``block_p`` raises instead);

  * each measurement is cross-checked against the analytical roofline
    (the running device's entry in ``launch/mesh.DEVICE_PEAKS`` +
    ``launch/hlo_cost.py`` HLO parsing): every sweep reports
    measured-vs-predicted so a config that "wins" at 40x the roofline
    bound is visibly suspicious; a device without peaks gets no
    prediction;

  * winning configs are cached in a versioned JSON keyed by ``(jax device
    kind, dtype, scheme, P-bucket, K-bucket)`` — under ``~/.cache`` for
    swept-on-this-chip entries, with a repo-committed default table
    (``autotune_default.json``) as the cold-start fallback — and loaded at
    ``SeaflServer`` construction via ``FLConfig.autotune``:

      'off'    no tuner anywhere — bit-identical to the untuned tree
               (pinned by tests/test_autotune.py);
      'cache'  cached/default-table winners applied, no measurement;
      'sweep'  measure the shapes this server will actually run, persist
               the winners to the user cache, then apply them.

    The tuner subsumes the one-shot ``IngestBatcher`` auto-bypass probe:
    a cached ingest verdict answers without running it, and the probe
    remains the cache-miss fallback.

Invariants: tuned configs change *timing only* — kernel-vs-oracle parity
and block_p-independence of the math are pinned to <=1e-6 across all five
algorithms; sweeps are deterministic given their timer (injectable, so
tests pin winner selection on a fake clock); a version or device-kind
mismatch invalidates a cache file entirely (re-sweep, never misapply
another chip's winners).
"""
from __future__ import annotations

import json
import math
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "CACHE_VERSION",
    "AGG_ENTRY_POINTS",
    "BLOCK_P_CANDIDATES",
    "CHUNK_ELEMS_CANDIDATES",
    "FLUSH_CANDIDATES",
    "DEFAULT_BLOCK_P",
    "TuningTable",
    "ServerTuning",
    "device_kind",
    "cache_key_prefix",
    "resolve_interpret",
    "user_cache_path",
    "default_table_path",
    "make_key",
    "bucket",
    "sweep_agg_entry",
    "sweep_codec",
    "sweep_ingest",
    "predict_agg_seconds",
]

# bump on any change to key grammar or entry schema: old files invalidate
# wholesale and re-sweep, they are never half-read
CACHE_VERSION = 1

DEFAULT_BLOCK_P = 2048
BLOCK_P_CANDIDATES = (512, 1024, 2048, 4096, 8192)
CHUNK_ELEMS_CANDIDATES = (1 << 14, 1 << 15, 1 << 16, 1 << 17)
FLUSH_CANDIDATES = (8, 16, 32)

# the four seafl_agg entry points the block_p sweep covers: the three raw
# kernels plus the fused delta-free server hot path
AGG_ENTRY_POINTS = (
    "similarity_partials",
    "similarity_partials_from_params",
    "weighted_aggregate",
    "seafl_aggregate_flat_from_params",
)

_DEFAULT_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "autotune_default.json")


# ------------------------------------------------------------ chip identity

def device_kind() -> str:
    """`jax.devices()[0].device_kind` — the cache's per-chip axis.  A
    backend that fails to start raises here rather than keying tuning
    entries to a made-up device."""
    return str(jax.devices()[0].device_kind)


def resolve_interpret(backend: Optional[str] = None) -> bool:
    """Runtime-resolved Pallas mode: compiled on real TPU backends,
    interpret everywhere Mosaic cannot lower (CPU/GPU containers).

    This is what ``repro.kernels.INTERPRET`` now evaluates — the constant
    used to be hand-flipped per deployment."""
    b = backend if backend is not None else jax.default_backend()
    return b != "tpu"


def cache_key_prefix() -> str:
    """Version + chip prefix every entry key on this host shares — the
    'active tuning-cache key' recorded in BENCH_*.json headers."""
    return f"v{CACHE_VERSION}|{device_kind()}"


def user_cache_path() -> str:
    root = os.environ.get(
        "XDG_CACHE_HOME", os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(root, "repro_autotune",
                        f"tuning_v{CACHE_VERSION}.json")


def default_table_path() -> str:
    """The repo-committed default table (cold-start fallback)."""
    return _DEFAULT_TABLE


# ------------------------------------------------------------------- keys

def bucket(n: int) -> int:
    """ceil(log2 n): shapes within one power-of-two band share an entry."""
    return max(0, math.ceil(math.log2(max(1, int(n)))))


def make_key(kind: str, name: str, dtype, scheme: Optional[str],
             p: int, k: int, device: Optional[str] = None) -> str:
    """One cache entry key: (device kind, dtype, scheme, P-bucket,
    K-bucket) plus the tuned surface (``kind:name``)."""
    return (f"{kind}:{name}|{device if device is not None else device_kind()}"
            f"|{jnp.dtype(dtype).name}|{scheme or '-'}"
            f"|P{bucket(p)}|K{bucket(k)}")


def _split_key(key: str):
    head, dev, dt, scheme, pb, kb = key.split("|")
    return head, dev, dt, scheme, int(pb[1:]), int(kb[1:])


# ------------------------------------------------------------------ table

@dataclass
class TuningTable:
    """Versioned winning-config store, one JSON file on disk.

    A file whose ``version`` or ``device_kind`` does not match the running
    process is *entirely* invalid (its winners were measured on a
    different schema or a different chip) — the loader reports it so the
    caller re-sweeps instead of misapplying."""

    device: str = field(default_factory=device_kind)
    jax_version: str = field(default_factory=lambda: jax.__version__)
    version: int = CACHE_VERSION
    entries: dict = field(default_factory=dict)
    source: str = "fresh"          # 'fresh' | 'user-cache' | 'default-table'

    def get(self, key: str) -> Optional[dict]:
        return self.entries.get(key)

    def put(self, key: str, value: dict) -> None:
        self.entries[key] = value

    def lookup(self, kind: str, name: str, dtype, scheme: Optional[str],
               p: int, k: int) -> Optional[dict]:
        """Exact (P-bucket, K-bucket) hit, else the nearest swept bucket of
        the same (kind, name, device, dtype, scheme) — a small committed
        table serves neighbouring shapes instead of missing them."""
        key = make_key(kind, name, dtype, scheme, p, k, device=self.device)
        hit = self.entries.get(key)
        if hit is not None:
            return hit
        head, dev, dt, sch, pb, kb = _split_key(key)
        best, best_d = None, None
        for other, entry in self.entries.items():
            try:
                h2, d2, t2, s2, pb2, kb2 = _split_key(other)
            except ValueError:                         # pragma: no cover
                continue
            if (h2, d2, t2, s2) != (head, dev, dt, sch):
                continue
            d = abs(pb2 - pb) + abs(kb2 - kb)
            if best_d is None or d < best_d:
                best, best_d = entry, d
        return best

    def to_json(self) -> dict:
        return {"version": self.version, "device_kind": self.device,
                "jax_version": self.jax_version, "entries": self.entries}

    @classmethod
    def from_json(cls, data: dict, source: str = "fresh") \
            -> Optional["TuningTable"]:
        """None when the file is for another schema version or another
        chip — the mismatch-means-resweep contract."""
        if not isinstance(data, dict):
            return None
        if data.get("version") != CACHE_VERSION:
            return None
        if data.get("device_kind") != device_kind():
            return None
        return cls(device=data["device_kind"],
                   jax_version=str(data.get("jax_version", "")),
                   version=int(data["version"]),
                   entries=dict(data.get("entries", {})),
                   source=source)

    @classmethod
    def load(cls, path: str, source: str = "user-cache") \
            -> Optional["TuningTable"]:
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return None
        return cls.from_json(data, source=source)

    def save(self, path: str) -> None:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)
        os.replace(tmp, path)


def load_table(prefer_user: bool = True,
               user_path: Optional[str] = None) -> TuningTable:
    """User cache if valid, else the committed default table, else a fresh
    empty table (every lookup misses -> hardcoded defaults / probe)."""
    if prefer_user:
        t = TuningTable.load(user_path or user_cache_path(),
                             source="user-cache")
        if t is not None:
            return t
    t = TuningTable.load(default_table_path(), source="default-table")
    if t is not None:
        return t
    return TuningTable()


# ------------------------------------------------------------- measurement

def _wall_timer(fn: Callable[[], object], label=None, reps: int = 3,
                telemetry=None) -> float:
    """The sweep clock: block-until-ready wall seconds, best-of-``reps``
    after a warm call; when a Telemetry is supplied the measurement also
    lands in an ``autotune.<entry>_us`` histogram."""
    jax.block_until_ready(fn())                         # warm (trace + jit)
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    if telemetry is not None and getattr(telemetry, "enabled", False) \
            and label:
        telemetry.histogram(f"autotune.{label[0]}_us", best * 1e6)
    return best


def _make_timer(timer=None, telemetry=None, reps: int = 3):
    """-> timer(fn, label) -> seconds.  ``label`` is ``(entry, knob,
    value)`` so an injected fake timer can be a pure function of the
    config — the sweep-determinism test's hook."""
    if timer is not None:
        return timer
    return lambda fn, label=None: _wall_timer(fn, label=label, reps=reps,
                                              telemetry=telemetry)


# ------------------------------------------------------------- prediction

def _roofline_constants(kind: Optional[str] = None):
    """(peak bf16 FLOP/s, HBM bytes/s) of the running device (or ``kind``),
    None for a device the peak table does not list."""
    from repro.launch.mesh import DEVICE_PEAKS
    peaks = DEVICE_PEAKS.get(kind if kind is not None else device_kind())
    if peaks is None:
        return None
    return peaks["flops_bf16"], peaks["hbm_bw"]


def predict_agg_seconds(entry: str, p: int, k: int, dtype,
                        kind: Optional[str] = None) -> Optional[float]:
    """Analytical roofline bound for one entry point (seconds on the
    running chip, or on ``kind``): max(memory, compute) with the
    ``benchmarks/roofline`` convention of 2x materialised bytes over HBM
    bandwidth.  None when the device has no entry in the peak table."""
    consts = _roofline_constants(kind)
    if consts is None:
        return None
    peak, hbm_bw = consts
    item = jnp.dtype(dtype).itemsize
    if entry == "weighted_aggregate":
        bytes_ = (k * p + p) * item + p * item          # read K+1, write 1
        flops = 2.0 * k * p + 2.0 * p
    elif entry in ("similarity_partials", "similarity_partials_from_params"):
        bytes_ = (k * p + p) * item + k * 4 * 4
        flops = 5.0 * k * p                             # dot + dsq (+ sub)
    else:  # fused from_params: both passes over the buffer
        bytes_ = 2.0 * (k * p + p) * item + p * item
        flops = 7.0 * k * p
    return max(2.0 * bytes_ / hbm_bw, flops / peak)


def predict_from_hlo(fn: Callable, *args) -> Optional[float]:
    """Cross-check: compile the XLA path and run the trip-count-aware HLO
    cost model (``launch/hlo_cost.py``) through the same roofline terms.
    None for a device without peaks, or when the backend will not hand
    back compiled HLO text."""
    consts = _roofline_constants()
    if consts is None:
        return None
    peak, hbm_bw = consts
    try:
        hlo = jax.jit(fn).lower(*args).compile().as_text()
        from repro.launch.hlo_cost import analyze_hlo
        cost = analyze_hlo(hlo)
        t = max(2.0 * cost.get("hbm_bytes", 0.0) / hbm_bw,
                cost.get("flops", 0.0) / peak)
        return t if t > 0 else None
    except Exception:
        return None


# ------------------------------------------------------------- agg sweeps

def _agg_inputs(p: int, k: int, dtype):
    """Deterministic device inputs (values are timing-irrelevant, but a
    constant array could be constant-folded — use a cheap ramp)."""
    dt = jnp.dtype(dtype)
    g = (jnp.arange(p, dtype=jnp.float32) % 97 / 97.0).astype(dt)
    stacked = jnp.broadcast_to(g[None, :] * 0.5, (k, p)).astype(dt) \
        + jnp.arange(k, dtype=dt)[:, None] * jnp.asarray(0.01, dt)
    weights = jnp.full((k,), 1.0 / k, jnp.float32)
    sizes = jnp.ones((k,), jnp.float32)
    stale = jnp.zeros((k,), jnp.float32)
    return {"g": g, "stacked": stacked, "weights": weights,
            "sizes": sizes, "stale": stale}


def _agg_call(entry: str, inputs: dict, block_p: Optional[int] = None,
              oracle: bool = False, interpret: Optional[bool] = None):
    """Zero-arg callable running one entry point at one config."""
    from repro.kernels import INTERPRET
    from repro.kernels.seafl_agg import ops, ref
    itp = INTERPRET if interpret is None else interpret
    bp = DEFAULT_BLOCK_P if block_p is None else int(block_p)
    g, stacked = inputs["g"], inputs["stacked"]
    w, sizes, stale = inputs["weights"], inputs["sizes"], inputs["stale"]
    theta = jnp.float32(0.8)
    if entry == "similarity_partials":
        if oracle:
            return lambda: ops._similarity_partials_oracle(stacked, g)
        return lambda: ops.similarity_partials(stacked, g, block_p=bp,
                                               interpret=itp)
    if entry == "similarity_partials_from_params":
        if oracle:
            return lambda: ops._similarity_partials_from_params_oracle(
                stacked, g)
        return lambda: ops.similarity_partials_from_params(
            stacked, g, block_p=bp, interpret=itp)
    if entry == "weighted_aggregate":
        if oracle:
            return lambda: ops._weighted_aggregate_oracle(w, stacked, g,
                                                          theta)
        return lambda: ops.weighted_aggregate(w, stacked, g, theta,
                                              block_p=bp, interpret=itp)
    if entry == "seafl_aggregate_flat_from_params":
        if oracle:
            return lambda: jax.jit(ref.seafl_aggregate_flat_from_params_ref)(
                g, stacked, sizes, stale, 3.0, 1.0, 10.0, 0.8)
        return lambda: ops._seafl_aggregate_flat_from_params_jit(
            g, stacked, sizes, stale, jnp.float32(3.0), jnp.float32(1.0),
            jnp.float32(10.0), theta, block_p=bp, interpret=itp)
    raise ValueError(f"unknown agg entry point {entry!r}")


def sweep_agg_entry(entry: str, p: int, k: int, dtype="float32", *,
                    candidates=BLOCK_P_CANDIDATES, timer=None,
                    telemetry=None, interpret: Optional[bool] = None,
                    reps: int = 3) -> dict:
    """Measure every ``block_p`` candidate plus the XLA-oracle twin for one
    entry point; return the winning config with its measured-vs-predicted
    roofline ratio.

    Deterministic given ``timer`` (a ``timer(fn, label) -> seconds``
    injectable; the default is the block-until-ready wall clock).  A
    candidate that fails to lower is recorded as ``inf`` and can never
    win — except the default ``block_p`` on a TPU backend, whose failure
    is a broken kernel and raises instead of letting the oracle win."""
    if entry not in AGG_ENTRY_POINTS:
        raise ValueError(f"unknown agg entry point {entry!r} "
                         f"(expected one of {AGG_ENTRY_POINTS})")
    clock = _make_timer(timer, telemetry, reps)
    inputs = _agg_inputs(int(p), int(k), dtype)
    cand_s: dict[int, float] = {}
    for bp in dict.fromkeys((DEFAULT_BLOCK_P, *candidates)):
        try:
            cand_s[int(bp)] = float(clock(
                _agg_call(entry, inputs, block_p=bp, interpret=interpret),
                (entry, "block_p", int(bp))))
        except Exception:
            if bp == DEFAULT_BLOCK_P and jax.default_backend() == "tpu":
                raise
            cand_s[int(bp)] = float("inf")   # failed to lower: cannot win
    try:
        oracle_s = float(clock(_agg_call(entry, inputs, oracle=True),
                               (entry, "oracle", None)))
    except Exception:                                   # pragma: no cover
        oracle_s = float("inf")
    best_bp = min(cand_s, key=lambda b: (cand_s[b], b))
    best_s = cand_s[best_bp]
    use_oracle = oracle_s < best_s
    tuned_s = oracle_s if use_oracle else best_s
    predicted = predict_agg_seconds(entry, int(p), int(k), dtype)
    if predicted is not None:
        hlo_pred = predict_from_hlo(_agg_call(entry, inputs, oracle=True))
        if hlo_pred is not None:
            predicted = max(predicted, hlo_pred)
    default_s = cand_s[DEFAULT_BLOCK_P]
    return {
        "kind": "agg", "entry": entry, "p": int(p), "k": int(k),
        "dtype": jnp.dtype(dtype).name,
        "use_oracle": bool(use_oracle), "block_p": int(best_bp),
        "default_us": round(default_s * 1e6, 3),
        "tuned_us": round(tuned_s * 1e6, 3),
        "oracle_us": round(oracle_s * 1e6, 3),
        "candidates_us": {str(b): round(s * 1e6, 3)
                          for b, s in sorted(cand_s.items())},
        "predicted_us": (round(predicted * 1e6, 3)
                         if predicted is not None else None),
        "measured_vs_predicted": round(tuned_s / predicted, 3)
        if predicted else None,
    }


# ----------------------------------------------------------- codec sweeps

def sweep_codec(spec: str, p: int, *, candidates=CHUNK_ELEMS_CANDIDATES,
                timer=None, telemetry=None, reps: int = 3) -> dict:
    """Measure an encode+decode round trip of a (p,) vector at each
    ``chunk_elems`` candidate; the winner minimises total wall time."""
    from repro.runtime.codecs import (
        decode_concat, encode_flat, make_wire_format, parse_spec,
    )
    scheme, _ = parse_spec(spec)
    clock = _make_timer(timer, telemetry, reps)
    vec = jnp.arange(int(p), dtype=jnp.float32) % 1003 / 1003.0
    cand_s: dict[int, float] = {}
    for ce in candidates:
        fmt = make_wire_format(spec, chunk_elems=int(ce))

        def roundtrip(fmt=fmt):
            return decode_concat(encode_flat(vec, fmt), fmt)

        cand_s[int(ce)] = float(clock(roundtrip,
                                      (f"codec_{scheme}", "chunk_elems",
                                       int(ce))))
    best = min(cand_s, key=lambda c: (cand_s[c], c))
    return {
        "kind": "codec", "scheme": scheme, "p": int(p),
        "chunk_elems": int(best),
        "tuned_us": round(cand_s[best] * 1e6, 3),
        "candidates_us": {str(c): round(s * 1e6, 3)
                          for c, s in sorted(cand_s.items())},
    }


# ---------------------------------------------------------- ingest sweeps

def sweep_ingest(length: int, dtype="float32", *,
                 flush_candidates=FLUSH_CANDIDATES, timer=None,
                 telemetry=None, reps: int = 3) -> dict:
    """Eager per-chunk writes vs one batched scatter per flush, at each
    flush-size candidate — the generalisation of the transport module's
    one-shot auto-bypass probe (which stays as the cache-miss fallback)."""
    from repro.core.buffer import UpdateBuffer
    clock = _make_timer(timer, telemetry, reps)
    length = int(length)
    rows = 8
    scratch = UpdateBuffer(rows, param_size=length * 2, dtype=dtype)
    vals = jnp.ones((length,), jnp.float32)

    def eager(n):
        def run():
            for i in range(n):
                scratch.write_range(i % rows, (i % 2) * length, vals)
            return scratch._buf
        return run

    def batched(n):
        items = [(i % rows, (i % 2) * length, vals) for i in range(n)]

        def run():
            scratch.write_batch(list(items))
            return scratch._buf
        return run

    batch_s = {int(fc): float(clock(batched(int(fc)),
                                    ("ingest_batched", "flush_chunks",
                                     int(fc))))
               for fc in flush_candidates}
    eager_s = {int(fc): float(clock(eager(int(fc)),
                                    ("ingest_eager", "flush_chunks",
                                     int(fc))))
               for fc in flush_candidates}
    # per-chunk cost decides the route: flushes land the same chunk count
    best_fc = min(batch_s, key=lambda f: (batch_s[f] / f, f))
    bypass = all(eager_s[f] < batch_s[f] for f in batch_s)
    return {
        "kind": "ingest", "length": length,
        "dtype": jnp.dtype(dtype).name,
        "bypass": bool(bypass), "flush_chunks": int(best_fc),
        "eager_us": {str(f): round(s * 1e6, 3)
                     for f, s in sorted(eager_s.items())},
        "batched_us": {str(f): round(s * 1e6, 3)
                       for f, s in sorted(batch_s.items())},
    }


# --------------------------------------------------------- server binding

_ALGO_AGG_ENTRY = {
    "seafl": "seafl_aggregate_flat_from_params",
    "seafl2": "seafl_aggregate_flat_from_params",
    "fedavg": "weighted_aggregate",
    "fedbuff": "weighted_aggregate",
    "fedasync": "weighted_aggregate",
}


@dataclass
class ServerTuning:
    """One server's view of the tuning table, resolved at construction.

    ``SeaflServer`` holds this when ``FLConfig.autotune != 'off'`` and
    consults it per aggregate call / batcher verdict — no process-global
    state, so two servers with different modes coexist and ``'off'``
    servers never see a tuner at all."""

    mode: str
    table: TuningTable
    p: int
    k: int
    dtype: str
    scheme: str
    algorithm: str
    keys: dict = field(default_factory=dict)

    @classmethod
    def build(cls, mode: str, p: int, k: int, dtype: str, scheme: str,
              algorithm: str, chunk_elems: int,
              flush_chunks: int, telemetry=None,
              cache_path: Optional[str] = None) -> "ServerTuning":
        table = load_table(user_path=cache_path)
        self = cls(mode=mode, table=table, p=int(p), k=int(k),
                   dtype=jnp.dtype(dtype).name, scheme=scheme,
                   algorithm=algorithm)
        agg_entries = dict.fromkeys(
            (_ALGO_AGG_ENTRY.get(algorithm,
                                 "seafl_aggregate_flat_from_params"),
             "weighted_aggregate"))
        if mode == "sweep":
            for entry in agg_entries:
                key = make_key("agg", entry, self.dtype, None,
                               self.p, self.k, device=table.device)
                if table.get(key) is None:
                    table.put(key, sweep_agg_entry(
                        entry, self.p, self.k, self.dtype,
                        telemetry=telemetry))
            ckey = make_key("codec", self.scheme, "float32", self.scheme,
                            self.p, 0, device=table.device)
            if table.get(ckey) is None:
                table.put(ckey, sweep_codec(self.scheme, self.p,
                                            telemetry=telemetry))
            ce = self.chunk_elems(int(chunk_elems))
            ikey = make_key("ingest", "bypass", self.dtype, self.scheme,
                            ce, int(flush_chunks), device=table.device)
            if table.get(ikey) is None:
                table.put(ikey, sweep_ingest(ce, self.dtype,
                                             telemetry=telemetry))
            table.save(cache_path or user_cache_path())
        for entry in agg_entries:
            self.keys[f"agg:{entry}"] = make_key(
                "agg", entry, self.dtype, None, self.p, self.k,
                device=table.device)
        self.keys[f"codec:{self.scheme}"] = make_key(
            "codec", self.scheme, "float32", self.scheme, self.p, 0,
            device=table.device)
        return self

    # -------------------------------------------------------- aggregation
    def agg_plan(self, entry: str) -> Optional[dict]:
        """-> {'use_oracle': bool, 'block_p': int} or None (use defaults)."""
        hit = self.table.lookup("agg", entry, self.dtype, None,
                                self.p, self.k)
        if hit is None:
            return None
        return {"use_oracle": bool(hit.get("use_oracle", False)),
                "block_p": int(hit.get("block_p", DEFAULT_BLOCK_P))}

    # -------------------------------------------------------------- codec
    def chunk_elems(self, default: int) -> int:
        hit = self.table.lookup("codec", self.scheme, "float32",
                                self.scheme, self.p, 0)
        if hit is None or hit.get("chunk_elems") is None:
            return int(default)
        return int(hit["chunk_elems"])

    # ------------------------------------------------------------- ingest
    def ingest_verdict(self, length: int, dtype,
                       flush_chunks: int) -> Optional[bool]:
        """Cached bypass verdict for the batcher (None -> probe fallback)."""
        hit = self.table.lookup("ingest", "bypass", dtype, self.scheme,
                                int(length), int(flush_chunks))
        if hit is None or hit.get("bypass") is None:
            return None
        return bool(hit["bypass"])

    def ingest_flush_chunks(self, default: int) -> int:
        hit = self.table.lookup("ingest", "bypass", self.dtype, self.scheme,
                                self.chunk_elems(1 << 16), int(default))
        if hit is None or hit.get("flush_chunks") is None \
                or hit.get("bypass"):
            return int(default)
        return int(hit["flush_chunks"])

    def active_keys(self) -> dict:
        """The cache keys this server resolved (bench-header provenance)."""
        return dict(self.keys)


# --------------------------------------------------- default-table writer

def write_default_table(path: Optional[str] = None,
                        p_values=(1 << 14, 1 << 16, 1 << 18),
                        k_values=(2, 8), timer=None) -> TuningTable:
    """Sweep the standard bench/smoke shapes on *this* chip and write the
    result as a committed default table (``autotune_default.json``).

    ``p_values`` tops out at 2^18: nearest-bucket lookup extrapolates the
    winners to larger models, and interpret-mode sweeps above that are
    minutes-per-cell on a CPU host for no extra routing signal.

    Run on the CI container class whose numbers the table should describe::

        PYTHONPATH=src python -m repro.runtime.autotune --write-default
    """
    table = TuningTable()
    for p in p_values:
        for k in k_values:
            for entry in AGG_ENTRY_POINTS:
                for dt in ("float32", "bfloat16"):
                    key = make_key("agg", entry, dt, None, p, k,
                                   device=table.device)
                    if table.get(key) is None:
                        table.put(key, sweep_agg_entry(entry, p, k, dt,
                                                       timer=timer, reps=2))
    for spec in ("f32", "bf16", "topk:0.1", "int8"):
        from repro.runtime.codecs import parse_spec
        scheme, _ = parse_spec(spec)
        for p in p_values:
            key = make_key("codec", scheme, "float32", scheme, p, 0,
                           device=table.device)
            table.put(key, sweep_codec(spec, p, timer=timer, reps=2))
        # ingest verdicts: chunk lengths from 4 Ki (the probe floor) up to
        # the largest chunk candidate, per buffer dtype x wire scheme
        for length in (1 << 12, 1 << 14, 1 << 16, 1 << 17):
            for dt in ("float32", "bfloat16"):
                swept = sweep_ingest(length, dt, timer=timer, reps=2)
                for fc in FLUSH_CANDIDATES:
                    key = make_key("ingest", "bypass", dt, scheme,
                                   length, fc, device=table.device)
                    table.put(key, swept)
    out = path or default_table_path()
    table.save(out)
    return table


if __name__ == "__main__":                              # pragma: no cover
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--write-default", action="store_true",
                    help="sweep standard shapes and write the committed "
                         "default table")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.write_default:
        t = write_default_table(args.out)
        print(f"wrote {len(t.entries)} entries "
              f"({cache_key_prefix()}) -> {args.out or default_table_path()}")
