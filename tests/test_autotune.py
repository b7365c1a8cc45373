"""Pins for the per-chip kernel autotuner (runtime/autotune.py).

Four contracts, per the layer's off-is-identical discipline:

  * sweeps are deterministic given their timer — winner selection is a
    pure function of the measured numbers (pinned on an injected fake
    clock, so no real kernel timing enters the test);
  * the tuning cache round-trips losslessly, and a version or device-kind
    mismatch invalidates a file *entirely* (the loader returns None, which
    is the caller's re-sweep signal) — another chip's winners are never
    misapplied;
  * ``autotune='off'`` is bit-identical to the untuned tree: no tuner
    object exists, no cache file is ever read, and the aggregate output
    equals the direct entry-point call exactly;
  * tuned routing changes timing only: oracle and alternate-block_p
    outputs match the default configuration to <= 1e-6 across all five
    algorithms.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import autotune as at
from repro.runtime.autotune import (
    AGG_ENTRY_POINTS, CACHE_VERSION, TuningTable, bucket, device_kind,
    make_key, resolve_interpret, sweep_agg_entry, sweep_codec, sweep_ingest,
)

P, K = 4096, 4


def fake_timer(schedule=None):
    """A pure-config clock: seconds depend only on the sweep label, never
    on the callable (which is not invoked).  ``schedule`` overrides
    specific labels; everything else gets a deterministic hash-free time
    derived from the label tuple."""
    schedule = schedule or {}

    def clock(fn, label=None):
        if label in schedule:
            return schedule[label]
        # label = (entry, knob, value): larger knob values "measure" slower
        # so the smallest candidate wins by default
        _, knob, value = label
        return 1.0 if knob == "oracle" else 2.0 + (value or 0) * 1e-6
    return clock


# ------------------------------------------------------------ determinism

def test_sweep_deterministic_on_fixed_timer():
    for entry in AGG_ENTRY_POINTS:
        a = sweep_agg_entry(entry, P, K, "float32", timer=fake_timer())
        b = sweep_agg_entry(entry, P, K, "float32", timer=fake_timer())
        assert a == b
    assert sweep_codec("topk:0.1", P, timer=fake_timer()) == \
        sweep_codec("topk:0.1", P, timer=fake_timer())
    assert sweep_ingest(P, "float32", timer=fake_timer()) == \
        sweep_ingest(P, "float32", timer=fake_timer())


def test_sweep_winner_follows_the_clock():
    # oracle fastest -> routed to the oracle
    r = sweep_agg_entry("weighted_aggregate", P, K, timer=fake_timer())
    assert r["use_oracle"] and r["tuned_us"] <= r["default_us"]
    # make one Pallas candidate the fastest -> it wins and oracle is off
    fast = {("weighted_aggregate", "block_p", 1024): 0.5}
    r2 = sweep_agg_entry("weighted_aggregate", P, K,
                         timer=fake_timer(fast))
    assert not r2["use_oracle"] and r2["block_p"] == 1024
    # tuned_us is min over a candidate set including the default, so the
    # BENCH_kernels within-report gate (tuned >= default) holds structurally
    assert r2["tuned_us"] <= r2["default_us"]


def test_sweep_rejects_unknown_entry():
    with pytest.raises(ValueError):
        sweep_agg_entry("not_an_entry", P, K, timer=fake_timer())


# ------------------------------------------------------------ cache file

def test_cache_round_trip(tmp_path):
    t = TuningTable()
    key = make_key("agg", "weighted_aggregate", "float32", None, P, K)
    t.put(key, sweep_agg_entry("weighted_aggregate", P, K,
                               timer=fake_timer()))
    path = str(tmp_path / "tuning.json")
    t.save(path)
    back = TuningTable.load(path)
    assert back is not None
    assert back.entries == t.entries
    assert back.version == CACHE_VERSION
    assert back.device == device_kind()


def test_cache_version_mismatch_invalidates(tmp_path):
    t = TuningTable()
    t.put(make_key("agg", "weighted_aggregate", "float32", None, P, K),
          {"use_oracle": True, "block_p": 2048})
    path = str(tmp_path / "tuning.json")
    t.save(path)
    data = json.loads(open(path).read())
    data["version"] = CACHE_VERSION + 1
    with open(path, "w") as f:
        json.dump(data, f)
    assert TuningTable.load(path) is None   # -> caller re-sweeps


def test_cache_device_kind_mismatch_invalidates(tmp_path):
    t = TuningTable()
    t.put(make_key("agg", "weighted_aggregate", "float32", None, P, K),
          {"use_oracle": True, "block_p": 2048})
    path = str(tmp_path / "tuning.json")
    t.save(path)
    data = json.loads(open(path).read())
    data["device_kind"] = "TPU v5e"          # some other chip's winners
    with open(path, "w") as f:
        json.dump(data, f)
    assert TuningTable.load(path) is None


def test_cache_mismatch_triggers_resweep(tmp_path, monkeypatch):
    # a stale user cache must not suppress the sweep: build(mode='sweep')
    # over an invalid file starts from an empty table and re-measures
    path = str(tmp_path / "tuning.json")
    with open(path, "w") as f:
        json.dump({"version": CACHE_VERSION + 1, "device_kind": "other",
                   "entries": {"bogus": {}}}, f)
    monkeypatch.setattr(at, "_DEFAULT_TABLE",
                        str(tmp_path / "no_default.json"))
    calls = []

    def counting_sweep(entry, p, k, dtype="float32", **kw):
        calls.append(entry)
        return {"use_oracle": True, "block_p": 2048}

    monkeypatch.setattr(at, "sweep_agg_entry", counting_sweep)
    monkeypatch.setattr(at, "sweep_codec",
                        lambda *a, **kw: {"chunk_elems": 1 << 16})
    monkeypatch.setattr(at, "sweep_ingest",
                        lambda *a, **kw: {"bypass": True,
                                          "flush_chunks": 16})
    tuning = at.ServerTuning.build(
        "sweep", p=P, k=K, dtype="float32", scheme="f32",
        algorithm="seafl", chunk_elems=1 << 16, flush_chunks=16,
        cache_path=path)
    assert calls, "invalid cache did not trigger a re-sweep"
    assert "bogus" not in tuning.table.entries
    # and the re-swept winners were persisted with the current schema
    saved = TuningTable.load(path)
    assert saved is not None and saved.version == CACHE_VERSION


def test_nearest_bucket_lookup():
    t = TuningTable()
    key = make_key("agg", "weighted_aggregate", "float32", None,
                   1 << 16, 8)
    t.put(key, {"use_oracle": True, "block_p": 4096})
    # a neighbouring shape with no exact entry resolves to the nearest
    # swept bucket of the same (entry, device, dtype, scheme)
    hit = t.lookup("agg", "weighted_aggregate", "float32", None,
                   1 << 18, 4)
    assert hit is not None and hit["block_p"] == 4096
    # a different dtype never matches
    assert t.lookup("agg", "weighted_aggregate", "bfloat16", None,
                    1 << 16, 8) is None


def test_bucket_and_interpret_resolution():
    assert bucket(1) == 0 and bucket(2) == 1 and bucket(65536) == 16
    assert bucket(65537) == 17
    assert resolve_interpret("cpu") is True
    assert resolve_interpret("gpu") is True
    assert resolve_interpret("tpu") is False


# --------------------------------------------------- off-mode bit identity

def _tiny_server(**kw):
    from repro.core.server import FLConfig, SeaflServer
    params = {"w": jnp.zeros((32, 32), jnp.float32),
              "b": jnp.zeros((32,), jnp.float32)}
    cfg = FLConfig(algorithm=kw.pop("algorithm", "seafl"), n_clients=4,
                   concurrency=2, buffer_size=2, **kw)
    return SeaflServer(cfg, params, {i: 10 for i in range(4)}), params


def test_autotune_defaults_off():
    from repro.core.server import FLConfig
    assert FLConfig().autotune == "off"


def test_off_mode_never_touches_the_cache(monkeypatch):
    # autotune='off' must not even *read* tuning state: poison both the
    # loader and the sweeps — construction and aggregation must not care
    def boom(*a, **kw):
        raise AssertionError("autotune='off' touched the tuning table")

    monkeypatch.setattr(at, "load_table", boom)
    monkeypatch.setattr(at.TuningTable, "load", boom)
    monkeypatch.setattr(at, "sweep_agg_entry", boom)
    server, _ = _tiny_server()
    assert server.tuning is None


def test_off_mode_bit_identical_to_direct_call():
    from repro.kernels.seafl_agg.ops import seafl_aggregate_flat_from_params
    server, _ = _tiny_server()
    rng = np.random.default_rng(3)
    pvec = server.packer.size
    for i in range(2):
        upd = server._flat + 0.01 * jnp.asarray(
            rng.normal(size=pvec).astype(np.float32))
        server.active[i] = 0
        server.on_update(i, server.packer.unpack(upd), n_epochs=1)
    got = np.asarray(server._flat)
    # replay the exact aggregation with the raw default entry point
    server2, _ = _tiny_server()
    stacked = []
    rng = np.random.default_rng(3)
    for i in range(2):
        upd = server2._flat + 0.01 * jnp.asarray(
            rng.normal(size=pvec).astype(np.float32))
        stacked.append(upd)
    h = server2.cfg.hyper()
    want, _w = seafl_aggregate_flat_from_params(
        server2._flat, jnp.stack(stacked), jnp.asarray([10., 10.]),
        jnp.zeros(2), h.alpha, h.mu, h.beta, h.theta,
        use_importance=h.use_importance, use_staleness=h.use_staleness)
    assert np.array_equal(got, np.asarray(want)), \
        "autotune='off' aggregation is not bit-identical to the raw entry point"


# ------------------------------------------------- tuned-vs-default parity

def test_tuned_value_parity_all_algorithms():
    from repro.kernels.seafl_agg import ops
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=P).astype(np.float32))
    stacked = jnp.asarray(rng.normal(size=(K, P)).astype(np.float32))
    deltas = stacked - g[None]
    sizes = jnp.asarray([10., 20., 30., 40.])
    stale = jnp.asarray([0., 1., 2., 3.])
    plans = ({"use_oracle": True},
             {"use_oracle": False, "block_p": 512},
             {"use_oracle": False, "block_p": 8192})

    def check(name, fn, *args, **kw):
        base = fn(*args, **kw)
        for plan in plans:
            out = fn(*args, tuned=plan, **kw)
            for b, o in zip(jax.tree_util.tree_leaves(base),
                            jax.tree_util.tree_leaves(out)):
                err = float(jnp.max(jnp.abs(b - o))) if b.size else 0.0
                assert err <= 1e-6, (name, plan, err)

    check("seafl", ops.seafl_aggregate_flat, g, stacked, deltas, sizes,
          stale, 3.0, 1.0, 10.0, 0.8)
    # seafl2 shares the entry point with importance/staleness toggles off
    check("seafl2", ops.seafl_aggregate_flat_from_params, g, stacked,
          sizes, stale, 3.0, 1.0, 10.0, 0.8, use_importance=False,
          use_staleness=False)
    check("seafl_from_params", ops.seafl_aggregate_flat_from_params, g,
          stacked, sizes, stale, 3.0, 1.0, 10.0, 0.8)
    check("fedavg", ops.fedavg_aggregate_flat, g, stacked, sizes)
    check("fedbuff", ops.fedbuff_aggregate_flat, g, stacked, 0.5)
    check("fedasync", ops.fedasync_aggregate_flat, g, stacked[0], 2.0,
          0.6, 0.5)


def test_tuned_server_matches_off_server():
    # end to end: a 'cache' server running on a table that routes every
    # entry to the oracle must converge to the same model within 1e-6
    import os
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        cache = os.path.join(td, "tuning.json")
        t = TuningTable()
        for entry in AGG_ENTRY_POINTS:
            for k in (1, 2):
                t.put(make_key("agg", entry, "float32", None, 1088, k),
                      {"use_oracle": True, "block_p": 2048})
        t.save(cache)
        import unittest.mock as mock
        with mock.patch.object(at, "user_cache_path", lambda: cache):
            on, _ = _tiny_server(autotune="cache")
        assert on.tuning is not None
        assert on.tuning.agg_plan("weighted_aggregate") is not None
        off, _ = _tiny_server()
        rng_a, rng_b = (np.random.default_rng(7), np.random.default_rng(7))
        pvec = off.packer.size
        for srv, rng in ((on, rng_a), (off, rng_b)):
            for i in range(2):
                upd = srv._flat + 0.01 * jnp.asarray(
                    rng.normal(size=pvec).astype(np.float32))
                srv.active[i] = 0
                srv.on_update(i, srv.packer.unpack(upd), n_epochs=1)
        err = float(jnp.max(jnp.abs(on._flat - off._flat)))
        assert err <= 1e-6, err


# ------------------------------------------------------- ingest verdicts

def test_batcher_tuned_verdict_skips_probe(monkeypatch):
    from repro.core.buffer import Update, UpdateBuffer
    from repro.runtime import transport
    from repro.runtime.transport import IngestBatcher

    def no_probe(*a, **kw):
        raise AssertionError("cached verdict should have answered")

    monkeypatch.setattr(transport, "_coalescing_loses", no_probe)
    buf = UpdateBuffer(2, 1 << 13)
    b = IngestBatcher(buf, flush_chunks=4, auto_bypass=True,
                      tuned_verdict=lambda length, dtype, flush: True)
    buf.reserve(Update(0, 1, 0, 1))
    b.enqueue(0, 0, jnp.ones((1 << 12,), jnp.float32))
    assert b._bypass is True and b.chunks_bypassed == 1 and b.pending == 0


def test_batcher_cache_miss_falls_back_to_probe(monkeypatch):
    from repro.core.buffer import Update, UpdateBuffer
    from repro.runtime import transport
    from repro.runtime.transport import IngestBatcher

    probed = []
    monkeypatch.setattr(transport, "_coalescing_loses",
                        lambda *a, **kw: probed.append(a) or False)
    buf = UpdateBuffer(2, 1 << 13)
    b = IngestBatcher(buf, flush_chunks=4, auto_bypass=True,
                      tuned_verdict=lambda length, dtype, flush: None)
    buf.reserve(Update(0, 1, 0, 1))
    b.enqueue(0, 0, jnp.ones((1 << 12,), jnp.float32))
    assert probed, "tuned miss (None) must fall back to the probe"
    assert b._bypass is False and b.pending == 1
