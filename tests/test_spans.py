"""Program spans on the profiler's clock.

Every ``Telemetry.span(name)`` is a ``jax.profiler.TraceAnnotation`` named
``seafl.<name>``, registry on or off.  These tests capture a profile on the
CPU and read the host events back from its ``.xplane.pb``: the span tree of
an ingest, an aggregation and a dispatch, of one simulator round, and of a
``launch/train.py --profile`` run; that spans open once per upload, not per
chunk; that a disabled registry records nothing and changes no byte; and
that the chunk join is the eager ``concatenate`` program under a name of
its own.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.server import FLConfig, SeaflServer
from repro.experiment import ExperimentConfig, run_experiment
from repro.runtime.simulator import SimConfig
from repro.runtime.telemetry import NULL, Telemetry
from repro.runtime import codecs


def _host_spans(log_dir) -> list:
    """``(name, start_ns, end_ns)`` of every ``seafl.*`` host event."""
    found = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1, found
    data = jax.profiler.ProfileData.from_file(found[0])
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("seafl.")]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(child, parents) -> bool:
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def _server(p: int = 40, chunk_elems: int = 8, **kw) -> SeaflServer:
    params = {"w": np.linspace(-1, 1, p).astype(np.float32)}
    cfg = FLConfig(algorithm="seafl", n_clients=4, concurrency=4,
                   buffer_size=2, chunk_elems=chunk_elems, **kw)
    return SeaflServer(cfg, params, {i: 10 + i for i in range(4)})


def _upload_until_aggregation(server: SeaflServer):
    """Client-side encode, then ingest, until one upload aggregates."""
    for cid in sorted(server.active) or server.start():
        w = {"w": server.params["w"] + 0.01 * (cid + 1)}
        ev = server.ingest_payload(server.encode_update(cid, w, 1))
        if ev is not None:
            return ev
    raise AssertionError("no aggregation")


def tiny_experiment(seed=3, **flkw):
    fl = FLConfig(algorithm="seafl", n_clients=6, concurrency=3,
                  buffer_size=2, staleness_limit=4, local_epochs=2,
                  local_lr=0.05, batch_size=16, seed=seed, **flkw)
    sim = SimConfig(speed_model="pareto", base_epoch_time=1.0, seed=seed)
    return ExperimentConfig(dataset="tiny", n_train=240, n_test=60,
                            model="mlp", fl=fl, sim=sim, seed=seed)


def test_server_spans_nest_in_the_profile(tmp_path):
    server = _server(dispatch_compression="topk:0.5")
    _upload_until_aggregation(server)           # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        ev = _upload_until_aggregation(server)
        for cid in ev.dispatch:
            server.deliver_dispatch(cid, server.encode_dispatch(cid))
        jax.block_until_ready(server.global_flat)
    spans = _host_spans(tmp_path)
    ingests = _named(spans, "seafl.ingest")
    assert len(ingests) == 2
    for child in ("seafl.ingest.decode", "seafl.ingest.write",
                  "seafl.ingest.commit", "seafl.server.aggregate"):
        got = _named(spans, child)
        assert got, child
        assert all(_inside(s, ingests) for s in got), child
    assert len(_named(spans, "seafl.server.aggregate")) == 1
    encodes = _named(spans, "seafl.dispatch.encode")
    assert len(encodes) == len(ev.dispatch) > 0
    assert not any(_inside(s, ingests) for s in encodes)
    assert len(_named(spans, "seafl.server.encode_update")) == 2


def test_spans_open_per_upload_not_per_chunk(tmp_path):
    server = _server(p=64, chunk_elems=4)       # 16 chunks an upload
    _upload_until_aggregation(server)
    with jax.profiler.trace(str(tmp_path)):
        _upload_until_aggregation(server)
    spans = _host_spans(tmp_path)
    for name in ("seafl.ingest", "seafl.ingest.decode", "seafl.ingest.write",
                 "seafl.ingest.commit", "seafl.server.encode_update"):
        assert len(_named(spans, name)) == 2, name


def test_simulator_round_spans_nest_in_the_profile(tmp_path):
    run_experiment(tiny_experiment(), max_rounds=1)   # compile first
    with jax.profiler.trace(str(tmp_path)):
        sim, hist = run_experiment(tiny_experiment(), max_rounds=1)
    assert len(hist) == 1
    spans = _host_spans(tmp_path)
    uploads = _named(spans, "seafl.sim.upload")
    delivers = _named(spans, "seafl.sim.deliver")
    assert uploads and delivers
    for child in ("seafl.client.train", "seafl.server.dispatch_model",
                  "seafl.server.encode_update"):
        got = _named(spans, child)
        assert len(got) == len(uploads), child
        assert all(_inside(s, uploads) for s in got), child
    trains = _named(spans, "seafl.client.train")
    for child in ("seafl.client.batches", "seafl.client.epoch"):
        assert all(_inside(s, trains) for s in _named(spans, child)), child
    # two epochs per upload: one batches and one epoch span each
    n_epochs = 2 * len(uploads)
    assert len(_named(spans, "seafl.client.batches")) == n_epochs
    assert len(_named(spans, "seafl.client.epoch")) == n_epochs
    for child in ("seafl.ingest", "seafl.server.aggregate", "seafl.eval"):
        got = _named(spans, child)
        assert got, child
        assert all(_inside(s, delivers) for s in got), child
    assert _named(spans, "seafl.sim.arrive")


def test_disabled_registry_spans_record_nothing_and_change_nothing(tmp_path):
    span = Telemetry(enabled=False).span("x")
    assert isinstance(span, jax.profiler.TraceAnnotation)
    sim_a, h_a = run_experiment(tiny_experiment(), max_rounds=3)
    with jax.profiler.trace(str(tmp_path)):
        sim_b, h_b = run_experiment(tiny_experiment(), max_rounds=3)
    assert _named(_host_spans(tmp_path), "seafl.ingest")
    assert h_a == h_b
    np.testing.assert_array_equal(np.asarray(sim_a.server.global_flat),
                                  np.asarray(sim_b.server.global_flat))
    assert sim_a.server.bytes_uploaded == sim_b.server.bytes_uploaded
    assert sim_a.server.bytes_downloaded == sim_b.server.bytes_downloaded
    assert sim_a._rng.bit_generator.state == sim_b._rng.bit_generator.state
    assert (sim_a.server._rng.bit_generator.state
            == sim_b.server._rng.bit_generator.state)
    for tel in (sim_b.server.tel, NULL):
        snap = tel.snapshot()
        assert snap["spans"] == 0 and snap["histograms"] == {}
        assert tel._wall_stack == []


def test_enabled_registry_keeps_its_records_beside_the_annotation(tmp_path):
    tel = Telemetry(enabled=True)
    with jax.profiler.trace(str(tmp_path)):
        with tel.span("outer"):
            with tel.span("inner"):
                pass
    names = [s[0] for s in _host_spans(tmp_path)]
    assert "seafl.outer" in names and "seafl.inner" in names
    snap = tel.snapshot()
    assert snap["spans"] == 2
    assert snap["histograms"]["outer_ms"]["count"] == 1
    assert snap["histograms"]["inner_ms"]["count"] == 1


@pytest.mark.parametrize("n", [2, 16])
def test_ingest_join_is_the_eager_concatenate_program(n):
    """The program eager ``jnp.concatenate`` dispatches, renamed: the same
    HLO below the module's name."""
    from jax._src import dispatch

    vals = [jnp.full((8,), float(i), jnp.float32) for i in range(n)]
    eager = dispatch.xla_primitive_callable(jax.lax.concatenate_p,
                                            dimension=0)
    want = eager.lower(*vals).as_text().splitlines()
    got = codecs._join_chunks.lower(*vals).as_text().splitlines()
    assert want[0].startswith("module @jit_concatenate ")
    assert got[0].startswith("module @jit__join_chunks ")
    assert got[1:] == want[1:]


@pytest.mark.parametrize("n", [1, 2, 16, 17, 300])
def test_ingest_join_tree_matches_jnp_concatenate(n, monkeypatch):
    """``decode_concat`` joins as ``jnp.concatenate`` does, and a window
    left alone in its group of 16 is passed on, not run through a join."""
    vals = [jnp.arange(i, i + 5, dtype=jnp.float32) for i in range(n)]
    chunks = [codecs.Chunk(i, 5 * i, 5, v, 0) for i, v in enumerate(vals)]
    calls, join = [], codecs._join_chunks

    def counted(*a):
        calls.append(len(a))
        return join(*a)

    monkeypatch.setattr(codecs, "_join_chunks", counted)
    got = codecs.decode_concat(chunks, codecs.make_wire_format(None))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.concatenate(vals)))
    assert all(k > 1 for k in calls)
    # 300 windows: 19 joins, then 2, then 1
    assert len(calls) == {1: 0, 2: 1, 16: 1, 17: 2, 300: 22}[n]


def test_train_profile_flag_writes_a_trace_with_the_spans(tmp_path,
                                                         monkeypatch):
    from repro.launch import train

    monkeypatch.setattr(train, "enable_compile_cache", lambda: "")
    prof = tmp_path / "prof"
    train.main(["--arch", "whisper-tiny", "--rounds", "1", "--clients", "2",
                "--concurrency", "2", "--buffer", "1", "--seq-len", "16",
                "--profile", str(prof)])
    names = {s[0] for s in _host_spans(prof)}
    assert {"seafl.sim.upload", "seafl.sim.deliver", "seafl.ingest",
            "seafl.server.aggregate", "seafl.client.epoch"} <= names
    assert glob.glob(str(prof / "**" / "perfetto_trace.json.gz"),
                     recursive=True)
