"""Unified telemetry layer: off-mode bit-identity, clocks, exporters.

The contract under test (runtime/telemetry.py + its threading through the
stack): telemetry **off is bit-identical** to the pre-telemetry code — same
RNG streams, wire bytes, aggregation outputs, history keys, state_dict
shape — and telemetry **on** changes nothing observable either, only adds
a `telemetry` key to history/state_dict and fills the registry.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.server import FLConfig, SeaflServer
from repro.experiment import ExperimentConfig, run_experiment
from repro.runtime.simulator import SimConfig
from repro.runtime.telemetry import (
    MAX_HIST_VALUES,
    SIM_PID,
    WALL_PID,
    NULL,
    Telemetry,
    of,
)


# ---------------------------------------------------------------- helpers

def tiny_cfg(telemetry=False, seed=3, **flkw):
    fl = FLConfig(algorithm="seafl", n_clients=12, concurrency=6,
                  buffer_size=3, staleness_limit=4, local_epochs=2,
                  local_lr=0.05, batch_size=16, seed=seed,
                  telemetry=telemetry, **flkw)
    sim = SimConfig(speed_model="pareto", base_epoch_time=1.0, seed=seed)
    return ExperimentConfig(dataset="tiny", n_train=600, n_test=120,
                            model="mlp", fl=fl, sim=sim, seed=seed)


def mlp_server(telemetry=False, **kw):
    params = {"w": np.zeros(8, np.float32)}
    cfg = FLConfig(algorithm="seafl", n_clients=4, concurrency=2,
                   buffer_size=2, telemetry=telemetry, **kw)
    return SeaflServer(cfg, params, {i: 10 for i in range(4)})


# ----------------------------------------------------------- registry unit

def test_disabled_records_nothing():
    tel = Telemetry(enabled=False)
    tel.counter("c")
    tel.gauge("g", 1.0)
    tel.histogram("h", 2.0)
    tel.sim_span("s", 0.0, 1.0, track="client0")
    tel.sim_instant("i", 0.5, track="client0")
    with tel.span("w"):
        pass
    snap = tel.snapshot()
    assert snap["counters"] == {}
    assert snap["gauges"] == {}
    assert snap["histograms"] == {}
    assert snap["spans"] == 0


def test_null_singleton_and_of():
    assert of(None) is NULL
    t = Telemetry(enabled=True)
    assert of(t) is t
    assert not NULL.enabled


def test_counter_gauge_histogram_and_label_folding():
    tel = Telemetry(enabled=True)
    tel.counter("hits")
    tel.counter("hits", 2)
    tel.counter("band", band=1)
    tel.counter("band", band=1)
    tel.counter("band", band=2)
    tel.gauge("fill", 3)
    tel.gauge("fill", 5)
    tel.histogram_many("st", [0.0, 1.0, 2.0])
    snap = tel.snapshot()
    assert snap["counters"]["hits"] == 3
    assert snap["counters"]["band[band=1]"] == 2
    assert snap["counters"]["band[band=2]"] == 1
    assert snap["gauges"]["fill"] == 5.0        # gauges keep the last value
    h = snap["histograms"]["st"]
    assert h["count"] == 3 and h["min"] == 0.0 and h["max"] == 2.0
    assert h["mean"] == pytest.approx(1.0)
    assert h["values"] == [0.0, 1.0, 2.0]
    assert snap["histograms"] == tel.snapshot()["histograms"]  # idempotent


def test_wall_span_nesting_depth_and_ms_histogram():
    tel = Telemetry(enabled=True)
    with tel.span("outer", k=1):
        with tel.span("inner"):
            pass
    evs = tel.chrome_trace()["traceEvents"]
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert spans["inner"]["args"]["depth"] == 1   # closed inside outer
    assert spans["outer"]["args"]["depth"] == 0
    assert spans["outer"]["args"]["k"] == 1
    assert spans["outer"]["pid"] == WALL_PID
    # inner is contained in outer on the wall timeline
    o, i = spans["outer"], spans["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6
    # every wall span doubles as a duration histogram sample
    assert tel.snapshot()["histograms"]["outer_ms"]["count"] == 1
    assert tel.snapshot()["histograms"]["inner_ms"]["count"] == 1


def test_sim_spans_use_explicit_clock_and_tracks():
    tel = Telemetry(enabled=True)
    tel.sim_span("train", 2.0, 5.0, track="client7", epochs=2)
    tel.sim_instant("crash", 6.0, track="client7")
    tel.sim_span("agg", 5.0, 5.5, track="server")
    evs = tel.chrome_trace()["traceEvents"]
    tr = next(e for e in evs if e.get("name") == "train")
    assert tr["pid"] == SIM_PID
    assert tr["ts"] == pytest.approx(2.0e6)       # seconds -> µs
    assert tr["dur"] == pytest.approx(3.0e6)
    assert tr["args"]["epochs"] == 2
    cr = next(e for e in evs if e.get("name") == "crash")
    assert cr["ph"] == "i" and cr["ts"] == pytest.approx(6.0e6)
    assert cr["tid"] == tr["tid"]                 # same client track
    ag = next(e for e in evs if e.get("name") == "agg")
    assert ag["tid"] == 1                         # "server" is tid 1
    assert ag["tid"] != tr["tid"]


def test_histogram_cap_overflows_to_counter():
    tel = Telemetry(enabled=True)
    for _ in range(MAX_HIST_VALUES + 5):
        tel.histogram("h", 1.0)
    snap = tel.snapshot(compact=True)
    assert snap["histograms"]["h"]["count"] == MAX_HIST_VALUES
    assert snap["counters"]["telemetry.hist_overflow"] == 5


def test_snapshot_roundtrip_and_compact():
    tel = Telemetry(enabled=True)
    tel.counter("c", 2)
    tel.gauge("g", 7.0)
    tel.histogram_many("h", [1.0, 3.0])
    full = tel.snapshot()
    compact = tel.snapshot(compact=True)
    assert "values" not in compact["histograms"]["h"]
    assert compact["histograms"]["h"]["mean"] == pytest.approx(2.0)
    tel2 = Telemetry(enabled=True)
    tel2.load_snapshot(full)
    assert tel2.snapshot()["counters"] == full["counters"]
    assert tel2.snapshot()["gauges"] == full["gauges"]
    assert tel2.snapshot()["histograms"]["h"]["values"] == [1.0, 3.0]
    json.dumps(full)   # everything JSON-able as exported


def test_compact_snapshot_is_bounded_summary_stats():
    """Compact histograms carry O(1) summary stats (count/mean/p50/p95/
    max), never the raw value list — the run-monitor ingests one of these
    per round, so its size must not grow with observation count."""
    tel = Telemetry(enabled=True)
    tel.histogram_many("h", [float(v) for v in range(1, 101)])
    h = tel.snapshot(compact=True)["histograms"]["h"]
    assert set(h) == {"count", "sum", "mean", "min", "max", "p50", "p95"}
    assert h["count"] == 100
    assert h["p50"] == 51.0 and h["p95"] == 96.0
    assert h["min"] == 1.0 and h["max"] == 100.0
    # size is pinned: 100 obs and 10_000 obs serialize identically large
    small = len(json.dumps(h))
    tel.histogram_many("h", [50.0] * 9_900)
    big = len(json.dumps(tel.snapshot(compact=True)["histograms"]["h"]))
    assert big <= small + 8      # digits may widen; the shape may not
    # empty histograms keep the schema with null stats
    tel._hists["empty"] = []
    e = tel.snapshot(compact=True)["histograms"]["empty"]
    assert e["count"] == 0 and e["p50"] is None and e["p95"] is None


def test_chrome_trace_schema():
    tel = Telemetry(enabled=True)
    tel.sim_span("train", 0.0, 1.0, track="client0")
    with tel.span("agg"):
        pass
    trace = tel.chrome_trace()
    assert trace["displayTimeUnit"] == "ms"
    evs = trace["traceEvents"]
    # both clock-domain processes are named
    procs = {e["pid"]: e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs == {SIM_PID: "simulated time", WALL_PID: "server wall time"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in evs
               if e["ph"] == "M" and e["name"] == "thread_name"}
    assert threads[(SIM_PID, 1)] == "server"
    assert "client0" in threads.values()
    for e in evs:
        assert e["ph"] in ("M", "X", "i")
        if e["ph"] == "X":
            assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
            assert e["dur"] >= 0.0
        if e["ph"] == "i":
            assert e["s"] == "t"
    json.dumps(trace)
    lines = list(tel.iter_jsonl_events())
    assert len(lines) == sum(1 for e in evs if e["ph"] in ("X", "i"))
    assert all(isinstance(json.loads(ln), dict) for ln in lines)


# --------------------------------------------- off-mode bit-identity pin

def test_off_mode_bit_identical_to_on_mode():
    """The load-bearing pin: enabling telemetry changes no simulated time,
    no RNG stream, no wire bytes, no aggregation output, and only ADDS the
    `telemetry` history key."""
    sim_off, h_off = run_experiment(
        tiny_cfg(False, dispatch_compression="topk:0.1"), max_rounds=6)
    sim_on, h_on = run_experiment(
        tiny_cfg(True, dispatch_compression="topk:0.1"), max_rounds=6)
    assert len(h_off) == len(h_on)
    for a, b in zip(h_off, h_on):
        assert a["time"] == b["time"]
        assert set(b) - set(a) == {"telemetry"}
        for k in a:
            if isinstance(a[k], float):
                assert a[k] == b[k], k
    np.testing.assert_array_equal(np.asarray(sim_off.server.global_flat),
                                  np.asarray(sim_on.server.global_flat))
    assert sim_off.server.bytes_uploaded == sim_on.server.bytes_uploaded
    assert sim_off.server.bytes_downloaded == sim_on.server.bytes_downloaded
    assert sim_off._rng.bit_generator.state == sim_on._rng.bit_generator.state


def test_off_mode_state_dict_has_no_telemetry_key():
    s_off = mlp_server(False)
    assert "telemetry" not in s_off.state_dict()
    s_on = mlp_server(True)
    assert "telemetry" in s_on.state_dict()


def test_off_mode_history_has_no_telemetry_key():
    _, hist = run_experiment(tiny_cfg(False), max_rounds=3)
    assert all("telemetry" not in h for h in hist)


# ------------------------------------------------- stack integration

def test_staleness_histogram_matches_history():
    sim, hist = run_experiment(tiny_cfg(True), max_rounds=8)
    snap = sim.server.tel.snapshot()
    st = snap["histograms"]["agg.staleness"]
    assert snap["counters"]["agg.count"] == len(hist)
    assert st["max"] == max(h["staleness_max"] for h in hist)
    # per-round compact snapshots carry the cumulative running max
    running = 0.0
    for h in hist:
        running = max(running, h["staleness_max"])
        assert h["telemetry"]["histograms"]["agg.staleness"]["max"] == running
    # Eq.(5)-(8) normalized weights sum to 1 per aggregation
    w = snap["histograms"]["agg.weight"]
    assert w["sum"] == pytest.approx(len(hist), rel=1e-5)
    assert w["count"] == st["count"]      # one weight per buffered update


def test_sim_span_clock_chain_dispatch_train_upload():
    """Per client, the simulated lifecycle is gapless: dispatch ends when
    train starts (payload arrival) and train ends when upload starts."""
    sim, _ = run_experiment(tiny_cfg(True), max_rounds=6)
    evs = sim.server.tel.chrome_trace()["traceEvents"]
    by_tid = {}
    for e in evs:
        if e["ph"] == "X" and e["pid"] == SIM_PID:
            by_tid.setdefault(e["tid"], []).append(e)
    assert by_tid, "no simulated spans recorded"
    checked = 0
    for tid, spans in by_tid.items():
        spans.sort(key=lambda e: e["ts"])
        ends = {e["name"]: [] for e in spans}
        for e in spans:
            ends[e["name"]].append((e["ts"], e["ts"] + e["dur"]))
        for t0, _ in ends.get("train", []):
            assert any(abs(e1 - t0) < 1.0 for _, e1 in ends["dispatch"])
            checked += 1
        for t0, _ in ends.get("upload", []):
            assert any(abs(e1 - t0) < 1.0 for _, e1 in ends["train"])
            checked += 1
    assert checked > 0


def test_dispatch_and_ingest_counters_match_server_stats():
    sim, _ = run_experiment(
        tiny_cfg(True, dispatch_compression="topk:0.1"), max_rounds=6)
    srv = sim.server
    c = srv.tel.snapshot()["counters"]
    disp = srv.dispatch
    assert c["dispatch.full"] == disp.full_dispatches
    assert c["dispatch.delta"] == disp.delta_dispatches
    assert c.get("dispatch.cache_hit", 0) == disp.cache_hits
    assert c.get("dispatch.cache_miss", 0) == disp.cache_misses
    h = srv.tel.snapshot()["histograms"]
    assert h["ingest.upload_bytes"]["sum"] == srv.bytes_uploaded
    assert h["dispatch.payload_bytes"]["sum"] == srv.bytes_downloaded


def test_checkpoint_roundtrip_restores_metrics():
    sim, _ = run_experiment(tiny_cfg(True), max_rounds=4)
    srv = sim.server
    state = srv.state_dict()
    trees = srv.checkpoint_trees()
    before = srv.tel.snapshot()
    params = srv.packer.unpack(srv._flat)
    fresh = SeaflServer(srv.cfg, params, dict(srv.client_sizes))
    fresh.load_state(state, trees)
    after = fresh.tel.snapshot()
    assert after["counters"] == before["counters"]
    assert after["gauges"] == before["gauges"]
    assert after["histograms"] == before["histograms"]


def test_target_not_reached_gauge():
    sim, _ = run_experiment(tiny_cfg(True), max_rounds=3)
    assert sim.time_to_accuracy(2.0) is None      # acc 2.0 is unreachable
    g = sim.server.tel.snapshot()["gauges"]
    assert g["sim.target_not_reached[metric=time,target=2.0]"] == 1.0
    assert sim.bytes_to_accuracy(2.0) is None
    assert any(k.startswith("sim.target_not_reached[direction=")
               for k in sim.server.tel.snapshot()["gauges"])


def test_policy_band_telemetry():
    from repro.runtime.policy import RatePolicy
    pol = RatePolicy(mode="drift")
    tel = Telemetry(enabled=True)
    assert pol.ratio_for(0.1, telemetry=tel) == pol.ratios[0]
    assert pol.ratio_for(5.0, telemetry=tel) == pol.ratios[-1]
    snap = tel.snapshot()
    assert snap["counters"]["policy.band[band=0]"] == 1
    assert snap["counters"]["policy.band[band=2]"] == 1
    assert snap["gauges"]["policy.ratio"] == pol.ratios[-1]
    assert snap["histograms"]["policy.drift_x_hist"]["count"] == 2


# ------------------------------------------------------- train.py records

def test_round_record_and_formatter_agree():
    from repro.launch.train import format_round, round_record
    h = {"round": 4, "time": 12.5, "acc": -3.25, "staleness_max": 2.0}
    rec = round_record(h, wall=7.0)
    assert rec["event"] == "round"
    assert rec["heldout_ce"] == pytest.approx(3.25)
    line = format_round(rec)
    assert "round   4" in line and "3.2500" in line and "stale_max=2" in line
    json.dumps(rec)


def test_jsonl_log_writes_and_null_path_noop(tmp_path):
    from repro.launch.train import JsonlLog
    log = JsonlLog(str(tmp_path / "run.jsonl"))
    log.write({"event": "round", "round": 1})
    log.write({"event": "summary"})
    log.close()
    lines = [json.loads(ln)
             for ln in (tmp_path / "run.jsonl").read_text().splitlines()]
    assert [ln["event"] for ln in lines] == ["round", "summary"]
    null = JsonlLog(None)
    null.write({"event": "round"})      # must not raise
    null.close()


# ------------------------------------------------------------ slow e2e

@pytest.mark.slow
def test_train_cli_emits_trace_and_jsonl(tmp_path):
    """End-to-end acceptance: the training driver with --telemetry writes a
    Perfetto-loadable trace with per-client simulated spans, a metrics
    snapshot whose staleness histogram is self-consistent, and a JSONL run
    log whose final record is the summary."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    trace_p = tmp_path / "trace.json"
    metrics_p = tmp_path / "metrics.json"
    jsonl_p = tmp_path / "run.jsonl"
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "internvl2-1b",
         "--rounds", "3", "--clients", "4", "--concurrency", "2",
         "--buffer", "2", "--dispatch-compression", "topk:0.1",
         "--telemetry", "--trace", str(trace_p), "--metrics", str(metrics_p),
         "--log-jsonl", str(jsonl_p)],
        env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-2000:]
    trace = json.loads(trace_p.read_text())
    evs = trace["traceEvents"]
    client_tids = {e["tid"] for e in evs
                   if e["ph"] == "M" and e["name"] == "thread_name"
                   and e["pid"] == SIM_PID
                   and e["args"]["name"].startswith("client")}
    assert len(client_tids) >= 2
    sim_spans = [e for e in evs if e["ph"] == "X" and e["pid"] == SIM_PID]
    assert {e["name"] for e in sim_spans} >= {"dispatch", "train", "upload"}
    metrics = json.loads(metrics_p.read_text())
    st = metrics["histograms"]["agg.staleness"]
    assert st["count"] >= metrics["counters"]["agg.count"]
    assert st["min"] >= 0.0 and st["max"] <= 1e9
    lines = [json.loads(ln) for ln in jsonl_p.read_text().splitlines()]
    assert lines[-1]["event"] == "summary"
    assert all(ln["event"] == "round" for ln in lines[:-1])
    assert lines[-1]["uplink_bytes"] > 0
