"""``spans.py``: the readers of the program's ``seafl.*`` spans on
hand-built trace events, with values computed by hand; that widening the
loader's prefix leaves the recorded v5e probe's events as they were; and
one traced run of each cell on the CPU at a small size, whose spans the
readers find."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

import _tiny  # noqa: E402
from benchmarks.chip import run, spans, trace as tr  # noqa: E402

PROBE = Path(__file__).resolve().parent / "data" / "v5e_agg_probe.xplane.pb"


class _Run:
    def __init__(self, trace, samples=None):
        self.trace, self.peaks = trace, None
        self.c = {} if samples is None else {"samples": samples}


def _trace():
    """A window of 100 us.  Host spans in ns; the device is busy over
    [0, 4], [20, 30] and [60, 95] us."""
    span_list = [
        ("bench.window", 0, 100_000),
        ("bench.round", -10_000, 15_000),           # clipped to [0, 5]
        ("seafl.sim.upload", 10_000, 30_000),       # [10, 40]
        ("seafl.client.batches", 15_000, 5_000),    # [15, 20]
        ("seafl.client.epoch", 20_000, 10_000),     # [20, 30]
        ("seafl.client.batches", 32_000, 3_000),    # [32, 35]
        ("seafl.sim.deliver", 50_000, 20_000),      # [50, 70]
        ("seafl.ingest", 55_000, 10_000),           # [55, 65]
        ("seafl.ingest.decode", 56_000, 4_000),     # [56, 60]
        ("seafl.server.aggregate", 61_000, 2_000),  # [61, 63]
        ("seafl.server.aggregate", 66_000, 2_000),  # [66, 68]
        ("seafl.sim.arrive", 90_000, 30_000),       # clipped to [90, 100]
        ("seafl.ingest.decode", 110_000, 5_000),    # after the window
        ("seafl.server.aggregate", 120_000, 1_000),  # after the window
    ]
    ops = [(0, "fusion", 0, 4_000), (0, "fusion", 20_000, 10_000),
           (0, "while", 60_000, 35_000)]
    return tr.Trace({"modules": [], "ops": ops, "spans": span_list})


def test_self_time_goes_to_the_innermost_span():
    got = spans.self_by_span(_trace())
    want = {"bench.round": 5e-6,
            spans.NO_SPAN: 5e-6 + 10e-6 + 20e-6,     # [5,10] [40,50] [70,90]
            "seafl.sim.upload": 5e-6 + 2e-6 + 5e-6,  # [10,15] [30,32] [35,40]
            "seafl.client.batches": 8e-6,
            "seafl.client.epoch": 10e-6,
            "seafl.sim.deliver": 5e-6 + 1e-6 + 2e-6,  # [50,55] [65,66] [68,70]
            "seafl.ingest": 1e-6 + 1e-6 + 2e-6,       # [55,56] [60,61] [63,65]
            "seafl.ingest.decode": 4e-6,
            "seafl.server.aggregate": 4e-6,
            "seafl.sim.arrive": 10e-6}
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k]), k
    assert sum(got.values()) == pytest.approx(100e-6)
    assert spans.self_seconds(_trace(), "seafl.sim.") == pytest.approx(30e-6)
    assert spans.self_seconds(_trace(), "seafl.client.") == pytest.approx(
        18e-6)


def test_span_readers_on_hand_built_events():
    r = _Run(_trace(), samples=4)
    assert spans.ingest_decode_ms(r) == pytest.approx(4e-3)
    assert spans.batch_us_per_sample(r) == pytest.approx(8 / 4)
    # 30 us of simulator self time over two aggregations in the window
    assert spans.sim_self_ms(r) == pytest.approx(15e-3)


def test_idle_gaps_name_the_innermost_program_span_and_coarse_share():
    trace = _trace()
    # gaps [4,20] (mid 12: the upload), [30,60] (mid 45: none), [95,100]
    assert dict(trace.idle_gaps(10)) == pytest.approx(
        {"seafl.sim.upload": 16e-6, "no bench span": 30e-6,
         "seafl.sim.arrive": 5e-6})
    assert spans.coarse_idle_share(_Run(trace)) == pytest.approx(30 / 51)


def test_readers_give_nothing_without_their_spans():
    ev = _trace().events
    bare = tr.Trace({**ev, "spans": [s for s in ev["spans"]
                                     if not s[0].startswith("seafl.")]})
    for r in (_Run(None, samples=4), _Run(bare, samples=4)):
        assert spans.ingest_decode_ms(r) is None
        assert spans.batch_us_per_sample(r) is None
        assert spans.sim_self_ms(r) is None
    assert spans.batch_us_per_sample(_Run(_trace())) is None   # no samples


def test_the_recorded_probe_reads_the_same_with_both_prefixes():
    before = tr.load(str(PROBE))
    with spans._program_spans([]):
        both = tr.load(str(PROBE))
    assert tr.SPAN_PREFIX == "bench."
    assert both == before


def _traced(cell, files):
    return spans.traced_run(cell, 2**40 + 29, 0.5, files=files,
                            require_chip=False, compile_cache=False)


def test_server_cell_traced_run_reads_the_ingest_spans():
    init = run.Run.__init__
    line = _traced("server-f32.whisper-tiny", _tiny.server_files())
    assert run.Run.__init__ is init and tr.SPAN_PREFIX == "bench."
    assert line["correct"]
    s = line["spans"]
    assert s["ingest_decode_ms"] > 0
    assert s["sim_self_ms"] is None and s["batch_us_per_sample"] is None
    own = dict(s["self_s"])
    assert {"seafl.ingest.decode", "seafl.ingest.write",
            "seafl.server.aggregate"} <= own.keys()
    assert any(k.startswith("seafl.") for k, _ in s["idle_gaps"])


def test_loop_cell_traced_run_reads_the_client_and_simulator_spans():
    line = _traced("loop.resnet18-cifar10", _tiny.loop_files())
    assert line["correct"]
    s = line["spans"]
    assert s["batch_us_per_sample"] > 0 and s["sim_self_ms"] > 0
    own = dict(s["self_s"])
    assert {"seafl.client.batches", "seafl.client.epoch",
            "seafl.sim.upload"} <= own.keys()
