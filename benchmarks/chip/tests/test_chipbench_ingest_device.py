"""``ingest_device_ms.server`` on hand-built trace events, with values
computed by hand, and on the recorded v5e probe, which holds no ingest."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import spec, trace as tr  # noqa: E402

PROBE = Path(__file__).resolve().parent / "data" / "v5e_agg_probe.xplane.pb"
READ = spec.reader("ingest_device_ms.server")


class _Run:
    def __init__(self, trace):
        self.trace, self.c, self.peaks = trace, {}, None


def _events():
    modules = [
        (0, "jit__write_range", 1_000, 6_000),
        (0, "jit__join_chunks", 8_000, 500),
        (0, "jit__join_chunks", 9_000, 700),
        (0, "jit__seafl_aggregate_flat_from_params_jit", 10_000, 9_000),
        (0, "jit_concatenate", 20_000, 300),       # not the ingest's
        (0, "jit__write_range", 30_000, 5_000),
        (0, "jit__ingest_add", 36_000, 200),
        (0, "jit__write_range", 200_000, 5_000),   # after the window
    ]
    spans = [("bench.window", 0, 100_000),
             ("bench.ingest", 500, 19_500),
             ("bench.ingest", 29_000, 8_000),
             ("bench.ingest", 199_000, 7_000)]     # after the window
    return {"modules": modules, "ops": [], "spans": spans}


def test_device_time_of_the_ingest_programs_per_upload():
    got = READ(_Run(tr.Trace(_events())))
    # (6,000 + 500 + 700 + 5,000 + 200) ns over two uploads, in ms
    assert got == pytest.approx(12_400e-6 / 2)


def test_no_value_without_a_trace_or_an_upload():
    assert READ(_Run(None)) is None
    ev = _events()
    ev["spans"] = [s for s in ev["spans"] if s[0] != "bench.ingest"]
    assert READ(_Run(tr.Trace(ev))) is None
    ev = _events()
    ev["modules"] = [m for m in ev["modules"] if not any(
        k in m[1] for k in ("write_range", "join_chunks", "ingest"))]
    assert READ(_Run(tr.Trace(ev))) is None


def test_the_recorded_probe_holds_no_ingest():
    ev = tr.load(str(PROBE))
    ev["spans"].append((tr.WINDOW_SPAN, 44_000_000.0, 28_500_000.0))
    assert READ(_Run(tr.Trace(ev))) is None
