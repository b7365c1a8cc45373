"""The server cell driven end to end on the CPU at whisper-tiny's smoke
widths: its builder and window loop, ``correct`` true for the program,
and false for the control and for each fault the cell can have, planted
in the program underneath the timed path."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

import _tiny  # noqa: E402
from benchmarks.chip import control, run, spec  # noqa: E402
from repro.kernels.seafl_agg import ops  # noqa: E402

CELL = "server-f32.whisper-tiny"
SEED = 2**40 + 17


def _run(trace=False):
    return run.run(CELL, SEED, 0.5, trace, files=_tiny.server_files(),
                   require_chip=False, compile_cache=False)


def test_window_is_correct_and_reports_its_metrics():
    line = _run()
    assert line["correct"], line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["attempted"] % 10 == 0         # whole rounds of K = 10
    assert {"server_updates_per_s", "peak_hbm_gb", "setup_s"} == set(
        line["metrics"])
    assert line["window_compiles"] == 0
    assert list(line)[-1] == "compared"


def test_traced_window_reports_per_layer_metrics():
    line = _run(trace=True)
    assert line["correct"]
    assert {"ingest_ms.server", "round_p95_ms.server",
            "device_idle.server"} <= set(line["metrics"])
    assert "breakdown" in line and line["device"]["window_s"] > 0


def test_control_fails_where_the_program_passes():
    limits = _tiny.server_files()["limits"]
    r = control.readings(CELL, SEED, 0.3, True, files=_tiny.server_files())
    assert all(r["program"][k] <= limits[k] for k in limits), r
    assert any(r["control"][k] > limits[k] for k in limits), r


def _unchanged(real):
    def agg(g, stacked, *a, **kw):
        return g, real(g, stacked, *a, **kw)[1]
    return agg


def _half(real):
    def agg(g, stacked, sizes, stale, *a, **kw):
        h = stacked.shape[0] // 2
        return real(g, stacked[:h], sizes[:h], stale[:h], *a, **kw)
    return agg


def _altered(real):
    def agg(*a, **kw):
        g, w = real(*a, **kw)
        return g.at[7].add(0.01), w
    return agg


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered],
                         ids=["state_unchanged", "half_batch", "altered"])
def test_fault_makes_correct_false(monkeypatch, fault):
    monkeypatch.setattr(ops, "seafl_aggregate_flat_from_params",
                        fault(ops.seafl_aggregate_flat_from_params))
    assert not _run()["correct"]


def test_limits_file_matches_the_compared_numbers():
    line = _run()
    assert set(line["compared"]) == set(spec.limits(
        spec.cell(spec.load(), CELL)))
