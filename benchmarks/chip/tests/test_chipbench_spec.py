"""``BENCHMARK.json``: every name and unit within the contract, every name
found as a file, and the validator refusing what breaks the contract."""
import copy
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import models, spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_benchmark_json_is_valid():
    assert spec.load() == BENCH


@pytest.mark.parametrize("m", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_name_unit_and_reader(m):
    assert spec.NAME_RE.match(m["name"])
    assert spec.UNIT_RE.match(m["unit"]) and len(m["unit"]) <= 16
    assert callable(spec.reader(m["name"]))


@pytest.mark.parametrize("cell", BENCH["workloads"],
                         ids=[w["name"] for w in BENCH["workloads"]])
def test_cell_files_are_found_by_name(cell):
    for key in ("name", "config", "traffic"):
        assert spec.NAME_RE.match(cell[key])
    config, traffic, limits = (spec.config(BENCH, cell), spec.traffic(cell),
                               spec.limits(cell))
    assert config["name"] == cell["config"]
    assert (spec.HERE / "drivers" / f"{traffic['driver']}.py").exists()
    assert limits and all(v >= 0 for v in limits.values())
    e2e = [m["name"] for m in spec.metrics(BENCH, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics(BENCH, cell, "per_layer")


@pytest.mark.parametrize("entry", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_config_file_agrees_with_the_program(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    shapes = models.param_shapes(cfg)       # raises on any disagreement
    assert shapes is not None


def _broken(path, value):
    b = copy.deepcopy(BENCH)
    node = b
    for k in path[:-1]:
        node = node[k]
    if value is KeyError:
        del node[path[-1]]
    else:
        node[path[-1]] = value
    return b


@pytest.mark.parametrize("path,value", [
    (("end_to_end", 0, "unit"), "updates per s"),
    (("end_to_end", 0, "unit"), "x" * 17),
    (("end_to_end", 0, "name"), "a/b"),
    (("end_to_end", 0, "bound"), 0.3),
    (("end_to_end", 0, "why"), "no such key"),
    (("per_layer", 0, "moves"), "not_a_metric"),
    (("per_layer", 0, "source"), "guess"),
    (("workloads", 0, "chips"), 2),
    (("workloads", 0, "why"), "two\nlines"),
    (("run_seconds",), 52),
    (("paths",), ["/abs"]),
    (("command",), ["python3", "../run.py"]),
    (("configs", 0, "reduced"), ["a b"]),
])
def test_validator_refuses(path, value):
    with pytest.raises(ValueError):
        spec.validate(_broken(path, value))


def test_validator_wants_setup_s():
    b = copy.deepcopy(BENCH)
    b["end_to_end"] = [m for m in b["end_to_end"] if m["name"] != "setup_s"]
    with pytest.raises(ValueError):
        spec.validate(b)
