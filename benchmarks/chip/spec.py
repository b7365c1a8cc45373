"""Load ``BENCHMARK.json`` and find a cell's files by the names in it.

Everything that belongs to one configuration, traffic mix or metric sits in
a file of its own, found by name:

    configs/<config>.json        sizes as run, and how they are built
    configs/<config>.ref.py      the plain reference beside it (if any)
    traffic/<traffic>.json       parameters read by the driver it names
    metrics/<metric>.py          ``read(run) -> float | None``
    limits/<workload>.json       the limits of the numbers compared

A later cell, mix or metric is new files and new entries, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
E2E_SOURCES = ("host_clock", "device_trace")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


def _text(value, what: str) -> None:
    if (not isinstance(value, str) or not 1 <= len(value) <= 200
            or "\n" in value or "\t" in value):
        raise ValueError(f"{what}: 1 to 200 characters on one line, "
                         f"got {value!r}")


def _name(value, what: str) -> None:
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise ValueError(f"{what}: not a valid name: {value!r}")


def _keys(entry: dict, allowed: set, what: str, optional=()) -> None:
    got = set(entry)
    if not allowed <= got or got - allowed - set(optional):
        raise ValueError(f"{what}: keys {sorted(got)}, want {sorted(allowed)}"
                         + (f" and optionally {sorted(optional)}"
                            if optional else ""))


def _unique(entries: list, what: str) -> None:
    names = [e["name"] for e in entries]
    if len(set(names)) != len(names):
        raise ValueError(f"{what}: duplicate names in {names}")


def validate(spec: dict) -> dict:
    """Raise ValueError where ``spec`` breaks the benchmark's contract on
    names, units, keys and counts; return it unchanged otherwise."""
    _keys(spec, TOP_KEYS, "BENCHMARK.json")
    cmd, paths = spec["command"], spec["paths"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        raise ValueError("command: a list of 1 to 32 strings")
    for word in cmd:
        _text(word, "command word")
        if word.startswith("/") or ".." in word.split("/"):
            raise ValueError(f"command word leaves the repo: {word!r}")
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        raise ValueError("paths: 1 to 16 directories")
    for p in paths:
        if (not PATH_RE.match(p) or p.startswith("/")
                or ".." in p.split("/")):
            raise ValueError(f"paths: bad directory {p!r}")
    rs = spec["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        raise ValueError("run_seconds: a whole number from 1 to 51")
    configs, cells = spec["configs"], spec["workloads"]
    e2e, layers = spec["end_to_end"], spec["per_layer"]
    for what, entries, lo, hi in (("configs", configs, 1, 24),
                                  ("workloads", cells, 1, 24),
                                  ("end_to_end", e2e, 1, 16),
                                  ("per_layer", layers, 1, 128)):
        if not (isinstance(entries, list) and lo <= len(entries) <= hi):
            raise ValueError(f"{what}: {lo} to {hi} entries")
        _unique(entries, what)
        for e in entries:
            _name(e.get("name"), f"{what} name")
    metric_names = [m["name"] for m in e2e + layers]
    if len(set(metric_names)) != len(metric_names):
        raise ValueError("a metric name is used twice")
    cfg_names = {c["name"] for c in configs}
    for c in configs:
        _keys(c, CONFIG_KEYS, f"config {c['name']}")
        for k in ("source", "why"):
            _text(c[k], f"config {c['name']} {k}")
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in paths):
            raise ValueError(f"config {c['name']}: file outside paths")
        if not isinstance(c["reduced"], list) or len(c["reduced"]) > 16:
            raise ValueError(f"config {c['name']}: reduced has at most 16")
        for k in c["reduced"]:
            _name(k, f"config {c['name']} reduced key")
    if len({c["file"] for c in configs}) != len(configs):
        raise ValueError("two configurations share a file")
    pairs = set()
    for w in cells:
        _keys(w, CELL_KEYS, f"workload {w['name']}")
        _name(w["config"], f"workload {w['name']} config")
        _name(w["traffic"], f"workload {w['name']} traffic")
        _text(w["why"], f"workload {w['name']} why")
        if w["config"] not in cfg_names:
            raise ValueError(f"workload {w['name']}: unknown config")
        if w["chips"] not in (1, 4):
            raise ValueError(f"workload {w['name']}: chips is 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            raise ValueError(f"workload {w['name']}: pair used twice")
        pairs.add((w["config"], w["traffic"]))
    if sum(w["chips"] == 4 for w in cells) > max(1, len(cells) // 2):
        raise ValueError("too many four-chip cells")
    unused = cfg_names - {w["config"] for w in cells}
    if unused:
        raise ValueError(f"configs used by no cell: {sorted(unused)}")
    cell_names = {w["name"] for w in cells}
    e2e_names = {m["name"] for m in e2e}
    if "setup_s" not in e2e_names:
        raise ValueError("end_to_end must hold setup_s")
    for m in e2e + layers:
        is_e2e = m in e2e
        _keys(m, E2E_KEYS if is_e2e else LAYER_KEYS, f"metric {m['name']}",
              optional=("workloads",))
        if not UNIT_RE.match(str(m["unit"])):
            raise ValueError(f"metric {m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            raise ValueError(f"metric {m['name']}: better is lower|higher")
        if m["source"] not in (E2E_SOURCES if is_e2e else SOURCES):
            raise ValueError(f"metric {m['name']}: bad source")
        if not set(m.get("workloads", [])) <= cell_names:
            raise ValueError(f"metric {m['name']}: unknown workload")
        if is_e2e:
            b = m["bound"]
            if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.25):
                raise ValueError(f"metric {m['name']}: bound in [0.01, 0.25]")
        else:
            _text(m["layer"], f"metric {m['name']} layer")
            if m["moves"] not in e2e_names:
                raise ValueError(f"metric {m['name']}: moves no end-to-end "
                                 "metric")
    for w in cells:
        mine = [m for m in e2e if w["name"] in m.get("workloads",
                                                     [w["name"]])]
        if len(mine) < 2 or not any(
                w["name"] in m.get("workloads", [w["name"]]) for m in layers):
            raise ValueError(f"workload {w['name']}: wants setup_s, another "
                             "end-to-end metric and a per-layer metric")
    return spec


def load(path: Path = ROOT / "BENCHMARK.json") -> dict:
    with open(path) as f:
        return validate(json.load(f))


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(spec: dict, cell_: dict, root: Path = ROOT) -> dict:
    entry = next(c for c in spec["configs"] if c["name"] == cell_["config"])
    return _json(root / entry["file"])


def traffic(cell_: dict) -> dict:
    return _json(HERE / "traffic" / f"{cell_['traffic']}.json")


def limits(cell_: dict) -> dict:
    return _json(HERE / "limits" / f"{cell_['name']}.json")


def metrics(spec: dict, cell_: dict, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in spec[kind]
            if cell_["name"] in m.get("workloads", [cell_["name"]])]


def load_file(path: Path, name: str):
    """Import a module from a file whose name need not be an identifier."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The ``read(run)`` function of metric ``name``."""
    return load_file(HERE / "metrics" / f"{name}.py",
                     f"chipbench_metric_{name}").read


def reference(config_name: str):
    """The plain reference module beside configuration ``config_name``."""
    return load_file(HERE / "configs" / f"{config_name}.ref.py",
                     f"chipbench_ref_{config_name}")
