"""The device gate: the command refuses the CPU and prints no result, also
from a directory that holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import device  # noqa: E402

ARGS = ["--workload", "server-f32.whisper-tiny", "--seed", "4294967311",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def _no_result(stdout: str) -> bool:
    return not any(line.lstrip().startswith("{")
                   for line in stdout.splitlines())


def test_command_refuses_the_cpu():
    r = _run(ROOT)
    assert r.returncode != 0 and _no_result(r.stdout)
    assert "no TPU" in r.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0 and _no_result(r.stdout)


def test_gate_and_peaks():
    with pytest.raises(device.NoChip):
        device.require_chips(1)
    assert device.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("cpu")
