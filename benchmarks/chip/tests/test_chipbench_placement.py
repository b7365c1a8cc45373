"""The benchmark's own arrays over several chips, on the CPU.

A cell on several chips runs in the program's axis rules over a ``pod``
mesh of its chips; a one-chip cell gets no mesh.  Over four virtual CPU
devices (a child process, so that the host platform can be split): the
seeded tree, its flat f32 vector and the pool rows are the same bits as on
one device, no device holds more than its share of a P-vector, and a
server cell on four chips runs ``correct`` and its control readings fail
the control, with every kept global in host memory.  In one process: a
one-chip cell's kept globals in host memory, the reference's P-blocked
round against the whole-array Eq. (4)-(8), and its bfloat16 control
against the limits.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

import _tiny  # noqa: E402
from benchmarks.chip import run, weights  # noqa: E402
from benchmarks.chip.reference import aggregation as ref  # noqa: E402

HYPER = {"alpha": 0.6, "mu": 0.4, "beta": 4.0, "theta": 0.9}
LIMITS = _tiny.server_files()["limits"]


def _four_devices(code: str) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"),
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_one_chip_builds_no_mesh():
    from repro.sharding import current_rules

    with run.placement(jax.devices()[:1]):
        assert current_rules().mesh is None
        assert weights.sharding((8,), 0) is None


def test_four_devices_hold_the_same_bits_split():
    out = _four_devices("""
import math
import jax, numpy as np
import _tiny
from benchmarks.chip import models, run, weights
from benchmarks.chip.drivers import server
from repro.sharding import current_rules

shapes = models.param_shapes(_tiny.server_files()["config"])
seed = 2**40 + 17
rule = weights.normal_all(0.02)
k = jax.random.fold_in(weights.key(seed), 1_000_003)

def build():
    tree = weights.init(shapes, seed, rule)
    g0 = weights.flatten(tree)
    return tree, g0, server._step(g0, k, 0.001)

one = build()
with run.placement(jax.devices()):
    assert current_rules().mesh.axis_names == ("pod",)
    four = build()
for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(four)):
    assert np.array_equal(np.asarray(a), np.asarray(b))
tree, g0, row = four
p = g0.shape[0]
for x in (g0, row):
    assert len(x.sharding.device_set) == 4
    assert max(s.data.size for s in x.addressable_shards) <= math.ceil(p / 4)
for x in jax.tree.leaves(tree):
    if any(d % 4 == 0 for d in x.shape):
        assert max(s.data.size for s in x.addressable_shards) == x.size // 4
print("SPLIT_OK", p)
""")
    assert "SPLIT_OK" in out


def test_server_cell_on_four_chips_is_correct_with_host_copies():
    """``run.run`` of the server cell with ``chips: 4`` at whisper-tiny's
    smoke widths: its set-up, window and check inside the pod mesh's
    rules; every global kept for the check is a host (numpy) array."""
    out = _four_devices("""
import copy
import numpy as np
import _tiny
from benchmarks.chip import run, spec
from benchmarks.chip.drivers import server

bench = copy.deepcopy(spec.load())
cell = dict(spec.cell(bench, "server-f32.whisper-tiny"), name="pod", chips=4)
bench["workloads"].append(cell)
kinds = set()
check = server.check

def spy(st, detail=False):
    kinds.update((type(g), g.shape) for g in st.kept_globals.values())
    return check(st, detail)

server.check = spy
line = run.run("pod", 2**40 + 17, 0.5, False, bench=bench,
               files=_tiny.server_files(), require_chip=False,
               compile_cache=False)
assert line["correct"], line["compared"]
assert line["device"]["count"] == 4 and line["window_compiles"] == 0
assert len(kinds) == 1 and next(iter(kinds))[0] is np.ndarray, kinds
print("POD_OK")
""")
    assert "POD_OK" in out


def test_control_readings_on_four_chips_are_split_and_fail_the_control():
    """``control.readings``, from which the limits are set, places a
    ``chips: 4`` server cell as ``run.run`` does: every flat vector the
    harness makes is split over the four devices, every kept global is a
    host (numpy) array, the program passes both limits and the bfloat16
    control fails both."""
    out = _four_devices("""
import copy
import numpy as np
import _tiny
from benchmarks.chip import control, spec, weights
from benchmarks.chip.drivers import server

bench = copy.deepcopy(spec.load())
cell = dict(spec.cell(bench, "server-f32.whisper-tiny"), name="pod", chips=4)
bench["workloads"].append(cell)
kinds, spread = set(), set()
check, flatten = server.check, weights.flatten

def spy_check(st, detail=False):
    kinds.update(type(g) for g in st.kept_globals.values())
    return check(st, detail)

def spy_flatten(tree):
    g = flatten(tree)
    spread.add(len(g.sharding.device_set))
    return g

server.check, weights.flatten = spy_check, spy_flatten
files = _tiny.server_files()
r = control.readings("pod", 2**40 + 29, 0.5, True, bench=bench, files=files)
limits = files["limits"]
assert all(r["program"][k] <= limits[k] for k in limits), r
assert all(r["control"][k] > limits[k] for k in limits), r
assert kinds == {np.ndarray}, kinds
assert spread == {4}, spread
print("READINGS_OK")
""")
    assert "READINGS_OK" in out


def test_one_chip_keeps_host_copies():
    """On one chip too, every global kept for the check is a host (numpy)
    array by the time the check reads it."""
    from benchmarks.chip.drivers import server

    kinds = set()
    check = server.check

    def spy(st, detail=False):
        kinds.update(type(g) for g in st.kept_globals.values())
        return check(st, detail)

    server.check = spy
    try:
        line = run.run("server-f32.whisper-tiny", 2**40 + 31, 0.3, False,
                       files=_tiny.server_files(), require_chip=False,
                       compile_cache=False)
    finally:
        server.check = check
    assert line["correct"], line["compared"]
    assert kinds == {np.ndarray}, kinds


def _round_inputs(p: int, seed: int, k: int = 10, pool: int = 10):
    """A global near init scale and a pool of seeded steps from it, as the
    server cell makes them, and one round's draw from the pool."""
    kg, kp = jax.random.split(jax.random.PRNGKey(seed))
    g = 0.02 * jax.random.normal(kg, (p,))
    rows = tuple(g + 0.001 * jax.random.normal(jax.random.fold_in(kp, i),
                                               (p,)) for i in range(pool))
    rng = np.random.default_rng(seed)
    idx = rng.integers(pool, size=k).astype(np.int32)
    sizes = rng.integers(300, 2200, size=k).astype(np.float32)
    stale = rng.integers(0, 3, size=k).astype(np.float32)
    return g, rows, idx, sizes, stale


@pytest.mark.parametrize("p,block", [(100_003, 4096), (30_001, 1 << 24)])
def test_blocked_round_equals_the_whole_array_round(p, block):
    g, rows, idx, sizes, stale = _round_inputs(p, 7)
    got_g, got_w = g, None
    want_g = g
    step = ref.replay_round(HYPER, jnp.float32, block=block)
    for _ in range(3):
        got_g, got_w = step(got_g, rows, idx, sizes, stale)
        want_g, want_w = ref.aggregate(want_g, jnp.stack(rows)[idx], sizes,
                                       stale, HYPER)
    scale = float(jnp.max(jnp.abs(want_g)))
    assert float(jnp.max(jnp.abs(got_g - want_g))) <= 1e-6 * scale
    assert float(jnp.max(jnp.abs(got_w - want_w))) <= 1e-6 * float(
        jnp.max(want_w))
    d, a, b = ref.widest_gap(block=block)(got_g, want_g)
    assert float(d) == float(jnp.max(jnp.abs(got_g - want_g)))
    assert float(b) == scale


@pytest.mark.parametrize("seed", [3, 11])
def test_blocked_bf16_control_fails_both_limits_tenfold(seed):
    p = 100_003
    g, rows, idx, sizes, stale = _round_inputs(p, seed)
    want_g, want_w = ref.aggregate(g, jnp.stack(rows)[idx], sizes, stale,
                                   HYPER)
    got_g, got_w = ref.replay_round(HYPER, jnp.bfloat16, block=4096)(
        g, rows, idx, sizes, stale)
    d, _, b = ref.widest_gap(block=4096)(got_g, want_g)
    assert float(d / b) >= 10 * LIMITS["global_gap"]
    assert float(jnp.max(jnp.abs(got_w - want_w))) >= (
        10 * LIMITS["weights_gap"])
