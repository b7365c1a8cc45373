"""The loop cell driven end to end on the CPU with a ResNet-10 of width 16
on four clients: its builder and window loop, ``correct`` true for the
program, and false for the control and for each fault the cell can have,
planted in the program underneath the timed path."""
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

import _tiny  # noqa: E402
import repro.experiment  # noqa: E402
from benchmarks.chip import control, models, run  # noqa: E402
from repro.core import client  # noqa: E402
from repro.kernels.seafl_agg import ops  # noqa: E402

CELL = "loop.resnet18-cifar10"
SEED = 2**33 + 5


def _run():
    return run.run(CELL, SEED, 0.5, False, files=_tiny.loop_files(),
                   require_chip=False, compile_cache=False)


def test_window_is_correct_and_reports_its_metrics():
    line = _run()
    assert line["correct"], line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"loop_samples_per_s", "peak_hbm_gb", "setup_s"} <= set(
        line["metrics"])
    assert line["window_compiles"] == 0


def test_control_fails_where_the_program_passes():
    files = _tiny.loop_files()
    limits = files["limits"]
    r = control.readings(CELL, SEED, 0.3, True, files=files)
    assert all(r["program"][k] <= limits[k] for k in limits), r
    assert any(r["control"][k] > limits[k] for k in limits), r


def _epoch_unchanged(loss_fn, lr=None):
    epoch = client.make_epoch_fn(loss_fn)
    return lambda p, d, lr_: (p, epoch(p, d, lr_)[1])


def _epoch_half_batch(loss_fn, lr=None):
    half = lambda b: jax.tree.map(lambda a: a[: a.shape[0] // 2], b)  # noqa
    return client.make_epoch_fn(lambda p, b: loss_fn(p, half(b)))


def _epoch_altered(loss_fn, lr=None):
    epoch = client.make_epoch_fn(loss_fn)

    def call(p, d, lr_):
        p, loss = epoch(p, d, lr_)
        leaves, tree = jax.tree.flatten(p)
        i = max(range(len(leaves)), key=lambda j: leaves[j].size)
        leaves[i] = leaves[i].ravel().at[0].set(1.0).reshape(leaves[i].shape)
        return jax.tree.unflatten(tree, leaves), loss
    return call


@pytest.mark.parametrize("factory", [_epoch_unchanged, _epoch_half_batch,
                                     _epoch_altered],
                         ids=["state_unchanged", "half_batch", "altered"])
def test_client_fault_makes_correct_false(monkeypatch, factory):
    monkeypatch.setattr(repro.experiment, "make_epoch_fn", factory)
    assert not _run()["correct"]


def test_aggregation_left_unchanged_makes_correct_false(monkeypatch):
    real = ops.seafl_aggregate_flat_from_params
    monkeypatch.setattr(ops, "seafl_aggregate_flat_from_params",
                        lambda g, s, *a, **kw: (g, real(g, s, *a, **kw)[1]))
    assert not _run()["correct"]


def test_lm_builder_gives_the_loop_its_parts():
    """A loop cell over an LM configuration needs no edit of the driver:
    its builder gives the clients, evaluation and FLConfig, and the
    parameter tree comes from the configuration."""
    cfg = {"name": "whisper-tiny", "builder": "lm", "arch": "whisper-tiny",
           "smoke": True}
    traffic = {"fleet_seed": 0, "fl": {
        "n_clients": 2, "concurrency": 1, "buffer_size": 1, "seq_len": 16,
        "shard_seqs": 4, "batch_size": 2, "local_epochs": 1}}
    clients, eval_fn, fl = models.loop_parts(cfg, traffic)
    assert sorted(clients) == [0, 1] and fl.buffer_size == 1
    assert all(c.n_samples == 4 for c in clients.values())
    assert jax.tree.leaves(models.param_shapes(cfg))
