"""CPU tests of the pieces that bring the trainer up on the chip: the
device gate of ``chip_smoke.py``, the compile-cache helper, full widths
through ``launch/train.py``, the slot-sharded aggregation path, and the
autotuner's refusal to hide a device or a broken kernel."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config, smoke_config
from repro.launch import compile_cache, train
from repro.runtime import autotune

REPO = Path(__file__).resolve().parent.parent


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_gate_refuses_cpu(capsys):
    cs = _chip_smoke()
    with pytest.raises(RuntimeError, match="no TPU"):
        cs.require_tpu()
    with pytest.raises(RuntimeError, match="no TPU"):
        cs.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_whisper_tiny_packed_size():
    from test_tpu_compile import P_WHISPER_TINY
    assert _chip_smoke().packed_size("whisper-tiny") == P_WHISPER_TINY


def test_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "xla"))
        assert compile_cache.enable_compile_cache() == str(tmp_path / "xla")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "xla")
        monkeypatch.delenv(compile_cache.ENV_VAR)
        path = compile_cache.enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path     # fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


class _Built(Exception):
    pass


@pytest.mark.parametrize("flags,want", [
    (["--no-smoke"], get_config("whisper-tiny")),
    ([], smoke_config("whisper-tiny")),
    (["--smoke"], smoke_config("whisper-tiny")),
], ids=["no-smoke", "default", "smoke"])
def test_train_builds_published_config_with_no_smoke(monkeypatch, capsys,
                                                     flags, want):
    """``main`` reaches the model builder with the published config under
    --no-smoke (stopped there, before any full-size array exists)."""
    def build_model(cfg):
        raise _Built(cfg)

    monkeypatch.setattr(train, "build_model", build_model)
    monkeypatch.setattr(train, "enable_compile_cache", lambda: "")
    with pytest.raises(_Built) as built:
        train.main(["--arch", "whisper-tiny", *flags])
    assert built.value.args[0] == want
    assert "platform=cpu device_kind=cpu devices=1" in capsys.readouterr().out


def test_slot_sharded_aggregation_matches_one_device():
    """On four host devices, a buffer placed P('pod', None) aggregates per
    shard (shard_map + psum) to the one-device result, for every 2-D
    entry point; a buffer split along P is refused."""
    code = """
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.kernels.seafl_agg import ops
from repro.sharding import axis_rules, shard_update_buffer

mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2), ("pod", "data"))
rng = np.random.default_rng(0)
for k in (2, 4):
    s = jnp.asarray(rng.normal(size=(k, 3000)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(3000,)), jnp.float32)
    n = jnp.asarray(rng.integers(1, 9, k), jnp.float32)
    st = jnp.asarray(rng.integers(0, 3, k), jnp.float32)
    with axis_rules(mesh):
        sb = shard_update_buffer(s)
    slots = ops.slot_sharding_of(sb)
    assert slots is not None and tuple(slots.spec) == ("pod", None)
    calls = [
        (ops.seafl_aggregate_flat_from_params, (n, st, 3.0, 1.0, 10.0, 0.8)),
        (ops.seafl_aggregate_flat, (s, n, st, 3.0, 1.0, 10.0, 0.8)),
        (ops.fedavg_aggregate_flat, (n,)),
        (ops.fedbuff_aggregate_flat, (0.5,)),
    ]
    for fn, rest in calls:
        a = fn(g, sb, *rest, block_p=1024)
        b = fn(g, s, *rest, block_p=1024)
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6, atol=1e-6)
bad = jax.device_put(jnp.zeros((4, 3000)), NamedSharding(mesh, P(None, "pod")))
try:
    ops.slot_sharding_of(bad)
except NotImplementedError:
    print("SLOT_SHARDED_OK")
"""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=240)
    assert "SLOT_SHARDED_OK" in out.stdout, out.stderr


def test_device_kind_propagates_backend_failure(monkeypatch):
    def broken():
        raise RuntimeError("backend failed to start")

    monkeypatch.setattr(autotune.jax, "devices", broken)
    with pytest.raises(RuntimeError, match="failed to start"):
        autotune.device_kind()


def test_roofline_prediction_only_for_devices_with_peaks():
    assert autotune.predict_agg_seconds("weighted_aggregate", 1 << 20, 4,
                                        "float32") is None       # cpu
    assert autotune.predict_from_hlo(lambda x: x + 1,
                                     jax.numpy.ones(4)) is None
    t = autotune.predict_agg_seconds("weighted_aggregate", 1 << 20, 4,
                                     "float32", kind="TPU v5 lite")
    # 2 x (4P + P + P) f32 bytes over 819 GB/s
    assert t == pytest.approx(2 * 6 * 4 * (1 << 20) / 819e9)


@pytest.mark.parametrize("backend,raises", [("cpu", False), ("tpu", True)])
def test_sweep_refuses_a_failing_default_block_p_on_tpu(monkeypatch,
                                                        backend, raises):
    def timer(fn, label):
        if label[1] == "block_p" and label[2] == autotune.DEFAULT_BLOCK_P:
            raise RuntimeError("Mosaic refused the kernel")
        return 1.0 if label[1] == "block_p" else 2.0

    monkeypatch.setattr(autotune.jax, "default_backend", lambda: backend)
    if raises:
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            autotune.sweep_agg_entry("weighted_aggregate", 4096, 4,
                                     timer=timer)
    else:
        r = autotune.sweep_agg_entry("weighted_aggregate", 4096, 4,
                                     timer=timer)
        assert r["block_p"] != autotune.DEFAULT_BLOCK_P
        assert r["candidates_us"][str(autotune.DEFAULT_BLOCK_P)] \
            == float("inf")
