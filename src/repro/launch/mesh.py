"""Production mesh factories.

Functions, not module-level constants: importing this module never touches
jax device state (dryrun.py must set XLA_FLAGS before the first jax init).

Production target: TPU v5e pods.  Single pod = 16x16 (256 chips,
data x model); multi-pod = 2 x 16 x 16 = 512 chips with a leading 'pod'
axis that (a) data-parallels across pods and (b) doubles as the concurrent
FL-cohort axis (one SEAFL client cohort per pod — see DESIGN.md §2).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape, axes=None):
    """Elastic mesh factory for tests and degraded operation.

    shape: tuple of ints.  axes default: trailing names of
    ('pod', 'data', 'model')."""
    shape = tuple(shape)
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):]
    return jax.make_mesh(shape, tuple(axes))


# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
# of inter-chip interconnect (four links of 50 GB/s).  A device that is
# not in the table has no peaks: callers report "no prediction" for it
# instead of borrowing another chip's numbers.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bw": 819e9,          # bytes/s
        "ici_bw": 50e9,           # bytes/s per link
    },
}

