"""The bytes and FLOPs cost functions, against hand counts at small
shapes."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import costs, spec  # noqa: E402

RESNET = spec.reference("resnet18-cifar10")


@pytest.mark.parametrize("k,p,item,want", [
    (2, 3, 4, 2 * 3 * 4 + 8 * 3),       # rows f32 + global read and write
    (10, 1000, 2, 10 * 1000 * 2 + 8000),
])
def test_agg_least_bytes(k, p, item, want):
    assert costs.agg_least_bytes(k, p, item) == want


def test_agg_flops_and_ingest_bytes():
    # partials 4 per row element + g.g 2 per element; mix 2 + 3
    assert costs.agg_flops(2, 5) == 4 * 10 + 2 * 5 + 2 * 10 + 3 * 5
    assert costs.ingest_least_bytes(100, 4.0, 4) == 800
    assert costs.ingest_least_bytes(100, 1.25, 2) == 325


def test_least_seconds_takes_the_binding_roof():
    pk = {"hbm_bw": 100.0, "flops_bf16": 1000.0}
    assert costs.least_seconds(200, 1000, pk) == 2.0      # bytes bind
    assert costs.least_seconds(10, 5000, pk) == 5.0       # FLOPs bind


@pytest.mark.parametrize("chips", [1, 4])
def test_least_seconds_spreads_the_work_over_the_chips(chips):
    """The same work on four chips takes a quarter of the least time, so a
    share of the peak reads the same whatever number of chips does it; one
    chip reads exactly what it read before ``chips`` existed."""
    pk = {"hbm_bw": 819e9, "flops_bf16": 197e12}
    one = max(2.26e9 / pk["hbm_bw"], 6.2e8 / pk["flops_bf16"])
    got = costs.least_seconds(2.26e9, 6.2e8, pk, chips)
    assert got == (one if chips == 1 else pytest.approx(one / chips))
    assert costs.least_seconds(2.26e9, 6.2e8, pk) == one


def test_conv_flops():
    assert costs.conv_flops(4, 4, 3, 3, 2, 5) == 2 * 16 * 9 * 2 * 5


def test_resnet_forward_flops_by_hand():
    """The forward FLOPs that the ResNet configuration's reference counts."""
    cfg = {"image_size": 8, "in_channels": 3, "width": 2,
           "stage_sizes": [1, 1], "num_classes": 10}
    stem = 2 * 64 * 9 * 3 * 2
    s0 = 2 * (2 * 64 * 9 * 2 * 2)                  # two 3x3, 8x8, 2->2
    s1 = (2 * 16 * 9 * 2 * 4 + 2 * 16 * 9 * 4 * 4  # stride 2 -> 4x4
          + 2 * 16 * 1 * 2 * 4)                    # 1x1 projection
    head = 2 * 4 * 10
    assert RESNET.forward_flops(cfg) == stem + s0 + s1 + head
    assert costs.train_flops(7) == 21


def test_resnet18_cifar_forward_is_about_half_a_gigamac():
    cfg = {"image_size": 32, "in_channels": 3, "width": 64,
           "stage_sizes": [2, 2, 2, 2], "num_classes": 10}
    # the published CIFAR ResNet-18 count is 0.56 GMAC = 1.11 GFLOP
    assert RESNET.forward_flops(cfg) == pytest.approx(1.11e9,
                                                            rel=0.01)
