"""Operations and bytes that the work needs, from shapes alone.

These are the least the chip has to do: a kernel or program that moves or
computes more than this shows up as a lower roofline share, never as a
different count.  Matrix-multiply and convolution FLOPs count 2 per
multiply-add; elementwise work (norms, activations, the optimizer) is left
out of model FLOPs, as is any recomputation.
"""
from __future__ import annotations

F32 = 4


def agg_least_bytes(k: int, p: int, buf_itemsize: int) -> int:
    """One Eq. (4)-(8) aggregation over a (k, p) buffer: every buffered row
    read once at its stored width, the f32 global read once and the new f32
    global written once."""
    return k * p * buf_itemsize + 2 * F32 * p


def agg_flops(k: int, p: int) -> int:
    """Eq. (5) partials (w.g and w.w per row: 4 per element, g.g once: 2)
    and the Eq. (7)+(8) mix (a multiply-add per row element, then the
    (1 - theta) g + theta m blend: 3 per element)."""
    return 4 * k * p + 2 * p + 2 * k * p + 3 * p


def ingest_least_bytes(p: int, wire_bytes_per_elem: float,
                       buf_itemsize: int) -> float:
    """One upload absorbed: the payload read once, the buffer row written
    once."""
    return p * wire_bytes_per_elem + p * buf_itemsize


def least_seconds(nbytes: float, flops: float, pk: dict,
                  chips: int = 1) -> float:
    """Roofline time of work spread over ``chips`` chips of peaks ``pk``:
    the larger of bytes over HBM bandwidth and FLOPs over the bf16 peak,
    over the chips.  ``nbytes`` and ``flops`` count the whole work once,
    whatever number of chips does it."""
    return max(nbytes / pk["hbm_bw"], flops / pk["flops_bf16"]) / chips


def conv_flops(h_out: int, w_out: int, kh: int, kw: int, cin: int,
               cout: int) -> int:
    return 2 * h_out * w_out * kh * kw * cin * cout


def train_flops(forward: int) -> int:
    """Forward, then a backward pass that computes the gradients of inputs
    and of weights: three times the forward."""
    return 3 * forward

