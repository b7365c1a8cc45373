"""Model FLOPs of local training (forward and backward per sample from the
configuration's shapes, no recompute) times the samples per second of the
traced window, over the chip's bf16 peak.  The f32 matmuls run at the
default precision, one bf16 pass, so bf16 is the ceiling."""


def read(run):
    c = run.c
    if run.trace is None or run.peaks is None or not c.get("samples"):
        return None
    rate = c["samples"] / c["window_s"]
    return 100.0 * c["train_flops_per_sample"] * rate / run.peaks["flops_bf16"]
