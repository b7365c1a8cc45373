"""Plain reference of ``resnet18-cifar10.json``: the forward pass, its
loss and one SGD step, in jax.numpy, from the configuration alone.

ResNet-18 (He et al., arXiv:1512.03385) as the CIFAR variant the file
states: a 3x3 stride-1 stem with no max-pool; four stages of two basic
blocks, each ``relu(gn(conv3x3)) -> gn(conv3x3)`` plus the shortcut, then
``relu``; a 1x1 projection shortcut where the width changes; stride 2 at
the first block of every stage after the first; global average pooling and
a dense head.  Departures from the paper, as the configuration states:
GroupNorm (8 groups, per-channel scale and bias) in place of BatchNorm,
and biases on every convolution.

Parameters are a dict tree: ``stem``/``stem_n``, ``blocks/s<i>b<j>`` with
``c1 n1 c2 n2 [proj]``, and ``head``; a convolution holds ``w`` (HWIO) and
``b``, a norm ``scale`` and ``b``.  ``dtype`` float32 runs every product
at the precision the configuration states (``matmul_precision``: the TPU's
default, one bfloat16 pass with float32 accumulation); bfloat16 runs the
whole step in bfloat16 (the control).

``init_rule`` gives the benchmark's weights for this configuration, and
``forward_flops`` counts the model FLOPs of one image, for ``mfu.loop``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.costs import conv_flops
from benchmarks.chip.weights import fan_in

PRECISION = {"default": None, "highest": jax.lax.Precision.HIGHEST}


def init_rule(path: str, shape) -> tuple:
    """``weights.fan_in`` (kernels N(0, 1/fan_in), zero biases, unit norm
    scales), except that the last norm scale of every residual branch
    starts at zero, so that each block starts as its shortcut (zero-gamma:
    Goyal et al., arXiv:1706.02677, Sec. 5.1; torchvision's
    ``zero_init_residual``).  From the plain fan-in start the loss of the
    first epoch call climbs from 2.7 to 19 at the cell's rate of 0.05 and
    rounding alone moves its worst leaf by 28%; from this one it stays
    near ln 10."""
    if path.endswith("n2/scale"):
        return ("const", 0.0)
    return fan_in(path, shape)


def _conv(p, x, stride, dtype, prec):
    y = jax.lax.conv_general_dilated(
        x, p["w"].astype(dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)
    return y + p["b"].astype(dtype)


def _gn(p, x, cfg, dtype):
    b, h, w, c = x.shape
    g = cfg["norm_groups"]
    xg = x.reshape(b, h, w, g, c // g)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + jnp.asarray(cfg["norm_eps"], dtype))
    return (xg.reshape(b, h, w, c) * p["scale"].astype(dtype)
            + p["b"].astype(dtype))


def forward(params, x, cfg, dtype=jnp.float32):
    prec = PRECISION[cfg["matmul_precision"]]
    x = x.astype(dtype)
    x = jax.nn.relu(_gn(params["stem_n"],
                        _conv(params["stem"], x, 1, dtype, prec), cfg, dtype))
    for si, n in enumerate(cfg["stage_sizes"]):
        for bi in range(n):
            p = params["blocks"][f"s{si}b{bi}"]
            stride = 2 if (bi == 0 and si > 0) else 1
            h = jax.nn.relu(_gn(p["n1"], _conv(p["c1"], x, stride, dtype,
                                               prec), cfg, dtype))
            h = _gn(p["n2"], _conv(p["c2"], h, 1, dtype, prec), cfg, dtype)
            sc = (_conv(p["proj"], x, stride, dtype, prec) if "proj" in p
                  else x)
            x = jax.nn.relu(h + sc)
    pooled = jnp.mean(x, axis=(1, 2))
    w, b = params["head"]["w"].astype(dtype), params["head"]["b"].astype(dtype)
    return jnp.dot(pooled, w, precision=prec) + b


def loss(params, batch, cfg, dtype=jnp.float32):
    """Mean softmax cross-entropy of the batch."""
    logits = forward(params, batch["x"], cfg, dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    gold = jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]
    return -jnp.mean(gold)


def sgd_step(params, batch, lr, cfg, dtype=jnp.float32):
    """One plain SGD step: ``p - lr * grad``, in ``dtype``."""
    lval, grads = jax.value_and_grad(loss)(params, batch, cfg, dtype)
    new = jax.tree.map(lambda p, g: (p.astype(dtype)
                                     - jnp.asarray(lr, dtype) * g.astype(dtype)),
                       params, grads)
    return new, lval


def forward_flops(cfg) -> int:
    """Forward FLOPs of one image: the stem, two 3x3 convolutions a block,
    a 1x1 projection where the width changes, stride 2 at the first block
    of every stage after the first, and the dense head."""
    h = cfg["image_size"]
    cin, width = cfg["in_channels"], cfg["width"]
    total = conv_flops(h, h, 3, 3, cin, width)
    cin = width
    for stage, n in enumerate(cfg["stage_sizes"]):
        cout = width * 2 ** stage
        for block in range(n):
            stride = 2 if (block == 0 and stage > 0) else 1
            h //= stride
            total += conv_flops(h, h, 3, 3, cin, cout)
            total += conv_flops(h, h, 3, 3, cout, cout)
            if cin != cout:
                total += conv_flops(h, h, 1, 1, cin, cout)
            cin = cout
    return total + 2 * cin * cfg["num_classes"]
