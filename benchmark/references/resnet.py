"""Plain reference: ResNet-50 forward in float32, training-mode batch
normalisation.

Written from He et al. 2015 (Table 1, 50-layer; bottleneck blocks with
projection shortcuts where the shape changes, option B) with the stride
of a down-sampling block on its 3x3 convolution, as fb.resnet.torch and
torchvision place it ("v1.5").  Batch normalisation is Ioffe & Szegedy
2015 in training mode: each channel is normalised by the mean and the
biased variance of the batch.  No kernels, none of the program's code;
``lax.conv_general_dilated`` at ``precision="highest"``.

Departure, the program's and stated in the configuration: every
convolution adds a bias (zero at initialisation).

Input is (N, 3, H, W) as the program takes it; weights are HWIO.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

STAGES = ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2))


def conv(x, p, stride: int, pad: int):
    y = lax.conv_general_dilated(
        x, p["w"], (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    return y if p.get("b") is None else y + p["b"]


def batch_norm(x, p, eps: float = 1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps) * p["w"] + p["b"]


def max_pool_3x3_s2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1),
                             [(0, 0), (1, 1), (1, 1), (0, 0)])


def log_probs(weights, images):
    """(N, 3, H, W) float32 -> (N, classes) log-probabilities."""
    convs, bns = iter(weights["convs"]), iter(weights["bns"])
    with jax.default_matmul_precision("highest"):
        x = jnp.transpose(images.astype(jnp.float32), (0, 2, 3, 1))
        x = jax.nn.relu(batch_norm(conv(x, next(convs), 2, 3), next(bns)))
        x = max_pool_3x3_s2(x)
        ch = 64
        for width, count, first_stride in STAGES:
            for block in range(count):
                stride = first_stride if block == 0 else 1
                y = jax.nn.relu(batch_norm(conv(x, next(convs), 1, 0),
                                           next(bns)))
                y = jax.nn.relu(batch_norm(conv(y, next(convs), stride, 1),
                                           next(bns)))
                y = batch_norm(conv(y, next(convs), 1, 0), next(bns))
                if ch != 4 * width:
                    x = batch_norm(conv(x, next(convs), stride, 0),
                                   next(bns))
                x = jax.nn.relu(x + y)
                ch = 4 * width
        x = jnp.mean(x, axis=(1, 2))
        logits = x @ weights["fc"]["w"] + weights["fc"]["b"]
        return jax.nn.log_softmax(logits, axis=-1)


def nll_loss(logp, labels):
    """Mean negative log-likelihood of (N,) 1-based labels."""
    picked = jnp.take_along_axis(
        logp, (labels.astype(jnp.int32) - 1)[:, None], axis=-1)
    return -jnp.mean(picked)
