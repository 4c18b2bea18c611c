"""Plain float32 reference of the ``resnet_dp`` job: loss and gradient.

ResNet v1.5 with bottleneck blocks (He et al., arXiv:1512.03385, Table 1;
v1.5 carries the stride on the 3x3 convolution) written in ``jax.numpy`` and
``lax.conv_general_dilated`` alone: no flax module, no bfloat16, every
product at ``highest`` precision. Batch normalisation is in training mode,
with the statistics of the shard it is given. It reads the parameter tree
the program's model has (``conv_init``, ``bn_init``,
``BottleneckResNetBlock_<k>/{Conv_i, BatchNorm_i, conv_proj, norm_proj}``,
``Dense_0``): parameters are the interface, the arithmetic is its own.

Departures from the paper, shared with the program: 'SAME' padding as XLA
computes it (for a stride-2 3x3 on an even input that is (0, 1), where the
PyTorch model pads (1, 1)), as in the TensorFlow benchmark Horovod published.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import shards

BN_EPS = 1e-5


def _conv(x, kernel, stride, padding="SAME"):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def _batch_norm(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    return (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride):
    y = jax.nn.relu(_batch_norm(_conv(x, p["Conv_0"]["kernel"], 1),
                                p["BatchNorm_0"]))
    y = jax.nn.relu(_batch_norm(_conv(y, p["Conv_1"]["kernel"], stride),
                                p["BatchNorm_1"]))
    y = _batch_norm(_conv(y, p["Conv_2"]["kernel"], 1), p["BatchNorm_2"])
    if "conv_proj" in p:
        x = _batch_norm(_conv(x, p["conv_proj"]["kernel"], stride),
                        p["norm_proj"])
    return jax.nn.relu(x + y)


def logits(params, images, stage_sizes):
    """``images``: float32 ``[N, H, W, 3]``, already normalised."""
    x = _conv(images, params["conv_init"]["kernel"], 2, [(3, 3), (3, 3)])
    x = jax.nn.relu(_batch_norm(x, params["bn_init"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    k = 0
    for i, blocks in enumerate(stage_sizes):
        for j in range(blocks):
            x = _bottleneck(x, params[f"BottleneckResNetBlock_{k}"],
                            2 if i > 0 and j == 0 else 1)
            k += 1
    x = jnp.mean(x, axis=(1, 2))
    dense = params["Dense_0"]
    return jnp.dot(x, dense["kernel"],
                   precision=lax.Precision.HIGHEST) + dense["bias"]


def shard_loss(params, images, labels, stage_sizes):
    logp = jax.nn.log_softmax(logits(params, images, stage_sizes))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def loss_and_grad(params, images, labels, stage_sizes):
    """``images`` ``[shards, n, H, W, 3]`` float32, ``labels`` ``[shards, n]``;
    each shard with its own batch statistics. The mean loss and the mean
    gradient."""
    one = jax.jit(jax.value_and_grad(
        lambda p, x, y: shard_loss(p, x, y, stage_sizes)))
    return shards.loss_and_grad(one, params, images, labels)
