"""Bottleneck ResNet, as He et al. 2015 table 1 with the stride on the 3x3
(torchvision).  Departure from the paper, as the configuration states:
GroupNorm over groups of 16 channels (eps 1e-6) where the paper has
BatchNorm."""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _conv(x, kernel, stride):
    return jax.lax.conv_general_dilated(
        x, kernel.astype(jnp.float32), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )


def _group_norm(x, p, group=16, eps=1e-6):
    b, h, w, c = x.shape
    g = x.reshape(b, h, w, c // group, group)
    mean = g.mean(axis=(1, 2, 4), keepdims=True)
    var = jnp.square(g - mean).mean(axis=(1, 2, 4), keepdims=True)
    g = (g - mean) * jax.lax.rsqrt(var + eps)
    return g.reshape(x.shape) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride):
    y = jax.nn.relu(_group_norm(_conv(x, p["Conv_0"]["kernel"], 1), p["GroupNorm_0"]))
    y = jax.nn.relu(_group_norm(_conv(y, p["Conv_1"]["kernel"], stride), p["GroupNorm_1"]))
    y = _group_norm(_conv(y, p["Conv_2"]["kernel"], 1), p["GroupNorm_2"])
    if "Conv_3" in p:  # shapes differ: projection shortcut
        x = _group_norm(_conv(x, p["Conv_3"]["kernel"], stride), p["GroupNorm_3"])
    return jax.nn.relu(y + x)


def forward(config, params, images):
    p = params["params"]
    x = images.astype(jnp.float32)
    x = jax.nn.relu(_group_norm(_conv(x, p["Conv_0"]["kernel"], 2), p["GroupNorm_0"]))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    index = 0
    for stage, blocks in enumerate(config["stage_sizes"]):
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            x = _bottleneck(x, p[f"BottleneckBlock_{index}"], stride)
            index += 1
    x = x.mean(axis=(1, 2))
    dense = p["Dense_0"]
    return jnp.dot(x, dense["kernel"], precision=HIGHEST) + dense["bias"]
