"""``family: resnet`` -- bottleneck ResNets through ``models/resnet.py``."""

from __future__ import annotations

import jax.numpy as jnp
import optax

from benchmark import flops
from benchmark.builders import DTYPES, Built, make_optax
from benchmark.references import resnet as plain



def rehearse(config: dict, cell: dict):
    config = dict(
        config, stage_sizes=[1, 1, 1, 1], image_size=32, num_classes=10,
        assumed=dict(config["assumed"], compute_dtype="float32"),
    )
    task = dict(cell["task"], pattern_size=4)
    return config, dict(cell, per_peer_batch=2, task=task)


def build(config: dict, cell: dict) -> Built:
    from dpwa_tpu.models.resnet import ImageNetResNet

    if config["stage_filters"] != [64, 128, 256, 512] or (
        config["stem_filters"] != 64 or config["bottleneck_expansion"] != 4
    ):
        raise ValueError("models/resnet.py fixes the published widths")
    assumed = config["assumed"]
    model = ImageNetResNet(
        stage_sizes=tuple(config["stage_sizes"]),
        num_classes=config["num_classes"],
        dtype=DTYPES[assumed["compute_dtype"]],
    )
    size = config["image_size"]

    def init_fn(key):
        return model.init(key, jnp.zeros((1, size, size, 3)))

    def loss_fn(params, batch):
        x, y = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(params, x), y
        ).mean()

    opt = cell.get("optimizer") or assumed["optimizer"]
    return Built(
        init_fn=init_fn,
        loss_fn=loss_fn,
        make_optimizer=lambda shapes: make_optax(opt),
        exchange_filter=None,
        batch_shape=dict(image_size=size, num_classes=config["num_classes"]),
        flops_per_sample=flops.resnet_train_flops_per_sample(config),
        apply_fn=model.apply,
        reference_forward=lambda params, x: plain.forward(config, params, x),
        reference_inputs=lambda batch: batch[0][:2],  # two images
    )
