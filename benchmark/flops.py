"""FLOPs and bytes that the work *requires*, from shapes alone.

These functions are the yardstick's own: they count what forward and backward
need by the layer equations, not what a compiler emitted (no optimizer, no
exchange, no recomputation).  A multiply-add is two operations.  Everything
here is plain Python on integers, so a CPU test can pin it to hand-worked
values.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
WIRE_BYTES = {"f32": 4, "bf16": 2, "int8": 1}


def peak(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json"
        )
    return table[device_kind]


def _same_out(size: int, stride: int) -> int:
    return -(-size // stride)  # SAME padding: ceil(size / stride)


def resnet_forward_macs(config: dict) -> int:
    """Multiply-adds of one image's forward pass through a bottleneck ResNet
    (7x7/2 stem, 3x3/2 max-pool, stages of 1x1 -> 3x3(stride) -> 1x1 with a
    1x1 projection on the first block of a stage, global mean, dense head).
    Normalisation, ReLU and pooling are not matmul work and are not counted."""
    size = _same_out(config["image_size"], 2)
    macs = size * size * 7 * 7 * 3 * config["stem_filters"]
    size = _same_out(size, 2)  # max-pool
    c_in = config["stem_filters"]
    expansion = config["bottleneck_expansion"]
    for stage, (blocks, f) in enumerate(
        zip(config["stage_sizes"], config["stage_filters"])
    ):
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out = _same_out(size, stride)
            macs += size * size * c_in * f  # 1x1 at the input resolution
            macs += out * out * 9 * f * f  # 3x3 carries the stride
            macs += out * out * f * f * expansion  # 1x1 expand
            if block == 0:  # shapes differ: projection shortcut
                macs += out * out * c_in * f * expansion
            size, c_in = out, f * expansion
    return macs + c_in * config["num_classes"]


def resnet_train_flops_per_sample(config: dict) -> float:
    """Forward + backward: the backward pass is two matmuls (to the input
    and to the weights) for each one of the forward."""
    return 3 * 2 * resnet_forward_macs(config)


def decoder_matmul_params(config: dict) -> dict:
    """Weights that multiply an activation, per layer and in the head."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    h, kv, hd = (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    projections = {
        "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
        "wo": (h * hd, d), "w_gate": (d, ff), "w_up": (d, ff),
        "w_down": (ff, d),
    }
    return dict(
        projections=projections,
        layer=sum(a * b for a, b in projections.values()),
        head=d * config["vocab_size"],
    )


def lora_params_per_layer(config: dict, rank: int) -> int:
    proj = decoder_matmul_params(config)["projections"]
    return sum(rank * (a + b) for a, b in proj.values())


def decoder_lora_train_flops_per_token(
    config: dict, seq_len: int, rank: int
) -> float:
    """LoRA fine-tuning of a frozen base, per token of a length-``seq_len``
    causal sequence: base matmuls forward and backward to the activations
    (no base-weight gradient), adapters forward, backward and their own
    gradients, causal attention at half the square (forward 2 matmuls,
    backward 4).  The embedding is a lookup and counts nothing."""
    p = decoder_matmul_params(config)
    layers = config["num_hidden_layers"]
    base = 2 * 2 * (layers * p["layer"] + p["head"])
    adapters = 3 * 2 * layers * lora_params_per_layer(config, rank)
    d_attn = config["num_attention_heads"] * config["head_dim"]
    attention = 3 * 2 * seq_len * d_attn * layers  # 6 matmuls x 2 x T/2 x d
    return float(base + adapters + attention)


def flash_attention_required(
    config: dict, seq_len: int, sequences: int, dtype_bytes: int = 2
) -> dict:
    """What the attention kernels of one training step must do over
    ``sequences`` sequences: FLOPs (forward QK^T and PV, backward dV, dP, dQ,
    dK; causal, so half the square; the backward's recomputed QK^T is not
    counted) and HBM bytes (forward reads Q K V and writes O; backward reads
    Q K V O dO and writes dQ dK dV), with K and V at the full head count
    because ``single_device_attention`` expands GQA before the kernel."""
    h, hd = config["num_attention_heads"], config["head_dim"]
    layers = config["num_hidden_layers"]
    per_matmul = 2 * seq_len * seq_len * hd * h / 2
    tensor = seq_len * h * hd * dtype_bytes
    return dict(
        flops=6 * per_matmul * layers * sequences,
        bytes=12 * tensor * layers * sequences,
    )


def exchange_bytes_per_peer(leaf_sizes, wire_dtype: str) -> int:
    """Bytes one peer ships in one exchange: every exchanged element once,
    in the wire's width (an int8 wire also ships one float32 scale a leaf)."""
    sizes = list(leaf_sizes)
    extra = 4 * len(sizes) if wire_dtype == "int8" else 0
    return sum(sizes) * WIRE_BYTES[wire_dtype] + extra
