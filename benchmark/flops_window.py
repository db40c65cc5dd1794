"""FLOPs and bytes that a decoder of sliding-window and full attention layers
by a published list of kinds, with sparse experts in every layer, *requires*
under LoRA fine-tuning, from shapes alone (``family: window_moe_decoder``; the
conventions of ``benchmark/flops.py`` hold: a multiply-add is two operations,
no base-weight gradient, no optimizer, no exchange, no recomputation, plain
Python on numbers).

What is new here is the count of (query, key) pairs: a full layer owes the
whole triangle with its diagonal, a sliding layer the band of
``sliding_window`` keys behind each query, its own among them.  A head's size
is the configuration's ``head_dim`` (the projected width ``heads x head_dim``
is not the hidden size), and an expert's width its
``moe_intermediate_size``."""

from __future__ import annotations

from benchmark import flops_moe
from benchmark.flops_latent import (
    _adapter_values, _values, swiglu_projections,
)

KINDS = ("sliding_attention", "full_attention")
# The attention core's matmuls a pair: ``QK^T`` and ``PV`` forward; ``dV``,
# ``dP``, ``dQ``, ``dK`` backward as ``benchmark/flops.py`` counts them for
# the model's FLOPs; and the scores once more for a kernel that keeps none
# (five to two, as ``flops_eva.CORE_BACKWARD`` has it for the same kernels).
MODEL_PRODUCTS = 6
KERNEL_PRODUCTS = 7


def layer_kinds(config: dict) -> dict:
    """How many layers take each kind of attention."""
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types names one of {KINDS} for every layer")
    if set(config["mlp_layer_types"]) != {"sparse"} or len(
        config["mlp_layer_types"]
    ) != len(kinds):
        raise ValueError("every layer's feed-forward is sparse here")
    return {kind: kinds.count(kind) for kind in KINDS}


def pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs a head of one sequence must score: the triangle
    with its diagonal, or with a ``window`` the band (query ``t`` sees ``min(t
    + 1, window)`` keys)."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def pairs_by_kind(config: dict, seq_len: int) -> dict:
    return dict(
        sliding_attention=pairs(seq_len, config["sliding_window"]),
        full_attention=pairs(seq_len),
    )


def attention_projections(config: dict) -> dict:
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d)}


def parts(config: dict, rank: int) -> dict:
    """``(frozen, adapter)`` values that multiply one token's activations: an
    ``attention`` layer's four projections (either kind), one ``expert``, the
    ``router`` and the ``head``."""
    d = config["hidden_size"]
    one = lambda shapes: (_values(shapes), _adapter_values(shapes, rank))
    return dict(
        attention=one(attention_projections(config)),
        expert=one(swiglu_projections(d, config["moe_intermediate_size"])),
        router=(d * config["num_experts"], 0),
        head=(d * config["vocab_size"], 0),
    )


def adapter_values(config: dict, rank: int) -> int:
    """Adapter values a replica holds (and a peer exchanges)."""
    p = parts(config, rank)
    return config["num_hidden_layers"] * (
        p["attention"][1] + config["num_experts"] * p["expert"][1]
    )


def layer_values(config: dict) -> int:
    """Frozen values of one layer: the four projections, the two norms a
    head, every expert, the router and the layer's two norms."""
    p = parts(config, 0)
    return (
        p["attention"][0] + 2 * config["head_dim"]
        + config["num_experts"] * p["expert"][0] + p["router"][0]
        + 2 * config["hidden_size"]
    )


def base_values(config: dict) -> int:
    """Frozen values a replica holds: the layers, the embedding, the untied
    head and the last norm."""
    return (
        config["num_hidden_layers"] * layer_values(config)
        + 2 * parts(config, 0)["head"][0] + config["hidden_size"]
    )


def core_pairs_per_sequence(config: dict, seq_len: int) -> int:
    """Pairs a head owes over the stack's layers, each at its kind's count."""
    kinds, owed = layer_kinds(config), pairs_by_kind(config, seq_len)
    return sum(kinds[kind] * owed[kind] for kind in KINDS)


def window_lora_train_flops_per_token(
    config: dict, seq_len: int, rank: int
) -> float:
    """Base matmuls forward and backward to the activations (attention, the
    router's every column, the token's ``num_experts_per_tok`` experts, the
    head), adapters forward, backward and their own gradients, and the
    attention cores over the pairs each layer's kind owes
    (:data:`MODEL_PRODUCTS` matmuls a pair)."""
    p, layers = parts(config, rank), config["num_hidden_layers"]
    k = config["num_experts_per_tok"]
    of = lambda i: layers * (
        p["attention"][i] + p["router"][i] + k * p["expert"][i]
    )
    width = config["num_attention_heads"] * config["head_dim"]
    core = (
        MODEL_PRODUCTS * 2 * width
        * core_pairs_per_sequence(config, seq_len) / seq_len
    )
    return float(2 * 2 * (of(0) + p["head"][0]) + 3 * 2 * of(1) + core)


def _core_required(
    config: dict, layers_pairs: int, layers: int, seq_len: int,
    sequences: int, products: int, dtype_bytes: int,
) -> dict:
    """FLOPs of ``products`` matmuls over ``layers_pairs`` pairs a head, and
    the HBM bytes of ``layers`` cores: the forward reads ``Q K V`` and writes
    ``O``; the backward reads ``Q K V O dO`` and writes ``dQ dK dV``; ``K V dK
    dV`` at the grouped head count, as the kernels move them."""
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    q_tensor = seq_len * h * hd * dtype_bytes
    kv_tensor = seq_len * kv * hd * dtype_bytes
    return dict(
        flops=float(products * 2 * hd * h * layers_pairs * sequences),
        bytes=float((6 * q_tensor + 6 * kv_tensor) * layers * sequences),
        pairs=layers_pairs,
    )


def window_core_required(
    config: dict, seq_len: int, sequences: int, dtype_bytes: int = 2
) -> dict:
    """What the sliding layers' cores of one training step must do over
    ``sequences`` sequences, whatever implements them: the band's pairs and
    no block's edge (:data:`KERNEL_PRODUCTS` matmuls a pair).  ``pairs`` and
    ``triangle_pairs`` are a head's, over the sliding layers of one sequence:
    what the window owes and what full attention would."""
    layers = layer_kinds(config)["sliding_attention"]
    owed = pairs_by_kind(config, seq_len)
    work = _core_required(
        config, layers * owed["sliding_attention"], layers, seq_len,
        sequences, KERNEL_PRODUCTS, dtype_bytes,
    )
    return dict(work, triangle_pairs=layers * owed["full_attention"])


def attention_required(
    config: dict, seq_len: int, sequences: int, dtype_bytes: int = 2
) -> dict:
    """What every layer's core must do a step, band and triangle together,
    by the count ``flops.flash_attention_required`` keeps
    (:data:`MODEL_PRODUCTS` matmuls a pair: the backward's second ``QK^T`` is
    not counted), for ``flash_attention_roofline``."""
    return _core_required(
        config, core_pairs_per_sequence(config, seq_len),
        config["num_hidden_layers"], seq_len, sequences, MODEL_PRODUCTS,
        dtype_bytes,
    )


def expert_layer_required(
    config: dict, tokens: int, peers: int, rank: int
) -> dict:
    """``flops_moe.moe_experts_required`` of this configuration's expert
    layers, handed its keys under the names that function reads (an expert's
    width is ``moe_intermediate_size``)."""
    return flops_moe.moe_experts_required(
        dict(config, intermediate_size=config["moe_intermediate_size"]),
        tokens, peers, rank,
    )
