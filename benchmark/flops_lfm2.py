"""FLOPs and bytes that a decoder of gated short convolutions and attention
layers by a published list of kinds, with a leading dense SwiGLU and sparse
experts after it, *requires* under LoRA fine-tuning, from shapes alone
(``family: conv_moe_decoder``; the conventions of ``benchmark/flops.py``
hold: a multiply-add is two operations, no base-weight gradient, causal
attention at half the square, no optimizer, no exchange, no recomputation,
plain Python on numbers).

Only what a token touches counts: of ``num_experts`` experts a token runs
``num_experts_per_tok``, whichever they are (dropless), and the router all its
columns."""

from __future__ import annotations

from benchmark import flops_moe
from benchmark.flops_latent import (
    _adapter_values, _values, swiglu_projections,
)

# Operations a (token, channel) of the gate, forward: ``b * u``, a
# multiply-add a tap (counted at the configuration's taps below) and ``c *
# z``.  The backward pass is counted as twice the forward, as a matmul's two
# products are.
GATE_PRODUCTS = 2


def layer_kinds(config: dict) -> dict:
    """How many layers take each mixer (``conv`` / ``attention``) and each
    feed-forward (``dense`` / ``experts``)."""
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types names a kind for every layer")
    dense = min(config["num_dense_layers"], len(kinds))
    return dict(
        conv=kinds.count("conv"), attention=kinds.count("full_attention"),
        dense=dense, experts=len(kinds) - dense,
    )


def conv_projections(config: dict) -> dict:
    d = config["hidden_size"]
    return {"in_proj": (d, 3 * d), "out_proj": (d, d)}


def parts(config: dict, rank: int) -> dict:
    """``(frozen, adapter)`` values that multiply one token's activations: a
    ``conv`` mixer, an ``attention`` mixer, the ``dense`` feed-forward, one
    ``expert``, the ``router`` and the ``head`` (the embedding, used a second
    time)."""
    d = config["hidden_size"]
    one = lambda shapes: (_values(shapes), _adapter_values(shapes, rank))
    return dict(
        conv=one(conv_projections(config)),
        attention=one(flops_moe.attention_projections(config)),
        dense=one(swiglu_projections(d, config["intermediate_size"])),
        expert=one(swiglu_projections(d, config["moe_intermediate_size"])),
        router=(d * config["num_experts"], 0),
        head=(d * config["vocab_size"], 0),
    )


def adapter_values(config: dict, rank: int) -> int:
    """Adapter values a replica holds (and a peer exchanges)."""
    kinds, p = layer_kinds(config), parts(config, rank)
    return (
        kinds["conv"] * p["conv"][1] + kinds["attention"] * p["attention"][1]
        + kinds["dense"] * p["dense"][1]
        + kinds["experts"] * config["num_experts"] * p["expert"][1]
    )


def base_values(config: dict) -> int:
    """Frozen values a replica holds: every kernel, the taps, two norms a
    layer and the two a head of each attention layer, the routers with their
    biases, the embedding (which is the head) and the last norm."""
    kinds, p = layer_kinds(config), parts(config, 0)
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    experts = config["num_experts"]
    head_dim = d // config["num_attention_heads"]
    return (
        kinds["conv"] * (p["conv"][0] + config["conv_L_cache"] * d)
        + kinds["attention"] * (p["attention"][0] + 2 * head_dim)
        + kinds["dense"] * p["dense"][0]
        + kinds["experts"] * (
            experts * p["expert"][0] + p["router"][0] + experts
        )
        + 2 * d * layers + p["head"][0] + d
    )


def gate_forward_ops(config: dict) -> int:
    """Operations a (token, channel) of one conv mixer's gate, forward."""
    return GATE_PRODUCTS + 2 * config["conv_L_cache"]


def lfm2_lora_train_flops_per_token(
    config: dict, seq_len: int, rank: int
) -> float:
    """Base matmuls forward and backward to the activations, adapters
    forward, backward and their own gradients, the attention layers' cores
    (6 matmuls at half the square) and the gates' own elementwise work (0.01 %
    of the whole at the published sizes)."""
    kinds, p = layer_kinds(config), parts(config, rank)
    k = config["num_experts_per_tok"]
    of = lambda i: (
        kinds["conv"] * p["conv"][i] + kinds["attention"] * p["attention"][i]
        + kinds["dense"] * p["dense"][i]
        + kinds["experts"] * (p["router"][i] + k * p["expert"][i])
    )
    core = 3 * 2 * seq_len * config["hidden_size"] * kinds["attention"]
    gate = 3 * gate_forward_ops(config) * config["hidden_size"] * kinds["conv"]
    return float(
        2 * 2 * (of(0) + p["head"][0]) + 3 * 2 * of(1) + core + gate
    )


def conv_gate_required(
    config: dict, tokens: int, dtype_bytes: int = 2
) -> dict:
    """What one training step's gates (``b * u``, the taps, ``c * z``) must
    do over ``tokens`` tokens, whatever implements them.  HBM bytes: the
    forward reads ``b``, ``c``, ``u`` and writes ``c * z`` once; the backward
    reads those three and the gradient and writes the three gradients once,
    each ``hidden_size`` wide in the stream's type: eleven passes.  FLOPs:
    :func:`gate_forward_ops` a (token, channel) forward and twice that
    backward.  On a v5e the bytes bound (22 bytes a channel against 24
    operations the MXU cannot take)."""
    d, layers = config["hidden_size"], layer_kinds(config)["conv"]
    return dict(
        flops=float(3 * gate_forward_ops(config) * d * tokens * layers),
        bytes=float((4 + 7) * d * dtype_bytes * tokens * layers),
    )


def expert_layer_required(
    config: dict, tokens: int, peers: int, rank: int
) -> dict:
    """``flops_moe.moe_experts_required`` of this configuration's expert
    layers, handed its keys under the names that function reads: an expert's
    width is ``moe_intermediate_size`` and only the layers after the dense
    ones hold experts."""
    return flops_moe.moe_experts_required(
        dict(
            config, intermediate_size=config["moe_intermediate_size"],
            num_hidden_layers=layer_kinds(config)["experts"],
        ),
        tokens, peers, rank,
    )
