"""FLOPs and bytes that a sparse-expert decoder with LoRA adapters
*requires*, from shapes alone (``family: moe_decoder``; the dense family's
counts are in ``benchmark/flops.py``, whose conventions hold here: a
multiply-add is two operations, no optimizer, no exchange, no recomputation,
plain Python on integers).

Only what a token touches counts: of ``num_experts`` experts a token runs
``num_experts_per_tok``, so the expert layer's work is counted over the
``tokens x num_experts_per_tok`` assignment rows, whichever experts they
fall to (dropless: every row is computed, none twice).
"""

from __future__ import annotations


def attention_projections(config: dict) -> dict:
    d = config["hidden_size"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // h
    return {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d)}


def expert_projections(config: dict) -> dict:
    """One expert's three matrices; ``intermediate_size`` is its width."""
    d, f = config["hidden_size"], config["intermediate_size"]
    return {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def active_matmul_params_per_token(config: dict) -> dict:
    """Frozen weights that multiply one token's activations, a layer and in
    the head: attention, the router (all experts' columns), and the
    ``num_experts_per_tok`` experts it is routed to."""
    attention = sum(a * b for a, b in attention_projections(config).values())
    router = config["hidden_size"] * config["num_experts"]
    experts = config["num_experts_per_tok"] * sum(
        a * b for a, b in expert_projections(config).values()
    )
    return dict(
        attention=attention, router=router, experts=experts,
        layer=attention + router + experts,
        head=config["hidden_size"] * config["vocab_size"],
    )


def adapter_values_per_layer(config: dict, rank: int) -> dict:
    """Adapter values a layer holds (and a peer exchanges): rank-``rank``
    factors on the four attention projections and on every expert's three."""
    attention = sum(
        rank * (a + b) for a, b in attention_projections(config).values()
    )
    experts = config["num_experts"] * sum(
        rank * (a + b) for a, b in expert_projections(config).values()
    )
    return dict(attention=attention, experts=experts, layer=attention + experts)


def active_adapter_values_per_token(config: dict, rank: int) -> int:
    """Adapter values that multiply one token's activations, a layer."""
    per_expert = sum(
        rank * (a + b) for a, b in expert_projections(config).values()
    )
    return (
        adapter_values_per_layer(config, rank)["attention"]
        + config["num_experts_per_tok"] * per_expert
    )


def moe_decoder_lora_train_flops_per_token(
    config: dict, seq_len: int, rank: int
) -> float:
    """LoRA fine-tuning of a frozen sparse-expert base, per token of a causal
    length-``seq_len`` sequence: frozen matmuls (attention, router, the
    token's experts, head) forward and backward to the activations; adapters
    forward, backward and their own gradients; causal attention at half the
    square (6 matmuls).  The embedding is a lookup, the softmax, top-k, sort
    and gathers of the router are not matmul work: neither counts."""
    p = active_matmul_params_per_token(config)
    layers = config["num_hidden_layers"]
    base = 2 * 2 * (layers * p["layer"] + p["head"])
    adapters = 3 * 2 * layers * active_adapter_values_per_token(config, rank)
    d_attn = config["hidden_size"]
    attention = 3 * 2 * seq_len * d_attn * layers
    return float(base + adapters + attention)


def moe_experts_required(
    config: dict, tokens: int, peers: int, rank: int,
    base_bytes: int = 2, adapter_bytes: int = 4, row_bytes: int = 2,
) -> dict:
    """What the expert matmuls of one training step must do over ``tokens``
    tokens (all peers') with ``peers`` copies of the weights.

    FLOPs: the frozen kernels forward and to the activations (no base-weight
    gradient), the adapters forward, to the activations and to themselves,
    over ``tokens x num_experts_per_tok`` rows.  Bytes: every expert's
    weights once a pass (kernels: 2 passes; adapters: 3), and each
    projection's rows in and out once a pass (3 passes: forward, to the
    activations, to the adapters)."""
    layers = config["num_hidden_layers"]
    rows = tokens * config["num_experts_per_tok"]
    shapes = expert_projections(config).values()
    kernel = sum(a * b for a, b in shapes)
    adapter = sum(rank * (a + b) for a, b in shapes)
    experts = peers * config["num_experts"]
    return dict(
        flops=layers * rows * 2 * (2 * kernel + 3 * adapter),
        bytes=layers * (
            experts * (2 * kernel * base_bytes + 3 * adapter * adapter_bytes)
            + 3 * rows * sum(a + b for a, b in shapes) * row_bytes
        ),
    )
