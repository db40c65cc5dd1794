"""FLOPs and bytes that a latent-attention decoder with a shared expert beside
a share of its routed experts *requires* under LoRA fine-tuning, from shapes
alone (``family: latent_moe_decoder``; the conventions of
``benchmark/flops.py`` hold: a multiply-add is two operations, no optimizer,
no exchange, no recomputation, plain Python on numbers).

Only what this chip computes counts.  Of the ``published.n_routed_experts``
experts it holds ``n_routed_experts``; a token sends
``num_experts_per_tok`` assignments over all of them, so under even routing
``num_experts_per_tok x held / total`` of a token's assignments land here
(8 x 8 / 192 = 1/3 at the published sizes), and the held experts' work is
counted over that expected number of rows.  Attention, the leading dense
layers, the shared expert, the router (all its columns) and the head over the
rows of the vocabulary held are counted whole.
"""

from __future__ import annotations


def attention_projections(config: dict) -> dict:
    d, h = config["hidden_size"], config["num_attention_heads"]
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    nope, pe, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                    config["v_head_dim"])
    return {
        "wq_a": (d, q_rank), "wq_b": (q_rank, h * (nope + pe)),
        "wkv_a": (d, kv_rank + pe), "wkv_b": (kv_rank, h * (nope + dv)),
        "wo": (h * dv, d),
    }


def swiglu_projections(d: int, width: int) -> dict:
    return {"w_gate": (d, width), "w_up": (d, width), "w_down": (width, d)}


def _values(shapes: dict) -> int:
    return sum(a * b for a, b in shapes.values())


def _adapter_values(shapes: dict, rank: int) -> int:
    return sum(rank * (a + b) for a, b in shapes.values())


def held_share(config: dict) -> float:
    """The expected share of a token's assignments that lands on the experts
    held here, under even routing."""
    return config["n_routed_experts"] / config["published"]["n_routed_experts"]


def parts(config: dict, rank: int) -> dict:
    """Frozen values that multiply one token's activations, and adapter
    values likewise, by part: ``attention`` (a layer), ``dense`` (a leading
    layer's MLP), ``shared``, ``router``, ``expert`` (one routed expert) and
    ``head``; ``(frozen, adapter)`` each."""
    d = config["hidden_size"]
    one = lambda shapes: (_values(shapes), _adapter_values(shapes, rank))
    expert = swiglu_projections(d, config["moe_intermediate_size"])
    shared = swiglu_projections(
        d, config["n_shared_experts"] * config["moe_intermediate_size"]
    )
    return dict(
        attention=one(attention_projections(config)),
        dense=one(swiglu_projections(d, config["intermediate_size"])),
        shared=one(shared),
        expert=one(expert),
        router=(d * config["published"]["n_routed_experts"], 0),
        head=(d * config["vocab_size"], 0),
    )


def core_forward_flops_per_token(config: dict, seq_len: int) -> float:
    """QK^T at the qk head size and PV at the v head size, causal: half the
    square, so ``seq_len / 2`` keys a query on average."""
    h = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return 2 * (seq_len / 2) * h * (qk + config["v_head_dim"])


def forward_flops_per_token(config: dict, seq_len: int, rank: int) -> dict:
    """One token's forward FLOPs through the frozen weights, a layer of each
    kind and the head, by part (the adapters aside)."""
    p = parts(config, rank)
    experts = config["num_experts_per_tok"] * held_share(config) * p["expert"][0]
    core = core_forward_flops_per_token(config, seq_len)
    attention = 2 * p["attention"][0]
    return dict(
        projections=attention, core=core, dense=2 * p["dense"][0],
        shared=2 * p["shared"][0], router=2 * p["router"][0],
        experts=2 * experts, head=2 * p["head"][0],
        expert_layer=attention + core
        + 2 * (p["shared"][0] + p["router"][0] + experts),
    )


def latent_moe_lora_train_flops_per_token(
    config: dict, seq_len: int, rank: int
) -> float:
    """LoRA fine-tuning of the frozen base, per token of a causal
    length-``seq_len`` sequence: frozen matmuls forward and backward to the
    activations (no base-weight gradient); adapters forward, backward and
    their own gradients; the attention core's six matmuls at 192 / 128 over
    half the square; the held experts at their expected rows.  The embedding
    is a lookup; the sigmoid, top-k, sort and gathers are not matmul work;
    the recomputed forward is not required work: none of them counts."""
    p = parts(config, rank)
    layers = config["num_hidden_layers"]
    dense_layers = config["first_k_dense_replace"]
    expert_layers = layers - dense_layers
    routed = config["num_experts_per_tok"] * held_share(config)
    frozen = (
        layers * p["attention"][0] + dense_layers * p["dense"][0]
        + expert_layers * (
            p["shared"][0] + p["router"][0] + routed * p["expert"][0]
        ) + p["head"][0]
    )
    adapters = (
        layers * p["attention"][1] + dense_layers * p["dense"][1]
        + expert_layers * (p["shared"][1] + routed * p["expert"][1])
    )
    core = 3 * layers * core_forward_flops_per_token(config, seq_len)
    return float(2 * 2 * frozen + 3 * 2 * adapters + core)


def latent_core_required(
    config: dict, seq_len: int, sequences: int, dtype_bytes: int = 2
) -> dict:
    """What the attention core of one training step must do over
    ``sequences`` sequences at the published head sizes (q and k 192, v
    128; every head with a key of its own, as the kernel is handed them):
    FLOPs (forward QK^T and PV; backward dV, dP, dQ, dK; causal, half the
    square; no recomputed product) and HBM bytes (forward reads Q K V and
    writes O; backward reads Q K V O dO and writes dQ dK dV).  Padding to
    one head size of 256 and full diagonal blocks are not required work."""
    h, layers = config["num_attention_heads"], config["num_hidden_layers"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    per_width = seq_len * seq_len * h  # 2 x (T^2 / 2) x h, a unit of width
    return dict(
        flops=3 * per_width * (qk + dv) * layers * sequences,
        bytes=6 * seq_len * h * (qk + dv) * dtype_bytes * layers * sequences,
    )


def held_experts_required(
    config: dict, tokens: int, peers: int, rank: int,
    base_bytes: int = 2, adapter_bytes: int = 4, row_bytes: int = 2,
) -> dict:
    """What the held experts' grouped matmuls of one training step must do
    over ``tokens`` tokens (all peers') with ``peers`` copies of the weights,
    as ``flops_moe.moe_experts_required`` counts a whole expert layer: FLOPs
    of the frozen kernels forward and to the activations and of the adapters
    three times, over the expected ``tokens x num_experts_per_tok x held /
    total`` rows; bytes of every held expert's weights once a pass (kernels
    2, adapters 3) and of each projection's rows in and out (3 passes)."""
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    rows = tokens * config["num_experts_per_tok"] * held_share(config)
    shapes = swiglu_projections(
        config["hidden_size"], config["moe_intermediate_size"]
    )
    kernel, adapter = _values(shapes), _adapter_values(shapes, rank)
    experts = peers * config["n_routed_experts"]
    return dict(
        flops=layers * rows * 2 * (2 * kernel + 3 * adapter),
        bytes=layers * (
            experts * (2 * kernel * base_bytes + 3 * adapter * adapter_bytes)
            + 3 * rows
            * sum(a + b for a, b in shapes.values()) * row_bytes
        ),
    )
