"""FLOPs and bytes that a decoder of state-space mixers with an attention
layer a period *requires* under LoRA fine-tuning, from shapes alone
(``family: hybrid_ssm_decoder``; the conventions of ``benchmark/flops.py``
hold: a multiply-add is two operations, no base-weight gradient, causal
attention at half the square, no optimizer, no exchange, no recomputation,
plain Python on numbers)."""

from __future__ import annotations

from benchmark.flops_latent import (
    _adapter_values, _values, swiglu_projections,
)

# Operations a (token, channel, state) of the recurrence, forward: delta A,
# exp, the decay times the state, (delta x) Bm, their sum, the state times Cm
# and its part of the sum over states, with the D x term and delta x spread
# over the states.  The backward pass is counted as twice the forward, as a
# matmul's two products are.
SCAN_FORWARD_OPS = 9
STREAM_BYTES = {"bfloat16": 2, "float32": 4}


def inner_channels(config: dict) -> int:
    return config["mamba_expand"] * config["hidden_size"]


def mamba_projections(config: dict) -> dict:
    d, e = config["hidden_size"], inner_channels(config)
    n, r = config["mamba_d_state"], config["mamba_dt_rank"]
    return {
        "in_proj": (d, 2 * e), "x_proj": (e, r + 2 * n), "dt_proj": (r, e),
        "out_proj": (e, d),
    }


def attention_projections(config: dict) -> dict:
    d, h = config["hidden_size"], config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    hd = d // h
    return {
        "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
        "wo": (h * hd, d),
    }


def mlp_projections(config: dict) -> dict:
    return swiglu_projections(
        config["hidden_size"], config["intermediate_size"]
    )


def layer_kinds(config: dict) -> dict:
    """How many of the layers keep attention, and how many take the mixer."""
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    attention = sum(
        i % period == offset for i in range(config["num_hidden_layers"])
    )
    return dict(
        attention=attention, mamba=config["num_hidden_layers"] - attention
    )


def parts(config: dict, rank: int) -> dict:
    """``(frozen, adapter)`` values that multiply one token's activations:
    a layer's ``mamba`` mixer, its ``attention``, its ``mlp``, and the
    ``head`` (the embedding, used a second time)."""
    one = lambda shapes: (_values(shapes), _adapter_values(shapes, rank))
    return dict(
        mamba=one(mamba_projections(config)),
        attention=one(attention_projections(config)),
        mlp=one(mlp_projections(config)),
        head=(config["hidden_size"] * config["vocab_size"], 0),
    )


def hybrid_lora_train_flops_per_token(
    config: dict, seq_len: int, rank: int
) -> float:
    """Base matmuls forward and backward to the activations, adapters
    forward, backward and their own gradients, the attention layers' cores
    (6 matmuls at half the square), and the scans' own elementwise work
    (0.3 % of the whole at the published sizes)."""
    kinds, p = layer_kinds(config), parts(config, rank)
    layers = kinds["mamba"] + kinds["attention"]
    frozen = (
        kinds["mamba"] * p["mamba"][0] + kinds["attention"] * p["attention"][0]
        + layers * p["mlp"][0] + p["head"][0]
    )
    adapters = (
        kinds["mamba"] * p["mamba"][1] + kinds["attention"] * p["attention"][1]
        + layers * p["mlp"][1]
    )
    core = 3 * 2 * seq_len * config["hidden_size"] * kinds["attention"]
    scan = (
        3 * SCAN_FORWARD_OPS * inner_channels(config)
        * config["mamba_d_state"] * kinds["mamba"]
    )
    return float(2 * 2 * frozen + 3 * 2 * adapters + core + scan)


def selective_scan_required(config: dict, tokens: int) -> dict:
    """What one training step's selective scans must do over ``tokens``
    tokens, whatever implements them.  FLOPs: :data:`SCAN_FORWARD_OPS` a
    (token, channel, state) forward and twice that backward.  HBM bytes: the
    forward reads ``x``, ``delta``, ``Bm``, ``Cm`` and writes ``y`` once; the
    backward reads those and ``dy`` and writes ``dx``, ``ddelta``, ``dBm``,
    ``dCm`` once, in the types the program hands over (``delta`` float32,
    the rest the stream's ``compute_dtype``).  The states kept at chunk
    boundaries, a chunk's recomputation and a recomputed block's second
    forward are not counted.  On a v5e the bytes bound (about 110 kB a token a
    layer against 2.2 MFLOP of work the MXU cannot take)."""
    e, n = inner_channels(config), config["mamba_d_state"]
    layers = layer_kinds(config)["mamba"]
    stream = STREAM_BYTES[config["assumed"]["compute_dtype"]]
    wide = (stream + 4) * e  # x (or dy, dx) beside delta (or ddelta)
    forward = wide + 2 * n * stream + e * stream
    backward = wide + 2 * n * stream + e * stream + wide + 2 * n * stream
    return dict(
        flops=float(3 * SCAN_FORWARD_OPS * e * n * tokens * layers),
        bytes=float((forward + backward) * tokens * layers),
    )
