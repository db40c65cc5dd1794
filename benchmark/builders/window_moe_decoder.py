"""``family: window_moe_decoder`` -- grouped-query attention behind a sliding
window or over every earlier key by a published list of kinds, each kind with
a rope of its own, a norm a head on q and k, and sparse experts (softmax top-k,
renormalised, no shared expert) in every layer, an untied head (the Mellum 2
block), with LoRA adapters on every projection, through ``models/llama.py``,
``ops/eva.causal_attention`` with a window and ``ops/moe.py``, at the sizes of
the configuration's own ``config.json`` keys."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import flops_window
from benchmark.builders import DTYPES, Built, make_optax
from benchmark.references import window_moe_decoder as plain
from dpwa_tpu.models.llama import (
    Llama, LlamaConfig, YarnScaling, lora_filter, lora_optimizer, routing_of,
)
from dpwa_tpu.ops.cross_entropy import softmax_cross_entropy
from dpwa_tpu.utils import scopes
# At the top, so that a program without the window fails before JAX looks for
# a device.
from dpwa_tpu.utils.scopes import ATTN_WINDOW  # noqa: F401

# What models/llama.py computes, whatever the file says.
FIXED = dict(
    attention_bias=False, hidden_act="silu", norm_topk_prob=True,
    tie_word_embeddings=False, use_sliding_window=True,
)
# Positions of one sequence that both sides of the model check are given:
# two windows, so that half of the queries have a whole window behind them
# (at the 256 the other expert cells take, none has, and the window would be
# checked on nothing); 2,048 x 64 experts in float32 and a [2048, 2048] mask
# are small.
REFERENCE_POSITIONS = 2048


def rehearse(config: dict, cell: dict):
    """Toy sizes that keep what is new: the cut's own list of kinds, 4 query
    heads on 2 k / v heads of a size that the hidden size does not give, a
    window of a quarter of the sequence, both ropes, 8 experts of a width
    that is no power of two, two a token."""
    config = dict(
        config, hidden_size=64, head_dim=32, moe_intermediate_size=48,
        num_attention_heads=4, num_key_value_heads=2, num_experts=8,
        num_experts_per_tok=2, vocab_size=512, sliding_window=128,
        assumed=dict(
            config["assumed"], compute_dtype="float32", base_dtype="float32"
        ),
    )
    return config, dict(cell, per_peer_batch=2, seq_len=512)


def rope_of(group: dict):
    """``(theta, scaling)`` of one ``rope_parameters`` group."""
    if group["rope_type"] == "default":
        return float(group["rope_theta"]), None
    if group["rope_type"] != "yarn":
        raise ValueError(f"no rope of type {group['rope_type']!r}")
    return float(group["rope_theta"]), YarnScaling(
        factor=group["factor"],
        original_max_position_embeddings=group[
            "original_max_position_embeddings"
        ],
        beta_fast=group["beta_fast"], beta_slow=group["beta_slow"],
        attention_factor=group["attention_factor"],
    )


def model_of(config: dict, seq_len: int) -> Llama:
    for key, value in FIXED.items():
        if config[key] != value:
            raise ValueError(
                f"models/llama.py computes {key} = {value!r}, the "
                f"configuration says {config[key]!r}"
            )
    if set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("every layer's feed-forward is sparse here")
    assumed = config["assumed"]
    lora = assumed["lora"]
    ropes = config["rope_parameters"]
    return Llama(LlamaConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_size=config["head_dim"], d_ff=config["moe_intermediate_size"],
        max_seq_len=seq_len, lora_rank=lora["rank"], lora_alpha=lora["alpha"],
        dtype=DTYPES[assumed["compute_dtype"]],
        n_experts=config["num_experts"],
        n_experts_per_tok=config["num_experts_per_tok"],
        norm_eps=config["rms_norm_eps"], norm_topk_prob=True,
        layer_mixers=tuple(config["layer_types"]),
        sliding_window=config["sliding_window"],
        rope_by_kind=tuple(
            (kind, *rope_of(ropes[kind])) for kind in sorted(ropes)
        ),
        qk_norm_per_head=True, remat=assumed["remat"],
        param_dtype=DTYPES[assumed["base_dtype"]],
    ))


def build(config: dict, cell: dict) -> Built:
    rank = config["assumed"]["lora"]["rank"]
    seq_len = cell["seq_len"]
    model = model_of(config, seq_len)

    def loss_fn(params, batch):
        tokens, targets = batch
        logits = model.apply(params, tokens)
        with jax.named_scope(scopes.LOSS):
            return softmax_cross_entropy(logits, targets).mean()

    def reference_forward(params, tokens):
        # The program's own routing of these tokens, for the reference to
        # verify against its float32 router logits.
        sown = model.apply(params, tokens, mutable=["intermediates"])[1]
        return plain.forward(
            config, params, tokens, routing=routing_of(sown)["experts"]
        )

    opt = cell.get("optimizer") or config["assumed"]["optimizer"]
    if cell["exchange_filter"] not in (None, "lora"):
        raise ValueError(f"unknown exchange_filter {cell['exchange_filter']!r}")
    sequences = cell["peers"] * cell["per_peer_batch"]
    return Built(
        # Base leaves are created in base_dtype (param_dtype): nothing is
        # cast; adapters and routers are float32.
        init_fn=lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
        loss_fn=loss_fn,
        make_optimizer=lambda shapes: lora_optimizer(make_optax(opt), shapes),
        exchange_filter=lora_filter if cell["exchange_filter"] else None,
        batch_shape=dict(vocab_size=config["vocab_size"], seq_len=seq_len),
        flops_per_sample=seq_len
        * flops_window.window_lora_train_flops_per_token(
            config, seq_len, rank
        ),
        apply_fn=model.apply,
        reference_forward=reference_forward,
        # Causal in both kinds, so the first positions of one sequence see
        # what they see in the whole.
        reference_inputs=lambda batch: batch[0][:1, :REFERENCE_POSITIONS],
        kernel_work=dict(
            # Every layer's core, band and triangle, at the published head
            # size; the sliding layers' alone, with what full attention
            # would owe beside it; the experts' grouped matmuls.
            flash_attention=flops_window.attention_required(
                config, seq_len, sequences
            ),
            window_attention=flops_window.window_core_required(
                config, seq_len, sequences
            ),
            expert_layer=flops_window.expert_layer_required(
                config, sequences * seq_len, cell["peers"], rank
            ),
        ),
    )
