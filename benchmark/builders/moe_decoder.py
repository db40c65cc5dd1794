"""``family: moe_decoder`` -- RMSNorm / RoPE / QK-norm attention with a
dropless top-k sparse-expert SwiGLU feed-forward (the OLMoE block) and LoRA
adapters on every projection, through ``models/llama.py`` and ``ops/moe.py``,
at the sizes of the configuration's own ``config.json`` keys."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import flops, flops_moe
from benchmark.builders import DTYPES, Built, make_optax
from benchmark.references import moe_decoder as plain
# At the top, so that a program without the expert layer fails before JAX
# looks for a device.
from dpwa_tpu.models.llama import (
    Llama, LlamaConfig, lora_filter, lora_optimizer, moe_loss, routing_of,
)

# What models/llama.py computes, whatever the file says.
FIXED = dict(
    attention_bias=False, clip_qkv=None, hidden_act="silu",
    norm_topk_prob=False, rms_norm_eps=1e-5, rope_scaling=None,
    tie_word_embeddings=False,
)


def rehearse(config: dict, cell: dict):
    config = dict(
        config, hidden_size=64, intermediate_size=64, num_attention_heads=4,
        num_key_value_heads=4, num_experts=8, num_experts_per_tok=2,
        num_hidden_layers=2, vocab_size=512,
        assumed=dict(
            config["assumed"], compute_dtype="float32", base_dtype="float32"
        ),
    )
    return config, dict(cell, per_peer_batch=2, seq_len=64)


def model_of(config: dict, seq_len: int) -> Llama:
    for key, value in FIXED.items():
        if config[key] != value:
            raise ValueError(
                f"models/llama.py computes {key} = {value!r}, the "
                f"configuration says {config[key]!r}"
            )
    assumed = config["assumed"]
    lora = assumed["lora"]
    return Llama(LlamaConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=seq_len,
        rope_theta=config["rope_theta"], lora_rank=lora["rank"],
        lora_alpha=lora["alpha"], dtype=DTYPES[assumed["compute_dtype"]],
        n_experts=config["num_experts"],
        n_experts_per_tok=config["num_experts_per_tok"], qk_norm=True,
        router_aux_loss_coef=assumed["router_aux_loss_coef"],
    ))


def build(config: dict, cell: dict) -> Built:
    assumed = config["assumed"]
    rank = assumed["lora"]["rank"]
    seq_len = cell["seq_len"]
    model = model_of(config, seq_len)
    base_dtype = DTYPES[assumed["base_dtype"]]

    def init_fn(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))

        # The frozen base is held in base_dtype; adapters and the router
        # (its logits decide a discontinuous choice) stay float32.
        def held(path, v):
            name = jax.tree_util.keystr(path)
            keep = lora_filter(name) or "router" in name
            return v if keep else v.astype(base_dtype)

        return jax.tree_util.tree_map_with_path(held, params)

    def reference_forward(params, tokens):
        # The program's own routing of these tokens, for the reference to
        # verify against its float32 router logits.
        sown = model.apply(params, tokens, mutable=["intermediates"])[1]
        return plain.forward(
            config, params, tokens, routing=routing_of(sown)["experts"]
        )

    opt = cell.get("optimizer") or assumed["optimizer"]
    if cell["exchange_filter"] not in (None, "lora"):
        raise ValueError(f"unknown exchange_filter {cell['exchange_filter']!r}")
    sequences = cell["peers"] * cell["per_peer_batch"]
    return Built(
        init_fn=init_fn,
        loss_fn=lambda params, batch: moe_loss(model, params, *batch),
        make_optimizer=lambda shapes: lora_optimizer(make_optax(opt), shapes),
        exchange_filter=lora_filter if cell["exchange_filter"] else None,
        batch_shape=dict(vocab_size=config["vocab_size"], seq_len=seq_len),
        flops_per_sample=seq_len
        * flops_moe.moe_decoder_lora_train_flops_per_token(
            config, seq_len, rank
        ),
        apply_fn=model.apply,
        reference_forward=reference_forward,
        # Causal, so the first 256 positions of one sequence see what they
        # see in the whole; every expert on each of them is small.
        reference_inputs=lambda batch: batch[0][:1, :256],
        kernel_work=dict(
            flash_attention=flops.flash_attention_required(
                dict(config, head_dim=config["hidden_size"]
                     // config["num_attention_heads"]),
                seq_len, sequences,
            ),
            moe_experts=flops_moe.moe_experts_required(
                config, sequences * seq_len, cell["peers"], rank
            ),
        ),
    )
