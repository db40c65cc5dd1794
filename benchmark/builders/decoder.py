"""``family: decoder`` -- RMSNorm / RoPE / GQA / SwiGLU decoders with LoRA
adapters through ``models/llama.py``, at the sizes of the configuration's own
``config.json`` keys."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from benchmark import flops
from benchmark.builders import DTYPES, Built, make_optax
from benchmark.references import decoder as plain



def rehearse(config: dict, cell: dict):
    config = dict(
        config, hidden_size=128, intermediate_size=256,
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        num_hidden_layers=1, vocab_size=512,
        assumed=dict(
            config["assumed"], compute_dtype="float32", base_dtype="float32"
        ),
    )
    return config, dict(cell, per_peer_batch=2, seq_len=64)


def build(config: dict, cell: dict) -> Built:
    from dpwa_tpu.models.llama import (
        Llama, LlamaConfig, lora_filter, lora_optimizer,
    )

    assumed = config["assumed"]
    lora = assumed["lora"]
    heads, head_dim = config["num_attention_heads"], config["head_dim"]
    if heads * head_dim != config["hidden_size"]:
        raise ValueError("models/llama.py takes head_dim = hidden / heads")
    if config["rms_norm_eps"] != 1e-5 or config["sliding_window"] is not None:
        raise ValueError(
            "models/llama.py fixes rms_norm_eps 1e-5 and has no window"
        )
    seq_len = cell["seq_len"]
    model = Llama(LlamaConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"], n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=seq_len,
        rope_theta=config["rope_theta"], lora_rank=lora["rank"],
        lora_alpha=lora["alpha"], dtype=DTYPES[assumed["compute_dtype"]],
    ))
    base_dtype = DTYPES[assumed["base_dtype"]]

    def init_fn(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))
        # The frozen base is held in base_dtype; adapters stay float32.
        return jax.tree_util.tree_map_with_path(
            lambda path, v: v if lora_filter(jax.tree_util.keystr(path))
            else v.astype(base_dtype),
            params,
        )

    def loss_fn(params, batch):
        tokens, targets = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(params, tokens), targets
        ).mean()

    opt = cell.get("optimizer") or assumed["optimizer"]
    if cell["exchange_filter"] not in (None, "lora"):
        raise ValueError(f"unknown exchange_filter {cell['exchange_filter']!r}")
    sequences = cell["peers"] * cell["per_peer_batch"]
    return Built(
        init_fn=init_fn,
        loss_fn=loss_fn,
        make_optimizer=lambda shapes: lora_optimizer(make_optax(opt), shapes),
        exchange_filter=lora_filter if cell["exchange_filter"] else None,
        batch_shape=dict(vocab_size=config["vocab_size"], seq_len=seq_len),
        flops_per_sample=seq_len * flops.decoder_lora_train_flops_per_token(
            config, seq_len, lora["rank"]
        ),
        apply_fn=model.apply,
        reference_forward=lambda params, t: plain.forward(config, params, t),
        # Causal, so the first 256 positions of one sequence see what they
        # see in the whole; dense float32 attention over them is small.
        reference_inputs=lambda batch: batch[0][:1, :256],
        kernel_work=dict(flash_attention=flops.flash_attention_required(
            config, seq_len, sequences
        )),
    )
