"""``family: hybrid_ssm_decoder`` -- Mamba-1 mixers in all layers but one a
period, which keeps attention (grouped-query, no rope), a dense SwiGLU in
every layer and a head tied to the embedding, with LoRA adapters on every
projection, through ``models/llama.py`` and ``ops/ssm.py``, at the sizes of
the configuration's own ``config.json`` keys."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import flops, flops_ssm
from benchmark.builders import DTYPES, Built, make_optax
from benchmark.references import hybrid_ssm_decoder as plain
# At the top, so that a program without a Mamba mixer fails before JAX looks
# for a device.
from dpwa_tpu.models.llama import (  # noqa: F401
    Llama, LlamaConfig, MambaMixer, lora_filter, lora_optimizer,
)
from dpwa_tpu.ops.cross_entropy import softmax_cross_entropy
from dpwa_tpu.utils import scopes

# What models/llama.py computes, whatever the file says.
FIXED = dict(
    hidden_act="silu", mamba_conv_bias=True, mamba_proj_bias=False,
    num_experts=1, sliding_window=None, tie_word_embeddings=True,
)


def rehearse(config: dict, cell: dict):
    """Toy sizes that keep what is new: a period of 3 with its attention
    layer in the middle of 4 layers, 4 query heads on one shared k / v head,
    4 states a channel, a rank-8 step size, and three chunks of time."""
    config = dict(
        config, hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=1, num_hidden_layers=4, attn_layer_period=3,
        attn_layer_offset=1, mamba_d_state=4, mamba_dt_rank=8, vocab_size=512,
        assumed=dict(
            config["assumed"], compute_dtype="float32", base_dtype="float32"
        ),
    )
    return config, dict(cell, per_peer_batch=2, seq_len=384)


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
        rope_theta=None, lora_rank=lora["rank"], lora_alpha=lora["alpha"],
        dtype=DTYPES[assumed["compute_dtype"]],
        norm_eps=config["rms_norm_eps"],
        attn_layer_period=config["attn_layer_period"],
        attn_layer_offset=config["attn_layer_offset"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_dt_rank=config["mamba_dt_rank"],
        mamba_expand=config["mamba_expand"], tie_embeddings=True,
        remat=assumed["remat"], param_dtype=DTYPES[assumed["base_dtype"]],
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

    opt = cell.get("optimizer") or config["assumed"]["optimizer"]
    if cell["exchange_filter"] not in (None, "lora"):
        raise ValueError(f"unknown exchange_filter {cell['exchange_filter']!r}")
    sequences = cell["peers"] * cell["per_peer_batch"]
    attention_layers = dict(
        config, head_dim=config["hidden_size"] // config["num_attention_heads"],
        num_hidden_layers=flops_ssm.layer_kinds(config)["attention"],
    )
    return Built(
        # Base leaves are created in base_dtype (param_dtype): nothing is cast.
        init_fn=lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
        loss_fn=loss_fn,
        make_optimizer=lambda shapes: lora_optimizer(make_optax(opt), shapes),
        exchange_filter=lora_filter if cell["exchange_filter"] else None,
        batch_shape=dict(vocab_size=config["vocab_size"], seq_len=seq_len),
        flops_per_sample=seq_len
        * flops_ssm.hybrid_lora_train_flops_per_token(config, seq_len, rank),
        apply_fn=model.apply,
        reference_forward=lambda params, t: plain.forward(config, params, t),
        # Causal in all three mixers, so the first 1,024 positions of one
        # sequence see what they see in the whole; at a chunk of 128 they
        # cross seven chunk boundaries of the scan.
        reference_inputs=lambda batch: batch[0][:1, :1024],
        kernel_work=dict(
            flash_attention=flops.flash_attention_required(
                attention_layers, seq_len, sequences
            ),
            selective_scan=flops_ssm.selective_scan_required(
                config, sequences * seq_len
            ),
        ),
    )
