"""``family: eva_decoder`` -- RMSNorm (unit offset) / RoPE / SwiGLU decoders
whose attention is EVA (exact inside a window, a summary a chunk of every
earlier window, one softmax over both) and whose head predicts the next
``num_pred_heads`` tokens, with LoRA adapters on all seven projections,
through ``models/llama.py`` and ``ops/eva.py``, at the sizes of the
configuration's own ``config.json`` keys."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import flops_eva
from benchmark.builders import DTYPES, Built, make_optax
from benchmark.references import eva_decoder as plain
# At the top, so that a program without EVA attention fails before JAX looks
# for a device.
from dpwa_tpu.models.llama import (  # noqa: F401
    EvaAttention, Llama, LlamaConfig, lora_filter, lora_optimizer,
)
from dpwa_tpu.ops.cross_entropy import softmax_cross_entropy
from dpwa_tpu.utils import scopes

# What models/llama.py computes, whatever the file says.
FIXED = dict(
    attention_bias=False, attention_class="eva", hidden_act="silu",
    fp32_ln=False, fp32_logits=True, mixedp_attn=True,
    norm_add_unit_offset=True, rope_scaling=None, tie_word_embeddings=False,
)


def rehearse(config: dict, cell: dict):
    """Toy sizes that keep what is new: three windows of 32 positions in
    chunks of 4 (the third sees two windows' summaries), 4 heads, 2 layers,
    3 next-byte heads over the whole vocabulary of 320."""
    config = dict(
        config, hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=4, num_hidden_layers=2, window_size=32,
        chunk_size=4, num_pred_heads=3,
        assumed=dict(
            config["assumed"], compute_dtype="float32", base_dtype="float32"
        ),
    )
    return config, dict(cell, per_peer_batch=2, seq_len=96)


def model_of(config: dict, seq_len: int) -> Llama:
    for key, value in FIXED.items():
        if config[key] != value:
            raise ValueError(
                f"models/llama.py computes {key} = {value!r}, the "
                f"configuration says {config[key]!r}"
            )
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("EVA attention has as many k / v heads as q heads")
    assumed = config["assumed"]
    lora = assumed["lora"]
    if not config["fp32_skip_add"]:
        raise ValueError(
            "models/llama.py keeps the residual stream float32 for this "
            "family (fp32_skip_add), the configuration says false"
        )
    return Llama(LlamaConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"], max_seq_len=seq_len,
        rope_theta=config["rope_theta"], lora_rank=lora["rank"],
        lora_alpha=lora["alpha"], dtype=DTYPES[assumed["compute_dtype"]],
        norm_eps=config["rms_norm_eps"], eva_window=config["window_size"],
        eva_chunk=config["chunk_size"], n_pred_heads=config["num_pred_heads"],
        norm_unit_offset=True, fp32_skip_add=True, remat=assumed["remat"], param_dtype=DTYPES[assumed["base_dtype"]],
    ))


def multi_head_loss(logits, targets):
    """The mean over the heads of each head's mean cross-entropy.  ``logits
    [B, T, heads, vocab]``; ``targets [B, T]`` are the inputs shifted left by
    one, so head ``i`` at position ``t`` is held to ``targets[t + i]`` over
    the ``T - i`` positions where that lies inside the sequence."""
    T, heads = logits.shape[1], logits.shape[2]
    ahead = jnp.arange(T)[:, None] + jnp.arange(heads)  # [T, heads]
    inside = ahead < T
    losses = softmax_cross_entropy(
        logits, targets[:, jnp.minimum(ahead, T - 1)]
    )  # [B, T, heads]
    per_head = jnp.where(inside, losses, 0.0).sum((0, 1)) / (
        logits.shape[0] * inside.sum(0)
    )
    return per_head.mean()


def build(config: dict, cell: dict) -> Built:
    rank = config["assumed"]["lora"]["rank"]
    seq_len = cell["seq_len"]
    model = model_of(config, seq_len)

    def loss_fn(params, batch):
        tokens, targets = batch
        logits = model.apply(params, tokens)
        with jax.named_scope(scopes.LOSS):
            return multi_head_loss(logits, targets)

    opt = cell.get("optimizer") or config["assumed"]["optimizer"]
    if cell["exchange_filter"] not in (None, "lora"):
        raise ValueError(f"unknown exchange_filter {cell['exchange_filter']!r}")
    sequences = cell["peers"] * cell["per_peer_batch"]
    # Causal, and a window's summaries are of earlier windows alone, so the
    # first three windows of one sequence see what they see in the whole: the
    # third has two windows' summaries and none of its own.
    checked = min(seq_len, 3 * config["window_size"])
    return Built(
        # Base leaves are created in base_dtype (param_dtype): nothing is cast.
        init_fn=lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
        loss_fn=loss_fn,
        make_optimizer=lambda shapes: lora_optimizer(make_optax(opt), shapes),
        exchange_filter=lora_filter if cell["exchange_filter"] else None,
        batch_shape=dict(vocab_size=config["vocab_size"], seq_len=seq_len),
        flops_per_sample=seq_len
        * flops_eva.eva_lora_train_flops_per_token(config, seq_len, rank),
        apply_fn=model.apply,
        reference_forward=lambda params, t: plain.forward(config, params, t),
        reference_inputs=lambda batch: batch[0][:1, :checked],
        kernel_work=dict(eva_attention=flops_eva.eva_core_required(
            config, seq_len, sequences
        )),
    )
