"""``family: conv_moe_decoder`` -- a mixer a layer by a published list of kinds
(a gated short convolution, or grouped-query attention with a norm a head and
rope), a dense SwiGLU in the leading layers and sparse experts behind a biased
sigmoid gate in the others, a head tied to the embedding (the LFM2-MoE block),
with LoRA adapters on every projection, through ``models/llama.py``,
``ops/moe.py`` and ``ops/ssm.causal_conv1d``, at the sizes of the
configuration's own ``config.json`` keys."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import flops, flops_lfm2
from benchmark.builders import DTYPES, Built, make_optax
from benchmark.references import conv_moe_decoder as plain
# At the top, so that a program without the short convolution fails before
# JAX looks for a device.
from dpwa_tpu.models.llama import (  # noqa: F401
    Llama, LlamaConfig, ShortConv, lora_filter, lora_optimizer, routing_of,
)
from dpwa_tpu.ops.cross_entropy import softmax_cross_entropy
from dpwa_tpu.utils import scopes

# What models/llama.py computes, whatever the file says.
FIXED = dict(conv_bias=False, norm_topk_prob=True, use_expert_bias=True)
# The file's names for a layer's mixer -> ``LlamaConfig.layer_mixers``'.
MIXERS = {"conv": "conv", "full_attention": "attention"}


def rehearse(config: dict, cell: dict):
    """Toy sizes that keep what is new: the cut's own list of kinds (a
    convolution first, attention second), a dense layer before four expert
    layers, 4 query heads on 2 k / v heads of size 16, and 8 experts of a
    width that is no power of two, two a token."""
    config = dict(
        config, hidden_size=64, intermediate_size=128, moe_intermediate_size=48,
        num_attention_heads=4, num_key_value_heads=2, num_experts=8,
        num_experts_per_tok=2, vocab_size=512,
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
        d_ff=config["moe_intermediate_size"], max_seq_len=seq_len,
        rope_theta=config["rope_theta"], lora_rank=lora["rank"],
        lora_alpha=lora["alpha"], dtype=DTYPES[assumed["compute_dtype"]],
        n_experts=config["num_experts"],
        n_experts_per_tok=config["num_experts_per_tok"],
        norm_eps=config["norm_eps"],
        n_dense_layers=config["num_dense_layers"],
        d_ff_dense=config["intermediate_size"], router_scoring="sigmoid",
        norm_topk_prob=True,
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        router_bias=True, norm_topk_eps=assumed["norm_topk_eps"],
        layer_mixers=tuple(MIXERS[kind] for kind in config["layer_types"]),
        conv_taps=config["conv_L_cache"], qk_norm_per_head=True,
        tie_embeddings=True, remat=assumed["remat"],
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
        # What the program's routers saw, computed and chose on these
        # tokens, for the reference to verify.
        sown = model.apply(params, tokens, mutable=["intermediates"])[1]
        return plain.forward(config, params, tokens, routing=routing_of(sown))

    opt = cell.get("optimizer") or config["assumed"]["optimizer"]
    if cell["exchange_filter"] not in (None, "lora"):
        raise ValueError(f"unknown exchange_filter {cell['exchange_filter']!r}")
    sequences = cell["peers"] * cell["per_peer_batch"]
    attention_layers = dict(
        config, head_dim=config["hidden_size"] // config["num_attention_heads"],
        num_hidden_layers=flops_lfm2.layer_kinds(config)["attention"],
    )
    return Built(
        # Base leaves are created in base_dtype (param_dtype): nothing is
        # cast; adapters, routers and their biases are float32.
        init_fn=lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
        loss_fn=loss_fn,
        make_optimizer=lambda shapes: lora_optimizer(make_optax(opt), shapes),
        exchange_filter=lora_filter if cell["exchange_filter"] else None,
        batch_shape=dict(vocab_size=config["vocab_size"], seq_len=seq_len),
        flops_per_sample=seq_len
        * flops_lfm2.lfm2_lora_train_flops_per_token(config, seq_len, rank),
        apply_fn=model.apply,
        reference_forward=reference_forward,
        # Causal in both mixers, so the first 256 positions of one sequence
        # see what they see in the whole; every expert on each of them is
        # small.
        reference_inputs=lambda batch: batch[0][:1, :256],
        kernel_work=dict(
            # What the cores must do at the published head size, whatever
            # the kernels pad it to.
            flash_attention=flops.flash_attention_required(
                attention_layers, seq_len, sequences
            ),
            expert_layer=flops_lfm2.expert_layer_required(
                config, sequences * seq_len, cell["peers"], rank
            ),
            conv_gate=flops_lfm2.conv_gate_required(
                config, sequences * seq_len
            ),
        ),
    )
