"""``family: latent_moe_decoder`` -- multi-head latent attention, leading
dense SwiGLU layers, then a shared expert beside one chip's share of the
routed experts behind a sigmoid gate, with LoRA adapters on every projection,
through ``models/llama.py`` and ``ops/moe.py``, at the sizes of the
configuration's own ``config.json`` keys.  The file's ``n_routed_experts`` is
the share held here; ``published.n_routed_experts`` is the router's width."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import flops_latent
from benchmark.builders import DTYPES, Built, make_optax
from benchmark.references import latent_moe_decoder as plain
# At the top, so that a program without latent attention fails before JAX
# looks for a device.
from dpwa_tpu.models.llama import (
    Llama, LlamaConfig, YarnScaling, lora_filter, lora_optimizer, moe_loss,
    routing_of,
)

# What models/llama.py computes, whatever the file says.
FIXED = dict(
    attention_bias=False, ep_size=1, hidden_act="silu", moe_layer_freq=1,
    tie_word_embeddings=False, topk_method="none",
)


def rehearse(config: dict, cell: dict):
    """Toy sizes that keep what is new: a qk head (16 + 8) unlike the v head
    (16), a dense layer before two expert layers, a shared expert, and 4 of
    8 experts held."""
    config = dict(
        config, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=4, num_experts_per_tok=2,
        num_hidden_layers=3, vocab_size=512,
        published=dict(config["published"], n_routed_experts=8),
        rope_scaling=dict(
            config["rope_scaling"], original_max_position_embeddings=16
        ),
        assumed=dict(
            config["assumed"], compute_dtype="float32", base_dtype="float32",
            expert_offset=2,
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
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention gives every head its own k and v")
    assumed = config["assumed"]
    lora = assumed["lora"]
    scaling = config["rope_scaling"]
    if scaling is not None:
        scaling = dict(scaling)
        if scaling.pop("type") != "yarn":
            raise ValueError("models/llama.py scales rope by yarn alone")
        scaling = YarnScaling(**scaling)
    return Llama(LlamaConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        d_ff=config["moe_intermediate_size"], max_seq_len=seq_len,
        rope_theta=config["rope_theta"], lora_rank=lora["rank"],
        lora_alpha=lora["alpha"], dtype=DTYPES[assumed["compute_dtype"]],
        n_experts=config["published"]["n_routed_experts"],
        n_experts_per_tok=config["num_experts_per_tok"],
        router_aux_loss_coef=assumed["router_aux_loss_coef"],
        norm_eps=config["rms_norm_eps"], q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], rope_scaling=scaling,
        n_dense_layers=config["first_k_dense_replace"],
        d_ff_dense=config["intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        router_scoring=config["scoring_func"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=config["routed_scaling_factor"],
        experts_held=config["n_routed_experts"],
        expert_offset=assumed["expert_offset"], remat=assumed["remat"],
        param_dtype=DTYPES[assumed["base_dtype"]],
        activation_dtype=DTYPES[assumed["activation_dtype"]],
    ))


def build(config: dict, cell: dict) -> Built:
    rank = config["assumed"]["lora"]["rank"]
    seq_len = cell["seq_len"]
    model = model_of(config, seq_len)

    def as_the_step(fn):
        """``fn(params, tokens)`` under ``vmap`` over a peer axis of one: the
        entry the stacked step takes, where a share's products are the
        grouped kernels (``ops/moe.held_matmul``)."""
        def call(params, tokens):
            out = jax.vmap(fn)(
                jax.tree.map(lambda v: v[None], params), tokens[None]
            )
            return jax.tree.map(lambda v: v[0], out)
        return call

    def reference_forward(params, tokens):
        # What the program's routers saw, computed and chose on these
        # tokens, for the reference to verify.
        sown = as_the_step(
            lambda p, t: model.apply(p, t, mutable=["intermediates"])[1]
        )(params, tokens)
        return plain.forward(config, params, tokens, routing=routing_of(sown))

    opt = cell.get("optimizer") or config["assumed"]["optimizer"]
    if cell["exchange_filter"] not in (None, "lora"):
        raise ValueError(f"unknown exchange_filter {cell['exchange_filter']!r}")
    sequences = cell["peers"] * cell["per_peer_batch"]
    return Built(
        # Base leaves are created in base_dtype (param_dtype): nothing is cast.
        init_fn=lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
        loss_fn=lambda params, batch: moe_loss(model, params, *batch),
        make_optimizer=lambda shapes: lora_optimizer(make_optax(opt), shapes),
        exchange_filter=lora_filter if cell["exchange_filter"] else None,
        batch_shape=dict(vocab_size=config["vocab_size"], seq_len=seq_len),
        flops_per_sample=seq_len
        * flops_latent.latent_moe_lora_train_flops_per_token(
            config, seq_len, rank
        ),
        apply_fn=as_the_step(model.apply),
        reference_forward=reference_forward,
        # Causal, so the first 256 positions of one sequence see what they
        # see in the whole; every held expert on each of them is small.
        reference_inputs=lambda batch: batch[0][:1, :256],
        kernel_work=dict(
            latent_attn_core=flops_latent.latent_core_required(
                config, seq_len, sequences
            ),
            held_experts=flops_latent.held_experts_required(
                config, sequences * seq_len, cell["peers"], rank
            ),
        ),
    )
