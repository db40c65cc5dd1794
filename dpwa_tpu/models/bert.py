"""BERT-style masked-LM encoder (Flax) — gossip config 4.

BASELINE.json:10: "BERT-base MLM (Flax), 64-peer gossip, hierarchical
intra/inter-host averaging".  Clean-room implementation of the standard
architecture (Devlin et al. 2018: learned positions, post-LN encoder blocks,
GELU FF, tied-free MLM head); :func:`bert_base_config` carries the real
BERT-base dimensions, tests use tiny ones — identical code and pytree paths.

The hierarchical averaging itself is a *schedule*, not a model property:
``protocol.schedule: hierarchical`` with ``group_size`` = chips per host
makes intra-group slots ride ICI and the sparse inter-group slots cross DCN
(see dpwa_tpu.parallel.schedules._hierarchical_pool)."""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 512
    dtype: jnp.dtype = jnp.float32


def bert_base_config(dtype=None) -> BertConfig:
    return BertConfig(**({} if dtype is None else {"dtype": dtype}))


def bert_tiny_config(dtype=None) -> BertConfig:
    return BertConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq_len=64,
        **({} if dtype is None else {"dtype": dtype}),
    )


class EncoderBlock(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.cfg
        attn_out = nn.MultiHeadDotProductAttention(
            num_heads=cfg.n_heads, dtype=cfg.dtype, name="attn"
        )(x, x, mask=mask)
        x = nn.LayerNorm(name="attn_ln")(x + attn_out)
        h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, name="ff_in")(x)
        h = nn.gelu(h)
        h = nn.Dense(cfg.d_model, dtype=cfg.dtype, name="ff_out")(h)
        return nn.LayerNorm(name="ff_ln")(x + h)


class BertMLM(nn.Module):
    """Encoder + MLM head; returns logits [B, T, vocab]."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, tokens, attention_mask=None):
        cfg = self.cfg
        B, T = tokens.shape
        x = nn.Embed(cfg.vocab_size, cfg.d_model, name="tok_embed")(tokens)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (cfg.max_seq_len, cfg.d_model),
        )
        x = x + pos[None, :T]
        x = nn.LayerNorm(name="embed_ln")(x)
        if attention_mask is None:
            mask = None
        else:
            mask = attention_mask[:, None, None, :].astype(bool)
        for i in range(cfg.n_layers):
            x = EncoderBlock(cfg, name=f"layer_{i}")(x, mask)
        x = nn.Dense(cfg.d_model, dtype=cfg.dtype, name="mlm_dense")(x)
        x = nn.gelu(x)
        x = nn.LayerNorm(name="mlm_ln")(x)
        return nn.Dense(cfg.vocab_size, dtype=jnp.float32, name="mlm_head")(x)


MASK_TOKEN = 0  # convention for the synthetic MLM task


def mlm_mask_batch(
    tokens: np.ndarray, rng: np.random.Generator, mask_prob: float = 0.15
):
    """Standard MLM corruption: returns (inputs, targets, loss_weights)."""
    mask = rng.random(tokens.shape) < mask_prob
    inputs = np.where(mask, MASK_TOKEN, tokens)
    return inputs.astype(np.int32), tokens.astype(np.int32), mask.astype(
        np.float32
    )


def mlm_loss_fn(model: BertMLM):
    """Per-peer masked-LM loss for the gossip train step."""
    from dpwa_tpu.ops.cross_entropy import softmax_cross_entropy

    def loss_fn(params, batch):
        inputs, targets, weights = batch
        logits = model.apply(params, inputs)
        losses = softmax_cross_entropy(logits, targets)
        return (losses * weights).sum() / jnp.maximum(weights.sum(), 1.0)

    return loss_fn
