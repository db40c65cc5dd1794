"""Compact Llama-family decoder with LoRA, for subset-pytree gossip.

BASELINE.json:11 (config 5): "Llama-3-8B LoRA fine-tune, pairwise-avg of
LoRA adapters across v5p-128" — only the LoRA adapter weights enter the
gossip exchange; base weights never move.  The reference never touches model
internals (it sees a flat parameter vector, SURVEY.md §5 "Long-context"), so
this is a clean-room Flax implementation of the standard architecture:
RMSNorm, rotary position embeddings, multi-head causal attention, SwiGLU
MLP.  ``llama3_8b_config()`` gives the real dimensions; tests and the
dry-run use tiny configs — same code, same pytree paths.

LoRA: :class:`LoRADense` adds ``lora_a``/``lora_b`` factors beside the
frozen base kernel.  Every LoRA leaf's path contains ``lora_``, so the
subset predicate :func:`lora_filter` selects exactly the adapter state for
the exchange (``dpwa_tpu.utils.pytree.partition``)."""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # GQA; None = MHA
    d_ff: int = 1376
    max_seq_len: int = 2048
    rope_theta: float = 500000.0
    lora_rank: int = 0  # 0 = no LoRA
    lora_alpha: float = 16.0
    dtype: jnp.dtype = jnp.float32
    # Sequence-parallel: name of the mesh axis the sequence is sharded
    # over.  When set, the model must run INSIDE shard_map over that axis
    # (each device holds a contiguous T_local block); attention becomes
    # exact ring attention over the axis and rope positions are globally
    # offset by the device's block index.  None = single-device attention.
    sp_axis: Optional[str] = None
    # Sequence layout over sp_axis: "contiguous" (device i holds block i)
    # or "zigzag" (device i holds global chunks i and 2n-1-i — the
    # causal-load-balanced layout of ops/zigzag_ring.py; callers shard
    # tokens/targets with zigzag_shard, and the model supplies matching
    # rope positions internally).
    sp_layout: str = "contiguous"
    # Sequence-parallel strategy over sp_axis: "ring" (K/V blocks rotate
    # by ppermute — ops/ring_attention.py and friends) or "a2a"
    # (Ulysses-style: all-to-all to head-sharded attention over the full
    # sequence — ops/ulysses.py; needs n_heads % sp == 0).
    sp_strategy: str = "ring"
    # Single-device attention implementation: "auto" uses the Pallas TPU
    # flash kernel when the backend is TPU and the shapes fit its tiling
    # (T and head_dim multiples of 128), else the dense O(T^2) einsum;
    # "flash" forces the kernel (raises off-TPU), "dense" forces einsum.
    # The sp path is unaffected (ring attention is already blockwise).
    attn_impl: str = "auto"
    # Sparse experts (the OLMoE block): ``n_experts`` > 0 replaces the dense
    # MLP by ``n_experts`` SwiGLU experts of width ``d_ff`` each, of which a
    # token takes its router's top ``n_experts_per_tok`` (dropless:
    # ops/moe.py).  ``qk_norm`` applies an RMSNorm to the whole projected q
    # and k before the split into heads.  ``router_aux_loss_coef`` weighs
    # the load-balancing term in :func:`moe_loss`.
    n_experts: int = 0
    n_experts_per_tok: int = 0
    qk_norm: bool = False
    router_aux_loss_coef: float = 0.0

    def __post_init__(self):
        if self.n_experts and not 0 < self.n_experts_per_tok <= self.n_experts:
            raise ValueError(
                f"n_experts_per_tok must lie in 1..{self.n_experts}, got "
                f"{self.n_experts_per_tok}"
            )
        if self.attn_impl not in ("auto", "flash", "dense"):
            raise ValueError(
                f"attn_impl must be auto|flash|dense, got {self.attn_impl!r}"
            )
        if self.sp_layout not in ("contiguous", "zigzag"):
            raise ValueError(
                f"sp_layout must be contiguous|zigzag, got {self.sp_layout!r}"
            )
        if self.sp_layout != "contiguous" and self.sp_axis is None:
            # Silently ignoring the layout would train single-device
            # attention on zigzag-permuted data — scrambled sequences.
            raise ValueError(
                "sp_layout='zigzag' requires sp_axis (the layout only "
                "exists for the sequence-parallel ring)"
            )
        if self.sp_strategy not in ("ring", "a2a"):
            raise ValueError(
                f"sp_strategy must be ring|a2a, got {self.sp_strategy!r}"
            )
        if self.sp_strategy == "a2a" and self.sp_layout != "contiguous":
            raise ValueError(
                "sp_strategy='a2a' shards heads, not sequence stripes — "
                "the zigzag layout only applies to the ring strategy"
            )

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def llama3_8b_config(lora_rank: int = 16) -> LlamaConfig:
    """The real Llama-3-8B dimensions (public architecture constants)."""
    return LlamaConfig(
        vocab_size=128256,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        max_seq_len=8192,
        rope_theta=500000.0,
        lora_rank=lora_rank,
        dtype=jnp.bfloat16,
    )


def lora_filter(path: str) -> bool:
    """Subset predicate: the LoRA adapter leaves (and nothing else)."""
    return "lora_" in path


class LoRADense(nn.Module):
    """Dense with a rank-r LoRA delta: ``y = x·W + (α/r)·x·A·B``.

    The base kernel is ordinary Flax state (frozen by the optimizer mask in
    LoRA fine-tuning); ``lora_a``/``lora_b`` are the trainable, gossiped
    adapter."""

    features: int
    rank: int
    alpha: float = 16.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        in_features = x.shape[-1]
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (in_features, self.features),
        )
        y = x @ kernel.astype(self.dtype)
        if self.rank > 0:
            lora_a = self.param(
                "lora_a",
                nn.initializers.normal(stddev=0.02),
                (in_features, self.rank),
            )
            lora_b = self.param(
                "lora_b", nn.initializers.zeros, (self.rank, self.features)
            )
            scale = self.alpha / self.rank
            y = y + (x @ lora_a.astype(self.dtype)) @ lora_b.astype(
                self.dtype
            ) * scale
        return y


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(self.dtype) * scale


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding over the last (head_dim) axis. x: [..., T, H, D]."""
    d = x.shape[-1]
    freqs = 1.0 / (
        theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    )
    angles = positions[..., None].astype(jnp.float32) * freqs  # [T, D/2]
    cos = jnp.cos(angles)[..., None, :]  # [T, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x1 * sin + x2 * cos
    return jnp.stack([out1, out2], axis=-1).reshape(x.shape).astype(x.dtype)


class Attention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        B, T, _ = x.shape
        H, KV, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        dense = lambda feats, name: LoRADense(
            feats, cfg.lora_rank, cfg.lora_alpha, cfg.dtype, name=name
        )
        q, k = dense(H * D, "wq")(x), dense(KV * D, "wk")(x)
        if cfg.qk_norm:
            q = RMSNorm(dtype=cfg.dtype, name="q_norm")(q)
            k = RMSNorm(dtype=cfg.dtype, name="k_norm")(k)
        q, k = q.reshape(B, T, H, D), k.reshape(B, T, KV, D)
        v = dense(KV * D, "wv")(x).reshape(B, T, KV, D)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        if cfg.sp_axis is not None:
            # Sequence-parallel: exact ring attention over the sp mesh
            # axis — K/V blocks rotate by ppermute, online softmax
            # accumulates; causality is enforced on GLOBAL positions
            # inside the kernel (long-context path; SURVEY.md §5).  K/V
            # stay GROUPED (KV heads) through the ring — expanded per
            # block inside the kernel — so GQA's bandwidth saving holds
            # on the fabric.
            if cfg.sp_strategy == "a2a":
                # Ulysses-style: all-to-all to head-sharded attention
                # over the full sequence (ops/ulysses.py), then back.
                from dpwa_tpu.ops.ulysses import ulysses_attention_local

                out = ulysses_attention_local(
                    q, k, v, axis_name=cfg.sp_axis, causal=True,
                    impl=cfg.attn_impl,
                ).reshape(B, T, H * D)
                return dense(cfg.d_model, "wo")(out)
            if cfg.sp_layout == "zigzag":
                # Causal-load-balanced layout: every device computes the
                # same number of half-length panels per hop
                # (ops/zigzag_ring.py) — no device idles on skipped
                # future blocks.
                from dpwa_tpu.ops.zigzag_ring import (
                    zigzag_ring_attention_local,
                )

                # attn_impl maps onto the panel kernels: dense pins the
                # jnp einsum panels; auto/flash let the resolver pick
                # the Pallas kernels on TPU (jnp twins elsewhere).
                out = zigzag_ring_attention_local(
                    q, k, v, axis_name=cfg.sp_axis,
                    impl="jnp" if cfg.attn_impl == "dense" else None,
                ).reshape(B, T, H * D)
                return dense(cfg.d_model, "wo")(out)
            from dpwa_tpu.ops.ring_attention import ring_attention_local

            # attn_impl maps onto the ring hop implementation: auto/flash
            # run each hop through the Pallas flash kernel (VMEM score
            # tiles) when eligible; dense keeps the q-chunked einsum hop.
            out = ring_attention_local(
                q, k, v, axis_name=cfg.sp_axis, causal=True,
                impl="xla" if cfg.attn_impl == "dense" else cfg.attn_impl,
            ).reshape(B, T, H * D)
            return dense(cfg.d_model, "wo")(out)
        # The framework's ONE single-device attention (GQA expansion,
        # flash-vs-dense dispatch, f32 accumulation) — shared with the
        # a2a strategy's per-device compute.  Flash: O(T) memory, score
        # panels in VMEM tiles, never HBM (what makes long single-device
        # sequences fit at all; artifacts/attention_memory.json).
        from dpwa_tpu.ops.ulysses import single_device_attention

        out = single_device_attention(
            q, k, v, causal=True, impl=cfg.attn_impl
        ).reshape(B, T, H * D)
        return dense(cfg.d_model, "wo")(out)


class MLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda feats, name: LoRADense(
            feats, cfg.lora_rank, cfg.lora_alpha, cfg.dtype, name=name
        )
        gate = dense(cfg.d_ff, "w_gate")(x)
        up = dense(cfg.d_ff, "w_up")(x)
        return dense(cfg.d_model, "w_down")(nn.silu(gate) * up)


class ExpertDense(nn.Module):
    """The stacked weights of one projection of every expert: ``kernel
    [E, in, out]`` with ``lora_a [E, in, r]`` / ``lora_b [E, r, out]`` beside
    it, under the names :class:`LoRADense` uses, so ``lora_filter`` picks the
    adapters.  Returns them; ``ops/moe.py`` multiplies."""

    n_experts: int
    in_features: int
    features: int
    rank: int

    @nn.compact
    def __call__(self):
        shape = (self.n_experts, self.in_features, self.features)
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(batch_axis=(0,)), shape
        )
        if self.rank <= 0:
            return kernel, None, None
        lora_a = self.param(
            "lora_a", nn.initializers.normal(stddev=0.02),
            shape[:2] + (self.rank,),
        )
        lora_b = self.param(
            "lora_b", nn.initializers.zeros,
            (self.n_experts, self.rank, self.features),
        )
        return kernel, lora_a, lora_b


class MoE(nn.Module):
    """Top-k of ``n_experts`` SwiGLU experts of width ``d_ff``, dropless
    (``ops/moe.py``).  Sows each call's routing into the ``intermediates``
    collection (``experts [N, k]``, ``counts [E]``, ``prob_mean [E]``, router
    ``logits [N, E]``): what :func:`moe_loss` and a reference that verifies
    the routing read; nothing is computed for it when the collection is not
    asked for."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        from dpwa_tpu.ops import moe
        from dpwa_tpu.utils import scopes

        cfg = self.cfg
        B, T, D = x.shape
        E, k = cfg.n_experts, cfg.n_experts_per_tok
        router = self.param("router", nn.initializers.lecun_normal(), (D, E))
        expert = lambda d_in, d_out, name: ExpertDense(
            E, d_in, d_out, cfg.lora_rank, name=name
        )()
        tokens = x.reshape(B * T, D)
        with jax.named_scope(scopes.MOE_ROUTE):
            weights, experts, logits = moe.route(tokens, router, k)
            self.sow("intermediates", "experts", experts)
            self.sow("intermediates", "logits", logits)
            self.sow("intermediates", "counts",
                     moe.assignment_counts(experts, E))
            self.sow("intermediates", "prob_mean",
                     jax.nn.softmax(logits, axis=-1).mean(0))
        out = moe.moe_ffn(
            tokens, (weights, experts),
            expert(D, cfg.d_ff, "w_gate"), expert(D, cfg.d_ff, "w_up"),
            expert(cfg.d_ff, D, "w_down"),
            cfg.lora_alpha / max(cfg.lora_rank, 1), cfg.dtype,
        )
        return out.reshape(B, T, D)


class Block(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        x = x + Attention(cfg, name="attn")(
            RMSNorm(dtype=cfg.dtype, name="attn_norm")(x), positions
        )
        x = x + (MoE if cfg.n_experts > 0 else MLP)(cfg, name="mlp")(
            RMSNorm(dtype=cfg.dtype, name="mlp_norm")(x)
        )
        return x


class Llama(nn.Module):
    """Decoder-only LM; returns logits [B, T, vocab]."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        B, T = tokens.shape
        x = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="embed"
        )(tokens)
        positions = jnp.arange(T)
        if cfg.sp_axis is not None:
            if cfg.sp_layout == "zigzag":
                # Device holds global chunks (i, 2n-1-i); rope positions
                # must follow the same zigzag map as the data.
                from dpwa_tpu.ops.zigzag_ring import zigzag_positions_local

                positions = zigzag_positions_local(T, cfg.sp_axis)
            else:
                # Inside shard_map: ``tokens`` is this device's contiguous
                # sequence block; rope needs the GLOBAL positions.
                positions = positions + jax.lax.axis_index(cfg.sp_axis) * T
        for i in range(cfg.n_layers):
            x = Block(cfg, name=f"layer_{i}")(x, positions)
        x = RMSNorm(dtype=cfg.dtype, name="final_norm")(x)
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=jnp.float32, name="lm_head"
        )(x)
        return logits


def routing_of(intermediates) -> dict:
    """The sown routing of every expert layer, layers stacked in order:
    ``{"experts": [L, N, k], "counts": [L, E], "prob_mean": [L, E], "logits":
    [L, N, E]}``, from the ``intermediates`` collection that
    ``Llama.apply(..., mutable=["intermediates"])`` returns."""
    layers = intermediates["intermediates"]
    names = sorted(layers, key=lambda name: int(name.rsplit("_", 1)[1]))
    return {
        key: jnp.stack([layers[name]["mlp"][key][0] for name in names])
        for key in ("experts", "counts", "prob_mean", "logits")
    }


def moe_loss(model: "Llama", params, tokens, targets):
    """Cross-entropy plus ``router_aux_loss_coef`` x the load-balancing term
    (``ops/moe.load_balancing_loss``, pooled over layers): the training loss
    of a sparse-expert configuration, still ``-> scalar`` for a step builder."""
    from dpwa_tpu.ops import moe
    from dpwa_tpu.ops.cross_entropy import softmax_cross_entropy
    from dpwa_tpu.utils import scopes

    logits, sown = model.apply(params, tokens, mutable=["intermediates"])
    routing = routing_of(sown)
    with jax.named_scope(scopes.LOSS):
        loss = softmax_cross_entropy(logits, targets).mean()
    return loss + model.cfg.router_aux_loss_coef * moe.load_balancing_loss(
        routing["counts"], routing["prob_mean"]
    )


def lora_mask(params) -> object:
    """Pytree of bools: True on LoRA leaves (trainable), False on base."""
    from dpwa_tpu.utils.pytree import _path_str

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    return jax.tree_util.tree_unflatten(
        treedef, [lora_filter(_path_str(p)) for p, _ in flat]
    )


def lora_optimizer(base_opt, params):
    """LoRA fine-tune optimizer: train adapters, hard-freeze base weights.

    (``optax.masked(opt, mask)`` alone is NOT a freeze — it passes unmasked
    gradients through as raw updates.  Base leaves here get
    ``set_to_zero``, so they stay bit-identical to init, matching config
    5's 'full base weights untouched'.)"""
    import optax

    labels = jax.tree.map(
        lambda is_lora: "train" if is_lora else "freeze", lora_mask(params)
    )
    return optax.multi_transform(
        {"train": base_opt, "freeze": optax.set_to_zero()}, labels
    )
