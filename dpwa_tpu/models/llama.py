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

import contextlib
import dataclasses
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


# What a layer mixes its tokens by (``LlamaConfig.layer_mixers``).  The last
# two are :class:`Attention` told its kind: behind a sliding window, or full
# beside layers that have one; each kind may have a rope of its own.
WINDOWED_KINDS = ("sliding_attention", "full_attention")
ATTENTION_KINDS = ("attention",) + WINDOWED_KINDS
MIXERS = ("attention", "conv", "mamba") + WINDOWED_KINDS


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # GQA; None = MHA
    d_ff: int = 1376
    max_seq_len: int = 2048
    # None: no rotary embedding (a model whose recurrent layers carry the
    # order of the tokens gives its attention layers no positions).
    rope_theta: Optional[float] = 500000.0
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
    norm_eps: float = 1e-5
    # Latent attention (MLA, the published DeepSeek-V3 block, unabsorbed):
    # ``kv_lora_rank`` > 0 replaces :class:`Attention` by
    # :class:`LatentAttention`: q through a rank-``q_lora_rank`` bottleneck
    # with its RMSNorm, k and v through a rank-``kv_lora_rank`` one, heads of
    # ``qk_nope_head_dim`` + ``qk_rope_head_dim`` for q and k (rope on the
    # second part only, one rope key shared by every head) and of
    # ``v_head_dim`` for v.  ``rope_scaling`` blends the rope frequencies
    # (:func:`rope_frequencies`) and sets the softmax scale.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional["YarnScaling"] = None
    # The first ``n_dense_layers`` blocks take a dense MLP of width
    # ``d_ff_dense`` whatever ``n_experts`` says.
    n_dense_layers: int = 0
    d_ff_dense: int = 0
    # Beside the routed experts, ``n_shared_experts`` experts of width
    # ``d_ff`` that every token takes (one MLP of their summed width).
    n_shared_experts: int = 0
    # The gate: scores are the "softmax" or the "sigmoid" of the router
    # logits; ``norm_topk_prob`` divides a token's chosen scores by their
    # sum; ``routed_scaling_factor`` multiplies the weights.
    router_scoring: str = "softmax"
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    # One chip's share of the experts (expert parallelism seen from one of
    # its chips): the router scores all ``n_experts``, this replica holds
    # ``experts_held`` of them from ``expert_offset`` on and computes their
    # part of the layer; what the absent experts would add is left out.
    # None holds them all.
    experts_held: Optional[int] = None
    expert_offset: int = 0
    # ``jax.checkpoint`` around every block: a block's activations are
    # recomputed in the backward pass and only its input is saved; a Mamba
    # block also keeps its scan's output and boundary states
    # (``_checkpoint_policy``).
    remat: bool = False
    # The type base leaves (kernels, norms, embedding, head) are created in;
    # adapters, the router and a Mamba mixer's ``A_log``, ``D`` and
    # ``dt_bias`` are float32 whatever it says.
    param_dtype: jnp.dtype = jnp.float32
    # The type activations have between matmuls (None: ``dtype``): the
    # residual stream, what the norms and projections put out, the
    # elementwise work.  float32 under a bfloat16 ``dtype`` rounds a value
    # once, where it enters a matmul (``ops/wide.py``), which makes the model
    # one function whatever the compiler fuses: the same under ``vmap`` over
    # peers as unbatched, to the last bit but for the order of a sum.
    # Written for the latent-attention block; :class:`Attention` refuses it.
    activation_dtype: Optional[jnp.dtype] = None
    # State-space layers (the Jamba block): ``attn_layer_period`` > 0 keeps
    # attention in the layers ``i % attn_layer_period == attn_layer_offset``
    # and gives every other layer a :class:`MambaMixer` of ``mamba_expand x
    # d_model`` channels, ``mamba_d_state`` states a channel, a causal
    # convolution ``mamba_d_conv`` wide and a rank-``mamba_dt_rank`` step
    # size.  0: every layer is attention.
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 0
    mamba_expand: int = 2
    # The head is the embedding (``logits = x E^T``): no ``lm_head`` leaf.
    tie_embeddings: bool = False
    # EVA attention (the published EvaByte block): ``eva_window`` > 0 replaces
    # :class:`Attention` by :class:`EvaAttention`: a query sees the positions
    # of its own window of ``eva_window`` exactly and of every earlier window
    # one summary a chunk of ``eva_chunk`` positions, under one softmax
    # (``ops/eva.py``).
    eva_window: int = 0
    eva_chunk: int = 0
    # The head has ``vocab_size x n_pred_heads`` columns and the logits come
    # back ``[B, T, n_pred_heads, vocab]``: head ``i`` at position ``t``
    # predicts token ``t + 1 + i``.  1: ``[B, T, vocab]`` as ever.
    n_pred_heads: int = 1
    # ``norm(x) = x / rms(x) * (1 + scale)`` with ``scale`` born zero, the
    # product taken in float32 (``norm_add_unit_offset``).
    norm_unit_offset: bool = False
    # The residual stream and its two adds a block are float32 whatever
    # ``dtype`` says, and everything else stays ``dtype``: the norms read the
    # wide stream and hand on ``dtype`` (``fp32_skip_add`` with ``fp32_ln``
    # false, as the EvaByte configuration publishes them).
    fp32_skip_add: bool = False
    # A list of kinds a layer (the published ``layer_types`` of LFM2, which no
    # period and offset can say): layer ``i``'s mixer is ``layer_mixers[i]``,
    # one of :data:`MIXERS`: ``"attention"`` (whichever attention the other
    # fields select), ``"conv"`` (a :class:`ShortConv` of ``conv_taps`` taps),
    # ``"mamba"``, or one of the two kinds of plain attention further down
    # (``sliding_window``).  None: ``attn_layer_period`` /
    # ``attn_layer_offset`` say, as above.
    layer_mixers: Optional[Tuple[str, ...]] = None
    conv_taps: int = 3
    # An RMSNorm over ``head_dim`` on every head of q and of k after the
    # split into heads and before rope, one weight ``[head_dim]`` for q and
    # one for k (``qk_norm`` is the other form: over the whole projected
    # width before the split).
    qk_norm_per_head: bool = False
    # The gate chooses a token's experts by ``scores + expert_bias`` and
    # weighs them by ``scores`` alone; ``expert_bias [n_experts]`` is a
    # float32 base leaf beside ``router``.  ``norm_topk_eps`` is added to the
    # sum that ``norm_topk_prob`` divides by.
    router_bias: bool = False
    norm_topk_eps: float = 0.0
    # A head's size where it is not ``d_model / n_heads`` (the projected width
    # ``n_heads x head_size`` is then not the hidden size).
    head_size: Optional[int] = None
    # ``layer_mixers`` may name a layer ``"sliding_attention"``: query ``t``
    # sees the keys ``t - sliding_window + 1 .. t``, its own among them; or
    # ``"full_attention"``: every earlier key, as ``"attention"``, in a stack
    # that has both.  ``rope_by_kind`` gives a kind a rope of its own, as
    # ``(kind, theta, scaling)`` triples; a kind it does not name takes
    # ``rope_theta`` / ``rope_scaling``.
    sliding_window: int = 0
    rope_by_kind: Tuple[Tuple[str, Optional[float], Optional["YarnScaling"]], ...] = ()

    def __post_init__(self):
        mixers = self.layer_mixers
        if mixers is not None:
            if self.attn_layer_period:
                raise ValueError(
                    "layer_mixers and attn_layer_period both say which "
                    "layers keep attention: give one"
                )
            if len(mixers) != self.n_layers or set(mixers) - set(MIXERS):
                raise ValueError(
                    f"layer_mixers names one of {MIXERS} for each of the "
                    f"{self.n_layers} layers, got {mixers!r}"
                )
            if "conv" in mixers and self.conv_taps < 1:
                raise ValueError("a convolution needs at least one tap")
            if "conv" in mixers and self.sp_axis is not None:
                raise ValueError(
                    "a short convolution has no sequence-parallel path: a "
                    "rank's first positions need the rank before's last"
                )
        if set(mixers or ()) & set(WINDOWED_KINDS):
            if self.kv_lora_rank or self.eva_window or self.sp_axis is not None:
                raise ValueError(
                    "sliding_attention / full_attention are kinds of plain "
                    "attention on one device: no latent or EVA attention, no "
                    "sequence-parallel path (a window has none yet)"
                )
        if "sliding_attention" in (mixers or ()) and (
            self.sliding_window < 128 or self.sliding_window % 128
        ):
            raise ValueError(
                "a sliding_attention layer needs a sliding_window that is a "
                f"multiple of 128, got {self.sliding_window}"
            )
        if set(kind for kind, _, _ in self.rope_by_kind) - set(MIXERS):
            raise ValueError(
                f"rope_by_kind names kinds of {MIXERS}, got {self.rope_by_kind!r}"
            )
        if self.attn_layer_period or "mamba" in (mixers or ()):
            if self.attn_layer_period and not (
                0 <= self.attn_layer_offset < self.attn_layer_period
            ):
                raise ValueError(
                    f"attn_layer_offset {self.attn_layer_offset} is not in "
                    f"0..{self.attn_layer_period - 1}"
                )
            if min(self.mamba_d_state, self.mamba_d_conv,
                   self.mamba_dt_rank, self.mamba_expand) < 1:
                raise ValueError("a Mamba layer needs its four sizes")
            if self.sp_axis is not None:
                raise ValueError(
                    "a Mamba layer has no sequence-parallel path: the scan "
                    "hands no state from one rank to the next"
                )
        if self.qk_norm and self.qk_norm_per_head:
            raise ValueError(
                "qk_norm (over the projected width) and qk_norm_per_head "
                "(over a head) are two forms of one norm: give one"
            )
        if self.router_bias and self.experts_held is not None:
            raise ValueError("a share of the experts has no router bias yet")
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"router_scoring must be softmax|sigmoid, got "
                f"{self.router_scoring!r}"
            )
        held = self.held_experts
        if self.n_experts and not (
            0 < held and 0 <= self.expert_offset
            and self.expert_offset + held <= self.n_experts
        ):
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + held} "
                f"are not among the {self.n_experts}"
            )
        if self.kv_lora_rank and self.sp_axis is not None:
            raise ValueError("latent attention has no sequence-parallel path")
        if self.eva_window:
            if self.eva_chunk < 1 or self.eva_window % self.eva_chunk:
                raise ValueError(
                    f"eva_chunk {self.eva_chunk} does not divide eva_window "
                    f"{self.eva_window}"
                )
            if self.sp_axis is not None or self.kv_lora_rank:
                raise ValueError(
                    "EVA attention has no sequence-parallel path and is no "
                    "latent attention"
                )
        if self.activation_dtype is not None and not self.kv_lora_rank:
            raise ValueError("activation_dtype needs latent attention")
        if self.n_pred_heads < 1 or (
            self.n_pred_heads > 1 and self.tie_embeddings
        ):
            raise ValueError(
                "n_pred_heads is at least 1, and more than one head needs an "
                "lm_head of its own"
            )
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
        return self.head_size or self.d_model // self.n_heads

    def rope_of(self, kind: str):
        """``(theta, scaling)`` of the rope a layer of ``kind`` turns by."""
        for named, theta, scaling in self.rope_by_kind:
            if named == kind:
                return theta, scaling
        return self.rope_theta, self.rope_scaling

    def mixer_of(self, index: int) -> str:
        """Layer ``index``'s mixer, one of :data:`MIXERS`."""
        if self.layer_mixers is not None:
            return self.layer_mixers[index]
        period = self.attn_layer_period
        if not period or index % period == self.attn_layer_offset:
            return "attention"
        return "mamba"

    def is_attention_layer(self, index: int) -> bool:
        return self.mixer_of(index) in ATTENTION_KINDS

    @property
    def norm_dtype(self) -> jnp.dtype:
        """What a norm hands on."""
        return self.activation_dtype or self.dtype

    @property
    def stream_dtype(self) -> jnp.dtype:
        """Of the residual stream."""
        return jnp.float32 if self.fp32_skip_add else self.norm_dtype

    @property
    def held_experts(self) -> int:
        return self.n_experts if self.experts_held is None else self.experts_held


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """A ``rope_scaling`` group of type ``yarn``, under its published keys.
    Two published forms say how much larger the scores get: ``mscale`` /
    ``mscale_all_dim`` (the DeepSeek form: ``cos`` and ``sin`` times their
    magnitudes' ratio, the scores times ``mscale_all_dim``'s squared), or an
    ``attention_factor`` that multiplies ``cos`` and ``sin`` alone (so the
    scores its square) and, where given, is all there is.  ``mscale`` 1 and
    ``mscale_all_dim`` 1 are the second form at its default factor ``0.1 ln
    factor + 1``."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    attention_factor: Optional[float] = None

    @staticmethod
    def magnitude(factor: float, mscale: float) -> float:
        return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0

    @property
    def embedding_scale(self) -> float:
        """What the rope's cos and sin are multiplied by."""
        if self.attention_factor is not None:
            return self.attention_factor
        return self.magnitude(self.factor, self.mscale) / self.magnitude(
            self.factor, self.mscale_all_dim
        )

    @property
    def softmax_scale(self) -> float:
        """What the ``1 / sqrt(d)`` of the scores is multiplied by."""
        if self.attention_factor is not None:
            return 1.0
        return self.magnitude(self.factor, self.mscale_all_dim) ** 2


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
    param_dtype: jnp.dtype = jnp.float32  # of the base kernel alone
    # The type of the result where it is not ``dtype``: the operands are
    # rounded to ``dtype`` and nothing else is (``ops/wide.py``).
    out_dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        from dpwa_tpu.ops.wide import wide_dot

        if self.out_dtype is None:
            dot = lambda a, b: a @ b.astype(self.dtype)
        else:
            dot = lambda a, b: wide_dot(a, b, self.dtype, self.out_dtype)
        in_features = x.shape[-1]
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (in_features, self.features),
            self.param_dtype,
        )
        y = dot(x, kernel)
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
            y = y + dot(dot(x, lora_a), lora_b) * scale
        return y


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    # ``x / rms(x) * (1 + scale)``, ``scale`` born zero, one float32 product.
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.initializers.zeros if self.unit_offset else nn.initializers.ones,
            (x.shape[-1],), self.param_dtype,
        )
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        normed = x * jax.lax.rsqrt(var + self.eps)
        if self.unit_offset:
            return (normed * (1.0 + scale.astype(jnp.float32))).astype(
                self.dtype
            )
        return normed.astype(self.dtype) * scale


def rope_frequencies(
    d: int, theta: float, scaling: Optional[YarnScaling] = None
) -> jnp.ndarray:
    """The ``d / 2`` rotary frequencies ``theta^(-2i/d)``; with a yarn
    ``scaling``, blended with their ``1 / factor``: pairs that turn more than
    ``beta_fast`` times over the original context keep their frequency,
    pairs that turn less than ``beta_slow`` times are interpolated, and a
    linear ramp over the pair index joins the two."""
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if scaling is None:
        return freqs

    def pair_turning(turns):  # the (real) pair index that turns so often
        return d * math.log(
            scaling.original_max_position_embeddings / (turns * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(scaling.beta_fast)), 0)
    high = min(math.ceil(pair_turning(scaling.beta_slow)), d - 1)
    ramp = jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - low)
        / max(high - low, 1e-3), 0.0, 1.0,
    )
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float,
    scaling: Optional[YarnScaling] = None, *, heads_axis: int = -2,
) -> jnp.ndarray:
    """Rotary embedding over the last (head_dim) axis of ``x [..., T, H, D]``,
    or of ``x [..., H, T, D]`` (heads before positions) with ``heads_axis``
    -3, with every array kept ``x``'s shape: a pair's partner (``-x[2i+1]``
    for lane ``2i``, ``x[2i]`` for lane ``2i+1``) comes from one matmul by a
    constant ``[D, D]`` matrix of 0 and +-1, which is exact, and not from
    cutting the pairs apart by strided slices and stacking them back.
    XLA:TPU lays a stacked ``[..., D/2, 2]`` array out padded fourfold and
    turns a strided slice into a gather and its gradient into scatters: nine
    passes over ``q`` a layer forward and as many backward at 2 x 4,096 x 32
    x 128, 2 GiB for each 0.5 GiB array at 2 x 16,384 x 32 x 128 (PERF.md
    section 6, PR 42 and PR 43); a roll along the lanes is written out as
    two padded slices likewise."""
    d = x.shape[-1]
    angles = positions[..., None].astype(jnp.float32) * rope_frequencies(
        d, theta, scaling
    )  # [..., T, D/2]
    per_lane = lambda z: jnp.expand_dims(jnp.repeat(z, 2, axis=-1), heads_axis)
    cos, sin = per_lane(jnp.cos(angles)), per_lane(jnp.sin(angles))
    if scaling is not None:
        cos, sin = (z * scaling.embedding_scale for z in (cos, sin))
    lane = jnp.arange(d)
    swap = (lane[:, None] ^ 1) == lane  # [from, to]: the pair's other lane
    partner_of = jnp.where(swap, jnp.where(lane % 2 == 0, -1.0, 1.0), 0.0)
    partner = jnp.dot(  # +-x: exact in x's own type
        x, partner_of.astype(x.dtype), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=x.dtype,
    )
    out = x.astype(jnp.float32) * cos + partner.astype(jnp.float32) * sin
    return out.astype(x.dtype)


def _dense(cfg: LlamaConfig, features: int, name: str) -> LoRADense:
    return LoRADense(
        features, cfg.lora_rank, cfg.lora_alpha, cfg.dtype, cfg.param_dtype,
        cfg.activation_dtype, name=name,
    )


def _norm(cfg: LlamaConfig, name: str) -> RMSNorm:
    return RMSNorm(
        cfg.norm_eps, cfg.norm_dtype, cfg.param_dtype, cfg.norm_unit_offset,
        name=name,
    )


class Attention(nn.Module):
    cfg: LlamaConfig
    # Of :data:`MIXERS`: ``"sliding_attention"`` sees ``cfg.sliding_window``
    # keys, and each kind turns by its own rope (``LlamaConfig.rope_of``).
    kind: str = "attention"

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        theta, scaling = cfg.rope_of(self.kind)
        B, T, _ = x.shape
        H, KV, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        dense = lambda feats, name: _dense(cfg, feats, name)
        q, k = dense(H * D, "wq")(x), dense(KV * D, "wk")(x)
        if cfg.qk_norm:
            q, k = _norm(cfg, "q_norm")(q), _norm(cfg, "k_norm")(k)
        q, k = q.reshape(B, T, H, D), k.reshape(B, T, KV, D)
        if cfg.qk_norm_per_head:
            q, k = _norm(cfg, "q_norm")(q), _norm(cfg, "k_norm")(k)
        v = dense(KV * D, "wv")(x).reshape(B, T, KV, D)
        if theta is not None:
            q = rope(q, positions, theta, scaling)
            k = rope(k, positions, theta, scaling)
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
        # sequences fit at all).
        from dpwa_tpu.ops.ulysses import single_device_attention

        how = {}
        if self.kind == "sliding_attention":
            how.update(window=cfg.sliding_window)
        if scaling is not None:
            how.update(sm_scale=D ** -0.5 * scaling.softmax_scale)
        out = single_device_attention(
            q, k, v, causal=True, impl=cfg.attn_impl, **how
        ).reshape(B, T, H * D)
        return dense(cfg.d_model, "wo")(out)


class LatentAttention(nn.Module):
    """Multi-head latent attention as a training step runs it (unabsorbed;
    no latent cache): ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` in heads of
    ``[q_nope | q_pe]``; ``[c_kv | k_pe] = x W_kva``, ``RMSNorm(c_kv) W_kvb``
    in heads of ``[k_nope | v]``; rope on ``q_pe`` a head and on the one
    ``k_pe``, which every head's key shares; causal ``softmax(q k^T s) v``
    with ``s = rope_scaling.softmax_scale / sqrt(qk dim)``; ``W_o`` on the
    concatenated values.  Adapters on all five projections."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        from dpwa_tpu.ops.ulysses import single_device_attention
        from dpwa_tpu.ops.wide import narrow
        from dpwa_tpu.utils import scopes

        cfg = self.cfg
        B, T, _ = x.shape
        H = cfg.n_heads
        nope, pe, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        scaling = cfg.rope_scaling
        with jax.named_scope(scopes.ATTN_LATENT):
            c_q = _norm(cfg, "q_norm")(_dense(cfg, cfg.q_lora_rank, "wq_a")(x))
            q = _dense(cfg, H * (nope + pe), "wq_b")(c_q)
            q_nope, q_pe = jnp.split(q.reshape(B, T, H, nope + pe), [nope], -1)
            c_kv, k_pe = jnp.split(
                _dense(cfg, cfg.kv_lora_rank + pe, "wkv_a")(x),
                [cfg.kv_lora_rank], -1,
            )
            kv = _dense(cfg, H * (nope + dv), "wkv_b")(
                _norm(cfg, "kv_norm")(c_kv)
            )
            k_nope, v = jnp.split(kv.reshape(B, T, H, nope + dv), [nope], -1)
            q_pe = rope(q_pe, positions, cfg.rope_theta, scaling)
            k_pe = rope(k_pe[:, :, None], positions, cfg.rope_theta, scaling)
            q = jnp.concatenate([q_nope, q_pe], -1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe, (B, T, H, pe))], -1
            )
            sm_scale = (nope + pe) ** -0.5
            if scaling is not None:
                sm_scale *= scaling.softmax_scale
            q, k, v = (narrow(z, cfg.dtype) for z in (q, k, v))
            out = single_device_attention(
                q, k, v, causal=True, impl=cfg.attn_impl, sm_scale=sm_scale
            )
            return _dense(cfg, cfg.d_model, "wo")(out.reshape(B, T, H * dv))


def _eva_init(key, shape, dtype=jnp.float32):
    """A standard normal clipped to +-1, times ``head size^-1/2``."""
    draw = jnp.clip(jax.random.normal(key, shape, jnp.float32), -1.0, 1.0)
    return (draw * shape[-1] ** -0.5).astype(dtype)


class EvaAttention(nn.Module):
    """EVA attention as a training step runs it (no cache): ``q``, ``k``,
    ``v`` of ``n_heads`` heads each, rope on all of ``q`` and ``k``; of each
    chunk of ``eva_chunk`` positions one pooled key and value, by a softmax
    of ``k . adaptive_phi`` with ``adaptive_mu_k`` added to the pooled key
    (both ``[heads, head size]``, base leaves); a query attends to its own
    window of ``eva_window`` exactly and to the summaries of every earlier
    window under one softmax (``ops/eva.py`` has the equations and the
    kernels); ``W_o``.  Adapters on the four projections."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        from dpwa_tpu.ops import eva

        cfg = self.cfg
        B, T, _ = x.shape
        H, D = cfg.n_heads, cfg.head_dim
        # Heads before positions from here to ``W_o``, as ``ops/eva.py``
        # takes them: each of ``q``, ``k``, ``v`` and ``o`` is turned once.
        heads = lambda name: jnp.swapaxes(
            _dense(cfg, H * D, name)(x).reshape(B, T, H, D), 1, 2
        )
        q, k, v = heads("wq"), heads("wk"), heads("wv")
        if cfg.rope_theta is not None:
            q = rope(q, positions, cfg.rope_theta, heads_axis=-3)
            k = rope(k, positions, cfg.rope_theta, heads_axis=-3)
        learned = lambda name: self.param(
            name, _eva_init, (H, D), cfg.param_dtype
        )
        ksum, vsum = eva.chunk_summaries(
            k, v, learned("adaptive_phi"), learned("adaptive_mu_k"),
            cfg.eva_chunk,
        )
        out = eva.eva_attention(
            q, k, v, ksum, vsum, window=cfg.eva_window, chunk=cfg.eva_chunk
        )
        return _dense(cfg, cfg.d_model, "wo")(
            jnp.swapaxes(out, 1, 2).reshape(B, T, H * D)
        )


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a log-uniform draw in [0.001, 0.1] (the Mamba
    paper's initial step sizes)."""
    low, high = math.log(1e-3), math.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, low, high))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _conv_init(taps: int):
    """Uniform in +-1 / sqrt(taps): a depthwise convolution's fan-in."""
    bound = taps ** -0.5
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
        key, shape, jnp.float32, -bound, bound
    ).astype(dtype)


class MambaMixer(nn.Module):
    """The Mamba-1 mixer with Jamba's three inner norms, ``u [B, T, D]``:

        [x, z] = u W_in;  x = silu(conv(x))         causal, depthwise, bias
        [dt, Bm, Cm] = x W_x, an RMSNorm each
        delta = softplus(dt W_dt + b_dt);  A = -exp(A_log)
        y = selective_scan(x, delta, A, Bm, Cm, D)  (``ops/ssm.py``)
        out = (y * silu(z)) W_out

    Adapters on the four projections (five matrices: ``in_proj`` is the x
    and the z one side by side); ``conv_kernel``, ``conv_bias``, ``dt_bias``,
    ``A_log``, ``D`` and the norms are base leaves.  ``delta`` and everything
    inside the scan are float32 whatever ``dtype`` says.  The convolution
    with its silu is one call, ``ops/ssm.conv_silu``: on a TPU a kernel a
    pass in the activations' type with its own gradient rule, elsewhere
    ``silu(causal_conv1d(...))``; the sum of the taps is float32 either way."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, u):
        from dpwa_tpu.ops import ssm
        from dpwa_tpu.utils import scopes

        cfg = self.cfg
        E = cfg.mamba_expand * cfg.d_model
        N, R, K = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
        parts = scopes.SSM_PARTS
        with jax.named_scope(scopes.SSM):
            with jax.named_scope(parts.proj):
                xz = _dense(cfg, 2 * E, "in_proj")(u)
            with jax.named_scope(parts.conv):
                # ``x`` is read where ``in_proj`` wrote it, the leading half
                # of ``xz``: a kernel handed the half itself would be handed
                # a copy.
                x = ssm.conv_silu(
                    xz,
                    self.param("conv_kernel", _conv_init(K), (K, E),
                               cfg.param_dtype),
                    self.param("conv_bias", _conv_init(K), (E,),
                               cfg.param_dtype),
                )
                z = xz[..., E:]
            with jax.named_scope(parts.proj):
                dbc = _dense(cfg, R + 2 * N, "x_proj")(x)
            with jax.named_scope(parts.dt):
                dt, Bm, Cm = jnp.split(dbc, [R, R + N], -1)
                dt, Bm, Cm = (
                    _norm(cfg, name)(v) for name, v in
                    (("dt_norm", dt), ("b_norm", Bm), ("c_norm", Cm))
                )
            with jax.named_scope(parts.proj):
                dt = _dense(cfg, E, "dt_proj")(dt)
            with jax.named_scope(parts.dt):
                delta = jax.nn.softplus(
                    dt.astype(jnp.float32)
                    + self.param("dt_bias", _dt_bias_init, (E,))
                )
                A_log = self.param(
                    "A_log",
                    lambda key, shape: jnp.broadcast_to(
                        jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), shape
                    ),
                    (E, N),
                )
                A = -jnp.exp(A_log)
            D = self.param("D", nn.initializers.ones, (E,))
            with jax.named_scope(scopes.SSM_SCAN):
                y = ssm.selective_scan(x, delta, A, Bm, Cm, D)
            with jax.named_scope(parts.gate):
                gated = y * nn.silu(z)
            with jax.named_scope(parts.proj):
                return _dense(cfg, cfg.d_model, "out_proj")(gated)


class ShortConv(nn.Module):
    """The gated short convolution of LFM2, ``x [B, T, D]``:

        [b, c, u] = x W_in                      D -> 3 D, no bias
        z = conv(b * u)                         causal, depthwise, no bias:
                                                z_t = sum_j w[j] (b u)_{t-(K-1)+j}
        out = (c * z) W_out                     D -> D, no bias

    with ``K = conv_taps`` and zeros before the sequence
    (``ops/ssm.causal_conv1d``, float32 multiply-adds).  Adapters on the two
    projections; ``conv_kernel [K, D]`` is a base leaf.  No state crosses the
    taps' reach, so nothing here knows a peer axis: under ``vmap`` it is the
    same elementwise and dense work on one more axis."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        from dpwa_tpu.ops import ssm
        from dpwa_tpu.utils import scopes

        cfg = self.cfg
        D, K = cfg.d_model, cfg.conv_taps
        with jax.named_scope(scopes.CONV.whole):
            bcu = _dense(cfg, 3 * D, "in_proj")(x)
            kernel = self.param(
                "conv_kernel", _conv_init(K), (K, D), cfg.param_dtype
            )
            with jax.named_scope(scopes.CONV.gate):
                b, c, u = jnp.split(bcu, 3, -1)
                z = ssm.causal_conv1d(
                    b * u, kernel, jnp.zeros((D,), jnp.float32)
                )
                gated = c * z
            return _dense(cfg, D, "out_proj")(gated)


class MLP(nn.Module):
    cfg: LlamaConfig
    d_ff: Optional[int] = None  # None: the configuration's ``d_ff``

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d_ff = self.d_ff or cfg.d_ff
        gate = _dense(cfg, d_ff, "w_gate")(x)
        up = _dense(cfg, d_ff, "w_up")(x)
        return _dense(cfg, cfg.d_model, "w_down")(nn.silu(gate) * up)


class ExpertDense(nn.Module):
    """The stacked weights of one projection of every expert: ``kernel
    [E, in, out]`` with ``lora_a [E, in, r]`` / ``lora_b [E, r, out]`` beside
    it, under the names :class:`LoRADense` uses, so ``lora_filter`` picks the
    adapters.  Returns them; ``ops/moe.py`` multiplies."""

    n_experts: int
    in_features: int
    features: int
    rank: int
    param_dtype: jnp.dtype = jnp.float32  # of the base kernel alone

    @nn.compact
    def __call__(self):
        shape = (self.n_experts, self.in_features, self.features)
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(batch_axis=(0,)), shape,
            self.param_dtype,
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
    (``ops/moe.py``), with ``n_shared_experts`` shared experts beside them
    that every token takes.  Where the configuration holds a share of the
    experts (``experts_held``), the router still scores all of them and the
    weights are normalised over a token's whole choice; the layer's routed
    part is the held experts' alone.

    Sows each call's routing into the ``intermediates`` collection
    (``router_input [N, D]``, ``experts [N, k]``, ``counts [E]``, ``prob_mean
    [E]``, router ``logits [N, E]``, with a router bias ``bias_moved`` = the
    share of the N x k assignments that are not among the top k of the scores
    alone, and of the held experts ``held_counts [held]``, ``held_share``
    = their part of all N x k assignments, ``held_max_over_mean``,
    ``held_over_cap``): what :func:`moe_loss` and a reference that verifies
    the routing read; nothing is computed for it when the collection is not
    asked for.

    ``held_over_cap`` is 1 where this call's held assignments outnumber the
    share's row cap (``ops/moe.row_cap``: four times what uniform routing
    sends it), else 0, and 0 where the shapes give no cap.  A share within
    its cap computes one window of that many sorted rows; one over it
    computes as many windows as its assignments fill, and in a stacked step
    (``vmap`` over peers) every peer walks as many as the fullest: exact as
    ever, each further window at about the first one's price.  A share that
    reads 1 often holds hot experts; a trace shows the window's instructions
    run more than once in that layer's call."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        from dpwa_tpu.ops import moe
        from dpwa_tpu.utils import scopes

        cfg = self.cfg
        B, T, D = x.shape
        E, k = cfg.n_experts, cfg.n_experts_per_tok
        held, offset = cfg.held_experts, cfg.expert_offset
        router = self.param("router", nn.initializers.lecun_normal(), (D, E))
        bias = None
        if cfg.router_bias:
            # Born off zero so that it moves some choices; the published
            # model moves it by the experts' load, not by the gradient.
            bias = self.param(
                "expert_bias", nn.initializers.normal(stddev=0.05), (E,)
            )
        expert = lambda d_in, d_out, name: ExpertDense(
            held, d_in, d_out, cfg.lora_rank, cfg.param_dtype, name=name
        )()
        tokens = x.reshape(B * T, D)
        with jax.named_scope(scopes.MOE_ROUTE):
            weights, experts, logits = moe.route(
                tokens, router, k, cfg.router_scoring, cfg.norm_topk_prob,
                cfg.routed_scaling_factor, bias, cfg.norm_topk_eps,
            )
            counts = moe.assignment_counts(experts, E)
            self.sow("intermediates", "router_input", tokens)
            self.sow("intermediates", "experts", experts)
            self.sow("intermediates", "logits", logits)
            self.sow("intermediates", "counts", counts)
            self.sow("intermediates", "prob_mean",
                     moe.router_scores(logits, cfg.router_scoring).mean(0))
            here = counts[offset:offset + held]
            self.sow("intermediates", "held_counts", here)
            self.sow("intermediates", "held_share", here.sum() / experts.size)
            self.sow("intermediates", "held_max_over_mean",
                     here.max() * held / jnp.maximum(here.sum(), 1))
            self.sow("intermediates", "held_over_cap",
                     moe.over_cap(here.sum(), experts.size, held, E))
            if bias is not None:
                self.sow("intermediates", "bias_moved", moe.choices_moved(
                    moe.router_scores(logits, cfg.router_scoring), experts
                ))
        out = moe.moe_ffn(
            tokens, (weights, experts),
            expert(D, cfg.d_ff, "w_gate"), expert(D, cfg.d_ff, "w_up"),
            expert(cfg.d_ff, D, "w_down"),
            cfg.lora_alpha / max(cfg.lora_rank, 1), cfg.dtype,
            None if held == E else offset, cfg.activation_dtype, E,
        )
        if cfg.n_shared_experts:
            with jax.named_scope(scopes.MOE_SHARED):
                out = out + MLP(
                    cfg, cfg.n_shared_experts * cfg.d_ff, name="shared"
                )(tokens)
        return out.reshape(B, T, D)


class Block(nn.Module):
    cfg: LlamaConfig
    # Of the layer: the first ``n_dense_layers`` are dense, and it picks the
    # layer's mixer (``LlamaConfig.mixer_of``).
    index: int = 0

    @nn.compact
    def __call__(self, x, positions):
        from dpwa_tpu.utils import scopes

        cfg = self.cfg
        # Latent attention, the Mamba mixer, the short convolution and the
        # expert layer name themselves; plain and EVA attention and the dense
        # feed-forward are named here, because the shared expert is an
        # ``MLP`` too.  Norms and residual adds stay outside every name.
        mixer = cfg.mixer_of(self.index)
        if mixer in ATTENTION_KINDS:
            h = _norm(cfg, "attn_norm")(x)
            if mixer == "attention" and cfg.kv_lora_rank:
                h = LatentAttention(cfg, name="attn")(h, positions)
            elif mixer == "attention" and cfg.eva_window:
                with jax.named_scope(scopes.ATTN_EVA.whole):
                    h = EvaAttention(cfg, name="attn")(h, positions)
            else:
                # Plain attention whole, of whichever kind; a sliding layer
                # carries its own name inside that one.
                with contextlib.ExitStack() as named:
                    named.enter_context(jax.named_scope(scopes.ATTN_GQA))
                    if mixer == "sliding_attention":
                        named.enter_context(
                            jax.named_scope(scopes.ATTN_WINDOW.whole)
                        )
                    h = Attention(cfg, mixer, name="attn")(h, positions)
            x = x + h
        elif mixer == "conv":
            x = x + ShortConv(cfg, name="conv")(_norm(cfg, "conv_norm")(x))
        else:
            x = x + MambaMixer(cfg, name="mamba")(_norm(cfg, "mamba_norm")(x))
        h = _norm(cfg, "mlp_norm")(x)
        leading = self.index < cfg.n_dense_layers
        if cfg.n_experts > 0 and not leading:
            h = MoE(cfg, name="mlp")(h)
        else:
            d_ff = cfg.d_ff_dense if leading else None
            with jax.named_scope(scopes.MLP):
                h = MLP(cfg, d_ff, name="mlp")(h)
        return x + h


def _checkpoint_policy(cfg: LlamaConfig, index: int):
    """What ``jax.checkpoint`` around block ``index`` keeps beside the
    block's input: nothing (None) for an attention or a convolution block;
    for a Mamba block
    what the scan's forward kernel alone produces (``ops/ssm.KEPT``: 105 MB
    a block at the Jamba cell's shapes), so that the recomputation has
    nothing to run that kernel for."""
    from dpwa_tpu.ops import ssm

    if cfg.mixer_of(index) != "mamba":
        return None
    return jax.checkpoint_policies.save_only_these_names(*ssm.KEPT)


class Llama(nn.Module):
    """Decoder-only LM; returns logits ``[B, T, vocab]``, or ``[B, T,
    n_pred_heads, vocab]`` where the configuration has several heads."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens):
        from dpwa_tpu.utils import scopes

        cfg = self.cfg
        B, T = tokens.shape
        # The rows are looked up in ``dtype`` and only they are widened: an
        # ``Embed`` of the activations' type would convert the whole table.
        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="embed",
        )
        x = embed(tokens).astype(cfg.stream_dtype)
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
        # The barrier that keeps XLA from merging the recomputation with the
        # forward pass stands on the activations alone (``x``, ``positions``),
        # not on the block's variables: a barrier on a frozen kernel makes a
        # caller that slices one replica out of a stacked tree
        # (``lax.map`` over peers) copy that replica's whole base.
        for i in range(cfg.n_layers):
            block = Block
            if cfg.remat:
                block = nn.remat(
                    Block, prevent_cse=(False, False, True, True),
                    policy=_checkpoint_policy(cfg, i),
                )
            x = block(cfg, i, name=f"layer_{i}")(x, positions)
        x = _norm(cfg, "final_norm")(x)
        with jax.named_scope(scopes.HEAD):
            if cfg.tie_embeddings:  # x E^T in float32, as the head below
                return jax.lax.dot_general(
                    x.astype(jnp.float32), embed.embedding.astype(jnp.float32),
                    (((x.ndim - 1,), (1,)), ((), ())),
                )
            logits = nn.Dense(
                cfg.vocab_size * cfg.n_pred_heads, use_bias=False,
                dtype=jnp.float32, param_dtype=cfg.param_dtype, name="lm_head",
            )(x)
            if cfg.n_pred_heads == 1:
                return logits
            return logits.reshape(B, T, cfg.n_pred_heads, cfg.vocab_size)


def routing_of(intermediates) -> dict:
    """The sown routing of every expert layer, layers stacked in order:
    ``{"router_input": [L, N, D], "experts": [L, N, k], "counts": [L, E],
    "prob_mean": [L, E], "logits": [L, N, E], "held_counts": [L, held], "held_share": [L],
    "held_max_over_mean": [L], "held_over_cap": [L]}`` (and ``"bias_moved":
    [L]`` where the router has a bias), from the ``intermediates`` collection that
    ``Llama.apply(..., mutable=["intermediates"])`` returns.  A dense layer
    sows nothing and is not among them."""
    layers = intermediates["intermediates"]
    names = sorted(layers, key=lambda name: int(name.rsplit("_", 1)[1]))
    return {
        key: jnp.stack([layers[name]["mlp"][key][0] for name in names])
        for key in layers[names[0]]["mlp"]
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
