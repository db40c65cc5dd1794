"""Pre-norm decoder with RMSNorm, rotary embeddings, causal attention with
QK-norm, and a sparse-expert SwiGLU feed-forward (the published
``OlmoeDecoderLayer``), with a rank-r LoRA delta ``(alpha / r) x A B`` on the
four attention projections and on every expert's gate, up and down:

    q = RMSNorm(x Wq), k = RMSNorm(x Wk)   over the whole projected vector,
                                            before the heads and before RoPE
    p = softmax(x Wr)                       over all E experts, float32
    (w, S) = top_k(p)                       w is not renormalised
    y = sum_{e in S} w_e W_down_e(silu(W_gate_e x) * W_up_e x)
    loss = cross-entropy + coef x E x sum_e f_e P_e

with f_e the share of all (layer, token, choice) assignments that went to
expert e and P_e the mean router probability of e, both pooled over layers.
Every expert is computed for every token, densely, one expert at a time, and
masked by the top-k weights: no sort, no gather, no grouped matmul.

Departures from the published code: the rotary pairs are interleaved, as in
``references/decoder.py`` and ``models/llama.py``; and the load-balancing term
is the sum over experts of f_e P_e once (the Hugging Face function sums it
over the k slots too, which is k times this; 1.0 here under uniform routing).

**Two modes of routing**, because top-k is discontinuous.  With seeded random
weights a token's 8th and 9th router logits lie about 0.08 apart on average,
so bfloat16 activations flip a choice in roughly one token in ten a layer,
and a flipped token moves the logits by far more than the model tolerance.

- *Its own routing* (``routing=None``): float32 against float32, where no
  choice flips (the CPU tests hold this under 1e-4, gradients included).
- *The program's routing, verified* (``routing`` = the program's chosen
  experts ``[L, N, k]``): the reference computes its own float32 router
  logits ``l`` and accepts the program's set S for a token only where

      min_{i in S} l_i  >=  max_{j not in S} l_j - ROUTING_EPS

  (and S holds k different experts); elsewhere the token's weights are NaN,
  so the comparison fails.  A set that is a top-k of the reference's logits
  to within the program's own rounding is a correct routing; any other set is
  refused.  The experts' weights ``w`` are always the reference's own.
"""

import jax
import jax.numpy as jnp

from benchmark.references.decoder import HIGHEST, _dot, _proj, _rms_norm, _rope

# How far below the best expert left out a chosen expert's float32 router
# logit may lie.  Three times the largest difference between the program's
# router logits (float32 arithmetic on bfloat16 activations) and this
# reference's, over both layers of 8 seeds x 256 tokens x 64 experts at the
# published widths on the v5e: 0.0292 (0.0261 to 0.0292 a seed; the logits'
# rms is about 1 and the typical difference 0.006, the model's 0.6 % of rms;
# the largest margin by which a program set needed it was 0.0261; my chip
# runs, PR 27, benchmark/moe_routing_report.py; the float32 program on the
# CPU differs by 7e-7).  A token's 8th and 9th logits lie about 0.08 apart on
# average, so at this eps a neighbour of the boundary passes and an expert
# from further down does not: what is held is that the program chose a top-k
# of its own logits, not which of two near-equal experts it preferred.
ROUTING_EPS = 0.09


def _route(logits, k, chosen, eps):
    """``(combine [N, E], counts [E], margin)``: each token's top-k softmax
    weights scattered over the experts; with ``chosen [N, k]`` given, those
    sets verified against ``logits`` (NaN weights where refused).  ``margin``
    is the largest ``max_out - min_in`` over the tokens: the ``eps`` that
    would just accept them all."""
    n_experts = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    if chosen is None:
        chosen = jax.lax.top_k(probs, k)[1]
    member = jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32).sum(1)
    inside = jnp.where(member > 0, logits, jnp.inf).min(-1)
    outside = jnp.where(member > 0, -jnp.inf, logits).max(-1)
    accepted = (inside >= outside - eps) & jnp.all(member <= 1, axis=-1)
    combine = jnp.where(accepted[:, None], probs * member, jnp.nan)
    return combine, member.sum(0), probs.mean(0), jnp.max(outside - inside)


def combine_of(weights, experts, n_experts):
    """``[N, E]`` from ``weights [N, k]`` on ``experts [N, k]``: zero on the
    experts a token did not choose."""
    chosen = jax.nn.one_hot(experts, n_experts, dtype=weights.dtype)
    return (chosen * weights[..., None]).sum(1)


def dense_experts(y, m, combine, scale):
    """``sum_e combine[:, e] x expert_e(y)``, every expert on every token."""

    def one(expert):
        gate, up, down, weight = expert
        hidden = jax.nn.silu(_proj(y, gate, scale)) * _proj(y, up, scale)
        return _proj(hidden, down, scale) * weight[:, None]

    return jax.lax.map(
        one, (m["w_gate"], m["w_up"], m["w_down"], combine.T)
    ).sum(0)


def forward_with_routing(config, params, tokens, routing=None,
                         eps=ROUTING_EPS):
    """``(logits [B, T, V], details)`` with ``details`` = each layer's
    ``counts [L, E]``, ``prob_mean [L, E]``, router ``logits [L, N, E]`` and
    the verification's ``margin [L]``."""
    p = params["params"]
    lora = config["assumed"]["lora"]
    scale = lora["alpha"] / lora["rank"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // h
    eps_norm, theta = config["rms_norm_eps"], config["rope_theta"]
    top = config["num_experts_per_tok"]
    b, t = tokens.shape
    x = p["embed"]["embedding"].astype(jnp.float32)[tokens]
    mask = jnp.tril(jnp.ones((t, t), bool))
    details = dict(counts=[], prob_mean=[], logits=[], margin=[])
    for i in range(config["num_hidden_layers"]):
        layer = p[f"layer_{i}"]
        a = layer["attn"]
        y = _rms_norm(x, layer["attn_norm"], eps_norm)
        q = _rms_norm(_proj(y, a["wq"], scale), a["q_norm"], eps_norm)
        k = _rms_norm(_proj(y, a["wk"], scale), a["k_norm"], eps_norm)
        q = _rope(q.reshape(b, t, h, d), theta)
        k = _rope(k.reshape(b, t, kv, d), theta)
        v = _proj(y, a["wv"], scale).reshape(b, t, kv, d)
        k, v = (jnp.repeat(z, h // kv, axis=2) for z in (k, v))
        s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST) / d ** 0.5
        s = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        o = jnp.einsum("bhts,bshd->bthd", s, v, precision=HIGHEST)
        x = x + _proj(o.reshape(b, t, h * d), a["wo"], scale)
        m = layer["mlp"]
        y = _rms_norm(x, layer["mlp_norm"], eps_norm).reshape(b * t, -1)
        router_logits = _dot(y, m["router"])
        combine, counts, prob_mean, margin = _route(
            router_logits, top, None if routing is None else routing[i], eps
        )
        x = x + dense_experts(y, m, combine, scale).reshape(x.shape)
        for key, value in zip(
            ("counts", "prob_mean", "logits", "margin"),
            (counts, prob_mean, router_logits, margin),
        ):
            details[key].append(value)
    x = _rms_norm(x, p["final_norm"], eps_norm)
    logits = _dot(x, p["lm_head"]["kernel"])
    return logits, {key: jnp.stack(v) for key, v in details.items()}


def forward(config, params, tokens, routing=None, eps=ROUTING_EPS):
    return forward_with_routing(config, params, tokens, routing, eps)[0]


def loss(config, params, tokens, targets, routing=None, eps=ROUTING_EPS):
    """Mean cross-entropy plus ``router_aux_loss_coef`` x the load-balancing
    term, pooled over layers."""
    logits, details = forward_with_routing(config, params, tokens, routing, eps)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    f = details["counts"].sum(0) / details["counts"].sum()
    balance = config["num_experts"] * jnp.sum(f * details["prob_mean"].mean(0))
    coef = config["assumed"]["router_aux_loss_coef"]
    return -picked.mean() + coef * balance


def routing_disagreement(own_logits, routing, k):
    """The share of (layer, token) pairs whose program set ``routing [L, N,
    k]`` is not the top-k of the reference's own ``own_logits [L, N, E]``."""
    own = jnp.sort(jax.lax.top_k(own_logits, k)[1], axis=-1)
    return jnp.mean(jnp.any(own != jnp.sort(routing, axis=-1), axis=-1))
