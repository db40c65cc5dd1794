"""Pre-norm decoder whose layers attend behind a sliding window or over every
earlier key, as a published list of kinds says (``layer_types``, one of
``sliding_attention`` / ``full_attention`` a layer), each kind with a rope of
its own (``rope_parameters``), and whose every layer's feed-forward is a
sparse-expert SwiGLU (``model_type: mellum``), with a rank-r LoRA delta
``(alpha / r) x A B`` on the four attention projections and on every expert's
gate, up and down.

With ``x`` the residual stream ``[B, T, hidden]`` and ``norm`` an RMSNorm with
a weight (eps ``rms_norm_eps``), every layer ``i`` is

    x = x + attn_i(norm_1(x));   x = x + moe(norm_2(x))

and after the last layer one more norm and ``logits = x W_head`` (untied),
in float32.

*attn_i*: ``q = x W_q`` as ``num_attention_heads`` heads of ``head_dim``, ``k,
v`` as ``num_key_value_heads`` heads shared by the q heads of their group, no
bias; **an RMSNorm over ``head_dim`` on every head of q and of k** (one weight
for q, one for k; assumed, the configuration file says why); rope on the whole
head by the layer's kind:

- ``rope_type: default``: the inverse frequencies ``theta^(-2j / head_dim)``;
- ``rope_type: yarn``: those blended with themselves over ``factor`` by a
  linear ramp over the pair index ``j``, between the pairs that turn
  ``beta_fast`` times and ``beta_slow`` times in
  ``original_max_position_embeddings`` positions (floor and ceiling, as
  published), and **``cos`` and ``sin`` times ``attention_factor``**: the
  published form, written here without ``models/llama.YarnScaling``;

scores ``q . k / sqrt(head_dim)``; query ``t`` sees key ``s`` iff ``0 <= t -
s`` in a full layer and iff ``0 <= t - s < sliding_window`` in a sliding one
(the query's own key among its ``sliding_window``); one softmax over a ``[T,
T]`` array under that mask; ``out W_o``.

*moe*:

    p = softmax(x R)                        # float32, all E experts
    S = top_k(p)                            # num_experts_per_tok
    w_e = p_e / sum_{j in S} p_j            # norm_topk_prob
    out = sum_{e in S} w_e W_down_e(silu(W_gate_e x) * W_up_e x)

No shared expert, no auxiliary term (the router is frozen).  Every expert is
computed for every token, densely, one expert at a time, and masked by the
eight weights: no sort, no gather, no grouped matmul; everything is float32 at
the highest matmul precision; nothing of ``dpwa_tpu``.  Departure from the
published code, as in ``references/decoder.py`` and ``models/llama.py``: the
rotary pairs are interleaved (dims 2i, 2i+1).

**The program's routing, verified**, as ``references/moe_decoder.py`` does it
and for its reason (top-k is discontinuous): given the program's chosen
experts ``[L, N, k]`` the reference computes its own float32 router logits and
accepts a token's set only where it is a top-k of them to within that file's
accepted ``ROUTING_EPS`` and holds ``k`` different experts; elsewhere the
token's weights are NaN and the comparison fails.  The weights of an accepted
set are always this file's own: its own ``p`` at those experts over their sum.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.references.decoder import HIGHEST, _dot, _proj, _rms_norm
from benchmark.references.moe_decoder import ROUTING_EPS, dense_experts

KINDS = ("sliding_attention", "full_attention")


def inverse_frequencies(d: int, rope: dict):
    """``(the d / 2 inverse frequencies, what cos and sin are multiplied
    by)`` of one ``rope_parameters`` group."""
    theta = rope["rope_theta"]
    plain = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if rope["rope_type"] == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"no rope of type {rope['rope_type']!r}")
    span = rope["original_max_position_embeddings"]
    # The (real) pair index that turns ``turns`` times over ``span``.
    turning = lambda turns: d * math.log(span / (turns * 2 * math.pi)) / (
        2 * math.log(theta)
    )
    low = max(math.floor(turning(rope["beta_fast"])), 0)
    high = min(math.ceil(turning(rope["beta_slow"])), d - 1)
    ramp = jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3),
        0.0, 1.0,
    )
    return (
        plain / rope["factor"] * ramp + plain * (1.0 - ramp),
        rope["attention_factor"],
    )


def rope(x, rope_parameters: dict):
    """``x [B, T, H, D]`` turned by one kind's rope, pairs interleaved."""
    t, d = x.shape[1], x.shape[-1]
    freqs, factor = inverse_frequencies(d, rope_parameters)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs  # [T, D/2]
    cos = jnp.cos(angles)[:, None, :] * factor
    sin = jnp.sin(angles)[:, None, :] * factor
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1
    ).reshape(x.shape)


def seen(kind: str, t: int, window: int):
    """``[T, T]``: whether query ``t`` (rows) sees key ``s`` (columns)."""
    behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    if kind == "full_attention":
        return behind >= 0
    return (behind >= 0) & (behind < window)


def attention(config, kind, a, y, scale):
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, eps = config["head_dim"], config["rms_norm_eps"]
    b, t, _ = y.shape
    turn = config["rope_parameters"][kind]
    q = _proj(y, a["wq"], scale).reshape(b, t, h, d)
    k = _proj(y, a["wk"], scale).reshape(b, t, kv, d)
    v = _proj(y, a["wv"], scale).reshape(b, t, kv, d)
    q = rope(_rms_norm(q, a["q_norm"], eps), turn)
    k = rope(_rms_norm(k, a["k_norm"], eps), turn)
    k, v = (jnp.repeat(z, h // kv, axis=2) for z in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST) / d ** 0.5
    mask = seen(kind, t, config["sliding_window"])
    s = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    o = jnp.einsum("bhts,bshd->bthd", s, v, precision=HIGHEST)
    return _proj(o.reshape(b, t, h * d), a["wo"], scale)


def gate_weights(config, logits, chosen=None, eps=ROUTING_EPS):
    """``(combine [N, E], counts [E], margin)``: each token's top-k softmax
    weights over their sum, scattered over the experts; with ``chosen [N,
    k]`` given, those sets verified against ``logits`` (NaN weights where
    refused).  ``margin`` is the largest ``max_out - min_in`` over the
    tokens: the ``eps`` that would just accept them all."""
    if not config["norm_topk_prob"]:
        raise ValueError("the reference divides a token's weights by their sum")
    n_experts = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    if chosen is None:
        chosen = jax.lax.top_k(probs, config["num_experts_per_tok"])[1]
    member = jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32).sum(1)
    inside = jnp.where(member > 0, logits, jnp.inf).min(-1)
    outside = jnp.where(member > 0, -jnp.inf, logits).max(-1)
    accepted = (inside >= outside - eps) & jnp.all(member <= 1, axis=-1)
    picked = probs * member
    weights = picked / picked.sum(-1, keepdims=True)
    combine = jnp.where(accepted[:, None], weights, jnp.nan)
    return combine, member.sum(0), jnp.max(outside - inside)


def forward_with_routing(config, params, tokens, routing=None,
                         eps=ROUTING_EPS):
    """``(logits [B, T, V], details)`` with ``details`` = each layer's
    ``counts [L, E]``, router ``logits [L, N, E]`` and the verification's
    ``margin [L]``.  ``routing`` is the program's chosen experts ``[L, N,
    k]``."""
    p = params["params"]
    lora = config["assumed"]["lora"]
    scale = lora["alpha"] / lora["rank"]
    eps_norm = config["rms_norm_eps"]
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types names one of {KINDS} a layer")
    if set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("every layer's feed-forward is sparse here")
    b, t = tokens.shape
    x = p["embed"]["embedding"].astype(jnp.float32)[tokens]
    details = dict(counts=[], logits=[], margin=[])
    for i, kind in enumerate(kinds):
        layer = p[f"layer_{i}"]
        y = _rms_norm(x, layer["attn_norm"], eps_norm)
        x = x + attention(config, kind, layer["attn"], y, scale)
        m = layer["mlp"]
        y = _rms_norm(x, layer["mlp_norm"], eps_norm).reshape(b * t, -1)
        router_logits = _dot(y, m["router"])
        combine, counts, margin = gate_weights(
            config, router_logits, None if routing is None else routing[i], eps
        )
        x = x + dense_experts(y, m, combine, scale).reshape(x.shape)
        for key, value in zip(
            ("counts", "logits", "margin"), (counts, router_logits, margin)
        ):
            details[key].append(value)
    x = _rms_norm(x, p["final_norm"], eps_norm)
    logits = _dot(x, p["lm_head"]["kernel"])
    return logits, {key: jnp.stack(v) for key, v in details.items()}


def forward(config, params, tokens, routing=None, eps=ROUTING_EPS):
    return forward_with_routing(config, params, tokens, routing, eps)[0]


def loss(config, params, tokens, targets, routing=None, eps=ROUTING_EPS):
    """Mean cross-entropy, no auxiliary term."""
    logits = forward(config, params, tokens, routing, eps)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
