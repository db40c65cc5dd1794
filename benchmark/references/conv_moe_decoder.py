"""Pre-norm decoder whose layers mix tokens by a gated short convolution or
by grouped-query attention, as a published list of kinds says (``layer_types``,
one of ``conv`` / ``full_attention`` a layer: no period), with a dense SwiGLU
in the first ``num_dense_layers`` layers and sparse experts behind a biased
sigmoid gate in the others (``model_type: lfm2_moe``), and a rank-r LoRA delta
``(alpha / r) x A B`` on every projection that multiplies an activation.

With ``h`` the residual stream ``[B, T, hidden]`` and ``norm`` an RMSNorm with
a weight (eps ``norm_eps``), every layer ``i`` is

    h = h + mixer_i(norm_op(h));   h = h + ffn_i(norm_ffn(h))

and after the last layer one more norm and ``logits = x E^T`` with ``E`` the
embedding, in float32.

*conv* (K = ``conv_L_cache`` taps, no bias anywhere):

    [b, c, u] = x W_in                       # hidden -> 3 x hidden
    s = b * u
    z_t = sum_{j<K} w[j] * s_{t-(K-1)+j}     # a channel; s_{<0} = 0; w [K, hidden]
    out = (c * z) W_out                      # hidden -> hidden

*full_attention*: ``q = x W_q`` as ``num_attention_heads`` heads, ``k, v`` as
``num_key_value_heads`` heads shared by the q heads of their group; **an
RMSNorm over the head size on every head of q and of k** (one weight for q,
one for k); rope at ``rope_theta`` on the whole head; causal softmax at scale
``1 / sqrt(head size)``; ``W_o``.

*dense*: ``W_down(silu(W_gate x) * W_up x)`` of width ``intermediate_size``; an
expert the same of width ``moe_intermediate_size``.

*expert layer*:

    p = sigmoid(x R)                         # float32, all E experts
    S = top_k(p + bias)                      # the bias chooses and weighs nothing
    w_e = f p_e / (sum_{j in S} p_j + 1e-6)  # norm_topk_prob; f = routed_scaling_factor;
                                             # the 1e-6 is assumed.norm_topk_eps
    out = sum_{e in S} w_e expert_e(x)

Every expert is computed for every token, densely, one expert at a time, and
masked by the gate's weights: no sort, no gather, no grouped matmul; attention
is dense under an explicit mask; everything is float32 at the highest matmul
precision; nothing of ``dpwa_tpu``.  Departures from the published code: the
rotary pairs are interleaved (dims 2i, 2i+1), as in ``references/decoder.py``
and ``models/llama.py``; the head is tied to the embedding and the ``1e-6`` is
the model type's (the configuration file lists both under ``assumed``).

**The program's routing, verified** (``routing`` = what the program sowed in
each expert layer: its chosen ``experts [L, N, k]``, its float32 router
``logits [L, N, E]`` and the ``router_input [L, N, D]`` it computed them
from), as ``references/latent_moe_decoder.py`` does it and for its reason:
top-k is discontinuous, and layers of bfloat16 activations move a score by
more than the gap between a token's 4th and 5th.  So the program's set is
held on the program's own input, where nothing but the router's own
arithmetic stands between the two sides: the program's logits must be this
file's float32 product of that input to within ``LOGIT_EPS`` (the accepted
limit of that file), and the set must be a top-k of ``sigmoid(those logits) +
bias`` to within ``CHOICE_EPS``.  Where either fails the token's weights are
NaN.  The weights of an accepted set are always this file's own, from its own
logits.
"""

import jax
import jax.numpy as jnp

from benchmark.references.decoder import (
    HIGHEST, _dot, _proj, _rms_norm, _rope,
)
from benchmark.references.latent_moe_decoder import LOGIT_EPS, swiglu
from benchmark.references.moe_decoder import dense_experts

# How far under the best biased score left out a chosen expert's may lie.
# Both sides compute ``sigmoid(logits) + bias`` in float32 from the program's
# own logits, so only a fused logistic's last bit separates them: between the
# program's reading (CHOICE_READING_PROGRAM in PERF.md section 6, PR 44: 0 in
# every layer of every seed read on the v5e) and what scores rounded to
# bfloat16, the nearest precision below, read there (CHOICE_READING_BF16,
# about 2e-3: 2^-9 of a score near one half), which has to be refused.
CHOICE_EPS = 1e-5
KINDS = ("conv", "full_attention")


def short_conv(m, y, scale):
    """The gated short convolution's output for normed input ``y [B, T, D]``."""
    b, c, u = jnp.split(_proj(y, m["in_proj"], scale), 3, -1)
    w = m["conv_kernel"].astype(jnp.float32)
    taps, t = w.shape[0], y.shape[1]
    s = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    z = sum(w[j] * s[:, j:j + t] for j in range(taps))
    return _proj(c * z, m["out_proj"], scale)


def attention(config, a, y, scale):
    """Grouped-query attention with a norm a head on q and k."""
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    b, t, hidden = y.shape
    d = hidden // h
    eps, theta = config["norm_eps"], config["rope_theta"]
    q = _proj(y, a["wq"], scale).reshape(b, t, h, d)
    k = _proj(y, a["wk"], scale).reshape(b, t, kv, d)
    v = _proj(y, a["wv"], scale).reshape(b, t, kv, d)
    q = _rope(_rms_norm(q, a["q_norm"], eps), theta)
    k = _rope(_rms_norm(k, a["k_norm"], eps), theta)
    k, v = (jnp.repeat(z, h // kv, axis=2) for z in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST) / d ** 0.5
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    o = jnp.einsum("bhts,bshd->bthd", s, v, precision=HIGHEST)
    return _proj(o.reshape(b, t, h * d), a["wo"], scale)


def gate_weights(config, logits, bias, routing=None, eps=LOGIT_EPS,
                 choice_eps=CHOICE_EPS, round_scores=lambda s: s):
    """``(combine [N, E], counts [E], said)`` from the router ``logits [N,
    E]``: each token's weights ``f p_e / (sum_S p + 1e-6)`` on its top-k S of
    ``p + bias``, zero elsewhere.  With ``routing = (experts [N, k], program
    logits [N, E], this file's logits of the program's input [N, E])`` the
    program's sets stand in for the top-k after verification (NaN where
    refused); ``said`` holds the largest ``logit_error``, the largest
    ``set_margin`` (how far a chosen biased score lies under the best one
    left out: 0 or less for a top-k) and ``bias_moved`` (the share of the
    assignments that are not among the top-k of ``p`` alone).
    ``round_scores`` is applied to the scores the choice is verified on (the
    identity; a test and the chip's second reading round them to a narrower
    type to show ``choice_eps`` can tell)."""
    if not (config["norm_topk_prob"] and config["use_expert_bias"]):
        raise ValueError(
            "the reference gates by normalised sigmoid scores with a bias"
        )
    n_experts, k = logits.shape[-1], config["num_experts_per_tok"]
    bias = bias.astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    said = dict(logit_error=jnp.float32(0), set_margin=jnp.float32(0))
    if routing is None:
        chosen = jax.lax.top_k(scores + bias, k)[1]
        accepted = True
    else:
        chosen, theirs, ours = routing
        error = jnp.abs(theirs - ours).max(-1)
        member = jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32).sum(1)
        biased = round_scores(jax.nn.sigmoid(theirs)).astype(jnp.float32) + bias
        inside = jnp.where(member > 0, biased, jnp.inf).min(-1)
        outside = jnp.where(member > 0, -jnp.inf, biased).max(-1)
        accepted = (
            (error <= eps) & (inside >= outside - choice_eps)
            & jnp.all(member <= 1, axis=-1)
        )[:, None]
        said = dict(
            logit_error=error.max(), set_margin=jnp.max(outside - inside)
        )
    member = jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32).sum(1)
    plain = jax.lax.top_k(scores, k)[1]
    kept = jnp.any(chosen[:, :, None] == plain[:, None, :], axis=-1)
    said["bias_moved"] = 1.0 - kept.mean()
    picked = scores * member
    weights = config["routed_scaling_factor"] * picked / (
        picked.sum(-1, keepdims=True) + config["assumed"]["norm_topk_eps"]
    )
    return jnp.where(accepted, weights, jnp.nan), member.sum(0), said


def expert_layer(config, m, y, scale, routing=None, **how):
    """``(the routed experts' sum [N, D], details)`` for ``y [N, D]``;
    ``routing = (experts, logits, router_input)`` of the program's layer."""
    logits = _dot(y, m["router"])
    if routing is not None:
        chosen, theirs, their_input = routing
        routing = (
            chosen, theirs, _dot(their_input.astype(jnp.float32), m["router"])
        )
    combine, counts, said = gate_weights(
        config, logits, m["expert_bias"], routing, **how
    )
    out = dense_experts(y, m, combine, scale)
    return out, dict(counts=counts, logits=logits, **said)


def forward_with_routing(config, params, tokens, routing=None, **how):
    """``(logits [B, T, V], details)`` with ``details`` = each expert layer's
    ``counts [L, E]``, router ``logits [L, N, E]`` and the verification's
    ``logit_error [L]``, ``set_margin [L]`` and ``bias_moved [L]``.
    ``routing`` is a dict of the program's sown ``experts``, ``logits`` and
    ``router_input``, expert layers stacked (``models/llama.routing_of``)."""
    p = params["params"]
    lora = config["assumed"]["lora"]
    scale = lora["alpha"] / lora["rank"]
    eps = config["norm_eps"]
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types names one of {KINDS} a layer")
    b, t = tokens.shape
    embedding = p["embed"]["embedding"].astype(jnp.float32)
    x = embedding[tokens]
    details = dict(
        counts=[], logits=[], logit_error=[], set_margin=[], bias_moved=[]
    )
    for i, kind in enumerate(kinds):
        layer = p[f"layer_{i}"]
        if kind == "conv":
            y = _rms_norm(x, layer["conv_norm"], eps)
            x = x + short_conv(layer["conv"], y, scale)
        else:
            y = _rms_norm(x, layer["attn_norm"], eps)
            x = x + attention(config, layer["attn"], y, scale)
        y = _rms_norm(x, layer["mlp_norm"], eps)
        if i < config["num_dense_layers"]:
            x = x + swiglu(y, layer["mlp"], scale)
            continue
        j = len(details["counts"])
        out, said = expert_layer(
            config, layer["mlp"], y.reshape(b * t, -1), scale,
            None if routing is None else tuple(
                routing[key][j] for key in ("experts", "logits", "router_input")
            ), **how,
        )
        x = x + out.reshape(x.shape)
        for key, value in said.items():
            details[key].append(value)
    x = _rms_norm(x, p["final_norm"], eps)
    logits = jnp.dot(x, embedding.T, precision=HIGHEST)
    return logits, {key: jnp.stack(v) for key, v in details.items() if v}


def forward(config, params, tokens, routing=None, **how):
    return forward_with_routing(config, params, tokens, routing, **how)[0]


def loss(config, params, tokens, targets, routing=None, **how):
    """Mean cross-entropy (no auxiliary term: ``router_aux_loss_coef`` is 0
    in this model type, which balances through the bias)."""
    logits = forward(config, params, tokens, routing, **how)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
