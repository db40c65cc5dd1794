"""Pre-norm decoder with multi-head latent attention, a leading dense SwiGLU
layer, and after it layers of a shared expert beside routed experts behind a
sigmoid gate (the published DeepSeek-V3 block, which ``model_type: axk1``
runs), with a rank-r LoRA delta ``(alpha / r) x A B`` on every projection
that multiplies an activation.  Per token ``x`` (RMSNorm eps from the file):

    c_q = RMSNorm(x W_qa);  q = c_q W_qb        heads of [q_nope | q_pe]
    [c_kv | k_pe] = x W_kva;  RMSNorm(c_kv) W_kvb   heads of [k_nope | v]
    k_h = [k_nope_h | rope(k_pe)]               one rope key for every head
    o = causal softmax(q k^T s) v;  x += o W_o  s = m^2 / sqrt(qk dim)
    s_e = sigmoid(x W_r);  S = top_k(s);  w_e = f s_e / sum_{j in S} s_j
    x += SwiGLU_shared(x) + sum_{e in S, e held} w_e SwiGLU_e(x)

with yarn's blended rope frequencies (``yarn_frequencies``), ``m = 0.1
mscale_all_dim ln(factor) + 1`` and ``f = routed_scaling_factor``.  The first
``first_k_dense_replace`` layers take a dense SwiGLU instead of the gate.

**The share.**  The file's ``n_routed_experts`` is how many experts this chip
holds, from ``assumed.expert_offset`` on; ``published.n_routed_experts`` is
the router's width.  The gate scores and chooses among all of them and
normalises over a token's whole choice; the layer adds the held experts' part
alone.  What the absent experts would add is left out, here as in the
program, and the partial result goes on (model-configs guide, section 4).
``share=(offset, held)`` overrides the file's, so that a test can add the
shares up.

Every held expert is computed for every token, densely, one expert at a
time, and masked by the gate's weights: no sort, no gather, no grouped
matmul.  Departures from the published code: the rotary pairs are interleaved
(dims 2i, 2i+1), as in ``references/decoder.py`` and ``models/llama.py``;
``topk_method: "none"`` is read as no group-limited choice and no correction
bias (``n_group`` / ``topk_group`` unused); the normaliser has no ``1e-20``.

**The program's routing, verified** (``routing`` = what the program sowed in
each expert layer: its chosen ``experts [L, N, k]``, its float32 router
``logits [L, N, E]`` and the ``router_input [L, N, D]`` it computed them
from).  Top-k is discontinuous, and five layers of bfloat16 activations move
a logit by more than the gap between a token's 8th and 9th, so the program's
set is not compared with the top-k of *this* file's activations.  It is held
on the program's own input instead, where nothing but the router's own
arithmetic stands between the two sides: the program's logits must be this
file's float32 product of that input to within ``LOGIT_EPS``, and the set
must be a top-k of those logits exactly (ties apart).  Where either fails the
token's weights are NaN.  The weights of an accepted set are always this
file's own, from its own logits.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.references.decoder import HIGHEST, _dot, _proj, _rms_norm
from benchmark.references.moe_decoder import dense_experts

# How far the program's float32 router logits may lie from this file's
# product of the same input (both at the highest matmul precision).  Between
# two readings at the published widths on the v5e (my chip runs, PR 32, four
# expert layers x 512 tokens x 192 experts, logits of rms 1.0; PERF.md
# section 6): the program's largest difference, LOGIT_READING_PROGRAM, and
# the same product at the precision below the one the configuration states
# (one bfloat16 pass, jax's default on a TPU), LOGIT_READING_BF16, which has
# to be refused.
LOGIT_EPS = 2e-4


def yarn_magnitude(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(d, theta, scaling):
    """``d / 2`` frequencies: ``theta^(-2i/d)`` blended with their ``1 /
    factor`` by a linear ramp over the pair index between the pair that
    turns ``beta_fast`` times over the original context and the one that
    turns ``beta_slow`` times."""
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if scaling is None:
        return freqs
    turning = lambda turns: d * math.log(
        scaling["original_max_position_embeddings"] / (turns * 2 * math.pi)
    ) / (2 * math.log(theta))
    low = max(math.floor(turning(scaling["beta_fast"])), 0)
    high = min(math.ceil(turning(scaling["beta_slow"])), d - 1)
    ramp = jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3),
        0.0, 1.0,
    )
    return freqs / scaling["factor"] * ramp + freqs * (1.0 - ramp)


def _rope(x, freqs, magnitude):
    """Interleaved pairs of ``x [B, T, H, d]`` turned by ``position x
    freqs``."""
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos = (jnp.cos(angles) * magnitude)[:, None, :]
    sin = (jnp.sin(angles) * magnitude)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1
    ).reshape(x.shape)


def latent_attention(config, a, y, scale):
    """The attention block's output for normed input ``y [B, T, D]``."""
    b, t, _ = y.shape
    h = config["num_attention_heads"]
    nope, pe, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                    config["v_head_dim"])
    eps, scaling = config["rms_norm_eps"], config["rope_scaling"]
    freqs = yarn_frequencies(pe, config["rope_theta"], scaling)
    magnitude, softmax_scale = 1.0, (nope + pe) ** -0.5
    if scaling is not None:
        all_dim = yarn_magnitude(scaling["factor"], scaling["mscale_all_dim"])
        magnitude = yarn_magnitude(scaling["factor"], scaling["mscale"]) / all_dim
        softmax_scale *= all_dim ** 2
    c_q = _rms_norm(_proj(y, a["wq_a"], scale), a["q_norm"], eps)
    q = _proj(c_q, a["wq_b"], scale).reshape(b, t, h, nope + pe)
    down = _proj(y, a["wkv_a"], scale)
    c_kv = _rms_norm(down[..., :-pe], a["kv_norm"], eps)
    kv = _proj(c_kv, a["wkv_b"], scale).reshape(b, t, h, nope + dv)
    k_pe = _rope(down[..., None, -pe:], freqs, magnitude)  # [B, T, 1, pe]
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], freqs, magnitude)], -1
    )
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, t, h, pe))], -1
    )
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST) * softmax_scale
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    o = jnp.einsum("bhts,bshd->bthd", s, kv[..., nope:], precision=HIGHEST)
    return _proj(o.reshape(b, t, h * dv), a["wo"], scale)


def swiglu(y, m, scale):
    gate = jax.nn.silu(_proj(y, m["w_gate"], scale))
    return _proj(gate * _proj(y, m["w_up"], scale), m["w_down"], scale)


def gate_weights(config, logits, routing=None, eps=LOGIT_EPS):
    """``(combine [N, E], counts [E], said)`` from the router ``logits [N,
    E]`` over all E experts: each token's weights ``f s_e / sum_S s`` on its
    top-k S (``norm_topk_prob``), zero elsewhere.  With ``routing =
    (experts [N, k], program logits [N, E], this file's logits of the
    program's input [N, E])`` the program's sets stand in for the top-k
    after verification (NaN where refused); ``said`` holds the largest
    ``logit_error`` and the largest ``set_margin`` (how far a chosen logit
    lies under the best one left out: 0 or less for a top-k)."""
    n_experts = logits.shape[-1]
    if config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"]:
        raise ValueError("the reference gates by normalised sigmoid scores")
    scores = jax.nn.sigmoid(logits)
    said = dict(logit_error=jnp.float32(0), set_margin=jnp.float32(0))
    if routing is None:
        chosen = jax.lax.top_k(scores, config["num_experts_per_tok"])[1]
        accepted = True
    else:
        chosen, theirs, ours = routing
        error = jnp.abs(theirs - ours).max(-1)
        member = jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32).sum(1)
        # The sigmoid is monotone: the logits order as the scores do.
        inside = jnp.where(member > 0, theirs, jnp.inf).min(-1)
        outside = jnp.where(member > 0, -jnp.inf, theirs).max(-1)
        accepted = (
            (error <= eps) & (inside >= outside)
            & jnp.all(member <= 1, axis=-1)
        )[:, None]
        said = dict(
            logit_error=error.max(), set_margin=jnp.max(outside - inside)
        )
    member = jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32).sum(1)
    picked = scores * member
    weights = config["routed_scaling_factor"] * picked / picked.sum(
        -1, keepdims=True
    )
    return jnp.where(accepted, weights, jnp.nan), member.sum(0), said


def expert_layer(config, m, y, scale, share, routing=None, eps=LOGIT_EPS):
    """``(shared + held routed part [N, D], details)`` for ``y [N, D]``;
    ``routing = (experts, logits, router_input)`` of the program's layer."""
    offset, held = share
    logits = _dot(y, m["router"])
    if routing is not None:
        chosen, theirs, their_input = routing
        routing = (
            chosen, theirs, _dot(their_input.astype(jnp.float32), m["router"])
        )
    combine, counts, said = gate_weights(config, logits, routing, eps)
    routed = dense_experts(y, m, combine[:, offset:offset + held], scale)
    out = routed + swiglu(y, m["shared"], scale)
    return out, dict(counts=counts, logits=logits, **said)


def share_of(config):
    return config["assumed"]["expert_offset"], config["n_routed_experts"]


def forward_with_routing(config, params, tokens, routing=None,
                         eps=LOGIT_EPS, share=None):
    """``(logits [B, T, V], details)`` with ``details`` = each expert layer's
    ``counts [L, E]``, router ``logits [L, N, E]`` and the verification's
    ``logit_error [L]`` and ``set_margin [L]``.  ``routing`` is a dict of the
    program's sown ``experts``, ``logits`` and ``router_input``, layers
    stacked (``models/llama.routing_of``)."""
    p = params["params"]
    lora = config["assumed"]["lora"]
    scale = lora["alpha"] / lora["rank"]
    eps_norm = config["rms_norm_eps"]
    share = share or share_of(config)
    b, t = tokens.shape
    x = p["embed"]["embedding"].astype(jnp.float32)[tokens]
    details = dict(counts=[], logits=[], logit_error=[], set_margin=[])
    for i in range(config["num_hidden_layers"]):
        layer = p[f"layer_{i}"]
        x = x + latent_attention(
            config, layer["attn"],
            _rms_norm(x, layer["attn_norm"], eps_norm), scale,
        )
        y = _rms_norm(x, layer["mlp_norm"], eps_norm)
        if i < config["first_k_dense_replace"]:
            x = x + swiglu(y, layer["mlp"], scale)
            continue
        j = len(details["counts"])
        out, said = expert_layer(
            config, layer["mlp"], y.reshape(b * t, -1), scale, share,
            None if routing is None else tuple(
                routing[key][j] for key in ("experts", "logits", "router_input")
            ), eps,
        )
        x = x + out.reshape(x.shape)
        for key, value in said.items():
            details[key].append(value)
    x = _rms_norm(x, p["final_norm"], eps_norm)
    logits = _dot(x, p["lm_head"]["kernel"])
    return logits, {key: jnp.stack(v) for key, v in details.items()}


def forward(config, params, tokens, routing=None, eps=LOGIT_EPS, share=None):
    return forward_with_routing(config, params, tokens, routing, eps, share)[0]


def loss(config, params, tokens, targets, routing=None, eps=LOGIT_EPS):
    """Mean cross-entropy over the vocabulary held (``router_aux_loss_coef``
    is 0 in this family: the router is frozen)."""
    logits = forward(config, params, tokens, routing, eps)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
