"""Pre-norm decoder with EVA attention and eight next-byte heads (the
published EvaByte block, ``model_type: evabyte``, ``attention_class: "eva"``),
with a rank-r LoRA delta ``(alpha / r) x A B`` on all seven projections.

With ``x`` the residual stream ``[B, T, hidden]`` and ``norm(x) = x / rms(x)
* (1 + w)`` (``norm_add_unit_offset``), every layer is

    x = x + W_o eva(norm_1(x));   x = x + W_down(silu(W_gate g) * W_up g),
    g = norm_2(x)

and after the last layer ``logits = lm_head(norm_f(x))`` of ``vocab x
num_pred_heads`` columns, viewed ``[B, T, num_pred_heads, vocab]``: head ``i``
at position ``t`` predicts byte ``t + 1 + i``.

*EVA attention*, per head of size ``d`` with ``s = d^-1/2``, window ``W``
(``window_size``) and chunk ``C`` (``chunk_size``): ``q, k, v = W_q h, W_k h,
W_v h``, rope (theta ``rope_theta``) on all of ``q`` and ``k`` at positions
0..T-1.  Chunk ``c`` holds positions ``C c .. C c + C - 1``; with the head's
``adaptive_phi``, ``adaptive_mu_k`` ``[d]``

    a_j = softmax over j in chunk c of (s k_j . phi)
    ksum_c = sum_j a_j k_j + mu        vsum_c = sum_j a_j v_j

Query ``t`` of window ``w = t // W`` attends to the positions ``j <= t`` of
window ``w`` and to the summaries of **every chunk of every window before**
``w`` (``(W / C) w`` of them; its own window gives none), under one softmax
over both kinds of key at scale ``s``.

Everything is float32 at the highest matmul precision; attention is a Python
loop over windows with dense scores under an explicit mask.  No kernel, no
cache, nothing of ``dpwa_tpu``.  Departures from the published description:
the rotary pairs are interleaved (dims 2i, 2i+1) where the published code
pairs dim i with i + d/2, as in ``references/decoder.py`` (a fixed permutation
of each head's dims under seeded weights); the published files are not in the
sandbox, so the summaries' weights, what the remote set holds and the layout
of the eight heads are as recalled, each listed under ``assumed`` in the
configuration file."""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _dot(x, w):
    return jnp.dot(x, w.astype(jnp.float32), precision=HIGHEST)


def _proj(x, p, scale):
    y = _dot(x, p["kernel"])
    if "lora_a" in p:
        y = y + _dot(_dot(x, p["lora_a"]), p["lora_b"]) * scale
    return y


def _rms_norm(x, p, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"].astype(jnp.float32))


def _rope(x, theta):
    t, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs  # [T, D/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1
    ).reshape(x.shape)


def summaries(k, v, phi, mu, chunk):
    """``ksum``, ``vsum`` ``[B, T / chunk, h, d]`` of ``k``, ``v`` ``[B, T, h,
    d]`` with ``phi``, ``mu`` ``[h, d]``."""
    b, t, h, d = k.shape
    kc = k.reshape(b, t // chunk, chunk, h, d)
    vc = v.reshape(b, t // chunk, chunk, h, d)
    a = jax.nn.softmax(
        jnp.einsum("bcjhd,hd->bcjh", kc, phi, precision=HIGHEST) / d ** 0.5, 2
    )
    return (
        jnp.einsum("bcjh,bcjhd->bchd", a, kc, precision=HIGHEST) + mu,
        jnp.einsum("bcjh,bcjhd->bchd", a, vc, precision=HIGHEST),
    )


def eva(q, k, v, ksum, vsum, window, chunk, round_scores=lambda s: s):
    """The core: ``o [B, T, h, d]``.  ``round_scores`` is applied to the
    scaled scores of both kinds (the identity; a test rounds them to a
    narrower type to show its tolerance can tell)."""
    t, d = q.shape[1], q.shape[-1]
    per_window = window // chunk
    out = []
    for w in range(-(-t // window)):
        here = slice(w * window, min((w + 1) * window, t))
        seen = slice(0, w * per_window)
        local = jnp.einsum(
            "bthd,bshd->bhts", q[:, here], k[:, here], precision=HIGHEST
        ) / d ** 0.5
        n = local.shape[-1]
        local = jnp.where(jnp.tril(jnp.ones((n, n), bool)), local, -jnp.inf)
        remote = jnp.einsum(
            "bthd,bchd->bhtc", q[:, here], ksum[:, seen], precision=HIGHEST
        ) / d ** 0.5
        p = jax.nn.softmax(
            round_scores(jnp.concatenate([local, remote], -1)), -1
        )
        out.append(
            jnp.einsum("bhts,bshd->bthd", p[..., :n], v[:, here],
                       precision=HIGHEST)
            + jnp.einsum("bhtc,bchd->bthd", p[..., n:], vsum[:, seen],
                         precision=HIGHEST)
        )
    return jnp.concatenate(out, 1)


def attention(config, a, y, scale):
    h = config["num_attention_heads"]
    b, t, hidden = y.shape
    d = hidden // h
    f32 = lambda v: v.astype(jnp.float32)
    q = _rope(_proj(y, a["wq"], scale).reshape(b, t, h, d), config["rope_theta"])
    k = _rope(_proj(y, a["wk"], scale).reshape(b, t, h, d), config["rope_theta"])
    v = _proj(y, a["wv"], scale).reshape(b, t, h, d)
    ksum, vsum = summaries(
        k, v, f32(a["adaptive_phi"]), f32(a["adaptive_mu_k"]),
        config["chunk_size"],
    )
    o = eva(q, k, v, ksum, vsum, config["window_size"], config["chunk_size"])
    return _proj(o.reshape(b, t, h * d), a["wo"], scale)


def forward(config, params, tokens):
    """Logits ``[B, T, num_pred_heads, vocab]``, float32."""
    p = params["params"]
    lora = config["assumed"]["lora"]
    scale = lora["alpha"] / lora["rank"]
    eps = config["rms_norm_eps"]
    x = p["embed"]["embedding"].astype(jnp.float32)[tokens]
    for i in range(config["num_hidden_layers"]):
        layer = p[f"layer_{i}"]
        y = _rms_norm(x, layer["attn_norm"], eps)
        x = x + attention(config, layer["attn"], y, scale)
        m = layer["mlp"]
        y = _rms_norm(x, layer["mlp_norm"], eps)
        gate = jax.nn.silu(_proj(y, m["w_gate"], scale))
        x = x + _proj(gate * _proj(y, m["w_up"], scale), m["w_down"], scale)
    x = _rms_norm(x, p["final_norm"], eps)
    logits = _dot(x, p["lm_head"]["kernel"])
    return logits.reshape(
        *tokens.shape, config["num_pred_heads"], config["vocab_size"]
    )


def multi_head_loss(logits, targets):
    """The mean over the heads of each head's mean cross-entropy: head ``i``
    at position ``t`` is held to ``targets[t + i]`` (``targets`` being the
    inputs shifted left by one), over the ``T - i`` positions where that lies
    inside the sequence."""
    t, heads = logits.shape[1], logits.shape[2]
    log_p = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    total = 0.0
    for i in range(heads):
        at = jnp.take_along_axis(
            log_p[:, :t - i, i], targets[:, i:, None], axis=-1
        )
        total = total - at.mean()
    return total / heads
