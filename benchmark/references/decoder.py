"""Pre-norm decoder with RMSNorm, rotary embeddings, grouped-query causal
attention and a SwiGLU feed-forward (the Mistral / Llama block equations),
with a rank-r LoRA delta ``(alpha / r) x A B`` on all seven projections.
Departure from the published code, as in ``models/llama.py``: the rotary
pairs are interleaved (dims 2i, 2i+1) where the Hugging Face code pairs dim i
with i + d/2; with seeded random weights the two differ by a fixed
permutation of each head's dims."""

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
    return x * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)


def _rope(x, theta):
    t, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs  # [T, D/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1
    ).reshape(x.shape)


def forward(config, params, tokens):
    p = params["params"]
    lora = config["assumed"]["lora"]
    scale = lora["alpha"] / lora["rank"]
    h, kv, d = (config["num_attention_heads"], config["num_key_value_heads"],
                config["head_dim"])
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    b, t = tokens.shape
    x = p["embed"]["embedding"].astype(jnp.float32)[tokens]
    mask = jnp.tril(jnp.ones((t, t), bool))
    for i in range(config["num_hidden_layers"]):
        layer = p[f"layer_{i}"]
        a = layer["attn"]
        y = _rms_norm(x, layer["attn_norm"], eps)
        q = _rope(_proj(y, a["wq"], scale).reshape(b, t, h, d), theta)
        k = _rope(_proj(y, a["wk"], scale).reshape(b, t, kv, d), theta)
        v = _proj(y, a["wv"], scale).reshape(b, t, kv, d)
        k, v = (jnp.repeat(z, h // kv, axis=2) for z in (k, v))
        s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST) / d ** 0.5
        s = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        o = jnp.einsum("bhts,bshd->bthd", s, v, precision=HIGHEST)
        x = x + _proj(o.reshape(b, t, h * d), a["wo"], scale)
        m = layer["mlp"]
        y = _rms_norm(x, layer["mlp_norm"], eps)
        gate = jax.nn.silu(_proj(y, m["w_gate"], scale))
        x = x + _proj(gate * _proj(y, m["w_up"], scale), m["w_down"], scale)
    x = _rms_norm(x, p["final_norm"], eps)
    return _dot(x, p["lm_head"]["kernel"])
