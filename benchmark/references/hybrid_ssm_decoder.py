"""Pre-norm decoder whose layers mix tokens by a Mamba-1 state-space mixer,
except one layer in every ``attn_layer_period`` that keeps attention (the
published Jamba block, ``model_type: jamba``, with ``num_experts: 1``, so that
every layer's feed-forward is the dense one), with a rank-r LoRA delta
``(alpha / r) x A B`` on every projection that multiplies an activation.

With ``h`` the residual stream ``[B, T, hidden]``, every layer ``i`` is

    h = h + mixer_i(RMSNorm_in(h));   h = h + MLP(RMSNorm_ff(h))

``MLP(x) = W_down(silu(W_gate x) * W_up x)``, no bias.  ``mixer_i`` is
attention where ``i % attn_layer_period == attn_layer_offset``, a Mamba mixer
elsewhere.  After the last layer a final RMSNorm and ``logits = x E^T`` with
``E`` the embedding (``tie_word_embeddings: true``), in float32.

*Attention*: ``q = W_q x`` as ``num_attention_heads`` heads, ``k, v = W_k x,
W_v x`` as ``num_key_value_heads`` heads shared by the q heads of their
group; **no rope, no bias, no window**; causal softmax at scale
``1 / sqrt(head size)``; ``W_o``.

*Mamba mixer* (E = ``mamba_expand`` x hidden, N = ``mamba_d_state``, R =
``mamba_dt_rank``, K = ``mamba_d_conv``), ``u`` the normed stream:

    [x, z] = W_in u                          # hidden -> 2 x E, no bias
    x = silu(conv(x))                        # depthwise, causal, width K, bias:
                                             # conv(x)_t = b + sum_{j<K} w_j * x_{t-(K-1)+j}, x_{<0} = 0
    [dt, Bm, Cm] = W_x x                     # E -> R + N + N, no bias
    dt, Bm, Cm = RMSNorm_dt(dt), RMSNorm_b(Bm), RMSNorm_c(Cm)   # Jamba's three inner norms
    delta = softplus(W_dt dt + b_dt)         # R -> E, with bias
    A = -exp(A_log)                          # [E, N]
    s_t = exp(delta_t (x) A) * s_{t-1} + (delta_t * x_t) (x) Bm_t      # s_0 = 0, [E, N]
    y_t = s_t . Cm_t + D * x_t
    out = W_out (y * silu(z))                # E -> hidden, no bias

Everything is float32 at the highest matmul precision; the scan is a
``lax.scan`` a token, attention is dense under an explicit mask.  No kernel,
no chunk, nothing of ``dpwa_tpu``.  Departures from the published code: none
known in the mathematics; the order of the layer kinds and the three inner
norms are the ``jamba`` model type's, which no key of the configuration
states (the file lists them under ``assumed``)."""

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


def attention(config, a, y, scale):
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    b, t, hidden = y.shape
    d = hidden // h
    q = _proj(y, a["wq"], scale).reshape(b, t, h, d)
    k = _proj(y, a["wk"], scale).reshape(b, t, kv, d)
    v = _proj(y, a["wv"], scale).reshape(b, t, kv, d)
    k, v = (jnp.repeat(z, h // kv, axis=2) for z in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST) / d ** 0.5
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    o = jnp.einsum("bhts,bshd->bthd", s, v, precision=HIGHEST)
    return _proj(o.reshape(b, t, h * d), a["wo"], scale)


def scan(x, delta, A, Bm, Cm, D, round_state=lambda s: s):
    """The recurrence a token at a time: ``x``, ``delta [B, T, E]``, ``A [E,
    N]``, ``Bm``, ``Cm [B, T, N]``, ``D [E]`` -> ``y [B, T, E]``.
    ``round_state`` is applied to the state after every step (the identity;
    a test rounds it to a narrower type to show its tolerance can tell)."""

    def step(s, inputs):
        x_t, d_t, b_t, c_t = inputs
        s = round_state(
            jnp.exp(d_t[:, :, None] * A) * s
            + (d_t * x_t)[:, :, None] * b_t[:, None, :]
        )
        return s, jnp.einsum("ben,bn->be", s, c_t, precision=HIGHEST) + D * x_t

    over_time = lambda v: jnp.swapaxes(v, 0, 1)
    s0 = jnp.zeros((x.shape[0],) + A.shape, jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(map(over_time, (x, delta, Bm, Cm))))
    return over_time(y)


def conv(x, w, b):
    """``conv(x)_t = b + sum_j w[j] * x_{t-(K-1)+j}``, zeros before the
    sequence; ``x [B, T, E]``, ``w [K, E]``."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return b + sum(w[j] * padded[:, j:j + t] for j in range(taps))


def mamba(config, m, u, scale):
    eps = config["rms_norm_eps"]
    n, r = config["mamba_d_state"], config["mamba_dt_rank"]
    f32 = lambda v: v.astype(jnp.float32)
    x, z = jnp.split(_proj(u, m["in_proj"], scale), 2, -1)
    x = jax.nn.silu(conv(x, f32(m["conv_kernel"]), f32(m["conv_bias"])))
    dt, Bm, Cm = jnp.split(_proj(x, m["x_proj"], scale), [r, r + n], -1)
    dt = _rms_norm(dt, m["dt_norm"], eps)
    Bm = _rms_norm(Bm, m["b_norm"], eps)
    Cm = _rms_norm(Cm, m["c_norm"], eps)
    delta = jax.nn.softplus(_proj(dt, m["dt_proj"], scale) + f32(m["dt_bias"]))
    y = scan(x, delta, -jnp.exp(f32(m["A_log"])), Bm, Cm, f32(m["D"]))
    return _proj(y * jax.nn.silu(z), m["out_proj"], scale)


def is_attention_layer(config, i: int) -> bool:
    return i % config["attn_layer_period"] == config["attn_layer_offset"]


def forward(config, params, tokens):
    p = params["params"]
    lora = config["assumed"]["lora"]
    scale = lora["alpha"] / lora["rank"]
    eps = config["rms_norm_eps"]
    embedding = p["embed"]["embedding"].astype(jnp.float32)
    x = embedding[tokens]
    for i in range(config["num_hidden_layers"]):
        layer = p[f"layer_{i}"]
        if is_attention_layer(config, i):
            y = _rms_norm(x, layer["attn_norm"], eps)
            x = x + attention(config, layer["attn"], y, scale)
        else:
            y = _rms_norm(x, layer["mamba_norm"], eps)
            x = x + mamba(config, layer["mamba"], y, scale)
        m = layer["mlp"]
        y = _rms_norm(x, layer["mlp_norm"], eps)
        gate = jax.nn.silu(_proj(y, m["w_gate"], scale))
        x = x + _proj(gate * _proj(y, m["w_up"], scale), m["w_down"], scale)
    x = _rms_norm(x, p["final_norm"], eps)
    return jnp.dot(x, embedding.T, precision=HIGHEST)
