"""Latent attention, the leading dense layer, the shared expert and a share
of the routed experts (``models/llama.py``, ``ops/moe.py``,
``ops/ulysses.single_device_attention``) against the plain reference
(``benchmark/references/latent_moe_decoder.py``) at toy sizes on the CPU.

Tolerances.  Float32 against float32 differs by the order of summation alone:
1e-4 of rms holds it (seen: some 1e-7), and fails bfloat16 where float32 is
stated (some 1e-2) and any term left out (the shared expert, the x 2.5, the
rope key: tenths).  Where two programs run the same float32 arithmetic in
another order (vmap against a loop, remat on and off) the bound is 1e-5."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.builders import latent_moe_decoder as builder  # noqa: E402
from benchmark.reference import MODEL_TOLERANCE  # noqa: E402
from benchmark.references import latent_moe_decoder as plain  # noqa: E402
from dpwa_tpu.config import make_local_config  # noqa: E402
from dpwa_tpu.models import llama  # noqa: E402
from dpwa_tpu.models.llama import (  # noqa: E402
    LatentAttention, Llama, MoE, YarnScaling, lora_filter, moe_loss,
    rope_frequencies, routing_of,
)
from dpwa_tpu.ops import moe  # noqa: E402
from dpwa_tpu.ops.ulysses import single_device_attention  # noqa: E402
from tests.yardstick.yardstick_paths import load  # noqa: E402

PUBLISHED = load("benchmark/configs/axk1-lora.json")
# hidden 64, 4 heads of 16 + 8 / 16, one dense layer then two expert layers,
# experts 2..5 of 8 held, top 2, a shared expert, yarn over 16 positions.
CONFIG, CELL = builder.rehearse(PUBLISHED, dict(seq_len=64, per_peer_batch=2))
T = CELL["seq_len"]
SCALE = CONFIG["assumed"]["lora"]["alpha"] / CONFIG["assumed"]["lora"]["rank"]
YARN = YarnScaling(
    factor=32, original_max_position_embeddings=4096, beta_fast=32,
    beta_slow=1, mscale=1, mscale_all_dim=1,
)


def model_of(config=CONFIG, **changes) -> Llama:
    model = builder.model_of(config, T)
    return Llama(llama.dataclasses.replace(model.cfg, **changes))


def perturbed(params, key=2):
    """Every leaf moved, so that LoRA B and the norms' scales matter."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(key), len(leaves))
    return treedef.unflatten([
        v + 0.05 * jax.random.normal(k, v.shape, v.dtype)
        for v, k in zip(leaves, keys)
    ])


@pytest.fixture(scope="module")
def seeded():
    tokens = jax.random.randint(jax.random.key(0), (2, T), 0, CONFIG["vocab_size"])
    params = perturbed(model_of().init(jax.random.key(1), tokens))
    return params, tokens, jnp.roll(tokens, -1, axis=1)


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2))


def adapters(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {
        jax.tree_util.keystr(path): leaf for path, leaf in flat
        if lora_filter(jax.tree_util.keystr(path))
    }


# ---- yarn and the softmax scale, by hand


def test_yarn_frequencies_by_hand():
    """d 64, theta 1e4, factor 32 over 4096: the ramp runs from pair 10 to
    pair 23 (64 ln(4096 / (2 pi b)) / (2 ln 1e4) = 10.47 for b = 32 and 22.51
    for b = 1, floored and ceiled)."""
    got = np.asarray(rope_frequencies(64, 10000.0, YARN), np.float64)
    plain_f = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(got[:11], plain_f[:11], rtol=1e-6)  # ramp 0
    np.testing.assert_allclose(got[23:], plain_f[23:] / 32, rtol=1e-6)  # ramp 1
    # Pair 16: ramp 6 / 13, so f (1 - 6/13) + f / 32 x 6/13.
    assert got[16] == pytest.approx(
        plain_f[16] * (7 / 13 + 6 / 13 / 32), rel=1e-6
    )
    assert got[16] == pytest.approx(1e-2 * 0.552885, rel=1e-5)
    np.testing.assert_allclose(
        got, np.asarray(plain.yarn_frequencies(64, 10000.0, PUBLISHED["rope_scaling"])),
        rtol=1e-6,
    )
    # Without a scaling group the frequencies are today's.
    np.testing.assert_allclose(
        np.asarray(rope_frequencies(64, 10000.0)), plain_f, rtol=1e-6
    )


def test_softmax_scale_and_embedding_magnitude_by_hand():
    m = 0.1 * np.log(32) + 1
    assert m == pytest.approx(1.3466, abs=1e-4)
    assert YARN.softmax_scale == pytest.approx(m * m)
    assert YARN.softmax_scale / np.sqrt(192) == pytest.approx(0.1309, abs=1e-4)
    # mscale / mscale_all_dim = 1: the rope's cos and sin keep their size.
    assert YARN.embedding_scale == pytest.approx(1.0)
    half = YarnScaling(32, 4096, mscale=1, mscale_all_dim=0)
    assert half.embedding_scale == pytest.approx(m)
    assert half.softmax_scale == pytest.approx(1.0)


# ---- the attention dispatcher with two head sizes


def _einsum_attention(q, k, v, scale):
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision="highest") * scale
    mask = jnp.tril(jnp.ones((q.shape[1],) * 2, bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhts,bshd->bthd", p, v, precision="highest")


def test_attention_with_a_qk_size_unlike_the_v_size_and_a_scale():
    keys = jax.random.split(jax.random.key(3), 3)
    q, k = (jax.random.normal(key, (2, 32, 4, 24)) for key in keys[:2])
    v = jax.random.normal(keys[2], (2, 32, 4, 16))
    got = single_device_attention(q, k, v, causal=True, sm_scale=0.37)
    assert got.shape == (2, 32, 4, 16)
    assert relative(got, _einsum_attention(q, k, v, 0.37)) < 1e-5
    # The default scale is 1 / sqrt(q's head size), not v's.
    default = single_device_attention(q, k, v, causal=True)
    assert relative(default, _einsum_attention(q, k, v, 24 ** -0.5)) < 1e-5
    assert relative(default, _einsum_attention(q, k, v, 16 ** -0.5)) > 1e-2
    # What the kernel path does with this shape is exact: zero columns add
    # nothing to a score or to a value.
    pad = lambda x: jnp.pad(x, [(0, 0)] * 3 + [(0, 128 - x.shape[-1])])
    padded = single_device_attention(
        pad(q), pad(k), pad(v), causal=True, impl="dense", sm_scale=0.37
    )[..., :16]
    assert relative(padded, got) < 1e-6


def test_the_kernel_path_takes_two_head_sizes_where_t_tiles(monkeypatch):
    """On a TPU at T a multiple of 128 the 192 / 128 shape goes to the
    kernels (padded to 256), and since PR 44 so does an equal head size that
    is no multiple of 128: 64 / 64 as it is, 96 / 96 padded to 128 with the
    scale still that of 96; no head size falls to the einsum's ``T x T``
    scores silently."""
    from jax.experimental.pallas.ops.tpu import flash_attention as library

    seen = []

    def fake(q, k, v, **kwargs):
        seen.append((q.shape, k.shape, v.shape, kwargs["sm_scale"]))
        return jnp.zeros_like(q)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(library, "flash_attention", fake)
    q = jnp.zeros((1, 128, 2, 192))
    out = single_device_attention(
        q, q, jnp.zeros((1, 128, 2, 128)), causal=True, sm_scale=0.1309
    )
    assert out.shape == (1, 128, 2, 128)
    assert seen == [((1, 2, 128, 256),) * 3 + (0.1309,)]
    out = single_device_attention(
        q[..., :64], q[..., :64], q[..., :64], causal=True
    )
    assert out.shape == (1, 128, 2, 64)
    assert seen[1:] == [((1, 2, 128, 64),) * 3 + (0.125,)]
    out = single_device_attention(
        q[..., :96], q[..., :96], q[..., :96], causal=True
    )
    assert out.shape == (1, 128, 2, 96)
    assert seen[2:] == [((1, 2, 128, 128),) * 3 + (float(1.0 / 96 ** 0.5),)]
    # Where T does not tile, the einsum, as before.
    single_device_attention(q[:, :100], q[:, :100], q[:, :100], causal=True)
    assert len(seen) == 3


# ---- latent attention and the whole model against the reference


def test_latent_attention_equals_the_reference(seeded):
    cfg = model_of().cfg
    y = jax.random.normal(jax.random.key(4), (2, T, cfg.d_model))
    block = LatentAttention(cfg)
    params = perturbed(block.init(jax.random.key(5), y, jnp.arange(T)), 6)
    got = block.apply(params, y, jnp.arange(T))
    want = plain.latent_attention(CONFIG, params["params"], y, SCALE)
    assert relative(got, want) < 1e-4
    # The one rope key is shared by the heads and is turned: without the
    # rope on it, or with plain frequencies, the output is elsewhere.
    no_scaling = LatentAttention(
        llama.dataclasses.replace(cfg, rope_scaling=None)
    ).apply(params, y, jnp.arange(T))
    assert relative(no_scaling, want) > 1e-2


@pytest.mark.parametrize("activation_dtype", [jnp.float32, None])
def test_the_model_equals_the_reference_logits_loss_and_adapter_gradients(
    seeded, activation_dtype
):
    """Through ``ops/wide.py`` (the configuration's way: its matmuls and
    their hand-written gradients) and through the plain ``@``."""
    params, tokens, targets = seeded
    model = model_of(activation_dtype=activation_dtype)
    assert relative(
        model.apply(params, tokens), plain.forward(CONFIG, params, tokens)
    ) < 1e-4
    loss, grads = jax.value_and_grad(
        lambda p: moe_loss(model, p, tokens, targets)
    )(params)
    want, want_grads = jax.value_and_grad(
        lambda p: plain.loss(CONFIG, p, tokens, targets)
    )(params)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    got, want_grads = adapters(grads), adapters(want_grads)
    # a and b: 5 attention projections x 3 layers, the dense MLP's 3, and 3
    # of the shared expert (+ 3 stacked over the held experts) x 2 layers.
    assert len(got) == 2 * (5 * 3 + 3 + 6 * 2)
    for name, grad in got.items():
        assert relative(grad, want_grads[name]) < 1e-4, name
        assert float(jnp.abs(grad).max()) > 0, name


@pytest.mark.parametrize("left_out", [
    "shared_expert", "routed_scaling_factor", "norm_topk_prob",
    "softmax_gate", "dense_layer_first", "all_experts_held_as_eight",
])
def test_a_term_left_out_is_outside_the_model_tolerance(seeded, left_out):
    params, tokens, _ = seeded
    want = plain.forward(CONFIG, params, tokens)
    changes = dict(
        shared_expert=dict(n_shared_experts=0),
        routed_scaling_factor=dict(routed_scaling_factor=1.0),
        norm_topk_prob=dict(norm_topk_prob=False),
        softmax_gate=dict(router_scoring="softmax"),
        dense_layer_first=dict(expert_offset=0),  # the wrong four experts
        all_experts_held_as_eight=dict(expert_offset=4),
    )[left_out]
    got = model_of(**changes).apply(params, tokens)
    assert relative(got, want) > MODEL_TOLERANCE


def test_bfloat16_with_verified_routing_is_inside_the_tolerance(seeded):
    params, tokens, _ = seeded
    model = model_of(dtype=jnp.bfloat16)
    logits, sown = model.apply(params, tokens, mutable=["intermediates"])
    routing = routing_of(sown)
    assert routing["experts"].shape == (2, 2 * T, 2)  # no dense layer's
    assert routing["router_input"].shape == (2, 2 * T, 64)
    want, details = plain.forward_with_routing(CONFIG, params, tokens, routing)
    assert 1e-5 < relative(logits, want) < MODEL_TOLERANCE
    # The program's logits are the float32 product of its own input, and its
    # sets the top-k of them: float32 against float32 on the CPU.
    assert float(details["logit_error"].max()) < 1e-5 < plain.LOGIT_EPS
    assert float(details["set_margin"].max()) <= 0


@pytest.mark.parametrize("fault", ["bfloat16_router", "ninth_for_eighth"])
def test_a_router_below_float32_or_a_set_that_is_no_top_k_is_refused(
    seeded, fault
):
    """What ``LOGIT_EPS`` and the exact top-k hold: logits as one bfloat16
    pass gives them (the precision below the configuration's) lie 5e-3 off,
    far outside; a token that takes its next-best expert is refused too."""
    params, tokens, _ = seeded
    model = model_of(dtype=jnp.bfloat16)
    routing = dict(routing_of(
        model.apply(params, tokens, mutable=["intermediates"])[1]
    ))
    if fault == "bfloat16_router":
        routers = jnp.stack([
            params["params"][f"layer_{i}"]["mlp"]["router"] for i in (1, 2)
        ])
        routing["logits"] = jnp.einsum(
            "lnd,lde->lne", routing["router_input"],
            routers.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
        )
        off = jnp.abs(routing["logits"] - routing_of(model.apply(
            params, tokens, mutable=["intermediates"]
        )[1])["logits"]).max()
        assert float(off) > 10 * plain.LOGIT_EPS
    else:
        order = jnp.argsort(-routing["logits"][0, 0])
        routing["experts"] = routing["experts"].at[0, 0].set(
            jnp.stack([order[0], order[2]])  # top 2 of 8: third for second
        )
    got = plain.forward(CONFIG, params, tokens, routing)
    assert bool(jnp.isnan(got).any())


def test_remat_on_and_off_give_the_same_loss_and_gradients(seeded):
    params, tokens, targets = seeded
    results = [
        jax.value_and_grad(
            lambda p: moe_loss(model_of(remat=remat), p, tokens, targets)
        )(params)
        for remat in (True, False)
    ]
    (loss_on, grads_on), (loss_off, grads_off) = results
    assert float(loss_on) == pytest.approx(float(loss_off), rel=1e-6)
    for name, grad in adapters(grads_on).items():
        assert relative(grad, adapters(grads_off)[name]) < 1e-5, name
    # And the checkpoint is there: the recomputation is named in the program.
    text = jax.jit(jax.grad(
        lambda p: moe_loss(model_of(remat=True), p, tokens, targets)
    )).lower(params).as_text(debug_info=True)
    assert "rematted_computation" in text


def test_base_leaves_are_born_in_param_dtype(seeded):
    _, tokens, _ = seeded
    shapes = jax.eval_shape(
        model_of(param_dtype=jnp.bfloat16).init, jax.random.key(0), tokens
    )
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        keep = lora_filter(name) or "router" in name
        assert leaf.dtype == (jnp.float32 if keep else jnp.bfloat16), name
    # No float32 copy of a base leaf is made on the way: the init program
    # holds no float32 array of the embedding's or a dense kernel's shape
    # (the stacked experts' [4, 64, 32] is also the shape of two adapters'
    # A sides side by side, so it cannot tell).
    jaxpr = str(jax.make_jaxpr(
        model_of(
            param_dtype=jnp.bfloat16, dtype=jnp.bfloat16, activation_dtype=None
        ).init
    )(jax.random.key(0), tokens))
    for shape in ("[512,64]", "[64,128]"):
        assert "bf16" + shape in jaxpr and "f32" + shape not in jaxpr, shape


# ---- the gate


def test_the_gate_is_sigmoid_renormalised_times_two_and_a_half():
    x = jax.random.normal(jax.random.key(7), (16, 64))
    router = jax.random.normal(jax.random.key(8), (64, 12)) / 8
    weights, experts, logits = moe.route(x, router, 4, "sigmoid", True, 2.5)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.5, rtol=1e-6)
    scores = 1 / (1 + np.exp(-np.asarray(logits, np.float64)))
    top = np.argsort(-scores, -1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(experts), -1), np.sort(top, -1))
    by_hand = np.take_along_axis(scores, np.asarray(experts), -1)
    np.testing.assert_allclose(
        np.asarray(weights), 2.5 * by_hand / by_hand.sum(-1, keepdims=True),
        rtol=1e-5,
    )
    # The reference's gate gives the same weights, scattered over experts.
    config = dict(CONFIG, num_experts_per_tok=4, routed_scaling_factor=2.5)
    combine, counts, _ = plain.gate_weights(config, logits)
    np.testing.assert_allclose(
        np.asarray(combine),
        np.asarray(plain_combine(weights, experts, 12)), rtol=1e-5, atol=1e-7,
    )
    assert int(counts.sum()) == 16 * 4
    # Today's gate is untouched: softmax scores, not renormalised, x 1.
    w, _, l = moe.route(x, router, 4)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(jax.lax.top_k(jax.nn.softmax(l, -1), 4)[0])
    )


def plain_combine(weights, experts, n_experts):
    from benchmark.references.moe_decoder import combine_of

    return combine_of(weights, experts, n_experts)


# ---- the share


def expert_weights(key, n_experts, d=64, f=32, rank=4):
    """Stacked (kernel, lora_a, lora_b) triples of gate, up and down."""
    keys = iter(jax.random.split(key, 9))
    triple = lambda a, b: (
        jax.random.normal(next(keys), (n_experts, a, b)) / np.sqrt(a),
        jax.random.normal(next(keys), (n_experts, a, rank)) * 0.1,
        jax.random.normal(next(keys), (n_experts, rank, b)) * 0.1,
    )
    return triple(d, f), triple(d, f), triple(f, d)


def share_of(layer, offset, held):
    return tuple(
        tuple(w[offset:offset + held] for w in triple) for triple in layer
    )


def dense_layer(x, weights, experts, layer, lora_scale, keep=lambda e: True):
    """Every (token, choice) computed one at a time in numpy float64."""
    x = np.asarray(x, np.float64)
    proj = lambda v, triple, e: v @ np.asarray(triple[0][e], np.float64) + (
        v @ np.asarray(triple[1][e], np.float64)
    ) @ np.asarray(triple[2][e], np.float64) * lora_scale
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        for j in range(experts.shape[1]):
            e = int(experts[n, j])
            if not keep(e):
                continue
            gate = proj(x[n], layer[0], e)
            hidden = gate / (1 + np.exp(-gate)) * proj(x[n], layer[1], e)
            out[n] += float(weights[n, j]) * proj(hidden, layer[2], e)
    return out


def test_a_token_all_on_the_held_experts_and_one_with_none_are_exact():
    layer = expert_weights(jax.random.key(9), 12)
    x = jax.random.normal(jax.random.key(10), (4, 64))
    # Held: experts 3..5.  Token 0 chooses them all, token 1 none, tokens 2
    # and 3 one and two of them.
    experts = jnp.array([[3, 5, 4], [0, 7, 11], [2, 4, 9], [5, 6, 3]], jnp.int32)
    weights = jax.random.uniform(jax.random.key(11), (4, 3)) + 0.5
    held = lambda e: 3 <= e < 6
    got = moe.moe_ffn(
        x, (weights, experts), *share_of(layer, 3, 3), 2.0, jnp.float32, 3
    )
    want = dense_layer(x, weights, experts, layer, 2.0, held)
    assert relative(got, want) < 1e-5
    assert float(jnp.abs(got[1]).max()) == 0.0  # nothing of it is held here
    # Token 0's part is the whole layer's: all its choices are held.
    whole = dense_layer(x, weights, experts, layer, 2.0)
    np.testing.assert_allclose(np.asarray(got[0]), whole[0], rtol=1e-4, atol=1e-6)
    # Every held assignment is in a group, whatever the imbalance; the rest
    # are sorted behind them and counted nowhere.
    order, _, sizes = moe.dispatch_plan(experts, 3, 3)
    assert sizes.tolist() == [2, 2, 2]
    flat = np.asarray(experts).reshape(-1)[np.asarray(order)]
    assert flat[:6].tolist() == [3, 3, 4, 4, 5, 5]
    assert not any(held(e) for e in flat[6:])


def test_the_shares_add_up_to_the_whole_layer(seeded):
    """12 experts as 4 shares of 3: the routed parts of all shares plus the
    shared expert once are the uncut layer, in the program and against the
    reference given all 12."""
    config = dict(
        CONFIG, n_routed_experts=12, num_experts_per_tok=4,
        published=dict(CONFIG["published"], n_routed_experts=12),
        assumed=dict(CONFIG["assumed"], expert_offset=0),
    )
    whole_cfg = model_of(config).cfg
    y = jax.random.normal(jax.random.key(12), (1, 32, 64))
    params = perturbed(MoE(whole_cfg).init(jax.random.key(13), y), 14)
    whole = MoE(whole_cfg).apply(params, y)
    m = params["params"]
    want, _ = plain.expert_layer(config, m, y[0], SCALE, (0, 12))
    assert relative(whole[0], want) < 1e-4
    shared = plain.swiglu(y[0], m["shared"], SCALE)
    routed_parts = []
    for offset in (0, 3, 6, 9):
        share_cfg = llama.dataclasses.replace(
            whole_cfg, experts_held=3, expert_offset=offset
        )
        share = share_params(params, offset)
        out = MoE(share_cfg).apply(share, y)[0]
        ref_share, _ = plain.expert_layer(
            config, share["params"], y[0], SCALE, (offset, 3)
        )
        assert relative(out, ref_share) < 1e-4
        routed_parts.append(out - shared)
    assert relative(sum(routed_parts) + shared, whole[0]) < 1e-5
    # A share is not the whole: one alone is far off.
    assert relative(routed_parts[0] + shared, whole[0]) > MODEL_TOLERANCE
    # The counters of a share: its experts' assignments and their part.
    _, sown = MoE(llama.dataclasses.replace(
        whole_cfg, experts_held=3, expert_offset=3
    )).apply(share_params(params, 3), y, mutable=["intermediates"])
    said = {k: v[0] for k, v in sown["intermediates"].items()}
    assert said["counts"].shape == (12,) and int(said["counts"].sum()) == 32 * 4
    assert said["held_counts"].tolist() == said["counts"][3:6].tolist()
    assert float(said["held_share"]) == pytest.approx(
        int(said["held_counts"].sum()) / (32 * 4)
    )
    assert float(said["held_max_over_mean"]) == pytest.approx(
        int(said["held_counts"].max()) * 3 / max(int(said["held_counts"].sum()), 1)
    )


def share_params(params, offset):
    return jax.tree_util.tree_map_with_path(
        lambda path, v: v[offset:offset + 3] if v.ndim == 3 else v, params
    )


@pytest.mark.parametrize("offset", [None, 2])
def test_vmap_over_peers_equals_a_loop_over_peers(offset):
    """The peer axis of the stacked step: folded into more groups where all
    experts are held, a call a peer where a share is (its groups end before
    its rows do); values and gradients, to the rows and to the weights."""
    n_experts, held = 8, (8 if offset is None else 4)
    peers = 3
    layers = [expert_weights(jax.random.key(20 + p), held) for p in range(peers)]
    stacked = jax.tree.map(lambda *v: jnp.stack(v), *layers)
    x = jax.random.normal(jax.random.key(30), (peers, 16, 64))
    experts = jnp.stack([
        jnp.stack([jax.random.permutation(k, n_experts)[:2] for k in
                   jax.random.split(jax.random.key(40 + p), 16)])
        for p in range(peers)
    ]).astype(jnp.int32)
    weights = jax.random.uniform(jax.random.key(50), (peers, 16, 2)) + 0.5

    def one(x, weights, experts, layer):
        out = moe.moe_ffn(
            x, (weights, experts), *layer, 2.0, jnp.float32, offset
        )
        return jnp.sum(out * jnp.cos(out))

    grad = jax.value_and_grad(one, argnums=(0, 3))
    batched = jax.vmap(grad)(x, weights, experts, stacked)
    looped = [grad(x[p], weights[p], experts[p], layers[p]) for p in range(peers)]
    looped = jax.tree.map(lambda *v: jnp.stack(v), *looped)
    for got, want in zip(jax.tree.leaves(batched), jax.tree.leaves(looped)):
        assert relative(got, want) < 1e-5
        assert float(jnp.abs(want).max()) > 0


# ---- the stacked state owns what it is given


def test_init_stacked_state_leaves_no_second_copy():
    from dpwa_tpu.parallel.stacked import StackedTransport, init_stacked_state

    n = 2
    params = {
        "w": jnp.ones((n, 64, 64), jnp.bfloat16), "lora_a": jnp.ones((n, 64, 4)),
    }
    model_state = {"mean": jnp.zeros((n, 4))}
    before = {
        name: leaf.unsafe_buffer_pointer()
        for name, leaf in {**params, **model_state}.items()
    }
    transport = StackedTransport(make_local_config(n, schedule="ring"))
    state = init_stacked_state(params, optax.adam(1e-3), transport, model_state)
    # The caller's arrays are gone and the state's leaves are their buffers.
    assert all(leaf.is_deleted() for leaf in params.values())
    assert model_state["mean"].is_deleted()
    assert state.params["w"].unsafe_buffer_pointer() == before["w"]
    assert state.params["lora_a"].unsafe_buffer_pointer() == before["lora_a"]
    assert state.model_state["mean"].unsafe_buffer_pointer() == before["mean"]
    assert state.params["w"].dtype == jnp.bfloat16
    assert float(state.params["w"].astype(jnp.float32).sum()) == n * 64 * 64


def test_the_kernel_calls_a_peer_equal_the_plain_products(monkeypatch):
    """What the vmap rule does on a TPU, with the kernels interpreted: a call
    a peer on the folded rows, that peer's groups behind a skipped leading
    group (``group_offset``), each call writing into the result of the one
    before (``existing_out``); forward, to the rows and to the weights,
    against the plain masked products a peer.  bfloat16 rows through float32
    accumulation on both sides: 1e-2 of rms is their rounding, and a group
    one row off moves a result by tenths."""
    import functools
    import types

    library = moe._kernels()
    interpreted = types.SimpleNamespace(
        gmm=functools.partial(library.gmm, interpret=True),
        tgmm=functools.partial(library.tgmm, interpret=True),
    )
    peers, rows, groups, k, n = 2, 512, 4, 256, 128
    keys = jax.random.split(jax.random.key(60), 3)
    lhs = jax.random.normal(keys[0], (peers, rows, k), jnp.bfloat16)
    rhs = jax.random.normal(keys[1], (peers, groups, k, n), jnp.bfloat16) / 16
    cot = jax.random.normal(keys[2], (peers, rows, n), jnp.bfloat16)
    # Peer 0's groups cover 100 of its 512 rows, peer 1's none of the first.
    sizes = jnp.array([[40, 0, 57, 3], [0, 300, 1, 99]], jnp.int32)

    def loss(lhs, rhs):
        out = jax.vmap(moe.held_matmul)(lhs, rhs, sizes)
        return jnp.sum((out * cot).astype(jnp.float32)), out

    plain = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(lhs, rhs)
    monkeypatch.setattr(moe, "_kernels", lambda: interpreted)
    monkeypatch.setattr(moe, "_use_kernels", lambda rows: True)
    kernels = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(lhs, rhs)
    for got, want in zip(jax.tree.leaves(kernels), jax.tree.leaves(plain)):
        assert relative(got, want) < 1e-2
    out = kernels[0][1]
    assert float(jnp.abs(out[0, 100:].astype(jnp.float32)).max()) == 0.0
    assert float(jnp.abs(out[1, 400:].astype(jnp.float32)).max()) == 0.0
    assert float(jnp.abs(out[1, :400].astype(jnp.float32)).min()) > 0.0


# ---- float32 between the matmuls (ops/wide.py)


def test_narrow_rounds_once_with_an_instruction_the_compiler_keeps():
    from dpwa_tpu.ops.wide import narrow

    x = jax.random.normal(jax.random.key(3), (64,), jnp.float32) * 3.0
    assert narrow(x, jnp.float32) is x
    got = narrow(x, jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    assert bool(jnp.all(got == x.astype(jnp.bfloat16)))  # to nearest even
    assert "reduce_precision" in str(jax.make_jaxpr(
        lambda v: narrow(v, jnp.bfloat16)
    )(x))


@pytest.mark.parametrize("batched", [False, True])
def test_wide_dot_rounds_its_operands_and_nothing_else(batched):
    """Value and both gradients are float32 products of operands rounded to
    bfloat16, the cotangent among them; unbatched and under vmap alike."""
    from dpwa_tpu.ops.wide import wide_dot

    lead = (2,) if batched else ()
    x = jax.random.normal(jax.random.key(4), lead + (3, 8, 32), jnp.float32)
    w = jax.random.normal(jax.random.key(5), lead + (32, 16), jnp.float32)
    g = jax.random.normal(jax.random.key(6), lead + (3, 8, 16), jnp.float32)
    r = lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)
    one = lambda x, w: wide_dot(x, w, jnp.bfloat16, jnp.float32)
    fn, vjp = one, lambda x, w, g: jax.vjp(one, x, w)[1](g)
    if batched:
        fn, vjp = jax.vmap(fn), jax.vmap(vjp)
    out = fn(x, w)
    assert out.dtype == jnp.float32
    hi = jax.lax.Precision.HIGHEST
    p = "p" if batched else ""
    forward = f"{p}btk,{p}kn->{p}btn"
    want = jnp.einsum(forward, r(x), r(w), precision=hi)
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-5)
    d_x, d_w = vjp(x, w, g)
    assert d_x.dtype == d_w.dtype == jnp.float32
    np.testing.assert_allclose(
        d_x, jnp.einsum(f"{p}btn,{p}kn->{p}btk", r(g), r(w), precision=hi),
        rtol=1e-6, atol=1e-5,
    )
    np.testing.assert_allclose(
        d_w, jnp.einsum(f"{p}btk,{p}btn->{p}kn", r(x), r(g), precision=hi),
        rtol=1e-6, atol=1e-4,
    )
    # Not what one float32 matmul of the unrounded operands gives.
    assert relative(out, jnp.einsum(forward, x, w)) > 1e-3


def test_activation_dtype_is_refused_without_latent_attention():
    with pytest.raises(ValueError, match="latent attention"):
        llama.LlamaConfig(
            vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
            activation_dtype=jnp.float32,
        )


def test_a_share_outside_vmap_says_what_it_costs(monkeypatch):
    """Where the kernels could run, the unbatched form warns that it is the
    plain one; under vmap (test above this section) the kernels run."""
    monkeypatch.setattr(moe, "_use_kernels", lambda rows: True)
    lhs = jnp.ones((8, 4)); rhs = jnp.ones((2, 4, 3))
    with pytest.warns(UserWarning, match="vmap over a peer axis"):
        out = moe.held_matmul(lhs, rhs, jnp.array([3, 2], jnp.int32))
    assert bool(jnp.all(out[:5] == 4.0)) and bool(jnp.all(out[5:] == 0.0))


# ---- a share's row cap: windows of sorted rows and one scalar (PR 34)

# 256 tokens x top 8 = 2,048 assignments a peer; experts 8..15 of 128 held:
# four times the expected 128 rows is a window of 512, four to a peer.
CAPPED = dict(n_experts=128, k=8, offset=8, held=8, tokens=256)
SHARE = (CAPPED["offset"], CAPPED["held"])


def test_the_cap_follows_from_shapes_alone():
    assert moe.row_cap(4096, 8, 192) == 1024  # the cell: 4 x 170.7 -> 1,024
    assert moe.row_cap(2048, 8, 192) == 512  # its model check, 256 tokens
    assert moe.row_cap(2048, 8, 128) == 512
    assert moe.row_cap(65536, 8, 192) is None  # 4 x 2730.7 -> 11,264: 5.8 windows
    assert moe.row_cap(65536, 8, 256) == 8192
    # No cap where it would be no less than all the rows (small shapes, a
    # share that is most of the experts) or would not divide them.
    assert moe.row_cap(256, 4, 8) is None
    assert moe.row_cap(1024, 16, 32) is None
    assert moe.row_cap(512, 1, 64) is None
    assert moe.row_cap(1280, 8, 128) is None  # 320 -> 512, and 2.5 windows
    cases = [(1000, 2048, 8, 128), (512, 2048, 8, 128), (513, 2048, 8, 128),
             (256, 256, 4, 8)]
    assert [
        int(moe.over_cap(jnp.int32(rows), *shape)) for rows, *shape in cases
    ] == [1, 0, 1, 0]


def routing_for(key, tokens, k, n_experts, only=None):
    """``[tokens, k]`` distinct experts a token: of all, or of ``only``."""
    pool = jnp.arange(n_experts) if only is None else jnp.asarray(only)
    return jnp.stack([
        jax.random.permutation(kk, pool)[:k]
        for kk in jax.random.split(key, tokens)
    ]).astype(jnp.int32)


def dense_share(x, weights, experts, layer, lora_scale, offset):
    """The share's part of the layer with no dispatch at all: every held
    expert on every token, weighted by what the gate gave that pair."""
    proj = lambda v, triple, e: v @ triple[0][e] + (
        v @ triple[1][e]
    ) @ triple[2][e] * lora_scale
    out = 0.0
    for e in range(layer[0][0].shape[0]):
        gate = proj(x, layer[0], e)
        y = proj(jax.nn.silu(gate) * proj(x, layer[1], e), layer[2], e)
        out = out + y * (weights * (experts == offset + e)).sum(-1)[:, None]
    return out


# How a peer's tokens choose: of all 128 experts (some 128 of its 2,048
# assignments held: one window); of the 8 held and 8 more (about half held:
# two or three windows); of the 8 held alone (all 2,048: four windows).
ROUTINGS = dict(
    uniform=None, warm=list(range(4, 20)), hot=list(range(8, 16)),
)


def capped_case(routings=("uniform", "uniform")):
    """Weights, tokens, gate weights and a routing a peer."""
    c = CAPPED
    peers = len(routings)
    layers = [expert_weights(jax.random.key(70 + p), c["held"]) for p in range(peers)]
    x = jax.random.normal(jax.random.key(80), (peers, c["tokens"], 64))
    weights = jax.random.uniform(
        jax.random.key(81), (peers, c["tokens"], c["k"])
    ) + 0.5
    experts = jnp.stack([
        routing_for(
            jax.random.key(90 + p), c["tokens"], c["k"], c["n_experts"],
            ROUTINGS[kind],
        ) for p, kind in enumerate(routings)
    ])
    return layers, x, weights, experts


def share_loss(n_routed):
    def loss(x, weights, experts, layer):
        out = moe.moe_ffn(
            x, (weights, experts), *layer, 2.0, jnp.float32,
            CAPPED["offset"], None, n_routed,
        )
        return jnp.sum(out * jnp.cos(out)), out
    return jax.value_and_grad(loss, argnums=(0, 1, 3), has_aux=True)


def reference_loss(x, weights, experts, layer):
    out = dense_share(x, weights, experts, layer, 2.0, CAPPED["offset"])
    return jnp.sum(out * jnp.cos(out)), out


def over_peers(fn, how, layers, *stacked_args):
    if how == "vmap":
        return jax.vmap(fn)(
            *stacked_args, jax.tree.map(lambda *v: jnp.stack(v), *layers)
        )
    results = [
        fn(*(a[p] for a in stacked_args), layers[p]) for p in range(len(layers))
    ]
    return jax.tree.map(lambda *v: jnp.stack(v), *results)


@pytest.mark.parametrize("how", ["vmap", "loop"])
@pytest.mark.parametrize("routings, windows", [
    (("uniform", "uniform"), (1, 1)), (("hot", "hot"), (4, 4)),
    (("uniform", "hot"), (1, 4)), (("warm", "uniform"), (None, 1)),
], ids=["all_inside_the_cap", "all_over_the_cap", "one_over_one_under",
        "one_part_over"])
def test_the_windowed_share_equals_all_rows_at_once_and_the_reference(
    how, routings, windows
):
    """The layer walked a window of 512 sorted rows at a time, the N x k
    program and a reference with no dispatch: the value, and the gradients
    to the tokens, to the gate's weights and to every adapter and kernel,
    under ``vmap`` over two peers and in a loop over them.  A peer whose
    tokens all choose the held experts needs all four windows; under ``vmap``
    the other walks them with it, over empty groups, and both stay exact."""
    layers, x, weights, experts = capped_case(routings)
    for routed, want in zip(experts, windows):
        held_rows = int(moe.routing_stats(routed, 128, SHARE)["held"])
        assert -(-held_rows // 512) == (want or -(-held_rows // 512))
        assert want is not None or 512 < held_rows < 1536
    capped = over_peers(share_loss(128), how, layers, x, weights, experts)
    full = over_peers(share_loss(None), how, layers, x, weights, experts)
    want = over_peers(
        jax.value_and_grad(reference_loss, argnums=(0, 1, 3), has_aux=True),
        how, layers, x, weights, experts,
    )
    for got, same, ref in zip(*map(jax.tree.leaves, (capped, full, want))):
        assert float(jnp.abs(ref).max()) > 0
        assert relative(got, same) < 1e-5
        assert relative(got, ref) < 1e-4


def loops_in(jaxpr):
    """Every ``while`` equation, sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += loops_in(sub)
    return found


def test_under_vmap_one_unbatched_count_bounds_one_walk(monkeypatch):
    """The vmapped layer and its gradient each hold one loop whose bound (and
    counter) has no peer axis, so it runs as many windows as the fullest
    peer needs and no body is masked.  With the count left a peer's own
    (``_most_of_peers`` made the identity) the bound is batched and ``vmap``
    masks every body: that is what the three lines are for."""
    layers, x, weights, experts = capped_case()
    stacked = jax.tree.map(lambda *v: jnp.stack(v), *layers)

    def integer_shapes():
        jaxpr = jax.make_jaxpr(jax.vmap(share_loss(128)))(
            x, weights, experts, stacked
        ).jaxpr
        return [
            [v.aval.shape for v in eqn.invars
             if jnp.issubdtype(v.aval.dtype, jnp.integer) and v.aval.ndim < 2]
            for eqn in loops_in(jaxpr)
        ]

    shapes = integer_shapes()
    assert len(shapes) == 2  # forward, and the backward pass's own
    assert all(shape == () for loop in shapes for shape in loop)
    monkeypatch.setattr(moe, "_most_of_peers", lambda count: count)
    assert all((2,) in loop for loop in integer_shapes())


def test_without_a_cap_there_is_no_walk():
    """Where four times the expected rows is no less than N x k (the toy model's
    256 rows; a share of half the experts) the layer is the N x k program
    alone, as it is without ``n_routed``."""
    layer = expert_weights(jax.random.key(9), 4)
    x = jax.random.normal(jax.random.key(10), (32, 64))
    experts = routing_for(jax.random.key(11), 32, 8, 8)
    weights = jnp.ones((32, 8))
    ffn = lambda n_routed: jax.make_jaxpr(lambda x: moe.moe_ffn(
        x, (weights, experts), *layer, 2.0, jnp.float32, 2, None, n_routed
    ))(x)
    assert loops_in(ffn(8).jaxpr) == []
    assert str(ffn(8)) == str(ffn(None))


def test_a_capped_share_is_one_program_with_or_without_vmap():
    """A peer alone (a loop over peers, one peer a chip) and a peer under
    ``vmap`` hold the same walk, one loop forward and one backward: what a
    share computes does not depend on how it is called."""
    layers, x, weights, experts = capped_case(("hot",))
    args = (x[0], weights[0], experts[0], layers[0])
    alone = jax.make_jaxpr(share_loss(128))(*args)
    assert len(loops_in(alone.jaxpr)) == 2
    one_peer = jax.make_jaxpr(jax.vmap(share_loss(128)))(
        *jax.tree.map(lambda v: v[None], args)
    )
    assert len(loops_in(one_peer.jaxpr)) == 2


def test_held_rows_come_out_the_same_to_the_bit_in_every_window():
    """The held rows are the same rows in the same groups through the same
    products whatever window they fall in: ``hidden``, the down projection
    and the down adapter's A side of each window agree bit for bit with the
    N x k program's on the window's rows, bfloat16 rows with float32 between
    the matmuls as the cell has them; rows in no group give 0 in both."""
    c = CAPPED
    layers, x, weights, experts = capped_case(("warm",))
    how = (2.0, jnp.bfloat16, True, jnp.float32)
    order, inverse, sizes = moe.dispatch_plan(experts[0], c["held"], c["offset"])
    held_rows = int(sizes.sum())
    assert 512 < held_rows < 1536  # the groups end inside a later window
    xe = x[0].astype(jnp.bfloat16)
    full = moe._swiglu_experts(xe[order // c["k"]], sizes, *layers[0], how)
    for start in range(0, 2048, 512):
        rows = jnp.dot(
            jax.nn.one_hot(order[start:start + 512] // c["k"], c["tokens"], dtype=xe.dtype),
            xe,
        )
        assert bool(jnp.all(rows == xe[order // c["k"]][start:start + 512]))
        cut = moe._cut_to_window(sizes, start, 512)
        here = max(0, min(held_rows - start, 512))
        assert int(cut.sum()) == here
        part = moe._swiglu_experts(rows, cut, *layers[0], how)
        for name, a, b in zip(("hidden", "out", "down"), part, full):
            a, b = (np.asarray(v, np.float32) for v in (a, b[start:start + 512]))
            assert np.array_equal(a[:here], b[:here]), (name, start)
            if name != "hidden":
                assert np.abs(a[here:]).max(initial=0) == 0
                assert np.abs(b[here:]).max(initial=0) == 0
            assert here == 0 or np.abs(a[:here]).max() > 0
    assert int(sum(
        moe._cut_to_window(sizes, s, 512).sum() for s in range(0, 2048, 512)
    )) == held_rows


def capped_model(**changes):
    return model_of(
        n_experts=128, n_experts_per_tok=8, experts_held=8, expert_offset=8,
        **changes,
    )


def test_remat_on_and_off_agree_through_the_walk(seeded):
    """The toy model with 128 experts, 8 held, top 8 at T 128: 2,048
    assignments a call, windows of 512, under ``vmap`` over two peers as a
    stacked step runs it.  The layer's gradient is hand-written around the
    walk (each window recomputes its forward), inside a checkpointed block
    or not; a peer alone holds the same walk and agrees."""
    tokens = jax.random.randint(jax.random.key(0), (2, 128), 0, CONFIG["vocab_size"])
    targets = jnp.roll(tokens, -1, axis=1)
    params = perturbed(capped_model().init(jax.random.key(1), tokens))
    peers = lambda tree: jax.tree.map(lambda v: jnp.stack([v, v * 1.01]), tree)
    loss_of = lambda remat: lambda p, tok, tgt: moe_loss(
        capped_model(remat=remat), p, tok, tgt
    )
    stacked = (peers(params), jnp.stack([tokens, tokens[::-1]]),
               jnp.stack([targets, targets[::-1]]))
    results = [
        jax.vmap(jax.value_and_grad(loss_of(remat)))(*stacked)
        for remat in (True, False)
    ]
    (loss_on, grads_on), (loss_off, grads_off) = results
    np.testing.assert_allclose(loss_on, loss_off, rtol=1e-6)
    for name, grad in adapters(grads_on).items():
        assert relative(grad, adapters(grads_off)[name]) < 1e-5, name
        assert float(jnp.abs(grad).max()) > 0, name
    jaxpr = jax.make_jaxpr(jax.vmap(jax.grad(loss_of(True))))(*stacked).jaxpr
    assert len(loops_in(jaxpr)) >= 4  # two expert layers, both passes
    # A peer alone: the same walk, the same numbers.
    alone = jax.make_jaxpr(jax.grad(loss_of(True)))(params, tokens, targets)
    assert len(loops_in(alone.jaxpr)) == len(loops_in(jaxpr))
    loss_alone, grads_alone = jax.value_and_grad(loss_of(True))(
        params, tokens, targets
    )
    assert float(loss_alone) == pytest.approx(float(loss_on[0]), rel=1e-6)
    for name, grad in adapters(grads_alone).items():
        assert relative(grad, adapters(grads_on)[name][0]) < 1e-5, name
    # And against the reference given the same share of 128 experts.
    config = dict(
        CONFIG, n_routed_experts=8, num_experts_per_tok=8,
        published=dict(CONFIG["published"], n_routed_experts=128),
        assumed=dict(CONFIG["assumed"], expert_offset=8),
    )
    assert relative(
        capped_model().apply(params, tokens), plain.forward(config, params, tokens)
    ) < 1e-4


@pytest.mark.parametrize("hot", [False, True])
def test_the_counters_say_whether_a_share_overflowed(hot):
    _, _, _, experts = capped_case(("hot" if hot else "uniform",))
    stats = moe.routing_stats(experts[0], 128, SHARE)
    assert int(stats["cap"]) == 512
    assert int(stats["held"]) == int(stats["assignments"][8:16].sum())
    assert bool(stats["over_cap"]) == hot
    assert int(stats["dropped"]) == 0
    # The layer's own sown counter; for the hot case a router whose logits
    # are highest for the held experts whatever the token.
    cfg = capped_model().cfg
    y = jnp.ones((2, 128, cfg.d_model))
    params = MoE(cfg).init(jax.random.key(13), y)
    if hot:
        to_held = jnp.where((jnp.arange(128) >= 8) & (jnp.arange(128) < 16), 1.0, -1.0)
        params["params"]["router"] = jnp.broadcast_to(to_held, (cfg.d_model, 128))
    _, sown = MoE(cfg).apply(params, y, mutable=["intermediates"])
    said = {k: v[0] for k, v in sown["intermediates"].items()}
    assert int(said["held_over_cap"]) == int(hot)
    assert (int(said["held_counts"].sum()) > 512) == hot
    if hot:
        assert int(said["held_counts"].sum()) == 2 * 128 * 8
