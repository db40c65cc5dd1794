"""The LFM2 family through ``models/llama.py``: ``Llama`` at the builder's toy
shape (the cut's own five kinds, a dense layer before four expert layers, 8
experts of width 48, two a token by biased sigmoid scores, heads of 16 with a
norm each) against the plain reference
(``benchmark/references/conv_moe_decoder.py``) on seeded weights; the whole
published list of 24 kinds at toy widths; ``ops/moe.route`` with a bias; the
norm a head beside OLMoE's; ``single_device_attention`` at a head size of 64;
two stacked peers through the stacked step against ``benchmark/reference.py``;
the counts at the published widths; and the accepted decoders' programs held
to the parent's text.

Tolerances.  Float32 against float32 differs by the order of summation alone:
1e-4 of rms holds it (seen: some 1e-6) and fails a term left out (the bias in
the choice, the norm a head, the ``1e-6``: see the tests that leave one
out)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops_lfm2, reference  # noqa: E402
from benchmark.builders import conv_moe_decoder as builder  # noqa: E402
from benchmark.references import conv_moe_decoder as plain  # noqa: E402
from dpwa_tpu.config import make_local_config  # noqa: E402
from dpwa_tpu.models.llama import (  # noqa: E402
    Attention, Llama, LlamaConfig, lora_filter, lora_optimizer, routing_of,
)
from dpwa_tpu.ops import moe  # noqa: E402
from dpwa_tpu.ops.cross_entropy import softmax_cross_entropy  # noqa: E402
from dpwa_tpu.ops.ulysses import single_device_attention  # noqa: E402
from tests.test_evabyte import PROGRAMS_AT_PARENT  # noqa: E402
from tests.test_hybrid_ssm import (  # noqa: E402
    adapters, paths, perturbed, program_digest, relative,
)
from tests.yardstick.yardstick_paths import MANIFEST, load  # noqa: E402

PUBLISHED = load("benchmark/configs/lfm2-8b-a1b-lora.json")
CONFIG, CELL = builder.rehearse(PUBLISHED, dict(
    seq_len=0, per_peer_batch=0, peers=2, exchange_filter="lora",
))
T = CELL["seq_len"]
# The published model's 24 layers at the toy widths.
WHOLE = dict(CONFIG, **PUBLISHED["published"])


def model_of(config=CONFIG, **changes) -> Llama:
    model = builder.model_of(config, T)
    return Llama(dataclasses.replace(model.cfg, **changes))


def seeded_for(config, key=1):
    tokens = jax.random.randint(
        jax.random.key(0), (2, T), 0, config["vocab_size"]
    )
    params = perturbed(model_of(config).init(jax.random.key(key), tokens))
    return params, tokens, jnp.roll(tokens, -1, axis=1)


@pytest.fixture(scope="module")
def seeded():
    return seeded_for(CONFIG)


def loss_of(model):
    return lambda p, tokens, targets: softmax_cross_entropy(
        model.apply(p, tokens), targets
    ).mean()


def test_the_toy_shape_keeps_what_is_new():
    assert CONFIG["layer_types"] == PUBLISHED["layer_types"] == [
        "conv", "full_attention", "conv", "conv", "conv",
    ]
    cfg = model_of().cfg
    assert cfg.layer_mixers == ("conv", "attention", "conv", "conv", "conv")
    assert (cfg.n_dense_layers, cfg.n_experts, cfg.n_experts_per_tok) == (1, 8, 2)
    assert (cfg.d_ff, cfg.d_ff_dense, cfg.head_dim) == (48, 128, 16)
    assert cfg.qk_norm_per_head and not cfg.qk_norm and cfg.tie_embeddings
    assert cfg.router_bias and cfg.router_scoring == "sigmoid"
    assert cfg.norm_topk_prob and cfg.norm_topk_eps == 1e-6
    assert cfg.conv_taps == 3 and cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-5
    assert cfg.router_aux_loss_coef == 0.0


@pytest.mark.parametrize("remat", [True, False])
def test_the_cut_equals_the_reference_logits_loss_and_adapter_gradients(
    seeded, remat
):
    params, tokens, targets = seeded
    model = model_of(remat=remat)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, T, 512) and logits.dtype == jnp.float32
    assert relative(logits, plain.forward(CONFIG, params, tokens)) < 1e-4
    got, grads = jax.value_and_grad(loss_of(model))(params, tokens, targets)
    want, want_grads = jax.value_and_grad(
        lambda p: plain.loss(CONFIG, p, tokens, targets)
    )(params)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    got, want_grads = adapters(grads), adapters(want_grads)
    # a and b of: 4 conv mixers x 2, the attention's 4, the dense layer's 3,
    # 4 expert layers x 3 (each expert's factors stacked in one leaf).
    assert len(got) == 2 * (4 * 2 + 4 + 3 + 4 * 3)
    for name, grad in got.items():
        assert relative(grad, want_grads[name]) < 1e-4, name
        assert float(jnp.abs(grad).max()) > 0, name


def test_the_whole_published_list_of_kinds_equals_the_reference():
    """All 24 layers at toy widths: two dense layers first, the six
    attention layers where the list puts them, the sixth after two
    convolutions and not three."""
    kinds = WHOLE["layer_types"]
    assert len(kinds) == 24 and WHOLE["num_dense_layers"] == 2
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [
        2, 6, 10, 14, 18, 21,
    ]
    params, tokens, _ = seeded_for(WHOLE, key=3)
    p = params["params"]
    assert "router" not in p["layer_1"]["mlp"] and "router" in p["layer_2"]["mlp"]
    assert "attn" in p["layer_21"] and "conv" in p["layer_22"]
    logits = model_of(WHOLE).apply(params, tokens)
    assert relative(logits, plain.forward(WHOLE, params, tokens)) < 1e-4
    # The list is read, not a period: with the last attention layer one
    # place later the reference is somewhere else.
    moved = list(kinds)
    moved[21], moved[22] = moved[22], moved[21]
    with pytest.raises(KeyError):
        plain.forward(dict(WHOLE, layer_types=moved), params, tokens)


@pytest.mark.parametrize("left_out", [
    "expert_bias", "q_norm", "conv_kernel_tap", "norm_topk_eps",
])
def test_a_term_left_out_is_outside_the_tolerance(seeded, left_out):
    """The comparison can tell: with the bias out of the choice, a head's
    norm weight of ones, a tap of one conv layer zero, or the renormalised
    sum without its 1e-6 (at scores scaled down so that it matters), the
    logits leave the 1e-4."""
    params, tokens, _ = seeded
    want = plain.forward(CONFIG, params, tokens)
    p = params["params"]
    if left_out == "expert_bias":
        layer = dict(p["layer_2"])
        layer["mlp"] = dict(
            layer["mlp"], expert_bias=jnp.zeros_like(layer["mlp"]["expert_bias"])
        )
        changed = dict(p, layer_2=layer)
    elif left_out == "q_norm":
        layer = dict(p["layer_1"])
        attn = dict(layer["attn"])
        attn["q_norm"] = dict(scale=jnp.ones_like(attn["q_norm"]["scale"]))
        changed = dict(p, layer_1=dict(layer, attn=attn))
    elif left_out == "conv_kernel_tap":
        layer = dict(p["layer_0"])
        conv = dict(layer["conv"])
        conv["conv_kernel"] = conv["conv_kernel"].at[0].set(0.0)
        changed = dict(p, layer_0=dict(layer, conv=conv))
    else:
        got = model_of(norm_topk_eps=1e-2).apply(params, tokens)
        assert relative(got, want) > 1e-4
        return
    assert relative(model_of().apply({"params": changed}, tokens), want) > 1e-3


# ---- the router: chosen by score + bias, weighed by the score


def test_route_chooses_by_score_plus_bias_and_weighs_by_score():
    x = jnp.eye(3, 4)
    kernel = jnp.array([
        [2.0, 1.0, 0.0, -1.0, -2.0],
        [0.0, 0.1, 0.2, 0.3, 0.4],
        [1.0, 1.0, 1.0, 1.0, 1.0],
        [0.0, 0.0, 0.0, 0.0, 0.0],
    ])
    bias = jnp.array([-1.0, 0.0, 0.0, 0.0, 1.0])
    scores = jax.nn.sigmoid(x @ kernel)
    plain_w, plain_e, logits = moe.route(x, kernel, 2, "sigmoid", True, 1.0)
    w, e, same_logits = moe.route(
        x, kernel, 2, "sigmoid", True, 1.0, bias, 1e-6
    )
    np.testing.assert_array_equal(logits, same_logits)
    # Token 0 prefers experts 0 and 1; the bias swaps in 4 for 0.
    assert sorted(plain_e[0].tolist()) == [0, 1]
    assert sorted(e[0].tolist()) == [1, 4]
    # Token 2 scores every expert alike: the bias alone decides.
    assert sorted(e[2].tolist()) == [1, 4] or 4 in e[2].tolist()
    for n in range(3):
        picked = scores[n, e[n]]
        np.testing.assert_allclose(
            w[n], picked / (picked.sum() + 1e-6), rtol=1e-6
        )
        # The bias is in no weight: they are the scores' own shares.
        assert abs(float(w[n].sum()) - 1.0) < 2e-6
    # Without the bias argument, the lowered program is the one it was.
    text = lambda *a: jax.jit(
        lambda x, k: moe.route(x, k, *a)
    ).lower(x, kernel).as_text()
    assert text(2, "sigmoid", True, 1.0) == text(
        2, "sigmoid", True, 1.0, None, 0.0
    )
    assert text(2, "sigmoid", True, 1.0) != text(
        2, "sigmoid", True, 1.0, None, 1e-6
    )


def test_the_share_the_bias_moved_is_sown_and_read(seeded):
    params, tokens, _ = seeded
    model = model_of()
    sown = routing_of(model.apply(params, tokens, mutable=["intermediates"])[1])
    assert sown["bias_moved"].shape == (4,) and sown["counts"].shape == (4, 8)
    assert sown["experts"].shape == (4, 2 * T, 2)
    _, details = plain.forward_with_routing(CONFIG, params, tokens, sown)
    np.testing.assert_allclose(
        sown["bias_moved"], details["bias_moved"], atol=1e-6
    )
    assert float(sown["bias_moved"].min()) > 0.0  # the bias does something
    assert float(details["logit_error"].max()) < 1e-5
    assert float(details["set_margin"].max()) <= 0.0
    # No bias, nothing moved.
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(0), (16, 8)))
    assert float(moe.choices_moved(scores, jax.lax.top_k(scores, 2)[1])) == 0.0


def test_a_wrong_choice_is_refused_by_the_reference(seeded):
    """The program's routing with one token's expert swapped for one outside
    the top of ``score + bias`` gives NaN logits, and so do verified scores
    rounded to bfloat16 (the precision below): ``CHOICE_EPS`` can tell."""
    params, tokens, _ = seeded
    sown = routing_of(
        model_of().apply(params, tokens, mutable=["intermediates"])[1]
    )
    good = plain.forward(CONFIG, params, tokens, routing=sown)
    assert bool(jnp.isfinite(good).all())
    assert relative(good, plain.forward(CONFIG, params, tokens)) < 1e-6
    chosen = sown["experts"]
    biased = jax.nn.sigmoid(sown["logits"][0, 0]) + (
        params["params"]["layer_1"]["mlp"]["expert_bias"]
    )
    worst = jnp.argmin(biased).astype(chosen.dtype)
    wrong = dict(sown, experts=chosen.at[0, 0, 0].set(worst))
    assert bool(jnp.isnan(plain.forward(CONFIG, params, tokens, wrong)).any())
    rounded = plain.forward(
        CONFIG, params, tokens, sown,
        round_scores=lambda s: jax.lax.reduce_precision(s, 8, 7),
    )
    assert bool(jnp.isnan(rounded).any())


# ---- the grouped kernels' tiles


@pytest.mark.parametrize("shape, tiles, accumulator", [
    # What the OLMoE and A.X-K1 cells pass: a contracted 2,048 whole, one
    # ``n`` tile of 2,048 over a contracted 1,024 (PERF.md section 6, PR 45);
    # ``tgmm``, whose tile is the accumulator, at PR 27's picks.
    ((2048, 1024), (256, 2048, 1024), (256, 1024, 1024)),
    ((1024, 2048), (256, 1024, 2048), (256, 1024, 1024)),
    ((7168, 2048), (256, 1024, 2048), (256, 1024, 1024)),
    ((2048, 7168), (256, 2048, 1024), (256, 1024, 1024)),
    # The adapters, theirs and the LFM2 cell's: one answer for both kernels.
    ((2048, 32), (512, 2048, 128), None), ((7168, 32), (512, 2048, 128), None),
    ((16, 1024), (512, 128, 1024), None), ((1024, 16), (512, 1024, 128), None),
    ((16, 2048), (512, 128, 1024), None), ((2048, 16), (512, 2048, 128), None),
    ((16, 7168), (512, 128, 1024), None), ((7168, 16), (512, 2048, 128), None),
    ((1792, 16), (512, 1792, 128), None), ((16, 1792), (512, 128, 1024), None),
])
def test_the_tiles_where_1024_divides_and_the_adapters(
    shape, tiles, accumulator
):
    assert moe._tiling(*shape) == tiles
    assert moe._tiling(*shape, contracts_k=False) == (accumulator or tiles)


def test_the_tiles_at_a_width_1024_does_not_divide():
    """``n`` by its largest divisor; in ``gmm`` a contracted ``k`` whole; in
    ``tgmm``, whose ``tk x tn`` tile is the accumulator, ``k`` by its divisor
    too (Mosaic refuses a whole 2,048 there: PERF.md section 6, PR 44)."""
    assert moe._tiling(2048, 1792) == (256, 2048, 896)
    assert moe._tiling(1792, 2048) == (256, 1792, 1024)
    assert moe._tiling(2048, 1792, contracts_k=False) == (256, 1024, 896)
    assert moe._tiling(1792, 2048, contracts_k=False) == (256, 896, 1024)
    # No divisor of 1,408 = 11 x 128 reaches 512: a masked 1,024 tile.
    assert moe._tiling(2048, 1408) == (256, 2048, 1024)
    assert moe._tiling(1408, 2048, contracts_k=False) == (256, 1024, 1024)
    # A short contracted ``k`` leaves the weights' tile room for more, but
    # the accumulator is ``tm x tn``: Mosaic refuses (256, 256, 8192).
    assert moe._tiling(256, 8192) == (256, 256, 2048)


@pytest.mark.parametrize("k, n", [
    # LFM2's down projection: forward (256, 1792, 1024), to the rows (256,
    # 2048, 896), to the weights (256, 896, 1024).
    (1792, 2048),
    # OLMoE's gate: forward (256, 2048, 1024), to the rows (256, 1024, 2048),
    # to the weights (256, 1024, 1024).
    (2048, 1024),
])
def test_the_kernels_under_the_tiles_picked_equal_ragged_dot(
    monkeypatch, k, n
):
    """The library's kernels, interpreted, under the tiles ``_tiling`` picks
    (a contracted dimension whole in one tile forward, ``n`` by its divisor
    within the weights' tile), against ``lax.ragged_dot``: values and both
    gradients, an empty group among them."""
    from jax.experimental.pallas import tpu as pltpu

    m = 512
    sizes = jnp.array([200, 0, 312], jnp.int32)
    lhs = jax.random.normal(jax.random.key(0), (m, k))
    rhs = jax.random.normal(jax.random.key(1), (3, k, n)) * k ** -0.5
    cot = jax.random.normal(jax.random.key(2), (m, n))
    one = jax.value_and_grad(
        lambda a, b: jnp.sum(moe.grouped_matmul(a, b, sizes) * cot), (0, 1)
    )
    want = one(lhs, rhs)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        assert "pallas_call" in str(jax.make_jaxpr(one)(lhs, rhs))
        got = one(lhs, rhs)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-3)


# ---- the norm a head, beside OLMoE's over the whole width


def test_the_norm_a_head_is_the_references_and_olmoes_form_is_unmoved():
    cfg = model_of().cfg
    y = jax.random.normal(jax.random.key(4), (2, T, cfg.d_model))
    positions = jnp.arange(T)
    block = Attention(cfg)
    params = perturbed(block.init(jax.random.key(5), y, positions), 6)
    a = params["params"]
    assert a["q_norm"]["scale"].shape == a["k_norm"]["scale"].shape == (16,)
    want = plain.attention(CONFIG, a, y, 1.0)
    assert relative(block.apply(params, y, positions), want) < 1e-4
    # OLMoE's form: one weight over the projected width, before the split.
    whole = dataclasses.replace(cfg, qk_norm=True, qk_norm_per_head=False)
    shapes = jax.eval_shape(Attention(whole).init, jax.random.key(5), y, positions)
    assert shapes["params"]["q_norm"]["scale"].shape == (4 * 16,)
    assert shapes["params"]["k_norm"]["scale"].shape == (2 * 16,)
    # (Its program is held to the parent's text at the end of this file.)


# ---- attention at a head size of 64


@pytest.mark.parametrize("head", [64, 96])
def test_a_head_that_is_no_multiple_of_128_equals_the_einsum(head):
    """``impl="flash"`` with the library's kernels interpreted: 64 / 64 goes
    to them as it is, 96 / 96 zero-padded to 128 with the scale still ``1 /
    sqrt(96)``; the output and the three gradients are the einsum's."""
    from jax.experimental.pallas import tpu as pltpu

    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (1, 256, 4, head), jnp.float32)
    k, v = (
        jax.random.normal(kk, (1, 256, 2, head), jnp.float32)
        for kk in keys[1:3]
    )
    w = jax.random.normal(keys[3], q.shape, jnp.float32)

    def value_and_grads(impl):
        attn = lambda q, k, v: single_device_attention(
            q, k, v, causal=True, impl=impl
        )
        loss = lambda q, k, v: jnp.sum(attn(q, k, v) * w)
        # One program each, and the first at rest before the second starts:
        # the TPU interpreter's callbacks run JAX operations of their own,
        # and an eager operation dispatched beside a kernel in flight can
        # wait on them for ever (seen under six test workers, PR 46).
        out = jax.block_until_ready(jax.jit(attn)(q, k, v))
        return (out, *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))

    with pltpu.force_tpu_interpret_mode():
        got = jax.block_until_ready(value_and_grads("flash"))
    want = value_and_grads("dense")
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape
        err = float(jnp.max(jnp.abs(a - b)))
        assert err <= 2e-4, f"{name} off the einsum by {err}"


# The first 16 hex digits of the SHA-256 of ``single_device_attention``'s
# gradient lowered for a TPU (the dispatcher answered "tpu", no traceback in
# the locations: a docstring's new line is no new program).  192 / 128 and
# 64 / 64 are the parent commit's (b5e8bd7, by the same lines): the library's
# kernels, as before.  128 / 128 and 256 / 256 were pinned again by PR 46
# (the parent read 6539f3edda51cd71 and a87653e5f5829c36): causal attention
# at a head that is a multiple of 128 is ``ops/eva.causal_attention``'s two
# kernels now, by design; and again by PR 54 (fd428cf9389094e6 and
# 3c56bfbe2e9a18b1 until then): those kernels read ``v`` and write ``o`` as
# ``[B, T, h D]`` and ``single_device_attention`` turns ``q`` and ``k`` alone.
ATTENTION_AT_PARENT = {
    (128, 128): "cd346854be42fc93", (192, 128): "00eb3ba5f83afbf5",
    (256, 256): "7cfe7f1edd4c04db", (64, 64): "b1420bb77a86c94a",
}


@pytest.mark.parametrize("sizes", sorted(ATTENTION_AT_PARENT))
def test_the_head_sizes_the_accepted_cells_pass_take_the_branch_they_took(
    sizes, monkeypatch
):
    import hashlib

    d, dv = sizes
    q = jax.ShapeDtypeStruct((1, 256, 2, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 256, 2, dv), jnp.bfloat16)

    def loss(q, k, v):  # the name is part of the lowered module's
        return single_device_attention(
            q, k, v, causal=True
        ).astype(jnp.float32).sum()

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            q, q, v
        ).lower(lowering_platforms=("tpu",)).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    assert "tpu_custom_call" in text
    digest = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert digest == ATTENTION_AT_PARENT[sizes]


# ---- the stacked step, the optimizer, the counts


def test_two_stacked_peers_match_the_references_local_update():
    """The stacked step (``vmap`` over peers: the expert layers' grouped
    products fold the peer axis, the conv mixers and the norm a head need no
    rule) against ``benchmark/reference.py``'s loop over peers, as ``run.py``
    checks it, and the model check with the program's routing verified."""
    from dpwa_tpu.parallel.stacked import (
        StackedTransport, init_stacked_state, make_stacked_train_step,
    )
    from dpwa_tpu.train import init_params_per_peer

    built = builder.build(CONFIG, CELL)
    transport = StackedTransport(make_local_config(2, schedule="ring"))
    optimizer = built.make_optimizer(
        jax.eval_shape(built.init_fn, jax.random.key(0))
    )
    stacked = init_params_per_peer(built.init_fn, jax.random.key(4), 2)
    state = init_stacked_state(stacked, optimizer, transport)
    step = make_stacked_train_step(
        built.loss_fn, optimizer, transport,
        exchange_filter=built.exchange_filter,
    )
    tokens = jax.random.randint(
        jax.random.key(5), (2, 2, T + 1), 0, CONFIG["vocab_size"]
    )
    batch = tokens[..., :-1], tokens[..., 1:]
    for _ in range(2):  # so that LoRA B has left zero
        state, _, _ = step(state, batch)
    local = reference.make_local_update(
        built.loss_fn, optimizer, built.exchange_filter
    )
    u_leaves, moved = local(state.params, state.opt_state, batch)
    jax.block_until_ready(u_leaves)
    state, losses, info = step(state, batch)
    assert not reference.check_info(
        info.partner, info.alpha, info.participated, 0.5
    )
    verdict = reference.compare(
        state.params, reference.merge(u_leaves, info.partner, info.alpha),
        moved, info.alpha, built.exchange_filter,
    )
    assert verdict.ok, verdict.reasons
    assert verdict.worst_ratio < 0.1 and bool(jnp.isfinite(losses).all())
    error, size = reference.make_model_check(
        built.apply_fn, built.reference_forward, built.reference_inputs,
    )(state.params, batch)
    assert float(error) < 1e-4 * float(size)


def test_the_optimizer_trains_adapters_alone(seeded):
    import optax

    params, _, _ = seeded
    optimizer = lora_optimizer(optax.sgd(0.1), params)
    updates, _ = optimizer.update(
        jax.tree.map(jnp.ones_like, params), optimizer.init(params), params
    )
    moved = {
        name for name, u in paths(updates).items()
        if float(jnp.abs(u).max()) > 0
    }
    assert moved == set(adapters(params))
    frozen = set(paths(params)) - moved
    for part in ("router", "expert_bias", "conv_kernel", "q_norm", "k_norm",
                 "conv_norm", "attn_norm", "mlp_norm", "final_norm", "embed"):
        assert any(part in name for name in frozen), part
        assert not any(part in name for name in moved), part


def test_base_leaves_are_born_in_param_dtype_and_the_gate_stays_float32():
    params = jax.eval_shape(
        model_of(param_dtype=jnp.bfloat16).init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32),
    )
    for name, leaf in paths(params).items():
        wide = lora_filter(name) or "router" in name or "expert_bias" in name
        assert leaf.dtype == (jnp.float32 if wide else jnp.bfloat16), name


def test_the_counts_at_the_published_widths():
    """``jax.eval_shape`` of the cell's own model: the values ISSUE 44
    counts, by part and whole, and ``flops_lfm2``'s own count of them."""
    model = builder.model_of(PUBLISHED, 4096)
    shapes = jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )
    sizes = {name: int(np.prod(v.shape)) for name, v in paths(shapes).items()}
    of = lambda *parts, lora: sum(
        n for name, n in sizes.items()
        if all(part in name for part in parts) and lora_filter(name) == lora
    )
    assert of("layer_0']['conv'", lora=False) == 16_783_360
    assert of("layer_1']['attn'", lora=False) == 10_485_888
    assert of("layer_0']['mlp'", lora=False) == 44_040_192
    assert of("layer_2']['mlp']['w_", lora=False) == 352_321_536
    assert of("layer_2']['mlp']['router", lora=False) == 65_536
    assert of("layer_2']['mlp']['expert_bias", lora=False) == 32
    assert of("embed", lora=False) == 134_217_728
    assert not any("lm_head" in name for name in sizes)
    base, trained = of(lora=False), of(lora=True)
    assert base == 1_665_448_064 + 4 * 32 == flops_lfm2.base_values(PUBLISHED)
    assert trained == 25_034_752 == flops_lfm2.adapter_values(PUBLISHED, 16)
    assert 4 * trained == 100_139_008  # bytes a peer exchanges
    whole = dict(PUBLISHED, **PUBLISHED["published"])
    assert flops_lfm2.base_values(whole) == 8_339_930_560  # the published 8.3 B
    assert flops_lfm2.layer_kinds(whole) == dict(
        conv=18, attention=6, dense=2, experts=22
    )


def test_a_configuration_file_the_program_does_not_compute_is_refused():
    for key, value in (("conv_bias", True), ("norm_topk_prob", False),
                       ("use_expert_bias", False)):
        with pytest.raises(ValueError, match=key):
            builder.model_of(dict(CONFIG, **{key: value}), T)
    with pytest.raises(KeyError, match="sliding_attention"):
        builder.model_of(dict(
            CONFIG, layer_types=["sliding_attention"] + CONFIG["layer_types"][1:]
        ), T)
    with pytest.raises(ValueError, match="each of the 5 layers"):
        builder.model_of(dict(CONFIG, layer_types=["conv"] * 4), T)


def test_the_defaults_are_todays_behaviour():
    cfg = LlamaConfig()
    assert cfg.layer_mixers is None and not cfg.qk_norm_per_head
    assert not cfg.router_bias and cfg.norm_topk_eps == 0.0
    shapes = jax.eval_shape(
        Llama(LlamaConfig(
            vocab_size=64, d_model=32, n_layers=1, n_heads=4, d_ff=48,
            n_experts=4, n_experts_per_tok=2,
        )).init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
    )
    assert set(shapes["params"]["layer_0"]["mlp"]) == {
        "router", "w_gate", "w_up", "w_down",
    }


# ---- what the accepted decoders computed before, they compute now

# As ``tests/test_evabyte.py`` holds the four decoder families before it, the
# EVA family's taken on the parent commit (68f6438) by the same lines.  (The
# two ResNet cells run ``models/resnet.py`` and ``parallel/``, which this PR
# does not touch.)
PROGRAMS_AT_PR_43 = dict(PROGRAMS_AT_PARENT, **{
    "evabyte-6.5b-lora": "4d977d09332f1e0a",
    "evabyte-6.5b-lora.loss_grad": "7321f1edb2d7e37b",
})


@pytest.mark.parametrize("name", [
    "mistral-7b-v0.3-lora", "olmoe-1b-7b-0125-lora", "axk1-lora",
    "jamba2-3b-lora", "evabyte-6.5b-lora",
])
def test_an_accepted_decoder_lowers_to_the_parents_programs(name):
    import importlib

    entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
    config = load(entry["file"])
    family = importlib.import_module("benchmark.builders." + config["family"])
    toy, cell = family.rehearse(config, dict(
        seq_len=64, per_peer_batch=2, peers=2, exchange_filter="lora",
    ))
    built = family.build(toy, cell)
    shapes = jax.eval_shape(built.init_fn, jax.random.key(0))
    tokens = jnp.zeros((2, cell["seq_len"]), jnp.int32)
    assert program_digest(
        built.apply_fn, shapes, tokens
    ) == PROGRAMS_AT_PR_43[name]
    assert program_digest(
        jax.grad(built.loss_fn), shapes, (tokens, tokens)
    ) == PROGRAMS_AT_PR_43[name + ".loss_grad"]
