"""The Mellum 2 family through ``models/llama.py``: ``Llama`` at the builder's
toy shape (the cut's own four kinds, heads of a size the hidden size does not
give with a norm each, a window of a quarter of the sequence, a rope a kind, 8
experts of width 48, two a token, renormalised) against the plain reference
(``benchmark/references/window_moe_decoder.py``) on seeded weights; the whole
published list of 28 kinds at toy widths; a rope a kind and the two forms of
the yarn scale; two stacked peers through the stacked step against
``benchmark/reference.py``; the counts at the published widths; ``_tiling`` at
the three expert cells' shapes; and the accepted cells' programs held to the
parent's text.

Tolerances.  Float32 against float32 differs by the order of summation alone:
1e-4 of rms holds it (seen: some 1e-6) and fails a term left out (the window,
the yarn factor, the norm a head, the renormalisation: see the test that
leaves one out)."""

import dataclasses
import hashlib
import importlib
import os
import re
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops_window, reference, traffic  # noqa: E402
from benchmark.builders import window_moe_decoder as builder  # noqa: E402
from benchmark.references import window_moe_decoder as plain  # noqa: E402
from dpwa_tpu.config import make_local_config  # noqa: E402
from dpwa_tpu.models.llama import (  # noqa: E402
    Attention, Llama, LlamaConfig, YarnScaling, lora_filter, lora_optimizer,
    rope, rope_frequencies,
)
from dpwa_tpu.ops import moe  # noqa: E402
from dpwa_tpu.ops.cross_entropy import softmax_cross_entropy  # noqa: E402
from dpwa_tpu.utils import scopes  # noqa: E402
from tests.test_hybrid_ssm import (  # noqa: E402
    adapters, paths, perturbed, relative,
)
from tests.yardstick.yardstick_paths import MANIFEST, cell_files, load  # noqa: E402

PUBLISHED = load("benchmark/configs/mellum2-12b-a2.5b-lora.json")
CONFIG, CELL = builder.rehearse(PUBLISHED, dict(
    seq_len=0, per_peer_batch=0, peers=2, exchange_filter="lora",
))
T = CELL["seq_len"]
# The published model's 28 layers at the toy widths.
WHOLE = dict(CONFIG, **{
    key: PUBLISHED["published"][key]
    for key in ("num_hidden_layers", "layer_types", "mlp_layer_types")
})
YARN = PUBLISHED["rope_parameters"]["full_attention"]


def model_of(config=CONFIG, **changes) -> Llama:
    model = builder.model_of(config, T)
    return Llama(dataclasses.replace(model.cfg, **changes))


def seeded_for(config, key=1, steps=T):
    tokens = jax.random.randint(
        jax.random.key(0), (2, steps), 0, config["vocab_size"]
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
    kinds = ["sliding_attention"] * 3 + ["full_attention"]
    assert CONFIG["layer_types"] == PUBLISHED["layer_types"] == kinds
    cfg = model_of().cfg
    assert cfg.layer_mixers == tuple(kinds)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.d_ff) == (8, 2, 48)
    # A head's size is the file's, not hidden / heads (16 here, 72 published).
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (64, 4, 2, 32)
    assert cfg.sliding_window == 128 and 4 * cfg.sliding_window == T
    assert cfg.qk_norm_per_head and not cfg.qk_norm and not cfg.tie_embeddings
    assert cfg.router_scoring == "softmax" and cfg.norm_topk_prob
    assert not cfg.router_bias and cfg.n_shared_experts == 0
    assert cfg.norm_eps == 1e-6 and cfg.router_aux_loss_coef == 0.0
    theta, scaling = cfg.rope_of("sliding_attention")
    assert (theta, scaling) == (500000.0, None)
    theta, scaling = cfg.rope_of("full_attention")
    assert theta == 500000.0 and scaling == YarnScaling(
        16, 8192, 32, 1, attention_factor=1.2772588722239782
    )
    # A kind the mapping does not name takes the model's one pair.
    assert cfg.rope_of("attention") == (cfg.rope_theta, cfg.rope_scaling)


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
    # a and b of: the four projections and the experts' three, a layer.
    assert len(got) == 2 * 4 * (4 + 3)
    for name, grad in got.items():
        assert relative(grad, want_grads[name]) < 1e-4, name
        assert float(jnp.abs(grad).max()) > 0, name


def test_the_whole_published_list_of_kinds_equals_the_reference():
    """All 28 layers at toy widths over two windows: three sliding layers
    then a full one, seven times, every layer's feed-forward sparse."""
    kinds = WHOLE["layer_types"]
    assert len(kinds) == 28 == len(WHOLE["mlp_layer_types"])
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [
        3, 7, 11, 15, 19, 23, 27,
    ]
    assert set(kinds) == {"sliding_attention", "full_attention"}
    steps = 2 * CONFIG["sliding_window"]
    params, tokens, _ = seeded_for(WHOLE, key=3, steps=steps)
    p = params["params"]
    assert all("router" in p[f"layer_{i}"]["mlp"] for i in range(28))
    assert all("q_norm" in p[f"layer_{i}"]["attn"] for i in range(28))
    logits = model_of(WHOLE).apply(params, tokens)
    assert relative(logits, plain.forward(WHOLE, params, tokens)) < 1e-4
    # The list is read, not a period: with the first full layer one place
    # earlier the reference is somewhere else.
    moved = list(kinds)
    moved[2], moved[3] = moved[3], moved[2]
    other = plain.forward(dict(WHOLE, layer_types=moved), params, tokens)
    assert relative(logits, other) > 1e-3


@pytest.mark.parametrize("left_out", [
    "the_window", "the_yarn_factor", "the_yarn_blend", "the_norm_a_head",
    "the_renormalisation", "the_ropes_kind",
])
def test_a_term_left_out_is_outside_the_tolerance(seeded, left_out):
    params, tokens, _ = seeded
    want = plain.forward(CONFIG, params, tokens)
    cfg = model_of().cfg
    yarn = cfg.rope_of("full_attention")[1]
    plain_rope = (("sliding_attention", 500000.0, None),)
    changes = dict(
        the_window=dict(sliding_window=4 * cfg.sliding_window),
        the_yarn_factor=dict(rope_by_kind=plain_rope + ((
            "full_attention", 500000.0,
            dataclasses.replace(yarn, attention_factor=1.0),
        ),)),
        the_yarn_blend=dict(rope_by_kind=plain_rope + ((
            "full_attention", 500000.0, dataclasses.replace(yarn, factor=1.0),
        ),)),
        the_norm_a_head=dict(qk_norm_per_head=False),
        the_renormalisation=dict(norm_topk_prob=False),
        the_ropes_kind=dict(rope_by_kind=(
            ("sliding_attention", 500000.0, yarn),
            ("full_attention", 500000.0, None),
        )),
    )[left_out]
    got = Llama(dataclasses.replace(cfg, **changes)).apply(params, tokens)
    assert relative(got, want) > 1e-3


# ---- a rope a kind


def scores_of(kind, q, k):
    """``q . k`` a head, scaled as the layer of ``kind`` scales them, through
    the program's ``rope`` and scale."""
    cfg = model_of().cfg
    theta, scaling = cfg.rope_of(kind)
    positions = jnp.arange(q.shape[1])
    q, k = (rope(z, positions, theta, scaling) for z in (q, k))
    scale = q.shape[-1] ** -0.5 * (scaling.softmax_scale if scaling else 1.0)
    return jnp.einsum("bthd,bshd->bhts", q, k) * scale


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_a_layers_scores_turn_by_its_kinds_rope(kind):
    """A full layer's scores against the published yarn form (``cos`` and
    ``sin`` times ``attention_factor``, the inverse frequencies blended by the
    ramp between the pairs that turn 32 times and once in 8,192 positions), a
    sliding layer's against the plain rope: the reference's own, which
    imports nothing of the program."""
    keys = jax.random.split(jax.random.key(5), 2)
    q, k = (jax.random.normal(key, (1, 64, 2, 128)) for key in keys)
    turn = PUBLISHED["rope_parameters"][kind]
    want = jnp.einsum(
        "bthd,bshd->bhts", plain.rope(q, turn), plain.rope(k, turn)
    ) / 128 ** 0.5
    assert relative(scores_of(kind, q, k), want) < 1e-5
    other = next(o for o in PUBLISHED["rope_parameters"] if o != kind)
    unlike = jnp.einsum(
        "bthd,bshd->bhts",
        plain.rope(q, PUBLISHED["rope_parameters"][other]),
        plain.rope(k, PUBLISHED["rope_parameters"][other]),
    ) / 128 ** 0.5
    assert relative(scores_of(kind, q, k), unlike) > 1e-2


def test_the_yarn_frequencies_are_the_published_blend():
    """At a head of 128, theta 500,000 and 8,192 positions the ramp runs from
    pair 18 (32 turns: 18.08, floored) to pair 35 (one turn: 34.98, ceiled):
    up to it the frequencies stand, from its end on they are a sixteenth."""
    cfg = model_of().cfg
    theta, scaling = cfg.rope_of("full_attention")
    blended = np.asarray(rope_frequencies(128, theta, scaling))
    standing = np.asarray(rope_frequencies(128, theta))
    np.testing.assert_allclose(blended[:19], standing[:19], rtol=1e-6)
    np.testing.assert_allclose(blended[35:], standing[35:] / 16, rtol=1e-6)
    assert np.all(blended[19:35] < standing[19:35])
    assert np.all(blended[19:35] > standing[19:35] / 16)
    ours, factor = plain.inverse_frequencies(128, YARN)
    np.testing.assert_allclose(blended, np.asarray(ours), rtol=1e-6)
    assert factor == 1.2772588722239782


def test_the_two_forms_of_the_yarn_scale_agree():
    """``mscale`` 1 with ``mscale_all_dim`` 1 (the scores times the squared
    magnitude, ``cos`` and ``sin`` as they are) and the published
    ``attention_factor`` (``cos`` and ``sin`` times it, the scores as they
    are) are one scaling of the scores: 1.27726 squared, 1.6314."""
    by_magnitude = YarnScaling(16, 8192, 32, 1, mscale=1.0, mscale_all_dim=1.0)
    published = YarnScaling(
        16, 8192, 32, 1, attention_factor=YARN["attention_factor"]
    )
    assert by_magnitude.embedding_scale == 1.0
    assert by_magnitude.softmax_scale == pytest.approx(1.2772588722239782 ** 2)
    assert published.embedding_scale == 1.2772588722239782
    assert published.softmax_scale == 1.0
    assert published.embedding_scale ** 2 == pytest.approx(1.6314, abs=5e-5)
    # The factor the file gives is the form's default, 0.1 ln 16 + 1.
    assert YarnScaling.magnitude(16, 1.0) == pytest.approx(
        YARN["attention_factor"], rel=1e-12
    )
    keys = jax.random.split(jax.random.key(6), 2)
    q, k = (jax.random.normal(key, (1, 48, 2, 128)) for key in keys)
    positions = jnp.arange(48)

    def scores(scaling):
        qr, kr = (rope(z, positions, 500000.0, scaling) for z in (q, k))
        return jnp.einsum("bthd,bshd->bhts", qr, kr) * scaling.softmax_scale

    assert relative(scores(by_magnitude), scores(published)) < 1e-6
    # A.X-K1's group (the DeepSeek form) reads as it did.
    axk1 = YarnScaling(40, 4096, 32, 1, mscale=1.0, mscale_all_dim=1.0)
    assert axk1.attention_factor is None
    assert axk1.softmax_scale == pytest.approx((0.1 * np.log(40) + 1) ** 2)


def test_attention_takes_a_window_and_a_scale_from_its_kind(monkeypatch):
    """What ``Attention`` hands ``single_device_attention``: a sliding layer
    its window, a full layer none; the scale only where the rope has one."""
    from dpwa_tpu.ops import ulysses

    seen = []
    real = ulysses.single_device_attention
    monkeypatch.setattr(
        ulysses, "single_device_attention",
        lambda q, k, v, **kw: seen.append(kw) or real(q, k, v, **kw),
    )
    cfg = model_of().cfg
    x, positions = jnp.zeros((1, 256, 64)), jnp.arange(256)
    for kind in ("sliding_attention", "full_attention", "attention"):
        module = Attention(cfg, kind)
        jax.eval_shape(
            module.apply,
            jax.eval_shape(module.init, jax.random.key(0), x, positions),
            x, positions,
        )
    sliding, full, plain_kind = seen[1::2]  # init, then apply, a kind
    assert sliding == dict(causal=True, impl="auto", window=128)
    assert full == dict(causal=True, impl="auto", sm_scale=32 ** -0.5)
    assert plain_kind == dict(causal=True, impl="auto")


def test_a_sliding_layer_lies_under_its_own_name_inside_attentions():
    """``dpwa.attn.gqa`` around ``Attention`` of either kind; inside it a
    sliding layer's under ``dpwa.attn.window``, forward and backward."""
    assert scopes.ATTN_WINDOW.whole == "dpwa.attn.window"
    model = model_of()
    tokens = jnp.zeros((1, T), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.key(0), tokens)
    text = jax.jit(jax.grad(loss_of(model))).lower(
        shapes, tokens, tokens
    ).as_text(debug_info=True)
    under = lambda layer: set(re.findall(
        rf'loc\("[^"]*layer_{layer}/(dpwa\.attn\.gqa(?:/dpwa\.attn\.window)?)'
        r'/attn/', text,
    ))
    for layer in range(3):
        assert under(layer) == {"dpwa.attn.gqa/dpwa.attn.window"}
    assert under(3) == {"dpwa.attn.gqa"}
    assert re.search(
        r'transpose\(jvp\(Llama\)\)/[^"]*layer_0/dpwa\.attn\.gqa/'
        r'dpwa\.attn\.window/attn/', text,
    )


# ---- the stacked step, the optimizer, the counts


def test_two_stacked_peers_match_the_references_local_update():
    """The stacked step (``vmap`` over peers) against ``benchmark/
    reference.py``'s loop over peers, as ``run.py`` checks it, and the model
    check with the program's routing verified."""
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


def test_a_wrong_choice_is_refused_by_the_reference(seeded):
    """A token sent to its ninth-best expert instead of one of its two: the
    reference, handed that routing, answers NaN."""
    params, tokens, _ = seeded
    from dpwa_tpu.models.llama import routing_of

    model = model_of()
    sown = model.apply(params, tokens, mutable=["intermediates"])[1]
    routing = routing_of(sown)["experts"]
    ok = plain.forward(CONFIG, params, tokens, routing=routing)
    assert bool(jnp.isfinite(ok).all())
    assert relative(ok, plain.forward(CONFIG, params, tokens)) < 1e-6
    worst = jnp.argmin(routing_of(sown)["logits"][0, 0])
    wrong = routing.at[0, 0, 0].set(worst.astype(routing.dtype))
    refused = plain.forward(CONFIG, params, tokens, routing=wrong)
    assert not bool(jnp.isfinite(refused).all())


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
    for part in ("router", "q_norm", "k_norm", "attn_norm", "mlp_norm",
                 "final_norm", "embed", "lm_head"):
        assert any(part in name for name in frozen), part
        assert not any(part in name for name in moved), part


def test_base_leaves_are_born_in_param_dtype_and_the_router_stays_float32():
    params = jax.eval_shape(
        model_of(param_dtype=jnp.bfloat16).init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32),
    )
    for name, leaf in paths(params).items():
        wide = lora_filter(name) or "router" in name
        assert leaf.dtype == (jnp.float32 if wide else jnp.bfloat16), name


def test_the_counts_at_the_published_widths():
    """``jax.eval_shape`` of the cell's own model: the values ISSUE 49
    counts, by part and whole, and ``flops_window``'s own count of them."""
    model = builder.model_of(PUBLISHED, 4096)
    shapes = jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )
    sizes = {name: int(np.prod(v.shape)) for name, v in paths(shapes).items()}
    of = lambda *parts, lora: sum(
        n for name, n in sizes.items()
        if all(part in name for part in parts) and lora_filter(name) == lora
    )
    assert shapes["params"]["layer_0"]["attn"]["wq"]["kernel"].shape == (2304, 4096)
    assert shapes["params"]["layer_0"]["attn"]["wo"]["kernel"].shape == (4096, 2304)
    assert shapes["params"]["layer_0"]["attn"]["q_norm"]["scale"].shape == (128,)
    assert shapes["params"]["layer_3"]["mlp"]["w_gate"]["kernel"].shape == (
        64, 2304, 896
    )
    assert of("layer_0']['attn'", lora=False) == 21_233_920
    assert of("layer_0']['mlp']['w_", lora=False) == 396_361_728 == 64 * 6_193_152
    assert of("layer_0']['mlp']['router", lora=False) == 147_456
    assert of("layer_3'", lora=False) == 417_747_712 == flops_window.layer_values(
        PUBLISHED
    )
    assert of("layer_2'", lora=True) == 10_125_312
    assert of("embed", lora=False) == of("lm_head", lora=False) == 24_576 * 2_304
    base, trained = of(lora=False), of(lora=True)
    assert base == 1_784_239_360 == flops_window.base_values(PUBLISHED)
    assert trained == 40_501_248 == flops_window.adapter_values(PUBLISHED, 16)
    assert 4 * trained == 162_004_992  # bytes a peer exchanges
    whole = dict(PUBLISHED, **PUBLISHED["published"])
    # The published 12 B, of which a token touches 2.5 B.
    assert flops_window.base_values(whole) == 12_149_923_072
    p = flops_window.parts(whole, 0)
    active = 28 * (
        p["attention"][0] + p["router"][0] + 8 * p["expert"][0]
    ) + 2 * p["head"][0]
    assert 2.4e9 < active < 2.6e9


def test_a_configuration_the_program_does_not_compute_is_refused():
    for key, value in (("attention_bias", True), ("norm_topk_prob", False),
                       ("tie_word_embeddings", True), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=key):
            builder.model_of(dict(CONFIG, **{key: value}), T)
    with pytest.raises(ValueError, match="sparse"):
        builder.model_of(dict(CONFIG, mlp_layer_types=["dense"] * 4), T)
    with pytest.raises(ValueError, match="each of the 4 layers"):
        builder.model_of(dict(CONFIG, layer_types=["full_attention"] * 3), T)
    cfg = model_of().cfg
    for match, changes in (
        ("multiple of 128", dict(sliding_window=0)),
        ("multiple of 128", dict(sliding_window=192)),
        ("sequence-parallel", dict(sp_axis="sp")),
        ("latent or EVA", dict(eva_window=128, eva_chunk=16)),
        ("latent or EVA", dict(kv_lora_rank=8)),
        ("rope_by_kind", dict(rope_by_kind=(("windowed", 1e4, None),))),
    ):
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(cfg, **changes)
    # A stack of full layers alone needs no window.
    LlamaConfig(n_layers=2, layer_mixers=("full_attention",) * 2)


def test_the_defaults_are_todays_behaviour():
    cfg = LlamaConfig()
    assert cfg.head_size is None and cfg.head_dim == cfg.d_model // cfg.n_heads
    assert cfg.sliding_window == 0 and cfg.rope_by_kind == ()
    assert cfg.rope_of("attention") == (cfg.rope_theta, None)
    assert Attention(cfg).kind == "attention"
    assert YarnScaling(40, 4096).attention_factor is None


# ---- the grouped matmuls' tiles


@pytest.mark.parametrize("shape, tiles, accumulator", [
    # OLMoE: 64 experts of 1,024 over a hidden size of 2,048.
    ((2048, 1024), (256, 2048, 1024), (256, 1024, 1024)),
    ((1024, 2048), (256, 1024, 2048), (256, 1024, 1024)),
    # A.X-K1's held experts: 2,048 over 7,168.
    ((7168, 2048), (256, 1024, 2048), (256, 1024, 1024)),
    ((2048, 7168), (256, 2048, 1024), (256, 1024, 1024)),
    # LFM2: 32 experts of 1,792 over 2,048.
    ((2048, 1792), (256, 2048, 896), (256, 1024, 896)),
    ((1792, 2048), (256, 1792, 1024), (256, 896, 1024)),
])
def test_the_tiles_of_the_three_expert_cells_are_todays(
    shape, tiles, accumulator
):
    assert moe._tiling(*shape) == tiles
    assert moe._tiling(*shape, contracts_k=False) == accumulator


def test_the_tiles_at_this_models_widths_divide_them():
    """2,304 = 18 x 128 and 896 = 7 x 128: every tile side divides its
    dimension (no masked tile), for ``gmm``, its transpose and ``tgmm``, and
    the adapters' sides go by the adapters' rule."""
    for k, n in ((2304, 896), (896, 2304)):
        for contracts in (True, False):
            tm, tk, tn = moe._tiling(k, n, contracts_k=contracts)
            assert tm == 256 and k % tk == 0 and n % tn == 0, (k, n, contracts)
            assert tk % 128 == 0 and tn % 128 == 0
    # What the sweep on the v5e kept (PERF.md section 6, PR 49): the
    # contracted side whole in one tile, forward and to the rows; a whole
    # 2,304 of ``n`` over a contracted 896; ``tgmm`` as the rule had it.
    assert moe._tiling(2304, 896) == (256, 2304, 896)
    assert moe._tiling(896, 2304) == (256, 896, 2304)
    assert moe._tiling(2304, 896, contracts_k=False) == (256, 768, 896)
    assert moe._tiling(896, 2304, contracts_k=False) == (256, 896, 768)
    # The weights' tile still bounds ``n`` over a longer ``k``, and the
    # accumulator's width a short one.
    assert moe._tiling(2304, 2304) == (256, 2304, 768)
    assert moe._tiling(256, 8192) == (256, 256, 2048)
    assert moe._tiling(2432, 1024) == (256, 1024, 1024)  # a longer one: as ever
    assert moe._tiling(2304, 16) == (512, 2048, 128)
    assert moe._tiling(16, 2304) == (512, 128, 1024)
    assert moe._tiling(896, 16) == (512, 896, 128)
    assert moe._tiling(16, 896) == (512, 128, 896)


# ---- what the accepted cells computed before, they compute now

# The first 16 hex digits of the SHA-256 of each accepted cell's loss
# gradient under ``vmap`` over two peers at its builder's toy shape, lowered
# for a TPU (the kernels' dispatchers answered as the chip; StableHLO, with
# the kernels' serialised bodies taken out: they carry the checkout's path
# and the callers' line numbers; ``tests/test_window_attention.py`` holds the
# bodies of ``ops/eva.py``'s kernels by their jaxprs), taken on the parent
# commit (2966108) by these lines.  Those that run ``ops/moe._all_rows`` at
# this shape were taken again at PR 50, whose combine keeps the sorted rows,
# and at PR 51, which put the frozen down projection and the combine under
# one gradient rule (``tests/test_moe.py`` holds each to the form before it,
# the forward pass to the bit): the OLMoE and LFM2 cells, the Mellum2 cell
# (held since PR 51), and the A.X-K1 cell, whose toy share has no row cap;
# the cell itself walks windows and did not move (``STEP_OF_THE_SHARE``).
# The Jamba cell's was taken again at PR 56, which runs the mixer's
# convolution with silu as ``ops/ssm.conv_silu``'s two kernels.
STEPS_AT_PARENT = {
    "resnet50-stacked8-fulltree": "9c86be813da01bc3",
    "resnet50-ici4-fulltree": "9c86be813da01bc3",
    "mistral7b-lora-stacked2-t4096": "fbaa370d5f1b8c05",
    "mistral7b-lora-stacked2-t512": "fbaa370d5f1b8c05",
    "olmoe-lora-stacked2-t4096": "9f692ef4953a8eb5",
    "axk1-lora-share8-stacked2": "871095af9a950f67",
    "jamba2-lora-period14-stacked2": "6f2cb2b5a2edc1a2",
    "evabyte-lora-stacked2-t16384": "42fa7dbca930eafd",
    "lfm2-lora-stacked2-t4096": "114566364614790a",
    "mellum2-lora-stacked2-t4096": "5f6437cf9ac79d2d",
}
_BODY = re.compile(r'(\\22body\\22: \\22)[^\\]*')


def lowered_step_text(name, toy=True):
    _, config, cell = cell_files(name)
    family = importlib.import_module("benchmark.builders." + config["family"])
    if toy:
        config, cell = family.rehearse(config, cell)
    built = family.build(config, cell)
    shapes = jax.eval_shape(
        jax.vmap(built.init_fn), jax.random.split(jax.random.key(0), 2)
    )
    batch = jax.eval_shape(
        traffic.make_generator(
            cell["task"], built.batch_shape, 2, cell["per_peer_batch"]
        ),
        jax.random.key(0), 0,
    )
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return jax.jit(jax.vmap(jax.grad(built.loss_fn))).trace(
            shapes, batch
        ).lower(lowering_platforms=("tpu",)).as_text()


def lowered_step_digest(name, toy=True):
    text = _BODY.sub(r"\1", lowered_step_text(name, toy))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(STEPS_AT_PARENT))
def test_an_accepted_cells_step_lowers_to_the_parents_text(name):
    assert lowered_step_digest(name) == STEPS_AT_PARENT[name]


# The A.X-K1 cell at its published shapes (a window of 1,024 rows,
# ``ops/moe._capped_ffn``), taken on PR 50's parent (56a0c59) by these lines.
STEP_OF_THE_SHARE = "b61bff4342ba4652"


@pytest.mark.filterwarnings("ignore:held_matmul outside vmap")
def test_the_share_cells_own_step_lowers_to_the_parents_text():
    assert lowered_step_digest(
        "axk1-lora-share8-stacked2", toy=False
    ) == STEP_OF_THE_SHARE


def test_every_accepted_cell_is_held():
    assert set(STEPS_AT_PARENT) == {w["name"] for w in MANIFEST["workloads"]}


_GROUPED = re.compile(
    r"call @gmm\w*\(.*?\) : \(tensor<(\d+)x\d+x\w+>, tensor<\d+x(\d+)x(\d+)x\w+>"
)


@pytest.mark.parametrize("name, layers, widths, products, before", [
    ("mellum2-lora-stacked2-t4096", 4, (64, 48), 8, 9),
    ("lfm2-lora-stacked2-t4096", 4, (64, 48), 8, 9),
    ("olmoe-lora-stacked2-t4096", 2, (64, 64), 6, 6),
])
def test_a_recomputed_expert_layer_runs_no_second_down_projection(
    name, layers, widths, products, before, monkeypatch
):
    """The grouped products by a frozen kernel (``widths``: the toy shape's
    hidden and expert widths; an adapter's rank is neither) in a cell's
    lowered step, an expert layer: gate and up forward, again under
    ``jax.checkpoint`` and to the rows (2 + 2 + 2, or 2 + 2 in the OLMoE step,
    which recomputes nothing), the down projection forward and the product to
    the rows on the gathered ``d_y`` (1 + 1).  With the down projection and
    the combine under two rules (``before``) a recomputed block ran the down
    projection again, for the weights' gradient alone.  And no pass writes a
    product of all ``N x k`` rows at the hidden width by the weights: the
    weight enters on the expert width."""
    from tests.test_moe import _the_two_rules_apart

    def count():
        text = lowered_step_text(name)
        by_kernel = [
            int(rows) for rows, k, n in _GROUPED.findall(text)
            if {int(k), int(n)} == set(widths)
        ]
        assert len(set(by_kernel)) == 1  # both peers' N x k rows, folded
        # (Where the two widths are one, the SwiGLU's own products have that
        # shape: nothing to tell apart.)
        wide = widths[0] != widths[1] and re.findall(
            rf"stablehlo\.multiply.*tensor<2x{by_kernel[0] // 2}x{widths[0]}xf32>",
            text,
        )
        return len(by_kernel) / layers, wide or []

    assert count() == (products, [])
    monkeypatch.setattr(moe, "_down_and_combine", _the_two_rules_apart)
    assert count()[0] == before
