"""The EVA family through ``models/llama.py``: ``Llama`` at the builder's toy
shape (three windows of 32 positions in chunks of 4, so that the third window
sees two windows' summaries; three next-byte heads) against the plain
reference (``benchmark/references/eva_decoder.py``) on seeded weights, the
several-head loss against hand-shifted cross-entropies, two stacked peers
through the stacked step against ``benchmark/reference.py``, and the accepted
decoders' programs held to the parent's text.

Tolerances.  Float32 against float32 differs by the order of summation alone:
1e-4 of rms holds it (seen: some 1e-6) and fails a term left out (``mu``, the
norm's unit offset, a head's columns shifted by one: hundredths and more)."""

import dataclasses
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

from benchmark import reference  # noqa: E402
from benchmark.builders import eva_decoder as builder  # noqa: E402
from benchmark.references import eva_decoder as plain  # noqa: E402
from dpwa_tpu.config import make_local_config  # noqa: E402
from dpwa_tpu.models.llama import (  # noqa: E402
    Attention, EvaAttention, LatentAttention, Llama, LlamaConfig, RMSNorm,
    YarnScaling, lora_filter, lora_optimizer, rope, rope_frequencies,
)
from dpwa_tpu.ops.cross_entropy import softmax_cross_entropy  # noqa: E402
from tests.test_hybrid_ssm import (  # noqa: E402
    PROGRAMS_BEFORE, adapters, paths, perturbed, program_digest, relative,
)
from tests.yardstick.yardstick_paths import MANIFEST, load  # noqa: E402

PUBLISHED = load("benchmark/configs/evabyte-6.5b-lora.json")
CONFIG, CELL = builder.rehearse(PUBLISHED, dict(
    seq_len=0, per_peer_batch=0, peers=2, exchange_filter="lora",
))
T, WINDOW, CHUNK = CELL["seq_len"], CONFIG["window_size"], CONFIG["chunk_size"]
HEADS = CONFIG["num_pred_heads"]


def model_of(config=CONFIG, **changes) -> Llama:
    model = builder.model_of(config, T)
    return Llama(dataclasses.replace(model.cfg, **changes))


@pytest.fixture(scope="module")
def seeded():
    tokens = jax.random.randint(
        jax.random.key(0), (2, T), 0, CONFIG["vocab_size"]
    )
    params = perturbed(model_of().init(jax.random.key(1), tokens))
    return params, tokens, jnp.roll(tokens, -1, axis=1)


def test_the_toy_shape_keeps_what_is_new():
    assert (T, WINDOW, CHUNK, HEADS) == (96, 32, 4, 3)
    assert CONFIG["vocab_size"] == PUBLISHED["vocab_size"] == 320
    cfg = model_of().cfg
    assert (cfg.eva_window, cfg.eva_chunk, cfg.n_pred_heads) == (32, 4, 3)
    assert cfg.norm_unit_offset and cfg.fp32_skip_add
    assert cfg.activation_dtype is None and cfg.stream_dtype == jnp.float32


@pytest.mark.parametrize("remat", [True, False])
def test_the_model_equals_the_reference_logits_loss_and_adapter_gradients(
    seeded, remat
):
    params, tokens, targets = seeded
    model = model_of(remat=remat)
    loss = lambda p: builder.multi_head_loss(model.apply(p, tokens), targets)
    wanted = lambda p: plain.multi_head_loss(
        plain.forward(CONFIG, p, tokens), targets
    )
    logits, want_logits = model.apply(params, tokens), plain.forward(
        CONFIG, params, tokens
    )
    assert logits.shape == (2, T, HEADS, 320) and logits.dtype == jnp.float32
    for head in range(HEADS):
        assert relative(logits[:, :, head], want_logits[:, :, head]) < 1e-4
    got, grads = jax.value_and_grad(loss)(params)
    want, want_grads = jax.value_and_grad(wanted)(params)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    got, want_grads = adapters(grads), adapters(want_grads)
    # a and b of the 4 + 3 projections of each of the 2 layers.
    assert len(got) == 2 * 7 * 2
    for name, grad in got.items():
        assert relative(grad, want_grads[name]) < 1e-4, name
        assert float(jnp.abs(grad).max()) > 0, name


def test_the_third_window_sees_the_first_twos_summaries(seeded):
    """A byte of window 0 changed: window 0 moves from there on, and windows
    1 and 2 move through its chunk's summary alone (the reference agrees on
    the changed input too, so the path is the same one)."""
    params, tokens, _ = seeded
    other = tokens.at[:, 5].set((tokens[:, 5] + 1) % 320)
    model = model_of()
    base, moved = model.apply(params, tokens), model.apply(params, other)
    np.testing.assert_array_equal(base[:, :5], moved[:, :5])
    for window in range(3):
        here = slice(max(window * WINDOW, 5), (window + 1) * WINDOW)
        assert relative(moved[:, here], base[:, here]) > 1e-6, window
    assert relative(moved, plain.forward(CONFIG, params, other)) < 1e-4


@pytest.mark.parametrize("left_out", [
    "adaptive_mu_k", "adaptive_phi", "attn_norm", "lm_head_shift",
])
def test_a_term_left_out_is_outside_the_tolerance(seeded, left_out):
    """The comparison can tell: with ``mu`` or ``phi`` of layer 0 zero, a
    norm's weight taken without its unit offset, or the heads' columns
    shifted by one head, the logits leave the 1e-4 by two orders."""
    params, tokens, _ = seeded
    p = params["params"]
    layer = dict(p["layer_0"])
    if left_out == "lm_head_shift":
        kernel = p["lm_head"]["kernel"]
        changed = dict(p, lm_head=dict(kernel=jnp.roll(kernel, 320, axis=1)))
    elif left_out == "attn_norm":
        scale = layer["attn_norm"]["scale"]
        changed = dict(p, layer_0=dict(
            layer, attn_norm=dict(scale=scale - 1.0)
        ))
    else:
        attn = dict(layer["attn"])
        attn[left_out] = jnp.zeros_like(attn[left_out])
        changed = dict(p, layer_0=dict(layer, attn=attn))
    assert relative(
        model_of().apply({"params": changed}, tokens),
        plain.forward(CONFIG, params, tokens),
    ) > 1e-2


@pytest.mark.parametrize("heads", [1, 3, 8])
def test_the_loss_is_the_mean_of_hand_shifted_cross_entropies(heads):
    """Head ``i`` at position ``t`` is held to byte ``t + 1 + i``, over the
    ``T - i`` positions where that byte is inside the sequence; the heads'
    means are averaged unweighted."""
    steps, vocab = 24, 11
    logits = jax.random.normal(jax.random.key(0), (2, steps, heads, vocab))
    tokens = jax.random.randint(jax.random.key(1), (2, steps + 1), 0, vocab)
    targets = tokens[:, 1:]  # as the benchmark's batches: inputs shifted by 1
    by_hand = []
    for i in range(heads):
        log_p = jax.nn.log_softmax(logits[:, :steps - i, i], -1)
        nll = [
            -float(log_p[b, t, int(tokens[b, t + 1 + i])])
            for b in range(2) for t in range(steps - i)
        ]
        assert len(nll) == 2 * (steps - i)
        by_hand.append(np.mean(nll))
    want = float(np.mean(by_hand))
    assert float(builder.multi_head_loss(logits, targets)) == pytest.approx(
        want, rel=1e-5
    )
    assert float(plain.multi_head_loss(logits, targets)) == pytest.approx(
        want, rel=1e-5
    )
    if heads == 1:
        assert float(builder.multi_head_loss(logits, targets)) == pytest.approx(
            float(softmax_cross_entropy(logits[:, :, 0], targets).mean()),
            rel=1e-6,
        )


def test_a_target_past_the_end_weighs_nothing():
    logits = jax.random.normal(jax.random.key(2), (1, 8, 3, 5))
    targets = jnp.zeros((1, 8), jnp.int32)
    grad = jax.grad(lambda z: builder.multi_head_loss(z, targets))(logits)
    # Head 1 has no target at the last position, head 2 none at the last two.
    assert bool((grad[0, 7, 1] == 0).all()) and bool((grad[0, 6:, 2] == 0).all())
    assert bool((grad[0, 7, 0] != 0).any()) and bool((grad[0, 5, 2] != 0).any())


def strided_rope(x, positions, theta, scaling=None):
    """``rope`` as it was written until PR 43, on ``x [..., T, H, D]``: the
    pairs cut apart by strided slices, turned, and stacked back."""
    freqs = rope_frequencies(x.shape[-1], theta, scaling)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    if scaling is not None:
        cos, sin = (z * scaling.embedding_scale for z in (cos, sin))
    x1, x2 = x[..., ::2], x[..., 1::2]
    out1, out2 = x1 * cos - x2 * sin, x1 * sin + x2 * cos
    return jnp.stack([out1, out2], axis=-1).reshape(x.shape).astype(x.dtype)


@pytest.mark.parametrize("scaling", [
    None, YarnScaling(32.0, 4096, mscale=1.0, mscale_all_dim=0.5),
], ids=["plain", "yarn"])
@pytest.mark.parametrize("head_size", [128, 64])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=str)
@pytest.mark.parametrize("heads_axis", [-2, -3], ids=["BTHD", "BHTD"])
def test_rope_is_the_strided_rope_in_either_layout(
    heads_axis, dtype, head_size, scaling
):
    """Values to the bit and gradients to rounding, positions before heads
    (``Attention``, ``LatentAttention``) and heads before positions
    (``EvaAttention``)."""
    assert scaling is None or scaling.embedding_scale != 1
    x = jax.random.normal(jax.random.key(0), (2, 40, 4, head_size)).astype(dtype)
    positions = 3 + jnp.arange(40)
    new = lambda z: jnp.moveaxis(
        rope(
            jnp.moveaxis(z, -2, heads_axis), positions, 1e5, scaling,
            heads_axis=heads_axis,
        ),
        heads_axis, -2,
    )
    old = lambda z: strided_rope(z, positions, 1e5, scaling)
    assert new(x).dtype == dtype
    np.testing.assert_array_equal(
        new(x).astype(jnp.float32), old(x).astype(jnp.float32)
    )
    weights = jnp.arange(float(head_size))
    grad = lambda fn: jax.grad(
        lambda z: (fn(z).astype(jnp.float32) ** 2 * weights).sum()
    )(x).astype(jnp.float32)
    np.testing.assert_allclose(grad(new), grad(old), rtol=1e-6, atol=1e-6)


def primitive_names(jaxpr):
    """The name of every equation's primitive, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from primitive_names(inner)


@pytest.mark.parametrize("latent", [False, True], ids=["gqa", "latent"])
def test_no_gather_and_no_scatter_is_left_in_attention(latent):
    """The strided rope and its gradient traced to 4 ``gather`` and 4
    ``scatter-add`` in either module (until PR 43) and nothing else in them
    traces to one: the partner matmul engages in every caller or this is
    red."""
    cfg = LlamaConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=48, lora_rank=4, rope_theta=1e4, attn_impl="dense",
    )
    module = Attention(cfg)
    if latent:
        module = LatentAttention(dataclasses.replace(
            cfg, q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8,
            rope_scaling=YarnScaling(32.0, 4096),
        ))
    x, positions = jnp.ones((2, 16, 32)), jnp.arange(16)
    params = module.init(jax.random.key(0), x, positions)
    loss = lambda p, z: (module.apply(p, z, positions) ** 2).sum()
    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
    found = set(primitive_names(traced.jaxpr))
    assert "dot_general" in found
    assert not {p for p in found if "gather" in p or "scatter" in p}


def test_the_norm_with_a_unit_offset_is_born_an_identity_scale():
    x = jax.random.normal(jax.random.key(0), (3, 16))
    norm = RMSNorm(1e-5, jnp.float32, jnp.float32, True)
    params = norm.init(jax.random.key(1), x)
    assert bool((params["params"]["scale"] == 0).all())
    rms = jnp.sqrt(jnp.mean(x ** 2, -1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(norm.apply(params, x), x / rms, rtol=1e-6)
    doubled = {"params": dict(scale=jnp.ones(16))}
    np.testing.assert_allclose(norm.apply(doubled, x), 2 * x / rms, rtol=1e-6)
    # Without it, as every accepted decoder: born one, multiplied as it is.
    plain_norm = RMSNorm(1e-5, jnp.float32, jnp.float32)
    born = plain_norm.init(jax.random.key(1), x)
    assert bool((born["params"]["scale"] == 1).all())


def test_the_residual_stream_is_float32_and_the_rest_bfloat16():
    """``fp32_skip_add`` with ``fp32_ln`` false: a block takes and gives a
    float32 stream while its norms hand on bfloat16."""
    from dpwa_tpu.models.llama import Block

    cfg = dataclasses.replace(model_of().cfg, dtype=jnp.bfloat16)
    assert cfg.stream_dtype == jnp.float32 and cfg.norm_dtype == jnp.bfloat16
    x = jnp.zeros((1, T, cfg.d_model), jnp.float32)
    block = Block(cfg, 0)
    shapes = jax.eval_shape(block.init, jax.random.key(0), x, jnp.arange(T))
    out = jax.eval_shape(block.apply, shapes, x, jnp.arange(T))
    assert out.dtype == jnp.float32
    jaxpr = str(jax.make_jaxpr(block.apply)(shapes, x, jnp.arange(T)))
    assert "bf16[1,96,64]" in jaxpr and "bf16[1,96,128]" in jaxpr
    wide = dataclasses.replace(cfg, fp32_skip_add=False)
    assert wide.stream_dtype == jnp.bfloat16


def test_the_attention_layer_has_its_leaves_and_only_adapters_train(seeded):
    params, tokens, targets = seeded
    layer = params["params"]["layer_0"]
    assert set(layer["attn"]) == {
        "wq", "wk", "wv", "wo", "adaptive_phi", "adaptive_mu_k",
    }
    heads, d = CONFIG["num_attention_heads"], 64 // CONFIG["num_attention_heads"]
    assert layer["attn"]["adaptive_phi"].shape == (heads, d)
    assert params["params"]["lm_head"]["kernel"].shape == (64, 320 * HEADS)
    model = model_of()
    optimizer = lora_optimizer(optax.adam(1e-2), params)
    opt_state = optimizer.init(params)

    @jax.jit
    def step(p, s):
        grads = jax.grad(lambda q: builder.multi_head_loss(
            model.apply(q, tokens), targets
        ))(p)
        updates, s = optimizer.update(grads, s, p)
        return optax.apply_updates(p, updates), s

    new = params
    for _ in range(2):
        new, opt_state = step(new, opt_state)
    before, after = paths(params), paths(new)
    frozen = [k for k in before if not lora_filter(k)]
    assert any("adaptive_phi" in k for k in frozen)
    assert any("adaptive_mu_k" in k for k in frozen)
    for name in before:
        same = bool((before[name] == after[name]).all())
        assert same != lora_filter(name), name


def test_the_initial_summaries_parameters_are_clipped_normals_times_s():
    cfg = dataclasses.replace(model_of().cfg, n_heads=8, d_model=8 * 128)
    leaves = EvaAttention(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8, cfg.d_model)), jnp.arange(8)
    )["params"]
    for name in ("adaptive_phi", "adaptive_mu_k"):
        leaf = np.asarray(leaves[name])
        assert leaf.shape == (8, 128)
        assert np.abs(leaf).max() <= 128 ** -0.5
        # A standard normal clipped to +-1 has a third of its mass there.
        assert 0.2 < np.mean(np.abs(leaf) == np.float32(128 ** -0.5)) < 0.45


def test_base_leaves_are_born_in_param_dtype():
    params = jax.eval_shape(
        model_of(param_dtype=jnp.bfloat16).init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32),
    )
    for name, leaf in paths(params).items():
        wide = lora_filter(name)
        assert leaf.dtype == (jnp.float32 if wide else jnp.bfloat16), name


@pytest.mark.parametrize("changes, message", [
    (dict(sp_axis="sp"), "sequence-parallel"),
    (dict(eva_chunk=5), "does not divide"),
    (dict(eva_chunk=0), "does not divide"),
    (dict(kv_lora_rank=8), "latent"),
    (dict(tie_embeddings=True), "lm_head of its own"),
    (dict(n_pred_heads=0), "at least 1"),
])
def test_what_is_not_built_is_refused(changes, message):
    with pytest.raises(ValueError, match=message):
        model_of(**changes)


def test_a_configuration_file_the_program_does_not_compute_is_refused():
    for key, value in (("fp32_logits", False), ("attention_class", "mha"),
                       ("norm_add_unit_offset", False), ("fp32_ln", True)):
        with pytest.raises(ValueError, match=key):
            builder.model_of(dict(CONFIG, **{key: value}), T)
    with pytest.raises(ValueError, match="k / v heads"):
        builder.model_of(dict(CONFIG, num_key_value_heads=2), T)
    with pytest.raises(ValueError, match="fp32_skip_add"):
        builder.model_of(dict(CONFIG, fp32_skip_add=False), T)


def test_the_defaults_are_todays_behaviour():
    cfg = LlamaConfig()
    assert (cfg.eva_window, cfg.eva_chunk, cfg.n_pred_heads) == (0, 0, 1)
    assert not cfg.norm_unit_offset and not cfg.fp32_skip_add
    assert cfg.stream_dtype == cfg.norm_dtype == cfg.dtype
    with pytest.raises(ValueError, match="activation_dtype"):
        LlamaConfig(activation_dtype=jnp.float32)


def test_two_stacked_peers_match_the_references_local_update():
    """The stacked step (``vmap`` over peers; off the TPU the core's plain
    twin under it) against ``benchmark/reference.py``'s loop over peers, as
    ``run.py`` checks it, and the model check over the toy's three windows."""
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
    # What the chip's model check reads: one sequence's first three windows.
    inputs = built.reference_inputs(batch[0])
    assert inputs.shape == (1, 3 * WINDOW)
    error, size = reference.make_model_check(
        built.apply_fn, built.reference_forward, built.reference_inputs,
    )(state.params, batch)
    assert float(error) < 1e-4 * float(size)


def test_the_kernels_under_the_model_are_the_model(seeded):
    """The model with the core's kernels (the Pallas interpreter; head size
    16 is no TPU shape, so the interpreter alone runs them) gives the plain
    twin's logits: the layout the model hands over is the one they read."""
    from unittest import mock

    from dpwa_tpu.ops import eva

    params, tokens, _ = seeded
    model = model_of()
    want = model.apply(params, tokens)
    with mock.patch.object(
        eva, "plain_eva_attention", eva.interpreted_eva_attention
    ):
        got = model.apply(params, tokens)
    assert relative(got, want) < 1e-5


# ---- what the accepted decoders computed before, they compute now

# As ``tests/test_hybrid_ssm.py`` holds the three decoder families before it:
# the first 16 hex digits of the SHA-256 of the lowered StableHLO, the
# state-space family's taken on the parent commit (c30f398) by the same lines
# and again at PR 56, whose ``MambaMixer`` hands ``in_proj``'s whole product
# to ``ops/ssm.conv_silu`` and slices ``z`` after it (off the TPU the same
# slices, taps and silu in another order; ``tests/test_ssm.py`` holds the
# call to ``silu(causal_conv1d(...))``, to the bit there).
PROGRAMS_AT_PARENT = dict(
    {
        name: digest for name, digest in PROGRAMS_BEFORE.items()
        if name.removesuffix(".loss_grad") in (
            "mistral-7b-v0.3-lora", "olmoe-1b-7b-0125-lora", "axk1-lora",
        )
    },
    **{
        "jamba2-3b-lora": "d0b1c80c1748b8ef",
        "jamba2-3b-lora.loss_grad": "ed42321f13cb8f59",
    },
)


@pytest.mark.parametrize("name", [
    "mistral-7b-v0.3-lora", "olmoe-1b-7b-0125-lora", "axk1-lora",
    "jamba2-3b-lora",
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
    ) == PROGRAMS_AT_PARENT[name]
    assert program_digest(
        jax.grad(built.loss_fn), shapes, (tokens, tokens)
    ) == PROGRAMS_AT_PARENT[name + ".loss_grad"]
