"""The gated short convolution (``models/llama.ShortConv``) and the list of
kinds a layer (``LlamaConfig.layer_mixers``): the mixer against the plain
reference (``benchmark/references/conv_moe_decoder.short_conv``) on seeded
weights, what makes it causal, its taps, its leaves, ``vmap`` over peers
against a loop (it needs no rule of its own), and what ``LlamaConfig``
refuses.

Tolerance: float32 against float32 differs by the order of summation alone;
1e-5 of rms holds it (seen: 1e-7) and fails a tap left out or a gate's
operands swapped (tenths)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import conv_moe_decoder as plain  # noqa: E402
from dpwa_tpu.models.llama import (  # noqa: E402
    MIXERS, Block, Llama, LlamaConfig, ShortConv, lora_filter,
    lora_optimizer,
)
from dpwa_tpu.utils import scopes  # noqa: E402
from tests.test_hybrid_ssm import paths, perturbed, relative  # noqa: E402

D, T, RANK, ALPHA = 48, 24, 4, 8.0
PUBLISHED = (
    "conv conv full_attention conv conv conv full_attention conv conv conv "
    "full_attention conv conv conv full_attention conv conv conv "
    "full_attention conv conv full_attention conv conv"
).split()


def cfg_of(**changes) -> LlamaConfig:
    return LlamaConfig(**dict(dict(
        vocab_size=64, d_model=D, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, lora_rank=RANK, lora_alpha=ALPHA,
        layer_mixers=("conv", "attention"),
    ), **changes))


@pytest.fixture(scope="module")
def seeded():
    x = jax.random.normal(jax.random.key(0), (2, T, D))
    mixer = ShortConv(cfg_of())
    return mixer, perturbed(mixer.init(jax.random.key(1), x)), x


def test_the_mixer_is_the_reference(seeded):
    mixer, params, x = seeded
    want = plain.short_conv(params["params"], x, ALPHA / RANK)
    assert relative(mixer.apply(params, x), want) < 1e-5
    got = jax.grad(lambda p: mixer.apply(p, x).sum())(params)
    want = jax.grad(
        lambda p: plain.short_conv(p["params"], x, ALPHA / RANK).sum()
    )(params)
    for name, grad in paths(got).items():
        assert relative(grad, paths(want)[name]) < 1e-5, name
        assert float(jnp.abs(grad).max()) > 0, name


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_the_taps_are_the_configurations(taps):
    mixer = ShortConv(cfg_of(conv_taps=taps))
    x = jax.random.normal(jax.random.key(2), (1, T, D))
    params = perturbed(mixer.init(jax.random.key(3), x))
    assert params["params"]["conv_kernel"].shape == (taps, D)
    want = plain.short_conv(params["params"], x, ALPHA / RANK)
    assert relative(mixer.apply(params, x), want) < 1e-5


def test_an_input_moves_nothing_before_it(seeded):
    mixer, params, x = seeded
    moved = mixer.apply(params, x.at[:, 9].add(1.0))
    base = mixer.apply(params, x)
    np.testing.assert_array_equal(base[:, :9], moved[:, :9])
    # Three taps reach two positions back: 9, 10 and 11 move, 12 does not.
    for t in (9, 10, 11):
        assert float(jnp.abs(moved[:, t] - base[:, t]).max()) > 1e-4, t
    np.testing.assert_allclose(moved[:, 12:], base[:, 12:], atol=1e-6)


def test_the_first_two_positions_see_zeros_before_the_sequence(seeded):
    """Position 0 is ``c_0 * w[2] * (b u)_0`` alone and position 1 adds
    ``w[1] * (b u)_0``: nothing wraps round from the end, and no bias."""
    mixer, params, x = seeded
    p = params["params"]
    dense = lambda v, m: v @ m["kernel"] + (ALPHA / RANK) * (
        v @ m["lora_a"] @ m["lora_b"]
    )
    b, c, u = jnp.split(dense(x, p["in_proj"]), 3, -1)
    w, s = p["conv_kernel"], b * u
    z0, z1 = w[2] * s[:, 0], w[2] * s[:, 1] + w[1] * s[:, 0]
    want = dense(jnp.stack([c[:, 0] * z0, c[:, 1] * z1], 1), p["out_proj"])
    assert relative(mixer.apply(params, x)[:, :2], want) < 1e-5
    # The end of the sequence changed: the first positions do not move.
    moved = mixer.apply(params, x.at[:, -1].add(1.0))
    np.testing.assert_array_equal(moved[:, :2], mixer.apply(params, x)[:, :2])


def test_its_leaves_and_what_trains(seeded):
    _, params, _ = seeded
    names = paths(params)
    assert {k.split("'")[3] for k in names} == {
        "in_proj", "out_proj", "conv_kernel",
    }
    assert names["['params']['in_proj']['kernel']"].shape == (D, 3 * D)
    assert names["['params']['out_proj']['kernel']"].shape == (D, D)
    trained = sorted(k for k in names if lora_filter(k))
    assert len(trained) == 4 and all("_proj" in k for k in trained)
    assert not any("bias" in k for k in names)


def test_vmap_over_peers_is_a_loop_over_peers(seeded):
    """Elementwise and dense work only: the stacked step's ``vmap`` needs no
    rule of the mixer's own, and finds none to call."""
    mixer, params, x = seeded
    stacked = jax.tree.map(
        lambda v: jnp.stack([v, perturbed({"v": v}, 7)["v"]]), params
    )
    xs = jnp.stack([x, x[::-1]])
    got = jax.vmap(mixer.apply)(stacked, xs)
    for peer in range(2):
        alone = mixer.apply(jax.tree.map(lambda v: v[peer], stacked), xs[peer])
        np.testing.assert_allclose(got[peer], alone, rtol=1e-5, atol=1e-6)
    jaxpr = str(jax.make_jaxpr(jax.vmap(mixer.apply))(stacked, xs))
    assert "custom_vmap" not in jaxpr and "pallas" not in jaxpr


def test_the_scopes_name_the_mixer_and_its_gate(seeded):
    mixer, params, x = seeded
    text = jax.jit(mixer.apply).lower(params, x).as_text(debug_info=True)
    assert scopes.CONV == ("dpwa.conv", "dpwa.conv.gate")
    assert '"jit(apply)/dpwa.conv/in_proj/' in text.replace("ShortConv/", "")
    gate = [line for line in text.splitlines() if "dpwa.conv.gate" in line]
    assert gate and all("dpwa.conv/" in line for line in gate)
    # The projections lie under the mixer's name and outside the gate's.
    assert not any("dot_general" in line for line in gate)


def test_the_published_list_picks_every_layers_mixer():
    kinds = tuple(
        {"conv": "conv", "full_attention": "attention"}[k] for k in PUBLISHED
    )
    assert len(kinds) == 24 and kinds.count("attention") == 6
    cfg = cfg_of(n_layers=24, layer_mixers=kinds)
    assert [cfg.mixer_of(i) for i in range(24)] == list(kinds)
    attention = [i for i in range(24) if cfg.is_attention_layer(i)]
    assert attention == [2, 6, 10, 14, 18, 21]  # no period says the last
    shapes = jax.eval_shape(
        Llama(cfg).init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    for i, kind in enumerate(kinds):
        mixer = {"conv": "conv", "attention": "attn"}[kind]
        assert mixer in shapes[f"layer_{i}"], i
        assert f"{mixer}_norm" in shapes[f"layer_{i}"], i
        assert len(shapes[f"layer_{i}"]) == 4  # mixer, its norm, mlp, its norm


def test_period_and_offset_pick_as_before():
    cfg = LlamaConfig(
        n_layers=8, attn_layer_period=4, attn_layer_offset=1, mamba_dt_rank=4,
    )
    assert [cfg.mixer_of(i) for i in range(8)] == [
        "mamba", "attention", "mamba", "mamba",
    ] * 2
    plain_cfg = LlamaConfig(n_layers=3)
    assert plain_cfg.layer_mixers is None and plain_cfg.conv_taps == 3
    assert all(plain_cfg.is_attention_layer(i) for i in range(3))
    # The three mixers, then the two kinds of plain attention (PR 49).
    assert MIXERS == (
        "attention", "conv", "mamba", "sliding_attention", "full_attention"
    )


def test_a_mamba_layer_can_be_named_in_the_list():
    cfg = cfg_of(
        n_layers=3, layer_mixers=("mamba", "conv", "attention"),
        mamba_dt_rank=4, mamba_d_state=4,
    )
    shapes = jax.eval_shape(
        Llama(cfg).init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    assert [
        next(k for k in ("mamba", "conv", "attn") if k in shapes[f"layer_{i}"])
        for i in range(3)
    ] == ["mamba", "conv", "attn"]


@pytest.mark.parametrize("changes, message", [
    (dict(sp_axis="sp"), "sequence-parallel"),
    (dict(layer_mixers=("conv",)), "each of the 2 layers"),
    (dict(layer_mixers=("conv", "window")), "names one of"),
    (dict(attn_layer_period=2, mamba_dt_rank=4), "give one"),
    (dict(conv_taps=0), "at least one tap"),
    (dict(layer_mixers=("mamba", "attention")), "four sizes"),
    (dict(qk_norm=True, qk_norm_per_head=True), "two forms of one norm"),
    (dict(router_bias=True, n_experts=4, n_experts_per_tok=2,
          experts_held=2), "no router bias"),
])
def test_what_cannot_run_is_refused(changes, message):
    with pytest.raises(ValueError, match=message):
        cfg_of(**changes)


def test_the_optimizer_freezes_the_taps_and_the_norms():
    import optax

    cfg = cfg_of()
    model = Llama(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = perturbed(model.init(jax.random.key(0), tokens))
    optimizer = lora_optimizer(optax.sgd(0.1), params)
    grads = jax.tree.map(jnp.ones_like, params)
    updates, _ = optimizer.update(grads, optimizer.init(params), params)
    for name, update in paths(updates).items():
        moved = float(jnp.abs(update).max()) > 0
        assert moved == lora_filter(name), name
    frozen = [k for k in paths(params) if not lora_filter(k)]
    assert any("conv_kernel" in k for k in frozen)
    assert any("conv_norm" in k for k in frozen)


def test_a_conv_block_keeps_nothing_under_remat():
    from dpwa_tpu.models.llama import _checkpoint_policy

    cfg = cfg_of(remat=True)
    assert _checkpoint_policy(cfg, 0) is None
    assert _checkpoint_policy(cfg, 1) is None
    x = jax.random.normal(jax.random.key(0), (1, T, D))
    block = Block(cfg, 0)
    params = block.init(jax.random.key(1), x, jnp.arange(T))
    assert set(params["params"]) == {"conv", "conv_norm", "mlp", "mlp_norm"}
