"""``ops/cross_entropy.softmax_cross_entropy`` against the optax function it
replaces: same values, same gradients, and a lowered gradient that neither
gathers nor scatters over the vocabulary axis (the point of it: XLA:TPU
serves that scatter with two relayouts of the whole float32 logits)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dpwa_tpu.models.llama import Llama, LlamaConfig, moe_loss, routing_of
from dpwa_tpu.ops import moe
from dpwa_tpu.ops.cross_entropy import softmax_cross_entropy
from dpwa_tpu.utils import scopes

OPTAX = optax.softmax_cross_entropy_with_integer_labels


def seeded(vocab, dtype=jnp.float32, rows=(3, 5), scale=3.0):
    logits = scale * jax.random.normal(jax.random.key(vocab), (*rows, vocab))
    targets = jax.random.randint(jax.random.key(vocab + 1), rows, 0, vocab)
    return logits.astype(dtype), targets


def weighted_sum_and_grad(fn, logits, targets):
    # Weighted, so that the cotangent differs from position to position.
    weights = 1.0 + jnp.arange(targets.size, dtype=jnp.float32).reshape(
        targets.shape
    )
    return jax.value_and_grad(
        lambda x: (fn(x, targets) * weights).sum()
    )(logits)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("vocab", [7, 1000, 32768, 50304])
def test_values_and_gradients_equal_optax(vocab, dtype):
    logits, targets = seeded(vocab, dtype)
    # bfloat16 logits are promoted: the comparison is with optax on the same
    # values held in float32.
    promoted = logits.astype(jnp.float32)
    got = softmax_cross_entropy(logits, targets)
    assert got.dtype == jnp.float32 and got.shape == targets.shape
    np.testing.assert_allclose(got, OPTAX(promoted, targets), rtol=2e-6)
    value, grad = weighted_sum_and_grad(softmax_cross_entropy, logits, targets)
    want_value, want_grad = weighted_sum_and_grad(OPTAX, promoted, targets)
    assert grad.dtype == dtype
    np.testing.assert_allclose(value, want_value, rtol=2e-6)
    np.testing.assert_allclose(
        grad.astype(jnp.float32), want_grad.astype(dtype).astype(jnp.float32),
        rtol=2e-5 if dtype == jnp.float32 else 2.0 ** -7, atol=1e-7,
    )


def test_vmap_over_two_peers_equals_a_loop():
    logits, targets = seeded(1000, rows=(2, 4, 6))
    fn = jax.value_and_grad(lambda x, y: softmax_cross_entropy(x, y).mean())
    values, grads = jax.vmap(fn)(logits, targets)
    for peer in range(2):
        value, grad = fn(logits[peer], targets[peer])
        np.testing.assert_allclose(values[peer], value, rtol=1e-6)
        np.testing.assert_allclose(grads[peer], grad, rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("column", [0, -1])
def test_target_at_the_edge_of_the_vocabulary(column):
    vocab = 50304
    logits, _ = seeded(vocab, rows=(4,))
    targets = jnp.full((4,), column % vocab, jnp.int32)
    value, grad = weighted_sum_and_grad(softmax_cross_entropy, logits, targets)
    want_value, want_grad = weighted_sum_and_grad(OPTAX, logits, targets)
    np.testing.assert_allclose(value, want_value, rtol=2e-6)
    np.testing.assert_allclose(grad, want_grad, rtol=2e-5, atol=1e-7)
    # softmax - onehot: the label's column alone is negative.
    assert bool((grad[:, column] < 0).all())
    assert int((grad < 0).sum()) == 4


def test_large_logits_stay_finite():
    logits = jnp.array([[1e4, -1e4, 0.0, 1e4], [-1e4, -1e4, -1e4, -1e4]])
    targets = jnp.array([1, 2])
    losses = softmax_cross_entropy(logits, targets)
    # One float32 step at 1e4 is 1e-3.
    np.testing.assert_allclose(
        losses, [2e4 + np.log(2.0), np.log(4.0)], atol=2e-3
    )
    grad = jax.grad(lambda x: softmax_cross_entropy(x, targets).sum())(logits)
    assert bool(jnp.isfinite(grad).all())
    np.testing.assert_allclose(
        grad, [[0.5, -1.0, 0.0, 0.5], [0.25, 0.25, -0.75, 0.25]], atol=1e-6
    )


def lowered_gradient(fn):
    logits, targets = seeded(50304, rows=(2, 8))
    return jax.jit(jax.value_and_grad(
        lambda x, y: fn(x, y).mean()
    )).lower(logits, targets).as_text()


def test_lowered_gradient_holds_no_scatter_and_no_gather():
    text = lowered_gradient(softmax_cross_entropy)
    assert "scatter" not in text and "gather" not in text


def test_the_optax_gradient_still_scatters():
    """The premise: if this fails, optax no longer scatters and
    ``ops/cross_entropy.py`` may have nothing left to cure."""
    assert "scatter" in lowered_gradient(OPTAX)


@pytest.fixture(scope="module")
def toy_moe():
    model = Llama(LlamaConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=64, max_seq_len=32, lora_rank=4, n_experts=8,
        n_experts_per_tok=2, qk_norm=True, router_aux_loss_coef=0.01,
    ))
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 128)
    params = model.init(jax.random.key(1), tokens)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    params = treedef.unflatten([
        v + 0.05 * jax.random.normal(k, v.shape, v.dtype)
        for v, k in zip(leaves, keys)
    ])
    return model, params, tokens, jnp.roll(tokens, -1, axis=1)


def moe_loss_with_optax(model, params, tokens, targets):
    """``moe_loss`` as it was before ``ops/cross_entropy.py``."""
    logits, sown = model.apply(params, tokens, mutable=["intermediates"])
    routing = routing_of(sown)
    return OPTAX(logits, targets).mean() + (
        model.cfg.router_aux_loss_coef
        * moe.load_balancing_loss(routing["counts"], routing["prob_mean"])
    )


@pytest.fixture(scope="module")
def moe_pair(toy_moe):
    model, params, tokens, targets = toy_moe
    return tuple(
        jax.value_and_grad(lambda p: fn(model, p, tokens, targets))(params)
        for fn in (moe_loss, moe_loss_with_optax)
    )


def test_moe_loss_value_is_what_it_was(moe_pair):
    (got, _), (want, _) = moe_pair
    assert float(got) == pytest.approx(float(want), rel=1e-6)


def test_moe_loss_gradients_are_what_they_were(moe_pair):
    (_, got), (_, want) = moe_pair
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    assert len(flat) == len(jax.tree.leaves(want)) > 0
    for (path, grad), old in zip(flat, jax.tree.leaves(want)):
        scale = float(jnp.abs(old).max())
        np.testing.assert_allclose(
            grad, old, rtol=1e-4, atol=1e-5 * scale + 1e-9,
            err_msg=jax.tree_util.keystr(path),
        )
    assert any(float(jnp.abs(g).max()) > 0 for _, g in flat)


def test_moe_loss_names_the_loss_forward_and_backward(toy_moe):
    model, params, tokens, targets = toy_moe
    loss_fn = scopes.scoped_loss(lambda p: moe_loss(model, p, tokens, targets))
    text = jax.jit(jax.grad(loss_fn)).lower(params).as_text(
        dialect="hlo", debug_info=True
    )
    named = [line for line in text.splitlines() if scopes.LOSS in line]
    assert any("transpose(jvp(dpwa.forward))/dpwa.loss" in n for n in named)
    assert any("/jvp(dpwa.forward)/dpwa.loss" in n for n in named)
    # The head's matmul is not the loss's.
    assert not any("lm_head" in n for n in named)
