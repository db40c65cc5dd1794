"""Overlapped-exchange mode: ``x_{k+1} = merge(x_k) + update_k``.

``overlap=True`` removes the optimizer→collective serial dependency so the
exchange DMA runs concurrently with fwd/bwd (the TPU-native form of the
reference's stale-publish semantics: a free-running peer pulls whatever its
partner last *published*, SURVEY.md §3.2/§3.3).  These tests pin down the
exact semantics, the ICI↔stacked parity, mean preservation, the LoRA
subset interaction, and convergence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dpwa_tpu.config import make_local_config
from dpwa_tpu.parallel.ici import IciTransport
from dpwa_tpu.parallel.mesh import make_mesh, peer_sharding
from dpwa_tpu.parallel.stacked import (
    StackedTransport,
    init_stacked_state,
    make_stacked_train_step,
)
from dpwa_tpu.train import (
    init_gossip_state,
    make_gossip_train_step,
    stack_params,
)

N = 8
# init_stacked_state takes ownership of (donates) what it is given.
_own = lambda tree: jax.tree.map(jnp.copy, tree)


def quad_loss(params, batch):
    x, y = batch
    pred = x @ params["w"]
    return jnp.mean((pred - y) ** 2)


def make_setup(seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((N, 4, 2)), jnp.float32)
    bx = jnp.asarray(rng.standard_normal((N, 16, 4)), jnp.float32)
    by = jnp.asarray(rng.standard_normal((N, 16, 2)), jnp.float32)
    return {"w": w}, (bx, by)


def test_overlap_semantics_exact_stacked():
    """One step must produce exactly merge(x_k) + update_k."""
    stacked, batch = make_setup()
    cfg = make_local_config(N, schedule="ring")
    transport = StackedTransport(cfg)
    opt = optax.sgd(0.1)
    state = init_stacked_state(_own(stacked), opt, transport)
    step = make_stacked_train_step(quad_loss, opt, transport, overlap=True)
    new_state, losses, info = step(state, batch)

    # Hand-computed expectation.
    partner = np.asarray(info.partner)
    grads = jax.vmap(jax.grad(quad_loss))(stacked, batch)
    update = -0.1 * np.asarray(grads["w"])
    x = np.asarray(stacked["w"])
    merged = 0.5 * x + 0.5 * x[partner]  # ring slot 0, alpha 0.5, all merge
    expect = merged + update
    np.testing.assert_allclose(
        np.asarray(new_state.params["w"]), expect, rtol=1e-6
    )


def test_overlap_ici_stacked_parity():
    stacked, batch = make_setup(seed=3)
    cfg = make_local_config(
        N, schedule="random", fetch_probability=0.7, pool_size=8
    )
    opt = optax.sgd(0.05, momentum=0.9)

    st = StackedTransport(cfg)
    s_state = init_stacked_state(_own(stacked), opt, st)
    s_step = make_stacked_train_step(quad_loss, opt, st, overlap=True)

    it = IciTransport(cfg, mesh=make_mesh(cfg))
    i_state = init_gossip_state(stacked, opt, it)
    i_step = make_gossip_train_step(quad_loss, opt, it, overlap=True)
    sh = peer_sharding(it.mesh)
    i_batch = tuple(jax.device_put(b, sh) for b in batch)

    for _ in range(5):
        s_state, s_losses, s_info = s_step(s_state, batch)
        i_state, i_losses, i_info = i_step(i_state, i_batch)
    np.testing.assert_array_equal(
        np.asarray(s_info.partner), np.asarray(i_info.partner)
    )
    np.testing.assert_array_equal(
        np.asarray(s_info.participated), np.asarray(i_info.participated)
    )
    np.testing.assert_allclose(
        np.asarray(s_state.params["w"]),
        np.asarray(i_state.params["w"]),
        rtol=1e-5,
        atol=1e-6,
    )


def test_overlap_preserves_mean_plus_updates():
    """Doubly-stochastic merges keep the peer mean; overlap adds exactly
    the mean update on top."""
    stacked, batch = make_setup(seed=5)
    cfg = make_local_config(N, schedule="ring")
    transport = StackedTransport(cfg)
    opt = optax.sgd(0.1)
    state = init_stacked_state(_own(stacked), opt, transport)
    step = make_stacked_train_step(quad_loss, opt, transport, overlap=True)
    new_state, _, _ = step(state, batch)
    grads = jax.vmap(jax.grad(quad_loss))(stacked, batch)
    want = np.asarray(stacked["w"]).mean(0) - 0.1 * np.asarray(
        grads["w"]
    ).mean(0)
    np.testing.assert_allclose(
        np.asarray(new_state.params["w"]).mean(0), want, rtol=1e-5
    )


def test_overlap_lora_subset_base_frozen():
    """Subset-filter + overlap: non-exchanged leaves still get their local
    update; exchanged leaves get merge(x_k) + update."""
    rng = np.random.default_rng(0)
    stacked = {
        "base": jnp.asarray(rng.standard_normal((N, 3, 3)), jnp.float32),
        "lora_a": jnp.asarray(rng.standard_normal((N, 3, 2)), jnp.float32),
    }
    bx = jnp.asarray(rng.standard_normal((N, 8, 3)), jnp.float32)
    by = jnp.asarray(rng.standard_normal((N, 8, 2)), jnp.float32)

    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["base"] @ params["lora_a"]
        return jnp.mean((pred - y) ** 2)

    cfg = make_local_config(N, schedule="ring")
    transport = StackedTransport(cfg)
    opt = optax.sgd(0.1)
    state = init_stacked_state(_own(stacked), opt, transport)
    step = make_stacked_train_step(
        loss_fn, opt, transport,
        exchange_filter=lambda path: "lora" in path,
        overlap=True,
    )
    new_state, _, info = step(state, (bx, by))

    partner = np.asarray(info.partner)
    grads = jax.vmap(jax.grad(loss_fn))(stacked, (bx, by))
    # base: plain local SGD, never exchanged.
    np.testing.assert_allclose(
        np.asarray(new_state.params["base"]),
        np.asarray(stacked["base"]) - 0.1 * np.asarray(grads["base"]),
        rtol=1e-6,
    )
    # lora: merge of pre-update values + local update.
    a = np.asarray(stacked["lora_a"])
    expect = 0.5 * a + 0.5 * a[partner] - 0.1 * np.asarray(grads["lora_a"])
    np.testing.assert_allclose(
        np.asarray(new_state.params["lora_a"]), expect, rtol=1e-6
    )


def test_overlap_ships_previous_loss_as_metadata():
    """Loss-weighted interpolation under overlap must see the PREVIOUS
    step's losses (the last published value, like the reference's Rx
    thread) — alpha = f(prev_loss), not this step's forward loss."""
    stacked, batch = make_setup(seed=9)
    cfg = make_local_config(
        N, schedule="ring", interpolation="loss", factor=1.0
    )
    transport = StackedTransport(cfg)
    opt = optax.sgd(0.1)
    state = init_stacked_state(stacked, opt, transport)
    pl = np.linspace(1.0, 3.0, N, dtype=np.float32)
    state = state._replace(loss=jnp.asarray(pl))  # donated by the step
    step = make_stacked_train_step(quad_loss, opt, transport, overlap=True)
    _, losses, info = step(state, batch)

    partner = np.asarray(info.partner)
    expect_alpha = pl / (pl + pl[partner])
    np.testing.assert_allclose(
        np.asarray(info.alpha), expect_alpha, rtol=1e-6
    )
    # And definitely NOT this step's losses.
    cl = np.asarray(losses)
    current_alpha = cl / (cl + cl[partner])
    assert not np.allclose(np.asarray(info.alpha), current_alpha)


def test_overlap_converges_digits():
    from dpwa_tpu.data import load_digits_dataset, peer_batches
    from dpwa_tpu.models.mnist import SmallNet
    from dpwa_tpu.train import make_gossip_eval_fn

    x_tr, y_tr, x_te, y_te = load_digits_dataset()
    model = SmallNet()
    params0 = model.init(jax.random.key(0), jnp.zeros((1, 8, 8, 1)))
    cfg = make_local_config(N, schedule="random", fetch_probability=0.5)
    transport = StackedTransport(cfg)
    opt = optax.sgd(0.05, momentum=0.9)
    state = init_stacked_state(stack_params(params0, N), opt, transport)

    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    step = make_stacked_train_step(loss_fn, opt, transport, overlap=True)
    batches = peer_batches(x_tr, y_tr, N, 32, seed=0)
    for _ in range(80):
        state, losses, _ = step(state, next(batches))
    eval_fn = make_gossip_eval_fn(model.apply)
    accs = np.asarray(eval_fn(state.params, x_te, y_te))
    assert accs.min() > 0.85, accs
