"""Mutable model state (BatchNorm running stats) under gossip.

The reference's stock torch models carry BN stats; here they gossip with
the parameters (same α) but never touch the optimizer."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dpwa_tpu.config import make_local_config
from dpwa_tpu.models.resnet import CifarResNet
from dpwa_tpu.parallel.ici import IciTransport
from dpwa_tpu.parallel.mesh import make_mesh
from dpwa_tpu.train import (
    init_gossip_state,
    make_gossip_train_step_with_state,
    stack_params,
)


def test_batchnorm_resnet_gossip_step():
    n = 4
    cfg = make_local_config(n, schedule="ring")
    transport = IciTransport(cfg, mesh=make_mesh(cfg, jax.devices()[:n]))
    model = CifarResNet(depth=8, norm_type="batch")
    variables = model.init(jax.random.key(0), jnp.zeros((2, 8, 8, 3)))
    params, batch_stats = variables["params"], variables["batch_stats"]
    stacked_params = stack_params(params, n)
    stacked_stats = stack_params(batch_stats, n)
    opt = optax.sgd(0.01)
    state = init_gossip_state(
        stacked_params, opt, transport, stacked_model_state=stacked_stats
    )

    def loss_fn(params, model_state, batch):
        x, y = batch
        logits, updated = model.apply(
            {"params": params, "batch_stats": model_state},
            x,
            mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()
        return loss, updated["batch_stats"]

    step_fn = make_gossip_train_step_with_state(loss_fn, opt, transport)
    rng = np.random.default_rng(0)
    # Give each peer a DIFFERENT input distribution so BN stats diverge and
    # the exchange visibly mixes them.
    shifts = np.arange(n)[:, None, None, None, None].astype(np.float32)
    batch = (
        jnp.asarray(rng.random((n, 4, 8, 8, 3), np.float32) + shifts),
        jnp.asarray(rng.integers(0, 10, (n, 4)).astype(np.int32)),
    )
    init_stats = jax.tree.map(np.asarray, stacked_stats)
    for step in range(3):
        state, losses, info = step_fn(state, batch)
    assert np.all(np.isfinite(np.asarray(losses)))
    final_stats = jax.tree.map(np.asarray, state.model_state)
    # Stats moved (training mode) ...
    moved = jax.tree.leaves(
        jax.tree.map(
            lambda a, b: not np.array_equal(a, b), init_stats, final_stats
        )
    )
    assert all(moved)
    # ...and were merged across pairs: step-0 ring pairs (0,1) and (2,3)
    # exchanged, so after the first exchange their stats moved toward each
    # other. Verify pairwise mixing by running a single step from scratch.
    state2 = init_gossip_state(
        stacked_params, opt, transport, stacked_model_state=stacked_stats
    )
    state2, _, _ = step_fn(state2, batch)
    mean_leaf = jax.tree.leaves(state2.model_state)[0]
    m = np.asarray(mean_leaf)
    np.testing.assert_allclose(m[0], m[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(m[2], m[3], rtol=1e-5, atol=1e-6)


def test_eval_with_merged_stats_is_finite():
    n = 4
    cfg = make_local_config(n)
    transport = IciTransport(cfg, mesh=make_mesh(cfg, jax.devices()[:n]))
    model = CifarResNet(depth=8, norm_type="batch")
    variables = model.init(jax.random.key(0), jnp.zeros((2, 8, 8, 3)))
    stacked_p = stack_params(variables["params"], n)
    stacked_s = stack_params(variables["batch_stats"], n)
    x = jnp.ones((2, 8, 8, 3))
    logits = model.apply(
        {
            "params": jax.tree.map(lambda v: v[0], stacked_p),
            "batch_stats": jax.tree.map(lambda v: v[0], stacked_s),
        },
        x,
        train=False,  # inference: use the (merged) running stats
    )
    assert jnp.all(jnp.isfinite(logits))
