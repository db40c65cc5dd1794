"""Single-device stacked (virtual-peer) transport: parity with the SPMD path.

SURVEY.md §7 notes the dev box has one chip; the stacked transport must be a
semantics-preserving stand-in for the mesh transport, so every test here is
phrased as equivalence against :class:`IciTransport` /
:func:`make_gossip_train_step` on the forced-CPU 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dpwa_tpu.config import make_local_config
from dpwa_tpu.interpolation import PeerMeta
from dpwa_tpu.parallel.ici import IciTransport
from dpwa_tpu.parallel.mesh import make_mesh
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


def stacked_params(n, d=16, key=0):
    k = jax.random.key(key)
    return {
        "w": jax.random.normal(k, (n, d)),
        "b": jnp.arange(float(n))[:, None] * jnp.ones((n, 4)),
    }


def stacked_meta(n, clocks=None, losses=None):
    return PeerMeta(
        jnp.asarray(clocks if clocks is not None else np.ones(n), jnp.float32),
        jnp.asarray(
            losses if losses is not None else np.linspace(1, 2, n), jnp.float32
        ),
    )


@pytest.mark.parametrize(
    "cfg_kwargs",
    [
        dict(schedule="ring"),
        dict(schedule="random", pool_size=4, seed=3),
        dict(schedule="ring", fetch_probability=0.5, seed=11),
        dict(schedule="ring", interpolation="clock"),
        dict(schedule="ring", interpolation="loss"),
        dict(schedule="ring", drop_probability=0.4, seed=5),
        dict(schedule="ring", mode="pull"),
        dict(schedule="random", mode="pull", pool_size=4,
             fetch_probability=0.6, seed=9),
        # The layouts share the wire's encode and decode and differ in the
        # fetch between them: codes and scales by gather or by ppermute.
        dict(schedule="random", pool_size=4, seed=3, wire_dtype="bf16"),
        dict(schedule="ring", mode="pull", fetch_probability=0.6, seed=9,
             wire_dtype="int8"),
    ],
)
def test_exchange_parity_with_ici(cfg_kwargs):
    n = 8
    cfg = make_local_config(n, **cfg_kwargs)
    ici = IciTransport(cfg, mesh=make_mesh(cfg))
    stk = StackedTransport(cfg)
    params = stacked_params(n)
    meta = stacked_meta(n, clocks=np.arange(1, n + 1))
    a, b = params, params
    for step in range(6):
        a, info_a = ici.exchange(a, meta, step)
        b, info_b = stk.exchange(b, meta, step)
        np.testing.assert_array_equal(
            np.asarray(info_a.partner), np.asarray(info_b.partner)
        )
        np.testing.assert_array_equal(
            np.asarray(info_a.participated), np.asarray(info_b.participated)
        )
        np.testing.assert_allclose(
            np.asarray(info_a.alpha), np.asarray(info_b.alpha), rtol=1e-6
        )
        for leaf in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(a[leaf]), np.asarray(b[leaf]), rtol=1e-6, atol=1e-7
            )


def test_stacked_preserves_global_mean():
    n = 8
    stk = StackedTransport(make_local_config(n, schedule="random", pool_size=4))
    params = stacked_params(n, d=32)
    meta = stacked_meta(n)
    cur = params
    for step in range(6):
        cur, _ = stk.exchange(cur, meta, step)
    np.testing.assert_allclose(
        np.asarray(cur["w"]).mean(axis=0),
        np.asarray(params["w"]).mean(axis=0),
        rtol=1e-5,
        atol=1e-6,
    )


def _mlp_init(key, din=8, dh=16, dout=4):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (din, dh)) * 0.1,
        "b1": jnp.zeros(dh),
        "w2": jax.random.normal(k2, (dh, dout)) * 0.1,
        "b2": jnp.zeros(dout),
    }


def _mlp_loss(params, batch):
    x, y = batch
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def _batches(n, steps, b=4, din=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (
            jnp.asarray(rng.normal(size=(n, b, din)), jnp.float32),
            jnp.asarray(rng.integers(0, classes, size=(n, b)), jnp.int32),
        )
        for _ in range(steps)
    ]


def test_train_step_parity_with_spmd():
    n = 8
    cfg = make_local_config(n, schedule="ring", fetch_probability=0.7, seed=9)
    ici = IciTransport(cfg, mesh=make_mesh(cfg))
    stk = StackedTransport(cfg)
    opt = optax.sgd(0.1)
    params = stack_params(_mlp_init(jax.random.key(0)), n)

    spmd_step = make_gossip_train_step(_mlp_loss, opt, ici)
    stk_step = make_stacked_train_step(_mlp_loss, opt, stk)
    s_spmd = init_gossip_state(params, opt, ici)
    s_stk = init_stacked_state(params, opt, stk)

    for batch in _batches(n, steps=5):
        s_spmd, losses_spmd, info_spmd = spmd_step(s_spmd, batch)
        s_stk, losses_stk, info_stk = stk_step(s_stk, batch)
        np.testing.assert_allclose(
            np.asarray(losses_spmd), np.asarray(losses_stk), rtol=1e-5
        )
        np.testing.assert_array_equal(
            np.asarray(info_spmd.participated), np.asarray(info_stk.participated)
        )
    for leaf in s_spmd.params:
        np.testing.assert_allclose(
            np.asarray(s_spmd.params[leaf]),
            np.asarray(s_stk.params[leaf]),
            rtol=1e-4,
            atol=1e-6,
        )


def test_stacked_train_converges_and_contracts():
    # 2-class toy problem: loss falls, and gossip keeps replicas close.
    n = 4
    cfg = make_local_config(n, schedule="ring")
    stk = StackedTransport(cfg)
    opt = optax.adam(1e-2)
    params = stack_params(_mlp_init(jax.random.key(1)), n)
    step_fn = make_stacked_train_step(_mlp_loss, opt, stk)
    state = init_stacked_state(params, opt, stk)

    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(8, 4))
    first = last = None
    for _ in range(60):
        x = rng.normal(size=(n, 8, 8)).astype(np.float32)
        y = np.argmax(x @ w_true, axis=-1).astype(np.int32)
        state, losses, _ = step_fn(state, (jnp.asarray(x), jnp.asarray(y)))
        if first is None:
            first = float(losses.mean())
        last = float(losses.mean())
    assert last < first * 0.7
    w = np.asarray(state.params["w1"])
    assert np.abs(w - w.mean(axis=0)).max() < 0.05


def _misuse_case(builder, with_state):
    """(step, init_state) of one builder over a 4-peer ring; the step was
    built with or without model state."""
    from dpwa_tpu.train import make_gossip_train_step_with_state
    from dpwa_tpu.train_sp import (
        init_gossip_sp_state,
        make_gossip_sp_train_step,
        make_gossip_sp_train_step_with_state,
        make_sp_mesh,
    )

    n = 4
    cfg = make_local_config(n, schedule="ring")
    opt = optax.sgd(0.1)
    stateful = lambda p, s, b: (_mlp_loss(p, b), s)
    if builder == "stacked":
        transport = StackedTransport(cfg)
        step = make_stacked_train_step(
            stateful if with_state else _mlp_loss, opt, transport,
            with_state=with_state,
        )
        init = init_stacked_state
    elif builder == "ici":
        transport = IciTransport(cfg, mesh=make_mesh(cfg))
        step = (
            make_gossip_train_step_with_state(stateful, opt, transport)
            if with_state
            else make_gossip_train_step(_mlp_loss, opt, transport)
        )
        init = init_gossip_state
    else:
        transport = IciTransport(cfg, mesh=make_sp_mesh(cfg, 2))
        step = (
            make_gossip_sp_train_step_with_state(stateful, opt, transport)
            if with_state
            else make_gossip_sp_train_step(_mlp_loss, opt, transport)
        )
        init = init_gossip_sp_state
    params = stack_params(_mlp_init(jax.random.key(0)), n)
    return step, lambda **kw: init(params, opt, transport, **kw)


@pytest.mark.parametrize("builder", ["stacked", "ici", "sp"])
@pytest.mark.parametrize(
    "with_state, error",
    [
        # The state carries model_state and the step would never update it:
        # silently frozen BatchNorm statistics, so it must raise.
        (False, "state carries model_state but this step was built without"),
        # The step wants model state and the state has none.
        (True, "step built with model state but state.model_state is None"),
    ],
)
def test_train_step_model_state_misuse_raises(builder, with_state, error):
    """One guard for every builder (``train.gossip_train_step``), both ways."""
    step, init = _misuse_case(builder, with_state)
    state = (
        init() if with_state
        else init(stacked_model_state={"bn": jnp.zeros((4, 3))})
    )
    with pytest.raises(ValueError, match=error):
        step(state, _batches(4, steps=1)[0])


def test_stacked_checkpoint_roundtrip_and_cross_layout_resume(tmp_path):
    from dpwa_tpu.checkpoint import restore_checkpoint, save_checkpoint
    from dpwa_tpu.parallel.stacked import StackedTrainState

    n = 8
    cfg = make_local_config(n, schedule="ring")
    stk = StackedTransport(cfg)
    opt = optax.adam(1e-2)
    params = stack_params(_mlp_init(jax.random.key(3)), n)
    step_fn = make_stacked_train_step(_mlp_loss, opt, stk)
    state = init_stacked_state(params, opt, stk)
    for batch in _batches(n, steps=3):
        state, _, _ = step_fn(state, batch)

    ckpt = str(tmp_path / "ckpt")
    save_checkpoint(ckpt, state)
    restored = restore_checkpoint(ckpt, like=state)
    assert isinstance(restored, StackedTrainState)
    assert int(restored.step) == 3
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        state.params,
        restored.params,
    )
    # Cross-layout: the same checkpoint resumes on the SPMD mesh path —
    # both states carry identical fields, only sharding differs.
    ici = IciTransport(cfg, mesh=make_mesh(cfg))
    mesh_state = init_gossip_state(
        jax.tree.map(np.asarray, state.params), opt, ici
    )
    resumed = restore_checkpoint(ckpt, like=mesh_state)
    spmd_step = make_gossip_train_step(_mlp_loss, opt, ici)
    stk_more, _, _ = step_fn(restored, _batches(n, steps=1, seed=42)[0])
    spmd_more, _, _ = spmd_step(resumed, _batches(n, steps=1, seed=42)[0])
    for leaf in stk_more.params:
        np.testing.assert_allclose(
            np.asarray(stk_more.params[leaf]),
            np.asarray(spmd_more.params[leaf]),
            rtol=1e-5,
            atol=1e-7,
        )


def test_restore_without_like_uses_layout_sidecar(tmp_path):
    # Since round 3 the save records its state class in a -meta.json
    # sidecar, so restore without ``like`` returns the SAVED layout
    # directly (round-2 weak item: it used to return GossipTrainState
    # for a stacked save).  Pre-sidecar checkpoints still default to
    # GossipTrainState and rewrap losslessly.
    from dpwa_tpu.checkpoint import restore_checkpoint, save_checkpoint
    from dpwa_tpu.parallel.stacked import StackedTrainState
    from dpwa_tpu.train import GossipTrainState

    n = 4
    cfg = make_local_config(n, schedule="ring")
    stk = StackedTransport(cfg)
    opt = optax.sgd(1e-2)
    state = init_stacked_state(stack_params(_mlp_init(jax.random.key(5)), n), opt, stk)
    ckpt = str(tmp_path / "ckpt")
    save_checkpoint(ckpt, state)
    restored = restore_checkpoint(ckpt)
    assert isinstance(restored, StackedTrainState)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        state.params,
        restored.params,
    )
    assert int(restored.step) == int(state.step)
    # Pre-sidecar format: drop the sidecar -> GossipTrainState fallback.
    import os as _os

    _os.remove(ckpt + "-meta.json")
    bare = restore_checkpoint(ckpt)
    assert isinstance(bare, GossipTrainState)
    rewrapped = StackedTrainState(**bare._asdict())
    assert int(rewrapped.step) == int(state.step)


def test_stacked_exchange_filter_keeps_rest_frozen():
    n = 4
    cfg = make_local_config(n, schedule="ring")
    stk = StackedTransport(cfg)
    opt = optax.sgd(0.0)  # lr 0: params change only via the exchange
    base = stack_params(_mlp_init(jax.random.key(2)), n)
    # Give peers diverged replicas so the exchange visibly moves leaves.
    params = jax.tree.map(
        lambda v: v + jnp.arange(float(n)).reshape((n,) + (1,) * (v.ndim - 1)),
        base,
    )
    step_fn = make_stacked_train_step(
        _mlp_loss, opt, stk, exchange_filter=lambda p: p.startswith("w1")
    )
    # The state takes ownership of what it is given: hand it a copy.
    state = init_stacked_state(jax.tree.map(jnp.copy, params), opt, stk)
    batch = _batches(n, steps=1)[0]
    new_state, _, info = step_fn(state, batch)
    assert bool(np.asarray(info.participated).any())
    # w1 gossips; w2/b1/b2 must be bit-identical.
    assert not np.array_equal(
        np.asarray(new_state.params["w1"]), np.asarray(params["w1"])
    )
    for leaf in ("w2", "b1", "b2"):
        np.testing.assert_array_equal(
            np.asarray(new_state.params[leaf]), np.asarray(params[leaf])
        )
