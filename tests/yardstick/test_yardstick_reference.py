"""The reference check passes an honest step and fails a coarser wire, a
wrong pairing and a wrong alpha."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
import yardstick_paths  # noqa: F401  (puts the repo root on sys.path)

from benchmark import reference

N = 4


def _loss(params, batch):
    x, y = batch
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return jnp.mean((h @ params["w2"] - y) ** 2)


def _setup(exchange_filter=None):
    keys = jax.random.split(jax.random.key(0), 5)
    params = dict(
        w1=jax.random.normal(keys[0], (N, 8, 16)),
        b1=jnp.zeros((N, 16)),
        w2=jax.random.normal(keys[1], (N, 16, 2)),
    )
    batch = (
        jax.random.normal(keys[2], (N, 5, 8)),
        jax.random.normal(keys[3], (N, 5, 2)),
    )
    optimizer = optax.sgd(1e-3, momentum=0.9)
    opt_state = jax.vmap(optimizer.init)(params)
    local = reference.make_local_update(_loss, optimizer, exchange_filter)
    u, moved = local(params, opt_state, batch)
    return params, batch, optimizer, opt_state, u, moved


def _system_step(params, opt_state, batch, optimizer, partner, alpha, wire=None):
    """A toy 'system': vmapped update, then the merge, optionally through a
    rounded wire."""

    def one(p, s, b):
        g = jax.grad(_loss)(p, b)
        upd, _ = optimizer.update(g, s, p)
        return optax.apply_updates(p, upd)

    u = jax.vmap(one)(params, opt_state, batch)

    def merge(x):
        a = alpha.reshape((-1,) + (1,) * (x.ndim - 1))
        y = x[partner]
        if wire is not None:
            y = y.astype(wire).astype(x.dtype)
        return (1 - a) * x + a * y

    return jax.tree.map(merge, u)


PARTNER = jnp.array([1, 0, 3, 2])
ALPHA = jnp.full((N,), 0.5)


def _verdict(system_params, u, moved, exchange_filter=None):
    merged = reference.merge(u, PARTNER, ALPHA)
    return reference.compare(system_params, merged, moved, ALPHA, exchange_filter)


def test_honest_step_passes():
    params, batch, opt, opt_state, u, moved = _setup()
    got = _system_step(params, opt_state, batch, opt, PARTNER, ALPHA)
    verdict = _verdict(got, u, moved)
    assert verdict.ok, verdict.reasons
    assert verdict.worst_ratio < 1 and verdict.wire_margin > 1


@pytest.mark.parametrize("fault", ["bf16_wire", "shifted_permutation", "alpha"])
def test_dishonest_step_fails(fault):
    params, batch, opt, opt_state, u, moved = _setup()
    kwargs = dict(partner=PARTNER, alpha=ALPHA)
    if fault == "bf16_wire":
        kwargs["wire"] = jnp.bfloat16
    elif fault == "shifted_permutation":
        kwargs["partner"] = jnp.array([2, 3, 0, 1])
    else:
        kwargs["alpha"] = jnp.full((N,), 0.45)
    got = _system_step(params, opt_state, batch, opt, **kwargs)
    verdict = _verdict(got, u, moved)
    assert not verdict.ok and verdict.worst_ratio > 1


@pytest.mark.parametrize("wire,ok", [(None, True), (jnp.bfloat16, False)])
def test_exchange_alone_is_held_to_float32_rounding(wire, ok):
    """With no update term the tolerance is B_PARAM of the leaf's size."""
    tree = dict(a=jax.random.normal(jax.random.key(5), (N, 33, 7)),
                b=jax.random.normal(jax.random.key(6), (N, 130)))

    def system(x):
        a = ALPHA.reshape((-1,) + (1,) * (x.ndim - 1))
        y = x[PARTNER]
        if wire is not None:
            y = jax.lax.reduce_precision(y, exponent_bits=8, mantissa_bits=7)
        return (1.0 - a) * x + a * y

    want = reference.merge(jax.tree.leaves(tree), PARTNER, ALPHA)
    verdict = reference.compare(
        jax.tree.map(system, tree), want, np.zeros(2), ALPHA, None
    )
    assert verdict.ok == ok and verdict.wire_margin > 100


def test_subset_exchange_compares_only_the_chosen_leaves():
    only_w = lambda path: "w" in path
    params, batch, opt, opt_state, u, moved = _setup(only_w)
    assert len(u) == 2 and moved.shape == (2,)
    got = _system_step(params, opt_state, batch, opt, PARTNER, ALPHA)
    assert _verdict(got, u, moved, only_w).ok
    got["b1"] = got["b1"] + 1.0  # outside the exchange: not this check's
    assert _verdict(got, u, moved, only_w).ok


@pytest.mark.parametrize("partner,alpha,participated,bad", [
    ([1, 0, 3, 2], [0.5] * 4, [True] * 4, False),
    ([1, 0, 2, 3], [0.5, 0.5, 0, 0], [True, True, False, False], False),
    ([1, 2, 0, 3], [0.5] * 4, [True] * 4, True),  # not an involution
    ([1, 0, 2, 3], [0.5] * 4, [True] * 4, True),  # own partner, took part
    ([1, 0, 3, 2], [0.25] * 4, [True] * 4, True),  # alpha is not the factor
    ([1, 0, 3, 9], [0.5] * 4, [True] * 4, True),  # out of range
])
def test_check_info(partner, alpha, participated, bad):
    reasons = reference.check_info(partner, alpha, participated, 0.5)
    assert bool(reasons) == bad


def test_frozen_checksum_sees_one_flipped_bit():
    tree = dict(a=jnp.arange(12.0).reshape(3, 4), lora_b=jnp.ones(3),
                c=jnp.ones((2, 2), jnp.bfloat16))
    keep = lambda path: "lora_" in path
    before = [int(v) for v in reference.frozen_checksum(tree, keep)]
    assert len(before) == 2
    tree["lora_b"] = tree["lora_b"] + 1  # exchanged leaf: not frozen
    assert before == [int(v) for v in reference.frozen_checksum(tree, keep)]
    flipped = np.asarray(tree["a"]).copy()
    flipped.view(np.uint32)[0, 0] ^= 1
    tree["a"] = jnp.asarray(flipped)
    assert before != [int(v) for v in reference.frozen_checksum(tree, keep)]
