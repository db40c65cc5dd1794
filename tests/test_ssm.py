"""``ops/ssm.py``: the scan kernels (run by the Pallas interpreter) and their
plain twin against a token-by-token reference, forward and all six gradients;
the vmap rule; the causal convolution."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import hybrid_ssm_decoder as plain
from dpwa_tpu.ops import ssm

NAMES = ("x", "delta", "A", "Bm", "Cm", "D")
IMPLEMENTATIONS = {
    "kernels": ssm.interpreted_scan, "plain_twin": ssm.plain_scan,
}
# Relative to the largest value of what is compared.  The implementations
# differ from the reference by the order of float32 sums (1e-6 and less);
# a recurrence kept in bfloat16 is off by 1e-3 (the test of that name).
TOLERANCE = 2e-5


def published_a(channels, states):
    """``A = -exp(A_log)`` at the published initial values: -(1..N)."""
    return -jnp.broadcast_to(
        jnp.arange(1, states + 1, dtype=jnp.float32), (channels, states)
    )


def arguments(seed, batch, steps, channels, states, published=False):
    keys = jax.random.split(jax.random.key(seed), 6)
    shape = (batch, steps, channels)
    if published:  # a channel's step size log-uniform in [0.001, 0.1], as
        # dt_bias starts it, and a token's within a tenth of its channel's
        delta = jnp.exp(
            jax.random.uniform(
                keys[1], (channels,), jnp.float32, np.log(1e-3), np.log(1e-1)
            ) + 0.1 * jax.random.normal(keys[2], shape)
        )
        A = published_a(channels, states)
    else:
        delta = jax.nn.softplus(jax.random.normal(keys[1], shape) - 1.0)
        A = -jnp.exp(0.5 * jax.random.normal(keys[2], (channels, states)))
    return (
        jax.random.normal(keys[0], shape), delta, A,
        jax.random.normal(keys[3], (batch, steps, states)),
        jax.random.normal(keys[4], (batch, steps, states)),
        jax.random.normal(keys[5], (channels,)),
    )


def value_and_grads(fn, args, weights):
    return jax.jit(jax.value_and_grad(
        lambda *a: (fn(*a) * weights).sum(), argnums=tuple(range(6))
    ))(*args)


def off(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


CASES = {
    "one_chunk": dict(batch=2, steps=16, channels=128, states=4),
    "three_chunks": dict(batch=1, steps=48, channels=256, states=16),
    "published_initial_values_t512": dict(
        batch=1, steps=512, channels=128, states=16, published=True
    ),
}


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_scan_and_its_six_gradients_against_a_token_by_token_reference(
    implementation, case
):
    args = arguments(0, **CASES[case])
    weights = jax.random.normal(jax.random.key(7), args[0].shape)
    steps = args[0].shape[1]
    assert steps // ssm.chunk_length(steps) == dict(
        one_chunk=1, three_chunks=3, published_initial_values_t512=4
    )[case]
    fn = IMPLEMENTATIONS[implementation]
    assert off(fn(*args), plain.scan(*args)) < TOLERANCE
    value, grads = value_and_grads(fn, args, weights)
    want_value, want = value_and_grads(plain.scan, args, weights)
    assert np.isfinite(float(value))
    assert abs(float(value - want_value)) < TOLERANCE * float(
        jnp.abs(plain.scan(*args) * weights).sum()
    )
    for name, g, w in zip(NAMES, grads, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert off(g, w) < TOLERANCE, name


def test_a_cumulative_decay_overflows_where_the_scan_does_not():
    """Why no form here divides by ``exp(sum delta A)``: at the published
    initial values it leaves float32 inside one chunk of 128 steps."""
    x, delta, A, *_ = arguments(
        0, **CASES["published_initial_values_t512"]
    )
    inverse_decay = jnp.exp(-jnp.cumsum(delta[0, :128, :, None] * A, 0))
    assert not bool(jnp.isfinite(inverse_decay).all())


def test_a_bfloat16_recurrence_fails_the_tolerance():
    """The tolerance can tell: the same scan with its state rounded to
    bfloat16 after every step is more than an order outside it."""
    args = arguments(0, **CASES["published_initial_values_t512"])
    want = plain.scan(*args)
    rounded = plain.scan(
        *args,
        round_state=lambda s: s.astype(jnp.bfloat16).astype(jnp.float32),
    )
    assert off(rounded, want) > 20 * TOLERANCE


@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_vmap_over_two_peers_equals_a_loop_over_them(implementation):
    """The stacked step's peer axis: each peer its own ``A`` and ``D``.  The
    kernels' rule folds the peers into the sequence axis and computes each
    sequence as a call on one peer would, bit for bit."""
    fn = IMPLEMENTATIONS[implementation]
    peers = [arguments(seed, 2, 32, 128, 4) for seed in (1, 2)]
    stacked = [jnp.stack(pair) for pair in zip(*peers)]
    assert not bool((stacked[2][0] == stacked[2][1]).all())
    grad = jax.grad(lambda *a: fn(*a).sum(), argnums=tuple(range(6)))
    together = jax.jit(jax.vmap(fn))(*stacked), jax.jit(jax.vmap(grad))(*stacked)
    alone = [(jax.jit(fn)(*p), jax.jit(grad)(*p)) for p in peers]
    same = (
        (lambda a, b: bool((a == b).all())) if implementation == "kernels"
        else (lambda a, b: off(a, b) < 1e-6)
    )
    for i, (y, grads) in enumerate(alone):
        assert same(together[0][i], y)
        for name, g, t in zip(NAMES, grads, together[1]):
            assert same(t[i], g), name


def two_peers_of_two_wide_blocks():
    """Two peers x 1 x T 16 x E 2,048, N 16: two channel blocks of 1,024."""
    peers = [arguments(seed, 1, 16, 2048, 16) for seed in (11, 12)]
    stacked = [jnp.stack(pair) for pair in zip(*peers)]
    weights = jax.random.normal(jax.random.key(13), stacked[0].shape)
    return stacked, weights


def stacked_value_and_grads(fn, stacked, weights):
    """``(y, (value, grads))`` of ``fn`` under ``vmap`` over the peers, each
    call traced afresh (the block is read when the kernels are traced)."""
    both = lambda *a: jax.vmap(fn)(*a)
    return jax.jit(both)(*stacked), jax.jit(jax.value_and_grad(
        lambda *a: (both(*a) * weights).sum(), argnums=tuple(range(6)),
    ))(*stacked)


def test_two_blocks_of_1024_channels_under_vmap_against_the_plain_twin():
    """The widest block the rule gives, twice over the channels, two peers
    folded into the sequence axis: the value and every gradient as tightly
    as one narrow block is held above.  ``dBm`` and ``dCm`` are sums over
    2,048 channels, here two partial sums of 1,024 lanes added outside the
    kernel: float32 rounding."""
    stacked, weights = two_peers_of_two_wide_blocks()
    assert stacked[0].shape[-1] // ssm.channel_block(2048) == 2
    got, (value, grads) = stacked_value_and_grads(
        ssm.interpreted_scan, stacked, weights
    )
    want, (want_value, want_grads) = stacked_value_and_grads(
        ssm.plain_scan, stacked, weights
    )
    assert off(got, want) < TOLERANCE
    assert abs(float(value - want_value)) < TOLERANCE * float(
        jnp.abs(want * weights).sum()
    )
    for name, g, w in zip(NAMES, grads, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert off(g, w) < (2e-6 if name in ("Bm", "Cm") else TOLERANCE), name


def test_the_block_moves_no_bit_but_in_the_sums_over_channels():
    """Channels are independent in the recurrence: at 512 channels a block
    (the rule until PR 40) and at 1,024 the kernels give the same ``y``,
    ``dx``, ``ddelta``, ``dA`` and ``dD`` to the bit; ``dBm`` and ``dCm``
    are four partial sums added outside where they are two, so they differ
    by the order of a float32 sum and nothing else."""
    stacked, weights = two_peers_of_two_wide_blocks()
    wide_y, (_, wide) = stacked_value_and_grads(
        ssm.interpreted_scan, stacked, weights
    )
    with mock.patch.object(ssm, "channel_block", lambda channels: 512):
        narrow_y, (_, narrow) = stacked_value_and_grads(
            ssm.interpreted_scan, stacked, weights
        )
    assert bool((wide_y == narrow_y).all())
    for name, g, w in zip(NAMES, wide, narrow):
        if name in ("Bm", "Cm"):
            # Another order of the sum did move some bit: two programs.
            assert 0 < off(g, w) < 1e-6, name
        else:
            assert bool((g == w).all()), name


def test_an_operand_the_vmap_did_not_batch_is_every_peers():
    # Two turns of the kernels' loop: where a chunk is one turn (8 steps
    # until PR 40, 16 since) XLA's CPU backend inlines the loop and fuses
    # the folded call and the single one differently, by one rounding.
    x, delta, A, Bm, Cm, D = arguments(3, 1, 2 * ssm._unroll(128), 128, 4)
    xs = jnp.stack([x, 2 * x])
    got = jax.vmap(
        ssm.interpreted_scan, in_axes=(0, None, None, None, None, None)
    )(xs, delta, A, Bm, Cm, D)
    for i in range(2):
        want = ssm.interpreted_scan(xs[i], delta, A, Bm, Cm, D)
        assert bool((got[i] == want).all())


def test_bfloat16_inputs_keep_a_float32_recurrence():
    """``x`` in bfloat16 (the cell's stream): ``y`` and ``dx`` come back in
    bfloat16, and differ from the float32 run on the same (rounded) values
    by one rounding of the result, not by a bfloat16 state."""
    x, delta, A, Bm, Cm, D = arguments(4, 1, 48, 128, 16)
    x = x.astype(jnp.bfloat16)
    y = ssm.interpreted_scan(x, delta, A, Bm, Cm, D)
    want = plain.scan(x.astype(jnp.float32), delta, A, Bm, Cm, D)
    assert y.dtype == jnp.bfloat16
    assert off(y.astype(jnp.float32), want) < 2.0 ** -8
    dx = jax.grad(
        lambda x: ssm.interpreted_scan(x, delta, A, Bm, Cm, D)
        .astype(jnp.float32).sum()
    )(x)
    assert dx.dtype == jnp.bfloat16


def test_off_the_tpu_the_scan_is_its_plain_twin():
    args = arguments(5, 1, 24, 128, 4)
    assert jax.default_backend() != "tpu"
    assert bool((ssm.selective_scan(*args) == ssm.plain_scan(*args)).all())
    # No argument picks the implementation.
    import inspect

    assert list(inspect.signature(ssm.selective_scan).parameters) == list(NAMES)


@pytest.mark.parametrize("steps,chunk", [
    (4096, 128), (384, 128), (1024, 128), (64, 64), (48, 16), (24, 8), (20, 0),
])
def test_the_chunk_is_a_function_of_the_shape(steps, chunk):
    assert ssm.chunk_length(steps) == chunk


@pytest.mark.parametrize("channels,block", [
    (5120, 1024), (2048, 1024), (1536, 512), (768, 256), (384, 128),
    (64, 64),
])
def test_the_channel_block_is_a_function_of_the_shape(channels, block):
    assert ssm.channel_block(channels) == block


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_causal_conv_is_four_shifted_multiply_adds(dtype):
    keys = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(keys[0], (2, 10, 6)).astype(dtype)
    w = jax.random.normal(keys[1], (4, 6))
    b = jax.random.normal(keys[2], (6,))
    got = ssm.causal_conv1d(x, w, b)
    assert got.dtype == dtype and got.shape == x.shape
    wide = np.asarray(x.astype(jnp.float32))
    want = np.zeros_like(wide) + np.asarray(b)
    for t in range(10):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(w)[j] * wide[:, t - 3 + j]
    tolerance = 1e-6 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(
        np.asarray(got.astype(jnp.float32)), want, rtol=tolerance,
        atol=tolerance,
    )
    np.testing.assert_allclose(
        np.asarray(plain.conv(jnp.asarray(wide), w, b)), want, rtol=1e-6,
        atol=1e-6,
    )
    # Causal: a later input moves no earlier output.
    later = x.at[:, 7].add(1.0)
    assert bool((ssm.causal_conv1d(later, w, b)[:, :7] == got[:, :7]).all())
