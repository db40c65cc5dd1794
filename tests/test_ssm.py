"""``ops/ssm.py``: the scan kernels (run by the Pallas interpreter) and their
plain twin against a token-by-token reference, forward and all six gradients;
the vmap rule; the causal convolution; the convolution with silu as kernels
(the interpreter again) against autodiff of its plain form."""

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


# ---------------------------------------------------------------------------
# The convolution with silu: ``conv_silu``'s two kernels under the Pallas
# interpreter, held to autodiff of ``silu(causal_conv1d(...))``.  One jitted
# program a test, blocked before the next dispatch.
# ---------------------------------------------------------------------------

CONV_NAMES = ("x", "w", "b")


def conv_arguments(seed, shape, taps, dtype=jnp.float32):
    """``x``, ``w``, ``b`` and the weights of a scalar loss, ``x``'s shape."""
    keys = jax.random.split(jax.random.key(seed), 4)
    channels = shape[-1]
    return (
        jax.random.normal(keys[0], shape).astype(dtype),
        jax.random.uniform(keys[1], (taps, channels), minval=-0.5, maxval=0.5),
        jax.random.uniform(keys[2], (channels,), minval=-0.5, maxval=0.5),
    ), jax.random.normal(keys[3], shape)


def conv_value_and_grads(fn, weights):
    """``(y, grads)`` of ``fn`` as float32, the loss ``(y * weights).sum()``."""

    def both(x, w, b):
        loss = lambda *a: (fn(*a).astype(jnp.float32) * weights).sum()
        return fn(x, w, b), jax.grad(loss, argnums=(0, 1, 2))(x, w, b)

    return both


def kernels_and_plain(args, weights):
    """Both implementations' ``(y, grads)`` from one jitted program."""
    return jax.block_until_ready(jax.jit(lambda *a: (
        conv_value_and_grads(ssm.interpreted_conv_silu, weights)(*a),
        conv_value_and_grads(ssm.plain_conv_silu, weights)(*a),
    ))(*args))


@pytest.mark.parametrize("taps", [4, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(2, 48, 128), (1, 1024, 256)])
def test_conv_silu_and_its_three_gradients_against_autodiff_of_the_plain_form(
    shape, dtype, taps
):
    """Three chunks of one tile, and two chunks of eight tiles by two."""
    args, weights = conv_arguments(0, shape, taps, dtype)
    steps, channels = shape[1:]
    assert steps // ssm.conv_chunk(steps) >= 2
    (y, grads), (want_y, want) = kernels_and_plain(args, weights)
    assert y.dtype == dtype and y.shape == shape
    # A rounding or two apart in bfloat16: the kernels keep the sum of the
    # taps and ``g silu'`` float32 to one rounding of the result, where the
    # plain form rounds the sum before silu and each factor of the gradient.
    tolerance = 1e-6 if dtype == jnp.float32 else 2.0 ** -6
    wide = lambda v: v.astype(jnp.float32)
    assert off(wide(y), wide(want_y)) < tolerance
    for name, g, w in zip(CONV_NAMES, grads, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert off(wide(g), wide(w)) < tolerance, name


@pytest.mark.parametrize("taps", [4, 3])
def test_an_impulse_at_a_chunks_end_crosses_into_the_next_and_back(taps):
    """``x`` is one impulse in each of the last ``K - 1`` rows of the first
    chunk: the rows after them, in the second chunk, see it through the rows
    kept of the chunk before.  The loss reads the second chunk alone, so
    every bit of ``dx`` in the first chunk came back across the boundary."""
    steps, channels = 48, 128
    chunk = ssm.conv_chunk(steps)
    assert steps // chunk == 3
    (_, w, b), _ = conv_arguments(1, (1, steps, channels), taps)
    rows = jnp.arange(steps)[None, :, None]
    x = jnp.where((rows >= chunk - (taps - 1)) & (rows < chunk), 1.0, 0.0)
    x = x * jnp.ones((1, steps, channels))
    weights = jnp.where(rows >= chunk, 1.0, 0.0) * jnp.ones_like(x)
    (y, grads), (want_y, want) = kernels_and_plain((x, w, b), weights)
    # Past the taps' reach the output is silu(b) again.
    quiet = jax.nn.silu(b)
    assert off(y[0, chunk + taps - 1:], jnp.broadcast_to(
        quiet, (steps - chunk - taps + 1, channels)
    )) < 1e-6
    assert float(jnp.abs(y[0, chunk:chunk + taps - 1] - quiet).min()) > 1e-4
    assert off(y, want_y) < 1e-6
    dx, want_dx = grads[0], want[0]
    assert float(jnp.abs(want_dx[0, chunk - (taps - 1):chunk]).min()) > 0
    assert not bool(want_dx[0, :chunk - (taps - 1)].any())
    assert not bool(dx[0, :chunk - (taps - 1)].any())
    assert off(dx, want_dx) < 1e-6


def test_a_sequences_first_rows_see_zeros_and_not_the_sequence_before():
    """Two sequences a call, three chunks each: what the kernels carry from
    chunk to chunk starts anew with a sequence, forward (the rows kept) and
    backward (the gradient kept).  Moving the first sequence's last rows, or
    the loss's weights on the second one's first rows, moves nothing across."""
    (x, w, b), weights = conv_arguments(2, (2, 48, 128), 4)
    loud = x.at[0, -3:].set(100.0)
    heavy = weights.at[1, :3].set(100.0)

    def both(x, w, b):
        run = lambda x, weights: conv_value_and_grads(
            ssm.interpreted_conv_silu, weights
        )(x, w, b)
        return (
            run(x, weights), run(loud, weights), run(x, heavy),
            conv_value_and_grads(ssm.plain_conv_silu, weights)(x, w, b),
        )

    (y, (dx, *_)), (loud_y, _), (_, (heavy_dx, *_)), (want_y, (want_dx, *_)) = (
        jax.block_until_ready(jax.jit(both)(x, w, b))
    )
    assert bool((loud_y[1] == y[1]).all()) and not bool((loud_y[0] == y[0]).all())
    assert bool((heavy_dx[0] == dx[0]).all())
    assert not bool((heavy_dx[1] == dx[1]).all())
    assert off(y, want_y) < 1e-6 and off(dx, want_dx) < 1e-6


def test_conv_silu_under_vmap_over_two_peers_equals_a_loop_over_them():
    """The stacked step's peer axis: each peer its own ``w`` and ``b``, the
    peers folded into the kernels' sequence axis; bit for bit a call a peer."""
    peers = [conv_arguments(seed, (2, 48, 256), 4) for seed in (3, 4)]
    stacked = [jnp.stack(pair) for pair in zip(*(args for args, _ in peers))]
    weights = jnp.stack([weights for _, weights in peers])
    assert not bool((stacked[1][0] == stacked[1][1]).all())

    def both(x, w, b):
        run = lambda weights: conv_value_and_grads(
            ssm.interpreted_conv_silu, weights
        )
        together = jax.vmap(
            lambda weights, *a: run(weights)(*a)
        )(weights, x, w, b)
        alone = [run(weights[i])(x[i], w[i], b[i]) for i in range(2)]
        return together, alone

    (y, grads), alone = jax.block_until_ready(jax.jit(both)(*stacked))
    for i, (want_y, want) in enumerate(alone):
        assert bool((y[i] == want_y).all())
        for name, g, w in zip(CONV_NAMES, grads, want):
            assert bool((g[i] == w).all()), name


def test_conv_silu_with_operands_the_vmap_did_not_batch():
    """``w`` and ``b`` shared by the peers: every peer's."""
    (x, w, b), weights = conv_arguments(5, (1, 48, 128), 3)
    xs = jnp.stack([x, 2 * x])

    def both(xs, w, b):
        run = conv_value_and_grads(ssm.interpreted_conv_silu, weights)
        return (
            jax.vmap(run, in_axes=(0, None, None))(xs, w, b),
            [run(xs[i], w, b) for i in range(2)],
        )

    (y, (dx, dw, db)), alone = jax.block_until_ready(jax.jit(both)(xs, w, b))
    for i, (want_y, (want_dx, want_dw, want_db)) in enumerate(alone):
        assert bool((y[i] == want_y).all()) and bool((dx[i] == want_dx).all())
        assert off(dw[i], want_dw) < 1e-6 and off(db[i], want_db) < 1e-6


def test_conv_silu_reads_the_leading_channels_of_a_wider_array():
    """``x`` as the leading half of a wider product (``in_proj``'s ``[x,
    z]``): the kernels pick their blocks out of it, the result is the
    half's, and the gradient to the half that was not read is zero."""
    (x, w, b), weights = conv_arguments(7, (2, 48, 256), 4)
    xz = jnp.concatenate([x, 3.0 * x[..., ::-1]], -1)

    def both(x, xz, w, b):
        run = conv_value_and_grads(ssm.interpreted_conv_silu, weights)
        plain = conv_value_and_grads(ssm.plain_conv_silu, weights)
        return run(x, w, b), run(xz, w, b), plain(xz, w, b)

    (y, (dx, dw, db)), (wide_y, (wide_dx, wide_dw, wide_db)), (_, want) = (
        jax.block_until_ready(jax.jit(both)(x, xz, w, b))
    )
    assert wide_y.shape == x.shape and wide_dx.shape == xz.shape
    assert bool((wide_y == y).all())
    assert bool((wide_dx[..., :256] == dx).all())
    assert not bool(wide_dx[..., 256:].any())
    assert off(wide_dw, dw) < 1e-6 and off(wide_db, db) < 1e-6
    for g, w_ in zip((wide_dx, wide_dw, wide_db), want):
        assert g.shape == w_.shape and off(g, w_) < 1e-6


def test_off_the_tpu_conv_silu_is_its_plain_twin():
    (x, w, b), _ = conv_arguments(6, (1, 32, 128), 4)
    assert jax.default_backend() != "tpu"
    want = jax.nn.silu(ssm.causal_conv1d(x, w, b))
    assert bool((ssm.conv_silu(x, w, b) == want).all())
    # No argument picks the implementation.
    import inspect

    assert list(inspect.signature(ssm.conv_silu).parameters) == list(CONV_NAMES)
    # On the TPU the shape does: lanes whole, a chunk that divides T, and
    # taps within the rows kept.
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert ssm._use_conv_kernels(4096, 5120, 4)
        assert not ssm._use_conv_kernels(4096, 5000, 4)
        assert not ssm._use_conv_kernels(4090, 5120, 4)
        assert not ssm._use_conv_kernels(4096, 5120, ssm.HALO + 2)


@pytest.mark.parametrize("steps,chunk", [
    (4096, 512), (1024, 512), (384, 128), (48, 16), (24, 0),
])
def test_the_convolutions_chunk_is_a_function_of_the_shape(steps, chunk):
    assert ssm.conv_chunk(steps) == chunk


@pytest.mark.parametrize("channels,block", [
    (5120, 1024), (1536, 512), (384, 128), (64, 64),
])
def test_the_convolutions_block_is_a_function_of_the_shape(channels, block):
    assert ssm.conv_block(channels) == block
