"""``ops/eva.py``: the core's kernels (run by the Pallas interpreter) and their
plain twin against the reference's dense windowed form, value and all five
gradients; the vmap rule; what a query can and cannot see; the summaries by
hand."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import eva_decoder as plain
from dpwa_tpu.ops import eva

# ``ops/eva.py`` has heads before positions, the reference positions first.
turned = lambda z: jnp.swapaxes(z, 1, 2)


def reference_eva(q, k, v, ksum, vsum, window, chunk, **kw):
    return turned(plain.eva(
        *map(turned, (q, k, v, ksum, vsum)), window, chunk, **kw
    ))


def reference_summaries(k, v, phi, mu, chunk):
    return tuple(map(turned, plain.summaries(turned(k), turned(v), phi, mu, chunk)))

NAMES = ("q", "k", "v", "ksum", "vsum")
IMPLEMENTATIONS = {
    "kernels": eva.interpreted_eva_attention,
    "plain_twin": eva.plain_eva_attention,
}
# Relative to the largest value of what is compared.  In float32 the
# implementations differ from the reference by the order of their sums (1e-6
# and less); scores kept in bfloat16 are off by 1e-3 and more (the test of
# that name).
TOLERANCE = 2e-5
# Head size 128 as published; a window of 256 is two of the kernels' blocks of
# 128 rows, so a window's last query block and the next window's first both
# have a diagonal block, a block under it, and another count of summaries.
WINDOW, CHUNK, HEADS, D = 256, 16, 2, 128
CASES = {"three_windows": 3 * WINDOW, "four_windows": 4 * WINDOW}


def arguments(seed, batch, steps, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(seed), 5)
    shape = (batch, HEADS, steps, D)
    q, k, v = (jax.random.normal(key, shape, dtype) for key in keys[:3])
    phi, mu = (
        jax.random.normal(key, (HEADS, D), dtype) * D ** -0.5
        for key in keys[3:]
    )
    return q, k, v, phi, mu


def summaries(k, v, phi, mu):
    return eva.chunk_summaries(k, v, phi, mu, CHUNK)


def value_and_grads(fn, operands, weights):
    return jax.jit(jax.value_and_grad(
        lambda *a: (fn(*a, WINDOW, CHUNK) * weights).sum(),
        argnums=tuple(range(5)),
    ))(*operands)


def off(got, want):
    wide = lambda z: z.astype(jnp.float32)
    return float(jnp.abs(wide(got) - wide(want)).max() / jnp.abs(wide(want)).max())


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_core_and_its_five_gradients_against_the_dense_reference(
    implementation, case
):
    q, k, v, phi, mu = arguments(0, 1, CASES[case])
    operands = (q, k, v, *summaries(k, v, phi, mu))
    weights = jax.random.normal(jax.random.key(7), q.shape)
    want = value_and_grads(reference_eva, operands, weights)
    got = value_and_grads(IMPLEMENTATIONS[implementation], operands, weights)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name, a, b in zip(NAMES, got[1], want[1]):
        assert a.shape == b.shape and off(a, b) < TOLERANCE, name


def test_scores_kept_in_bfloat16_are_outside_the_tolerance():
    q, k, v, phi, mu = arguments(1, 1, 3 * WINDOW)
    operands = (q, k, v, *summaries(k, v, phi, mu))
    rounded = reference_eva(
        *operands, WINDOW, CHUNK,
        round_scores=lambda s: s.astype(jnp.bfloat16).astype(jnp.float32),
    )
    assert off(rounded, reference_eva(*operands, WINDOW, CHUNK)) > 50 * TOLERANCE


def test_the_kernels_in_bfloat16_stay_beside_their_twin():
    """``mixedp_attn``: bfloat16 operands, float32 scores and sums.  The
    kernels also round the probabilities where they enter a matmul; the twin
    does not."""
    q, k, v, phi, mu = arguments(2, 1, 3 * WINDOW, jnp.bfloat16)
    operands = (q, k, v, *summaries(k, v, phi, mu))
    weights = jax.random.normal(jax.random.key(3), q.shape)
    got = value_and_grads(eva.interpreted_eva_attention, operands, weights)
    want = value_and_grads(eva.plain_eva_attention, operands, weights)
    for name, a, b in zip(NAMES, got[1], want[1]):
        assert a.dtype == jnp.bfloat16 and off(a, b) < 2e-2, name


@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_under_vmap_over_two_peers_it_is_a_loop_over_them(implementation):
    fn = IMPLEMENTATIONS[implementation]
    q, k, v, phi, mu = arguments(4, 2, 3 * WINDOW)
    operands = (q, k, v, *summaries(k, v, phi, mu))
    peers = tuple(z[:, None] for z in operands)  # [peers, 1, ...]
    weights = jax.random.normal(jax.random.key(5), peers[0].shape)
    one = jax.value_and_grad(
        lambda w, *a: (fn(*a, WINDOW, CHUNK) * w).sum(), argnums=(1, 2, 3, 4, 5)
    )
    stacked = jax.jit(jax.vmap(one))(weights, *peers)
    for i in range(2):
        alone = jax.jit(one)(weights[i], *(z[i] for z in peers))
        for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(stacked)):
            assert off(b[i], a) < TOLERANCE


def test_an_operand_the_vmap_does_not_batch_is_shared():
    q, k, v, phi, mu = arguments(6, 1, 3 * WINDOW)
    ksum, vsum = summaries(k, v, phi, mu)
    queries = jnp.stack([q, 2.0 * q])
    got = jax.vmap(
        lambda z: eva.interpreted_eva_attention(z, k, v, ksum, vsum, WINDOW, CHUNK)
    )(queries)
    want = eva.plain_eva_attention(2.0 * q, k, v, ksum, vsum, WINDOW, CHUNK)
    assert off(got[1], want) < TOLERANCE


@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_nothing_after_t_reaches_t(implementation):
    """Bytes after ``t`` change no output at or before ``t``: inside the
    window by the causal mask, across windows because a summary is of an
    earlier window alone.  ``t`` is a window's last position but one, so the
    positions after it are the rest of its chunk and every later window."""
    fn = IMPLEMENTATIONS[implementation]
    q, k, v, phi, mu = arguments(8, 1, 3 * WINDOW)
    t = 2 * WINDOW - 2
    other = arguments(9, 1, 3 * WINDOW)
    later = lambda a, b: a.at[:, :, t + 1:].set(b[:, :, t + 1:])
    q2, k2, v2 = (later(a, b) for a, b in zip((q, k, v), other))
    run = lambda q, k, v: fn(q, k, v, *summaries(k, v, phi, mu), WINDOW, CHUNK)
    first, second = run(q, k, v), run(q2, k2, v2)
    np.testing.assert_array_equal(first[:, :, :t + 1], second[:, :, :t + 1])
    assert off(second[:, :, t + 1:], first[:, :, t + 1:]) > 0.1


@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_a_querys_own_window_gives_it_no_summary(implementation):
    """Changing the *summaries* of window 1 and later leaves every query of
    windows 0 and 1 as it was, and moves window 2; and a changed byte of
    window 1 reaches the queries of window 1 through the exact keys alone:
    with the summaries held, those before it do not move."""
    fn = IMPLEMENTATIONS[implementation]
    q, k, v, phi, mu = arguments(10, 1, 3 * WINDOW)
    ksum, vsum = summaries(k, v, phi, mu)
    per_window = WINDOW // CHUNK
    base = fn(q, k, v, ksum, vsum, WINDOW, CHUNK)
    moved = fn(
        q, k, v, ksum.at[:, :, per_window:].add(1.0),
        vsum.at[:, :, per_window:].add(1.0), WINDOW, CHUNK,
    )
    np.testing.assert_array_equal(
        base[:, :, :2 * WINDOW], moved[:, :, :2 * WINDOW]
    )
    assert off(moved[:, :, 2 * WINDOW:], base[:, :, 2 * WINDOW:]) > 1e-3
    at = WINDOW + 100  # a byte of window 1
    changed = fn(
        q, k.at[:, :, at].add(1.0), v.at[:, :, at].add(1.0), ksum, vsum,
        WINDOW, CHUNK,
    )
    np.testing.assert_array_equal(base[:, :, :at], changed[:, :, :at])
    # The summaries of its own window do move, and no query of it sees them.
    ksum2, _ = summaries(k.at[:, :, at].add(1.0), v, phi, mu)
    assert off(ksum2[:, :, at // CHUNK], ksum[:, :, at // CHUNK]) > 1e-3
    np.testing.assert_array_equal(
        ksum2[:, :, :per_window], ksum[:, :, :per_window]
    )


@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_window_zero_is_plain_causal_attention(implementation):
    q, k, v, phi, mu = arguments(11, 2, WINDOW)
    got = IMPLEMENTATIONS[implementation](
        q, k, v, *summaries(k, v, phi, mu), WINDOW, CHUNK
    )
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((WINDOW, WINDOW), bool)), s, -jnp.inf)
    want = jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, -1), v)
    assert bool(jnp.isfinite(got).all()) and off(got, want) < TOLERANCE


def test_chunk_summaries_by_hand():
    """One head of size 2, one chunk of 2 positions, ``s = 2^-1/2``."""
    k = jnp.array([[1.0, 0.0], [0.0, 2.0]]).reshape(1, 1, 2, 2)
    v = jnp.array([[3.0, 1.0], [5.0, -1.0]]).reshape(1, 1, 2, 2)
    phi, mu = jnp.array([[1.0, 1.0]]), jnp.array([[10.0, 20.0]])
    ksum, vsum = eva.chunk_summaries(k, v, phi, mu, 2)
    a0 = 1.0 / (1.0 + np.exp((2.0 - 1.0) * 2 ** -0.5))  # of position 0
    np.testing.assert_allclose(
        ksum[0, 0, 0], [a0 * 1.0 + 10.0, (1 - a0) * 2.0 + 20.0], rtol=1e-6
    )
    np.testing.assert_allclose(
        vsum[0, 0, 0], [a0 * 3.0 + (1 - a0) * 5.0, a0 - (1 - a0)], rtol=1e-6
    )


def test_chunk_summaries_and_their_gradients_against_the_reference():
    _, k, v, phi, mu = arguments(12, 2, 3 * WINDOW)
    weights = jax.random.normal(
        jax.random.key(13), (2, 2, HEADS, 3 * WINDOW // CHUNK, D)
    )
    loss = lambda fn: jax.value_and_grad(
        lambda *a: (jnp.stack(fn(*a, CHUNK)) * weights).sum(),
        argnums=(0, 1, 2, 3),
    )(k, v, phi, mu)
    got, want = loss(eva.chunk_summaries), loss(reference_summaries)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert off(a, b) < TOLERANCE


def test_a_chunk_cut_short_has_no_summary():
    _, k, v, phi, mu = arguments(14, 1, 40)
    ksum, vsum = eva.chunk_summaries(k, v, phi, mu, CHUNK)
    assert ksum.shape == vsum.shape == (1, HEADS, 2, D)
    whole, _ = eva.chunk_summaries(k[:, :, :32], v[:, :, :32], phi, mu, CHUNK)
    np.testing.assert_array_equal(ksum, whole)
    # Shorter than a chunk (a model's init): no summary, and the core runs.
    none = eva.chunk_summaries(k[:, :, :8], v[:, :, :8], phi, mu, CHUNK)
    assert none[0].shape == (1, HEADS, 0, D)
    q = k[:, :, :8]
    out = eva.eva_attention(
        q, k[:, :, :8], v[:, :, :8], *none, window=WINDOW, chunk=CHUNK
    )
    assert out.shape == q.shape and bool(jnp.isfinite(out).all())


def test_a_sequence_that_is_no_whole_number_of_windows_takes_the_twin():
    q, k, v, phi, mu = arguments(15, 1, 2 * WINDOW + 48)
    operands = (q, k, v, *summaries(k, v, phi, mu))
    want = reference_eva(*operands, WINDOW, CHUNK)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert not eva._use_kernels(2 * WINDOW + 48, D, WINDOW, CHUNK)
        got = eva.eva_attention(*operands, window=WINDOW, chunk=CHUNK)
    assert off(got, want) < TOLERANCE


@pytest.mark.parametrize("steps,d,window,chunk,backend,taken", [
    (16384, 128, 2048, 16, "tpu", True),
    (6144, 128, 2048, 16, "tpu", True),
    (16384, 128, 2048, 16, "cpu", False),
    (16384, 64, 2048, 16, "tpu", False),   # a head narrower than the lanes
    (4096, 128, 2048, 32, "tpu", False),   # 64 summaries a window
    (768, 128, 256, 16, "tpu", False),     # 16 summaries a window
    (5000, 128, 2048, 16, "tpu", False),
])
def test_when_the_kernels_are_taken(steps, d, window, chunk, backend, taken):
    with mock.patch.object(jax, "default_backend", lambda: backend):
        assert eva._use_kernels(steps, d, window, chunk) is taken


def test_sub_block_divides_the_window():
    assert [eva.sub_block(w) for w in (2048, 1536, 768, 256, 128, 96)] == [
        512, 512, 256, 256, 128, 96
    ]
