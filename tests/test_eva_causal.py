"""``ops/eva.causal_attention``: causal softmax attention on the EVA core's two
kernels (run by the Pallas interpreter), a query's earlier windows seen by
their keys themselves, against the float32 masked-softmax einsum of
``ops/ulysses.single_device_attention`` in the output and in ``dq dk dv``;
grouped keys read as they are; the vmap rule; the rules that pick the window
and say which shapes the kernels take."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dpwa_tpu.ops import eva
from dpwa_tpu.ops.ulysses import single_device_attention

D = 128
# In float32 the kernels differ from the einsum by the order of their sums.
TOLERANCE = 2e-5
# query heads / heads of keys and values
HEADS = {"mha_4_4": (4, 4), "gqa_8_2": (8, 2), "mqa_4_1": (4, 1)}
# A window of 256 in blocks of 128: a window's second query block has a
# diagonal block and one under it, and each earlier window is two turns of
# the loop over the head's whole keys.
WINDOW, BLOCK = 256, 128
STEPS = {
    "one_block": BLOCK, "one_window": WINDOW, "two_windows": 2 * WINDOW,
    "four_windows": 4 * WINDOW,
}

turned = lambda z: jnp.swapaxes(z, -3, -2)


@pytest.fixture
def small_windows(monkeypatch):
    """The kernels' window and block brought down to what the interpreter
    runs in seconds: rules of the shapes, so nothing else can set them."""
    monkeypatch.setattr(eva, "causal_window", lambda T: min(T, WINDOW))
    monkeypatch.setattr(eva, "sub_block", lambda window: min(window, BLOCK))
    eva._differentiable.cache_clear()
    yield
    eva._differentiable.cache_clear()


def arguments(seed, heads, steps, dtype=jnp.float32, lead=(1,)):
    h, kv = HEADS[heads]
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (*lead, h, steps, D), dtype)
    k, v = (jax.random.normal(key, (*lead, kv, steps, D), dtype) for key in keys[1:3])
    return q, k, v, jax.random.normal(keys[3], q.shape, jnp.float32)


def einsum(q, k, v, scale):
    """The masked-softmax einsum, heads first like the kernels."""
    return turned(single_device_attention(
        *map(turned, (q, k, v)), causal=True, impl="dense", sm_scale=scale
    ))


def kernels(q, k, v, scale):
    return eva.causal_attention(q, k, v, scale, interpret=True)


def value_and_grads(fn, q, k, v, weights, scale=D ** -0.5):
    return jax.jit(jax.value_and_grad(
        lambda *a: (fn(*a, scale).astype(jnp.float32) * weights).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)


def off(got, want):
    wide = lambda z: z.astype(jnp.float32)
    return float(jnp.abs(wide(got) - wide(want)).max() / jnp.abs(wide(want)).max())


@pytest.mark.parametrize("steps", STEPS)
@pytest.mark.parametrize("heads", HEADS)
def test_output_and_three_gradients_against_the_masked_softmax_einsum(
    heads, steps, small_windows
):
    q, k, v, weights = arguments(0, heads, STEPS[steps])
    np.testing.assert_allclose(
        kernels(q, k, v, D ** -0.5), einsum(q, k, v, D ** -0.5),
        atol=TOLERANCE,
    )
    got = value_and_grads(kernels, q, k, v, weights)
    want = value_and_grads(einsum, q, k, v, weights)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name, a, b in zip(("dq", "dk", "dv"), got[1], want[1]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert off(a, b) < TOLERANCE, name


@pytest.mark.parametrize("steps", [512, 1024])
def test_with_the_window_and_the_block_the_rules_pick(steps):
    """No fixture: one window of ``T``, in one block of 512 and in two."""
    assert eva.causal_window(steps) == steps and eva.sub_block(steps) == 512
    q, k, v, weights = arguments(1, "gqa_8_2", steps)
    got = value_and_grads(kernels, q, k, v, weights)
    want = value_and_grads(einsum, q, k, v, weights)
    for name, a, b in zip(("dq", "dk", "dv"), got[1], want[1]):
        assert off(a, b) < TOLERANCE, name


@pytest.mark.parametrize("heads", HEADS)
def test_a_scale_of_the_callers(heads, small_windows):
    q, k, v, weights = arguments(2, heads, 2 * WINDOW)
    got = value_and_grads(kernels, q, k, v, weights, 0.37)
    want = value_and_grads(einsum, q, k, v, weights, 0.37)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert off(a, b) < TOLERANCE
    default = value_and_grads(einsum, q, k, v, weights)
    assert off(got[1][0], default[1][0]) > 1e-2


@pytest.mark.parametrize("heads", HEADS)
def test_under_vmap_over_two_peers_it_is_a_loop_over_them(heads, small_windows):
    q, k, v, weights = arguments(3, heads, 2 * WINDOW, lead=(2, 1))
    one = functools.partial(value_and_grads, kernels)
    stacked = jax.vmap(one)(q, k, v, weights)
    for i in range(2):
        alone = one(q[i], k[i], v[i], weights[i])
        for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(stacked)):
            assert off(b[i], a) < TOLERANCE


@pytest.mark.parametrize("heads", ["gqa_8_2", "mqa_4_1"])
def test_in_bfloat16_the_kernels_stay_beside_the_einsum(heads, small_windows):
    """``mixedp_attn``: bfloat16 operands, float32 scores, sums and
    accumulators (``dk``, ``dv`` of a group's query heads and of every window
    summed in float32, rounded once); the kernels also round the
    probabilities where they enter a matmul, the einsum does not."""
    q, k, v, weights = arguments(4, heads, 2 * WINDOW, jnp.bfloat16)
    got = value_and_grads(kernels, q, k, v, weights)
    want = value_and_grads(einsum, q, k, v, weights)
    for name, a, b in zip(("dq", "dk", "dv"), got[1], want[1]):
        assert a.dtype == jnp.bfloat16 and off(a, b) < 2e-2, name


def test_nothing_after_t_reaches_t(small_windows):
    """Keys after ``t`` change no output at or before ``t``: inside the
    window by the mask on the diagonal block, across windows because only
    windows before a query's own are walked."""
    q, k, v, _ = arguments(5, "gqa_8_2", 3 * WINDOW)
    t = 2 * WINDOW - 2
    other = arguments(6, "gqa_8_2", 3 * WINDOW)
    later = lambda a, b: a.at[:, :, t + 1:].set(b[:, :, t + 1:])
    q2, k2, v2 = (later(a, b) for a, b in zip((q, k, v), other))
    first, second = kernels(q, k, v, 0.1), kernels(q2, k2, v2, 0.1)
    np.testing.assert_array_equal(first[:, :, :t + 1], second[:, :, :t + 1])
    assert off(second[:, :, t + 1:], first[:, :, t + 1:]) > 0.1


@pytest.mark.parametrize("steps,window,block", [
    (128, 128, 128), (256, 256, 256), (384, 384, 128), (512, 512, 512),
    (640, 640, 128), (1024, 1024, 512), (1536, 1536, 512),
    (1920, 640, 128),   # fifteen blocks of 128 are no window: three of five
    (2048, 2048, 512), (2560, 1280, 256), (3072, 1536, 512),
    (4096, 2048, 512), (16384, 2048, 512),
    (100, 100, 100), (5000, 5000, 5000),  # no lanes divide it: not taken
])
def test_the_window_is_a_function_of_t(steps, window, block):
    assert eva.causal_window(steps) == window
    assert eva.sub_block(window) == block
    assert steps % window == 0 and window // block <= 8


@pytest.mark.parametrize("steps,d,heads,kv,dtype,taken", [
    (4096, 128, 32, 8, jnp.bfloat16, True),    # the Mistral cells
    (512, 128, 32, 8, jnp.bfloat16, True),
    (4096, 128, 16, 16, jnp.bfloat16, True),   # OLMoE
    (4096, 128, 20, 1, jnp.bfloat16, True),    # Jamba's attention layer
    (256, 128, 4, 2, jnp.float32, True),
    (8192, 128, 32, 8, jnp.bfloat16, True),
    (4096, 256, 8, 8, jnp.bfloat16, True),
    (4096, 64, 32, 8, jnp.bfloat16, False),    # LFM2: narrower than the lanes
    (4096, 192, 64, 64, jnp.bfloat16, False),
    (4096, 128, 32, 5, jnp.bfloat16, False),   # no whole groups
    (200, 128, 4, 4, jnp.float32, False),      # no block divides T
    (16384, 128, 32, 8, jnp.bfloat16, False),  # dk, dv of a whole head: 8 MB
    (8192, 256, 8, 8, jnp.bfloat16, False),    # each, twice, over the ceiling
    (4096, 512, 8, 8, jnp.bfloat16, False),
])
def test_which_shapes_the_kernels_take(steps, d, heads, kv, dtype, taken):
    assert eva.causal_kernels_take(steps, d, heads, kv, dtype) is taken


def test_the_backward_call_at_the_cells_shapes_is_inside_the_ceiling():
    """What the rule adds up for the Mistral step at T 4,096: whole-head
    ``k``, ``v`` 1 MB each and float32 ``dk``, ``dv`` 2 MB each, all twice
    for the pipeline, a window's ``q do dq`` and the float32 ``dq``, six
    score tiles."""
    shaped = lambda h: jax.ShapeDtypeStruct((1, h, 4096, 128), jnp.bfloat16)
    rows = jax.ShapeDtypeStruct((1, 32, 1, 4096), jnp.float32)
    q, k = shaped(32), shaped(8)
    need = eva._vmem_need(
        2048, *eva._layout(2048, q, k, k, backward=True),
        (q, k, k, q, rows, rows),
    )
    mb = 2 ** 20
    assert need == (
        2 * (2 * 1 + 2 * 2) * mb       # k v, dk dv
        + 2 * 3 * mb // 2              # q do dq of 2,048 rows
        + 2 * 2 * 4 * 2048             # two rows of a window
        + mb + 6 * mb                  # dq in float32, the score tiles
    )
    assert eva.vmem_limit(need) < eva.VMEM_CEILING
