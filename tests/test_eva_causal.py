"""``ops/eva.causal_attention``: causal softmax attention on the EVA core's two
kernels (run by the Pallas interpreter), a query's earlier windows seen by
their keys themselves, against the float32 masked-softmax einsum of
``ops/ulysses.single_device_attention`` in the output and in ``dq dk dv``;
grouped keys read as they are; the vmap rule; the rules that pick the window
and say which shapes the kernels take.  ``q`` and ``k`` are ``[B, h, T, D]``,
heads first as the rope leaves them, ``v`` is ``[B, T, kv, D]`` as its
projection writes it and ``o`` ``[B, T, h, D]`` as the output projection
reads it (PR 54): the kernels read a head of ``v`` (``do``) and write one of
``o`` (``dv``) as a block of lanes, and what they give is held bit for bit to
the same kernels over heads-first operands all round (the layout until PR 54,
kept here as :func:`heads_first_calls`)."""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dpwa_tpu.ops import eva
from dpwa_tpu.ops.ulysses import single_device_attention

D = 128
# In float32 the kernels differ from the einsum by the order of their sums.
TOLERANCE = 2e-5
# query heads / heads of keys and values
HEADS = {"mha_4_4": (4, 4), "gqa_8_2": (8, 2), "mqa_4_1": (4, 1)}
# the same, for the cases held to the heads-first calls: groups of 1, 4 and 8
GROUPS = {"group_1": (4, 4), "group_4": (8, 2), "group_8": (8, 1)}
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
    h, kv = HEADS.get(heads) or GROUPS[heads]
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (*lead, h, steps, D), dtype)
    k = jax.random.normal(keys[1], (*lead, kv, steps, D), dtype)
    v = jax.random.normal(keys[2], (*lead, steps, kv, D), dtype)
    return q, k, v, jax.random.normal(keys[3], turned(q).shape, jnp.float32)


def einsum(q, k, v, scale):
    """The masked-softmax einsum, on operands that lie as the kernels'."""
    return single_device_attention(
        turned(q), turned(k), v, causal=True, impl="dense", sm_scale=scale
    )


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
    later = lambda a, b: a.at[:, t + 1:].set(b[:, t + 1:])  # [B, T, ...]
    q2, k2 = (turned(later(turned(a), turned(b))) for a, b in zip((q, k), other))
    v2 = later(v, other[2])
    first, second = kernels(q, k, v, 0.1), kernels(q2, k2, v2, 0.1)
    np.testing.assert_array_equal(first[:, :t + 1], second[:, :t + 1])
    assert off(second[:, t + 1:], first[:, t + 1:]) > 0.1


# ---- the layout: a head as a block of lanes against heads first


def heads_first_calls(q, k, v, do, scale, band=None):
    """``(o, dq, dk, dv)`` by the two kernels over heads-first operands, ``q
    do [S, h, T, D]`` and ``k v [S, kv, T, D]``: the blocks ``ops/eva._layout``
    gave causal attention until PR 54 (a window of a head ``(None, None,
    window, D)`` at ``(s, h, w, 0)``, a head's whole keys at ``(s, h // group,
    0, 0)``), kept here for what the lane blocks are compared with.  The same
    tiles in the same order, so the results are equal to the bit."""
    seqs, heads, steps, d = q.shape
    group = heads // k.shape[1]
    window = eva.causal_window(steps)
    block = eva.sub_block(window)
    seq = pl.BlockSpec((None, None, window, d), lambda s, h, w: (s, h, w, 0))
    row = pl.BlockSpec((None, None, 1, window), lambda s, h, w: (s, h, 0, w))
    whole = pl.BlockSpec(
        (None, None, steps, d), lambda s, h, w: (s, h // group, 0, 0)
    )
    like = lambda z, dtype: jax.ShapeDtypeStruct(z.shape, dtype)
    rows = jax.ShapeDtypeStruct((seqs, heads, 1, steps), jnp.float32)
    static = dict(block=block, per_window=window, scale=scale, band=band)
    call = lambda kernel, scratch, **told: pl.pallas_call(
        kernel, grid=(seqs, heads, steps // window), interpret=True,
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch], **told,
    )
    o, lse = call(
        functools.partial(eva._forward_kernel, **static),
        2 * [(block, eva.LANES)] + [(block, d)],
        in_specs=[seq, whole, whole], out_specs=[seq, row],
        out_shape=[like(q, q.dtype), rows],
    )(q, k, v)
    di = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    dq, dk, dv = call(
        functools.partial(
            eva._backward_kernel, group=group, own=False, **static
        ),
        [(window, d)],
        in_specs=[seq, whole, whole, seq, row, row],
        out_specs=[seq, whole, whole],
        out_shape=[
            like(q, q.dtype), like(k, jnp.float32), like(v, jnp.float32),
        ],
    )(q, k, v, do, lse, di[:, :, None])
    return o, dq, dk.astype(k.dtype), dv.astype(v.dtype)


def lane_blocks_and_heads_first(q, k, v, do, band=None, scale=D ** -0.5):
    """One jitted program: ``(o, dq, dk, dv)`` of ``causal_attention`` on
    heads-first ``q k`` and positions-first ``v do`` (all four come
    ``[..., h, T, D]``), and of :func:`heads_first_calls` on the four as they
    came, ``o`` and ``dv`` turned to lie as ours.  A leading peer axis is
    ``vmap``ped on the one side and folded into the sequences on the other,
    as the ``vmap`` rule folds it."""

    def ours(q, k, v, do):
        o, pull = jax.vjp(
            lambda *a: eva.causal_attention(
                *a, scale, interpret=True, window=band
            ),
            q, k, turned(v),
        )
        return (o, *pull(turned(do)))

    def both(q, k, v, do):
        got = ours
        for _ in q.shape[:-4]:
            got = jax.vmap(got)
        fold = lambda z: z.reshape(-1, *z.shape[-3:])
        o, dq, dk, dv = (
            z.reshape(like.shape) for z, like in zip(
                heads_first_calls(*map(fold, (q, k, v, do)), scale, band),
                (q, q, k, v),
            )
        )
        return got(q, k, v, do), (turned(o), dq, dk, turned(dv))

    return jax.jit(both)(q, k, v, do.astype(q.dtype))


def groups_arguments(seed, group, steps, lead=(2,)):
    """``q k v do`` in bfloat16, two sequences as the cells run them, all four
    heads first: what :func:`lane_blocks_and_heads_first` takes."""
    q, k, v, do = arguments(seed, group, steps, jnp.bfloat16, lead)
    return q, k, turned(v), turned(do)


def assert_equal_to_the_bit(got, want):
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert float(jnp.abs(a.astype(jnp.float32)).max()) > 0, name
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("steps", ["one_window", "two_windows"])
@pytest.mark.parametrize("group", GROUPS)
def test_a_head_as_a_block_of_lanes_gives_the_heads_first_calls_bits(
    group, steps, small_windows
):
    """Two sequences in bfloat16, as the cells run them: the output and the
    three gradients of the call with ``v``, ``o``, ``do`` and ``dv`` read and
    written as blocks of lanes are those of the parent's call, heads first
    all round, on the same operands turned: bit for bit."""
    args = groups_arguments(7, group, STEPS[steps])
    assert_equal_to_the_bit(*lane_blocks_and_heads_first(*args))


@pytest.mark.parametrize("group", GROUPS)
def test_the_heads_first_calls_bits_under_vmap_over_two_peers(
    group, small_windows
):
    args = groups_arguments(8, group, 2 * WINDOW, lead=(2, 1))
    assert_equal_to_the_bit(*lane_blocks_and_heads_first(*args))


def operands_of(primitive, fn, *args):
    """The shapes of the first operand of every ``primitive`` in the jaxpr of
    ``fn`` with its gradients, kernel bodies left out."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == primitive:
                found.append(eqn.invars[0].aval.shape)
            if eqn.primitive.name == "pallas_call":
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    grads = jax.grad(
        lambda *a: fn(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
    )
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        walk(jax.make_jaxpr(grads)(*args).jaxpr)
    return found


def test_the_kernel_branch_turns_q_and_k_alone_and_the_library_branch_all_four():
    """``single_device_attention`` at T 512, 8 query heads on 2, forward and
    backward.  At a head of 128 (our kernels) ``q`` and ``k`` go heads first
    and ``dq``, ``dk`` come back, one turn each, and nothing the size of ``v``
    or ``o`` beside them (until PR 54: ``v``, ``o``, ``do`` and ``dv`` too,
    eight in all); what else is turned is the float32 row sums ``di [B, T /
    8, 8, h]``, a 128th of ``q``.  At a head of 64 (the library's kernels)
    ``q k v`` go heads first and ``o`` comes back, and their gradients the
    same way."""
    eva._differentiable.cache_clear()
    shaped = lambda h, d: jax.ShapeDtypeStruct((2, 512, h, d), jnp.bfloat16)
    attend = functools.partial(single_device_attention, causal=True)
    ours = shaped(8, 128), shaped(2, 128), shaped(2, 128)
    assert sorted(operands_of("transpose", attend, *ours)) == sorted([
        (2, 512, 8, 128), (2, 8, 512, 128),  # q in, dq out
        (2, 512, 2, 128), (2, 2, 512, 128),  # k in, dk out
        (2, 64, 8, 8),                       # di
    ])
    # Two things are held where they lie until their reader takes them: ``do``
    # with ``o`` (flat) for the row sums, and ``dq`` with ``dk``.
    assert operands_of("optimization_barrier", attend, *ours) == [
        (2, 512, 8 * 128), (2, 8, 512, 128),
    ]
    theirs = 3 * [shaped(8, 64)]
    sized = {(2, 512, 8, 64), (2, 8, 512, 64)}
    turned_there = operands_of("transpose", attend, *theirs)
    assert sum(shape in sized for shape in turned_there) >= 8
    assert not operands_of("optimization_barrier", attend, *theirs)
    # And the turned layout is what the library is still handed.
    forward = jax.make_jaxpr(attend)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = str(forward(shaped(8, 64), shaped(8, 64), shaped(8, 64)))
    assert text.count("transpose[permutation=(0, 2, 1, 3)]") == 4


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
    v = jax.ShapeDtypeStruct((1, 4096, 8 * 128), jnp.bfloat16)
    need = eva._vmem_need(
        2048, *eva._layout(2048, q, k, v, backward=True),
        (q, k, v, q, rows, rows),
    )
    mb = 2 ** 20
    assert need == (
        2 * (2 * 1 + 2 * 2) * mb       # k v, dk dv
        + 2 * 3 * mb // 2              # q do dq of 2,048 rows
        + 2 * 2 * 4 * 2048             # two rows of a window
        + mb + 6 * mb                  # dq in float32, the score tiles
    )
    assert eva.vmem_limit(need) < eva.VMEM_CEILING
