"""A sliding window in ``ops/eva.causal_attention`` and in
``ops/ulysses.single_device_attention``: the two kernels with a band (run by
the Pallas interpreter) against the float32 masked-softmax einsum in the
output and in ``dq dk dv``, at grouped keys, at one, two and five windows, at a
window that is and is not a multiple of the kernels' block; the two edges of
the band (key ``t - window + 1`` is seen, key ``t - window`` is not); what a
call without a window runs, held to the parent's program; which family a
windowed call takes, and what is refused."""

import functools
import hashlib
import os
import re
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dpwa_tpu.ops import eva
from dpwa_tpu.ops.ulysses import single_device_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # the benchmark's readers, for the kernels' names
    sys.path.insert(0, ROOT)

from tests import test_eva_causal  # noqa: E402  (the heads-first reference)

D = 128
# In float32 the kernels differ from the einsum by the order of their sums.
TOLERANCE = 2e-5
turned = test_eva_causal.turned


@pytest.fixture
def grid(monkeypatch):
    """``set(window, block)``: the kernels' grid window and block brought
    down to what the interpreter runs in seconds (rules of the shapes, so
    nothing else can set them)."""

    def set_to(window, block):
        monkeypatch.setattr(eva, "causal_window", lambda T: min(T, window))
        monkeypatch.setattr(eva, "sub_block", lambda w: min(w, block))
        eva._differentiable.cache_clear()

    yield set_to
    eva._differentiable.cache_clear()


def arguments(seed, steps, heads=(8, 1), dtype=jnp.float32):
    h, kv = heads
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (1, h, steps, D), dtype)
    k = jax.random.normal(keys[1], (1, kv, steps, D), dtype)
    v = jax.random.normal(keys[2], (1, steps, kv, D), dtype)
    return q, k, v, jax.random.normal(keys[3], turned(q).shape, jnp.float32)


def einsum(q, k, v, window):
    """The masked-softmax einsum, on operands that lie as the kernels': ``q``
    and ``k`` heads first, ``v`` and the result positions first."""
    return single_device_attention(
        turned(q), turned(k), v, causal=True, window=window, impl="dense"
    )


def kernels(q, k, v, window):
    return eva.causal_attention(q, k, v, D ** -0.5, interpret=True, window=window)


def value_and_grads(fn, q, k, v, weights, window):
    return jax.jit(jax.value_and_grad(
        lambda *a: (fn(*a, window).astype(jnp.float32) * weights).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)


def off(got, want):
    wide = lambda z: z.astype(jnp.float32)
    return float(jnp.abs(wide(got) - wide(want)).max() / jnp.abs(wide(want)).max())


# (positions, the model's window, the grid's window, the block): a T of one,
# two and five windows; a window of one, two and one and a half blocks; a
# grid window that is the model's, half of it and a multiple of it; a block
# larger than the window.
CASES = {
    "one_window": (256, 256, 256, 128),
    "two_windows": (512, 256, 256, 128),
    "five_windows": (1280, 256, 640, 128),
    "window_of_one_block": (512, 128, 256, 128),
    "window_of_a_block_and_a_half": (768, 384, 768, 256),
    "window_under_the_block": (512, 128, 512, 256),
    "grid_window_of_half_the_window": (1024, 512, 256, 128),
    "window_over_the_sequence": (256, 1024, 256, 128),
}


@pytest.mark.parametrize("case", CASES)
def test_output_and_three_gradients_against_the_masked_softmax_einsum(
    case, grid
):
    steps, window, grid_window, block = CASES[case]
    grid(grid_window, block)
    q, k, v, weights = arguments(0, steps)  # eight query heads on one
    np.testing.assert_allclose(
        kernels(q, k, v, window), einsum(q, k, v, window), atol=TOLERANCE
    )
    got = value_and_grads(kernels, q, k, v, weights, window)
    want = value_and_grads(einsum, q, k, v, weights, window)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name, a, b in zip(("dq", "dk", "dv"), got[1], want[1]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert off(a, b) < TOLERANCE, name


@pytest.mark.parametrize("heads", [(4, 4), (8, 2)])
def test_with_the_window_and_the_block_the_rules_pick(heads):
    """No fixture: T 1,024 in one grid window of two blocks of 512, a window
    of 256: a query block's band lies in its own block and the one before,
    both cut by an edge."""
    steps, window = 1024, 256
    assert eva.causal_window(steps) == steps and eva.sub_block(steps) == 512
    q, k, v, weights = arguments(1, steps, heads)
    got = value_and_grads(kernels, q, k, v, weights, window)
    want = value_and_grads(einsum, q, k, v, weights, window)
    for name, a, b in zip(("dq", "dk", "dv"), got[1], want[1]):
        assert off(a, b) < TOLERANCE, name


@pytest.mark.parametrize("form", ["kernels", "einsum"])
def test_the_two_edges_of_the_band(form, grid):
    """Query ``t`` sees key ``t - window + 1`` and does not see key ``t -
    window``: moving the first key's value moves the query's output, moving
    the second's leaves it bit for bit; and no later query sees either less."""
    steps, window = 768, 256
    grid(256, 128)
    fn = dict(kernels=kernels, einsum=einsum)[form]
    q, k, v, _ = arguments(2, steps, (2, 1))
    t = 600  # in the third grid window, off every block's first row
    base = fn(q, k, v, window)
    for behind, seen in ((window - 1, True), (window, False)):
        moved = fn(q, k, v.at[:, t - behind].add(1.0), window)
        changed = jnp.abs(moved - base).max(axis=(0, 2, 3)) > 0  # [T]
        assert bool(changed[t]) == seen, behind
        # Exactly the queries from the key's own to the last that sees it.
        first, last = t - behind, t - behind + window - 1
        want = (jnp.arange(steps) >= first) & (jnp.arange(steps) <= last)
        np.testing.assert_array_equal(changed, want)
    # The same through the keys: the gradient of query t's output reaches
    # key t - window + 1 and not key t - window.
    dk = jax.grad(lambda k: fn(q, k, v, window)[:, t].sum())(k)  # [B, kv, T, D]
    reached = jnp.abs(dk).max(axis=(0, 1, 3)) > 0
    np.testing.assert_array_equal(
        reached, (jnp.arange(steps) > t - window) & (jnp.arange(steps) <= t)
    )


def test_under_vmap_over_two_peers_it_is_a_loop_over_them(grid):
    grid(256, 128)
    keys = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(keys[0], (2, 1, 4, 512, D))
    k = jax.random.normal(keys[1], (2, 1, 2, 512, D))
    v = jax.random.normal(keys[2], (2, 1, 512, 2, D))
    one = jax.grad(
        lambda q, k, v: kernels(q, k, v, 256).sum(), argnums=(0, 1, 2)
    )
    stacked = jax.vmap(one)(q, k, v)
    for peer in range(2):
        alone = one(q[peer], k[peer], v[peer])
        for a, b in zip(stacked, alone):
            np.testing.assert_allclose(a[peer], b, atol=1e-5)


# query heads / heads of keys and values (groups of 1, 4 and 8) x positions
# at a grid window of 256 in blocks of 128 (one window and two), a band of
# 256 keys: a query block's own block, the one behind it and an edge in the
# third, which in the second window's first block lies in the window before.
BAND_CASES = {
    f"{group}-{name}": (group, steps)
    for group in test_eva_causal.GROUPS
    for name, steps in (("one_window", 256), ("two_windows", 512))
}


@pytest.mark.parametrize("case", BAND_CASES)
def test_with_a_band_a_head_as_a_block_of_lanes_gives_the_heads_first_bits(
    case, grid
):
    """The output and the three gradients of the windowed call, ``v o do dv``
    read and written as blocks of lanes, are those of the same kernels over
    heads-first operands all round (the layout until PR 54:
    ``tests/test_eva_causal.heads_first_calls``), bit for bit, in bfloat16 at
    two sequences."""
    grid(256, 128)
    args = test_eva_causal.groups_arguments(9, *BAND_CASES[case])
    test_eva_causal.assert_equal_to_the_bit(
        *test_eva_causal.lane_blocks_and_heads_first(*args, band=256)
    )


def test_with_a_band_the_heads_first_bits_under_vmap_over_two_peers(grid):
    grid(256, 128)
    args = test_eva_causal.groups_arguments(10, "group_4", 512, lead=(2, 1))
    test_eva_causal.assert_equal_to_the_bit(
        *test_eva_causal.lane_blocks_and_heads_first(*args, band=128)
    )


def test_without_a_window_the_call_is_the_causal_call_bit_for_bit(grid):
    grid(256, 128)
    q, k, v, _ = arguments(4, 512, (4, 2), jnp.bfloat16)
    plain = eva.causal_attention(q, k, v, D ** -0.5, interpret=True)
    same = eva.causal_attention(q, k, v, D ** -0.5, interpret=True, window=None)
    np.testing.assert_array_equal(plain, same)
    at = lambda *a, **kw: single_device_attention(
        turned(q), turned(k), v, *a, causal=True, impl="dense", **kw
    )
    np.testing.assert_array_equal(at(), at(window=None))
    # A window over the whole sequence is the causal call's values, by
    # another program (every block masked, none skipped).
    np.testing.assert_allclose(
        kernels(q, k, v, 512).astype(jnp.float32), plain.astype(jnp.float32),
        atol=2e-2,
    )


# The first 16 hex digits of the SHA-256 of the jaxpr (kernel bodies, grids,
# blocks and names written out; the addresses of functions taken out) of the
# calls without a window, under ``vmap`` over two peers with their gradients,
# by these lines.  The EVA core's was taken on PR 49's parent (2966108) and
# has held since: heads first all round, to the text.  The causal calls' were
# re-taken on PR 54, whose change they are (``v o do dv`` as ``[S, T, h D]``,
# a head a block of lanes, the row sums in groups of eight positions, ``dq``
# and ``dk`` held as they leave the kernel; a9824a5f3db7c3c4 /
# 1e70bbb553d5883a until then): with ``window=None`` nothing of the band is
# traced.
KERNELS_AT_PARENT = {
    "causal_t512": "33984e906ea35e55",
    "causal_t4096": "5b2a4a392bd4411d",
    "eva_core": "0fa4745f970f5dc4",
}


def kernel_digest(name):
    shaped = lambda *shape: jax.ShapeDtypeStruct((2, 1, *shape, D), jnp.bfloat16)
    total = lambda out: out.astype(jnp.float32).sum()
    if name == "eva_core":  # heads first
        fn = lambda *a: total(eva.kernel_eva_attention(*a, 256, 2))
        args = 3 * [shaped(4, 512)] + 2 * [shaped(4, 256)]
    else:  # q and k heads first, v as its projection writes it
        T = int(name.rsplit("_t", 1)[1])
        fn = lambda q, k, v: total(eva.causal_attention(q, k, v, 0.25))
        args = [shaped(8, T), shaped(2, T), shaped(T, 2)]
    grads = jax.vmap(jax.grad(fn, argnums=tuple(range(len(args)))))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = str(jax.make_jaxpr(grads)(*args))
    assert text.count("pallas_call") >= 2
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(KERNELS_AT_PARENT))
def test_the_calls_without_a_window_are_the_parents_kernels(name):
    eva._differentiable.cache_clear()
    assert kernel_digest(name) == KERNELS_AT_PARENT[name]


# ---- which family a call takes


# (S, T, H, KV, D) of the attention calls the accepted cells make, the model
# check's among them, and whether our kernels take them.
ACCEPTED_CALLS = {
    "mistral_t4096": ((1, 4096, 32, 8, 128), True),
    "mistral_t512": ((8, 512, 32, 8, 128), True),
    "mistral_check": ((1, 256, 32, 8, 128), True),
    "olmoe_t4096": ((1, 4096, 16, 16, 128), True),
    "jamba_t4096": ((1, 4096, 20, 1, 128), True),
    "lfm2_t4096": ((1, 4096, 32, 8, 64), False),
}


@pytest.mark.parametrize("call", ACCEPTED_CALLS)
def test_a_call_of_an_accepted_cell_takes_the_branch_it_took(
    call, monkeypatch
):
    """Without a window the dispatcher takes the family it took, and hands
    ``causal_attention`` no window."""
    (S, T, H, KV, d), ours = ACCEPTED_CALLS[call]
    seen = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        eva, "causal_attention",
        lambda q, k, v, scale, *a, **kw: seen.append((a, kw)) or turned(q),
    )
    shaped = lambda heads: jax.ShapeDtypeStruct((S, T, heads, d), jnp.bfloat16)
    attend = functools.partial(single_device_attention, causal=True)
    if ours:
        jax.eval_shape(attend, shaped(H), shaped(KV), shaped(KV))
        assert seen == [((), {})]
        return
    from jax.experimental.pallas.ops.tpu import flash_attention as library

    called = []
    monkeypatch.setattr(
        library, "flash_attention",
        lambda q, k, v, **kw: called.append(kw) or q,
    )
    jax.eval_shape(attend, shaped(H), shaped(KV), shaped(KV))
    assert not seen and len(called) == 1 and called[0]["causal"] is True


def test_a_windowed_call_takes_our_kernels_or_the_masked_einsum(monkeypatch):
    seen = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        eva, "causal_attention",
        lambda q, k, v, scale, *a, **kw: seen.append(kw) or turned(q),
    )
    shaped = lambda T, heads, d=128: jax.ShapeDtypeStruct(
        (1, T, heads, d), jnp.bfloat16
    )
    attend = lambda *a, **kw: jax.eval_shape(
        functools.partial(single_device_attention, causal=True, **kw), *a
    )
    attend(shaped(4096, 32), shaped(4096, 4), shaped(4096, 4), window=1024)
    assert seen == [dict(window=1024)]
    # A window off the lanes, a head of 64: the library's kernels know no
    # window, so the masked einsum, and never the library's.
    from jax.experimental.pallas.ops.tpu import flash_attention as library

    monkeypatch.setattr(
        library, "flash_attention", lambda *a, **kw: pytest.fail("no window")
    )
    for args, window in (
        (3 * [shaped(512, 4)], 100), (3 * [shaped(512, 4, 64)], 128),
    ):
        out = attend(*args, window=window)
        assert out.shape == args[0].shape and len(seen) == 1
        with pytest.raises(ValueError, match="no flash kernel takes a window"):
            attend(*args, window=window, impl="flash")
    assert eva.causal_kernels_take(4096, 128, 32, 4, jnp.bfloat16, 1024)
    assert eva.causal_kernels_take(8192, 128, 32, 4, jnp.bfloat16, 1024)
    assert not eva.causal_kernels_take(4096, 128, 32, 4, jnp.bfloat16, 1000)
    assert not eva.causal_kernels_take(4096, 128, 32, 4, jnp.bfloat16, 0)
    assert not eva.causal_kernels_take(16384, 128, 32, 4, jnp.bfloat16, 1024)


def test_a_window_is_a_causal_calls():
    q = jnp.zeros((1, 128, 2, 128))
    with pytest.raises(ValueError, match="a causal call"):
        single_device_attention(q, q, q, causal=False, window=128)
    with pytest.raises(ValueError, match="at least one key"):
        single_device_attention(q, q, q, causal=True, window=0)


def test_the_windowed_calls_carry_names_under_the_readers_prefixes():
    """``tracered.FLASH_KERNEL`` counts them, the window's own reader tells
    them from the calls over the whole triangle."""
    from benchmark import tracered
    from benchmark.layer_metrics import window_kernel_ms_per_step as reader

    assert eva.BAND_KERNEL_NAMES == (
        "flash_attention_fwd_dpwa_window", "flash_mha_bwd_dpwa_window"
    )
    for name in eva.BAND_KERNEL_NAMES:
        assert re.search(tracered.FLASH_KERNEL, name + ".3")
        assert re.search(reader.WINDOW_KERNEL, name + ".3")
    for name in eva.KERNEL_NAMES[False]:
        assert re.search(tracered.FLASH_KERNEL, name + ".3")
        assert not re.search(reader.WINDOW_KERNEL, name + ".3")
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: eva.causal_attention(
                q, k, v, 0.1, window=128
            ).astype(jnp.float32).sum()
        ))(
            *2 * [jax.ShapeDtypeStruct((1, 2, 256, D), jnp.bfloat16)],
            jax.ShapeDtypeStruct((1, 256, 2, D), jnp.bfloat16),
        ))
    for name in eva.BAND_KERNEL_NAMES:
        assert name in text
