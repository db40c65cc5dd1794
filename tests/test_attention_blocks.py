"""Which kernels `single_device_attention` runs a shape on (PR 46: causal
attention with one head size that is a multiple of 128 on the EVA core's two
kernels, `ops/eva.causal_attention`; every other shape on the library's
flash kernels), the block sizes it hands the library (forward, dkv, dq): the
rule over the shapes the gate lets through, and either family's kernels
*with the chosen blocks*, interpreted, against the dense branch.  CPU only;
the times are the chip's business (PERF.md, PR 25 and PR 46)."""

import contextlib
import dataclasses
import functools

import re

import jax
import jax.numpy as jnp
import pytest

from dpwa_tpu.ops import eva
from dpwa_tpu.ops.ulysses import _flash_block_sizes, single_device_attention

# The blocks that span the kernels' grids, less dq's k-major: that one is
# held at 128 because the library widens `di` in HBM by it.
GRID_BLOCKS = (
    "block_q", "block_k_major",
    "block_q_major_dkv", "block_k_major_dkv",
    "block_q_dq",
)


@pytest.mark.parametrize("head_dim", [128, 256, 512])
@pytest.mark.parametrize(
    "T",
    [128, 256, 384, 512, 640, 768, 1024, 1536, 2048, 2560, 3072, 4096, 8192,
     16384],
)
def test_the_rule_gives_blocks_the_library_takes(T, head_dim):
    """Every block a multiple of 128, at most T and dividing T (what
    `_verify_block` raises on), minors dividing majors (the dataclass
    itself checks), backward blocks all set."""
    blocks = _flash_block_sizes(T, head_dim)
    assert blocks.has_backward_blocks
    fields = dataclasses.asdict(blocks)
    assert fields.pop("block_b") == 1  # divides every batch
    for name, b in fields.items():
        assert b % 128 == 0 and 128 <= b <= T and T % b == 0, (name, b, T)


@pytest.mark.parametrize("T,head_dim", [
    (512, 128), (4096, 128),  # the Mistral cells, OLMoE, Jamba's one layer
    (4096, 64),               # LFM2, on the library
    (4096, 256),              # latent attention's 192 / 128, padded
])
def test_the_mechanism_engages_at_the_cells_lengths(T, head_dim):
    """Read without a chip: the grid shrinks only where the blocks that span
    it grow.  On the chip the library's backward kernels carry the same
    numerals in their names in the trace, and ours their own names."""
    if head_dim == 128:
        assert eva.causal_kernels_take(T, head_dim, 32, 8, jnp.bfloat16)
        window = eva.causal_window(T)
        assert window == min(T, 2048) and eva.sub_block(window) == 512
        return
    blocks = dataclasses.asdict(_flash_block_sizes(T, head_dim))
    for name in GRID_BLOCKS:
        assert blocks[name] >= 512, (name, blocks[name])


def test_the_causal_kernels_names_carry_the_readers_two_prefixes():
    """What the accepted readers of a trace know a flash-attention kernel by
    (`benchmark/tracered.FLASH_KERNEL`, two prefixes) matches our causal
    kernels and not the EVA core's, which have readers of their own."""
    from benchmark import tracered

    forward, backward = eva.KERNEL_NAMES[False]
    assert forward.startswith("flash_attention")
    assert backward.startswith("flash_mha_bwd")
    assert all(re.match(tracered.FLASH_KERNEL, n) for n in (forward, backward))
    for name in eva.KERNEL_NAMES[True]:
        assert name.startswith("dpwa_eva_attention")
        assert not re.match(tracered.FLASH_KERNEL, name)


def _families(monkeypatch):
    """Both families replaced by recorders: `(seen, call)`."""
    from jax.experimental.pallas.ops.tpu import flash_attention as library

    seen = []

    def ours(q, k, v, sm_scale):
        seen.append(("ours", q.shape, k.shape, v.shape, sm_scale))
        return jnp.zeros_like(q).swapaxes(1, 2)  # o comes positions first

    def theirs(q, k, v, **kwargs):
        seen.append(("library", q.shape, k.shape, v.shape, kwargs))
        return jnp.zeros_like(q)

    monkeypatch.setattr(eva, "causal_attention", ours)
    monkeypatch.setattr(library, "flash_attention", theirs)
    return seen


@pytest.mark.parametrize("T,heads,kv,d,dv,causal,family", [
    (512, 8, 2, 128, 128, True, "ours"),
    (4096, 8, 2, 128, 128, True, "ours"),
    (256, 4, 4, 128, 128, True, "ours"),
    (256, 4, 1, 256, 256, True, "ours"),
    (256, 8, 2, 64, 64, True, "library"),      # LFM2's head, as it is
    (256, 4, 4, 192, 128, True, "library"),    # latent attention, padded
    (256, 8, 2, 96, 96, True, "library"),      # padded to 128
    (256, 8, 2, 128, 128, False, "library"),   # no mask: not the core's
    (16384, 8, 2, 128, 128, True, "library"),  # `vmem_need` refuses dk, dv
    (4096, 4, 4, 512, 512, True, "library"),
    (200, 4, 4, 128, 128, True, "einsum"),     # no block divides T
])
def test_which_family_a_shape_takes(
    T, heads, kv, d, dv, causal, family, monkeypatch
):
    """Under `auto` on a TPU.  The library is handed `_flash_block_sizes` of
    the call's own `T` and padded head, keys repeated to the query heads;
    ours the grouped keys as they are, `q` and `k` heads first and `v` as it
    came (PR 54: each where its neighbour in the model holds it)."""
    seen = _families(monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shaped = lambda h, size: jax.ShapeDtypeStruct((1, T, h, size), jnp.bfloat16)
    out = jax.eval_shape(
        functools.partial(single_device_attention, causal=causal),
        shaped(heads, d), shaped(kv, d), shaped(kv, dv),
    )
    assert out.shape == (1, T, heads, dv)
    if family == "einsum":
        assert not seen
        return
    (name, q_shape, k_shape, v_shape, rest), = seen
    assert name == family
    if family == "ours":
        assert q_shape == (1, heads, T, d) and k_shape == (1, kv, T, d)
        assert v_shape == (1, T, kv, d)
        assert rest == float(1.0 / d ** 0.5)
    else:
        padded = d if d == dv == 64 else -(-max(d, dv) // 128) * 128
        assert q_shape == k_shape == v_shape == (1, heads, T, padded)
        assert rest["causal"] is causal
        assert rest["block_sizes"] == _flash_block_sizes(T, padded)


def test_the_forced_path_raises_where_neither_family_tiles(monkeypatch):
    """`impl="flash"` at a `T` no block divides: ours does not take it and
    the library's block rule refuses it, as before."""
    seen = _families(monkeypatch)
    q = jnp.zeros((1, 200, 4, 128))
    with pytest.raises(ValueError):
        single_device_attention(q, q, q, causal=True, impl="flash")
    assert not seen


def _qkv(T, head, lead=()):
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (*lead, 1, T, 4, head), jnp.float32)
    k, v = (
        jax.random.normal(kk, (*lead, 1, T, 2, head), jnp.float32)
        for kk in ks[1:3]
    )
    return q, k, v, jax.random.normal(ks[3], q.shape, jnp.float32)


@pytest.mark.parametrize("stacked", [False, True], ids=["plain", "vmap2"])
@pytest.mark.parametrize("T", [256, 512, 1024])
@pytest.mark.parametrize("head", [128, 64], ids=["ours128", "library64"])
def test_flash_with_the_chosen_blocks_agrees_with_dense(
    head, T, stacked, monkeypatch
):
    """Forward and gradients to q, k, v; causal, GQA 4/2, float32; plain and
    under `jax.vmap` over 2 as the stacked step runs it; a head of 128 on our
    kernels, a head of 64 on the library's."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu import flash_attention as library

    q, k, v, w = _qkv(T, head, lead=(2,) if stacked else ())
    interpreted = contextlib.nullcontext()
    if head == 128:
        monkeypatch.setattr(
            eva, "causal_attention",
            functools.partial(eva.causal_attention, interpret=True),
        )
    elif stacked:
        # jax 0.9.0's TPU interpreter zips the batched grid (five long)
        # against the kernel's four `dimension_semantics` and raises; Mosaic
        # does not.  The generic interpreter takes the batched call.
        monkeypatch.setattr(
            library.pl, "pallas_call",
            functools.partial(library.pl.pallas_call, interpret=True),
        )
    else:
        interpreted = pltpu.force_tpu_interpret_mode()

    def value_and_grads(impl):
        attn = functools.partial(
            single_device_attention, causal=True, impl=impl
        )
        if stacked:
            attn = jax.vmap(attn)
        loss = lambda q, k, v: jnp.sum(attn(q, k, v) * w)
        # One program each, and the first at rest before the second starts:
        # the TPU interpreter's callbacks run JAX operations of their own,
        # and an eager operation dispatched beside a kernel in flight can
        # wait on them for ever (seen under six test workers, PR 46).
        out = jax.block_until_ready(jax.jit(attn)(q, k, v))
        return (out, *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))

    with interpreted:
        got = jax.block_until_ready(value_and_grads("flash"))
    want = value_and_grads("dense")
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape
        err = float(jnp.max(jnp.abs(a - b)))
        assert err <= 2e-4, f"{name} off the dense branch by {err} at T {T}"
