"""The block sizes `single_device_attention` hands to the library flash
kernels (forward, dkv, dq): the rule over the shapes the gate lets through,
and the kernels *with the chosen blocks*, interpreted, against the dense
branch.  CPU only; the times are the chip's business (PERF.md, PR 25)."""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

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


@pytest.mark.parametrize("T", [512, 4096])
def test_the_mechanism_engages_at_the_cells_lengths(T):
    """Read without a chip: the grid shrinks only where the blocks that span
    it grow.  On the chip the same numerals stand in the backward kernels'
    names in the trace."""
    blocks = dataclasses.asdict(_flash_block_sizes(T, 128))
    for name in GRID_BLOCKS:
        assert blocks[name] >= 512, (name, blocks[name])


def _qkv(T, lead=()):
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (*lead, 1, T, 4, 128), jnp.float32)
    k, v = (
        jax.random.normal(kk, (*lead, 1, T, 2, 128), jnp.float32)
        for kk in ks[1:3]
    )
    return q, k, v, jax.random.normal(ks[3], q.shape, jnp.float32)


@pytest.mark.parametrize("stacked", [False, True], ids=["plain", "vmap2"])
@pytest.mark.parametrize("T", [256, 512, 1024])
def test_flash_with_the_chosen_blocks_agrees_with_dense(
    T, stacked, monkeypatch
):
    """Forward and gradients to q, k, v; causal, GQA 4/2, float32; plain and
    under `jax.vmap` over 2 as the stacked step runs it."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu import flash_attention as library

    q, k, v, w = _qkv(T, lead=(2,) if stacked else ())
    if stacked:
        # jax 0.9.0's TPU interpreter zips the batched grid (five long)
        # against the kernel's four `dimension_semantics` and raises; Mosaic
        # does not.  The generic interpreter takes the batched call.
        monkeypatch.setattr(
            library.pl, "pallas_call",
            functools.partial(library.pl.pallas_call, interpret=True),
        )
        interpreted = contextlib.nullcontext()
    else:
        interpreted = pltpu.force_tpu_interpret_mode()

    def value_and_grads(impl):
        attn = functools.partial(
            single_device_attention, causal=True, impl=impl
        )
        if stacked:
            attn = jax.vmap(attn)
        loss = lambda q, k, v: jnp.sum(attn(q, k, v) * w)
        out = attn(q, k, v)
        return (out, *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

    with interpreted:
        got = value_and_grads("flash")
    want = value_and_grads("dense")
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape
        err = float(jnp.max(jnp.abs(a - b)))
        assert err <= 2e-4, f"{name} off the dense branch by {err} at T {T}"
