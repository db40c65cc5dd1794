"""Zigzag (load-balanced causal) ring attention — CPU parity.

Same testing stance as tests/test_flash_ring.py: off-TPU the panels run
through the jnp twin kernels, which share the pallas kernels' exact
(o, lse)/global-residual contracts — so the stripe case analysis, the
per-stripe logsumexp merges, and the custom-vjp (including dk/dv
accumulation on the rotating block and GQA group folding) are fully
verified on the emulated mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dpwa_tpu.ops.ring_attention import full_attention_reference
from dpwa_tpu.ops.zigzag_ring import (
    zigzag_positions_local,
    zigzag_ring_attention_local,
    zigzag_shard,
    zigzag_unshard,
)


def qkv(B=1, T=64, H=4, D=16, seed=0, KV=None):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    kvh = KV or H
    k = jax.random.normal(ks[1], (B, T, kvh, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, kvh, D), jnp.float32)
    return q, k, v


def run_zigzag(q, k, v, sp):
    """Global-view driver: zigzag-shard, run the balanced ring, unshard."""
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    spec = P(None, "sp", None, None)
    zz = shard_map(
        lambda a, b, c: zigzag_ring_attention_local(a, b, c, "sp"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    out = zz(
        zigzag_shard(q, sp), zigzag_shard(k, sp), zigzag_shard(v, sp)
    )
    return zigzag_unshard(out, sp)


def test_zigzag_shard_roundtrip():
    x = jnp.arange(48).reshape(1, 48, 1)
    for sp in (2, 4):
        back = zigzag_unshard(zigzag_shard(x, sp), sp)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    with pytest.raises(ValueError, match="divisible"):
        zigzag_shard(jnp.zeros((1, 50, 1)), 4)


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_zigzag_matches_full_causal_attention(sp):
    q, k, v = qkv(T=64)
    want = np.asarray(full_attention_reference(q, k, v, causal=True))
    got = np.asarray(run_zigzag(q, k, v, sp))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_zigzag_gradients_match_autodiff():
    q, k, v = qkv(B=1, T=32, H=2, D=8, seed=2)
    sp = 4

    g = jax.grad(
        lambda q, k, v: jnp.sum(run_zigzag(q, k, v, sp) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(
            full_attention_reference(q, k, v, causal=True) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=f"d{name}",
        )


def test_zigzag_grouped_kv():
    q, k, v = qkv(B=1, T=32, H=8, D=8, KV=2, seed=5)
    sp = 4
    got = np.asarray(run_zigzag(q, k, v, sp))
    k_rep = jnp.repeat(k, 4, axis=2)
    v_rep = jnp.repeat(v, 4, axis=2)
    want = np.asarray(full_attention_reference(q, k_rep, v_rep, causal=True))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    g = jax.grad(
        lambda q, k, v: jnp.sum(run_zigzag(q, k, v, sp) ** 2),
        argnums=(1, 2),
    )(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(
            full_attention_reference(
                q, jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2),
                causal=True,
            ) ** 2
        ),
        argnums=(1, 2),
    )(q, k, v)
    for a, b, name in zip(g, g_ref, "kv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=f"d{name}",
        )


def test_zigzag_positions_cover_global_range():
    """Per-device positions must be exactly the zigzag-sharded global
    arange — the rope inputs that make the layout transparent to the
    model."""
    sp, T_local = 4, 16
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    pos = shard_map(
        lambda _: zigzag_positions_local(T_local, "sp")[None],
        mesh=mesh,
        in_specs=(P("sp"),),
        out_specs=P("sp"),
    )(jnp.zeros((sp,)))
    got = np.asarray(pos).reshape(-1)
    want = np.asarray(
        zigzag_shard(jnp.arange(sp * T_local)[None, :, None], sp)
    ).reshape(-1)
    np.testing.assert_array_equal(got, want)


def test_zigzag_matches_contiguous_ring():
    """Both ring layouts compute the same exact attention on the same
    GLOBAL inputs — only the work distribution differs."""
    from dpwa_tpu.ops.ring_attention import ring_attention

    q, k, v = qkv(T=64, seed=7)
    sp = 4
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    a = np.asarray(run_zigzag(q, k, v, sp))
    b = np.asarray(ring_attention(q, k, v, mesh, causal=True, impl="flash"))
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
