"""Ring attention == full attention, over a real sharded sequence axis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dpwa_tpu.ops.ring_attention import (
    full_attention_reference,
    ring_attention,
)


def qkv(B=2, T=32, H=4, D=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


def sp_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("sp",))


@pytest.mark.parametrize("n_sp", [2, 4, 8])
@pytest.mark.parametrize("causal", [True, False])
def test_matches_full_attention(n_sp, causal):
    q, k, v = qkv(T=32)
    want = np.asarray(full_attention_reference(q, k, v, causal=causal))
    got = np.asarray(
        ring_attention(q, k, v, sp_mesh(n_sp), causal=causal)
    )
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_long_sequence_multiblock():
    q, k, v = qkv(B=1, T=128, H=2, D=8, seed=3)
    want = np.asarray(full_attention_reference(q, k, v))
    got = np.asarray(ring_attention(q, k, v, sp_mesh(8)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_gradients_flow():
    q, k, v = qkv(B=1, T=16, H=2, D=8)
    mesh = sp_mesh(4)

    def loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention_reference(q, k, v) ** 2)

    g = jax.grad(loss)(q, k, v)
    g_ref = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(g_ref), rtol=5e-4, atol=5e-5
    )


@pytest.mark.parametrize("causal", [True, False])
def test_q_chunked_matches_full_attention(causal):
    # The flash-style inner loop (q_chunk) must be numerically equivalent
    # to the unchunked hop — forward AND backward (the chunk scan + remat
    # changes only memory, never math).
    q, k, v = qkv(T=32, seed=5)
    mesh = sp_mesh(4)
    want = np.asarray(full_attention_reference(q, k, v, causal=causal))
    got = np.asarray(
        ring_attention(q, k, v, mesh, causal=causal, q_chunk=4)
    )
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_q_chunked_gradients_match():
    q, k, v = qkv(B=1, T=16, H=2, D=8, seed=6)
    mesh = sp_mesh(4)

    g = jax.grad(
        lambda q, k, v: jnp.sum(
            ring_attention(q, k, v, mesh, q_chunk=2) ** 2
        )
    )(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(full_attention_reference(q, k, v) ** 2)
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(g_ref), rtol=5e-4, atol=5e-5
    )


def test_q_chunk_must_divide_block():
    q, k, v = qkv(T=32)
    with pytest.raises(ValueError, match="must divide"):
        ring_attention(q, k, v, sp_mesh(4), q_chunk=3)


def test_auto_q_chunk_policy():
    from dpwa_tpu.ops.ring_attention import _auto_q_chunk

    assert _auto_q_chunk(64) == 0  # short blocks: unchunked
    assert _auto_q_chunk(512) == 0
    assert _auto_q_chunk(1024) == 256
    assert _auto_q_chunk(4096) == 256
    assert _auto_q_chunk(768) == 256  # largest pow2 divisor <= 256
    assert _auto_q_chunk(1000) == 8
    assert _auto_q_chunk(999) == 0  # no even divisor: stay unchunked


def test_first_block_causality():
    # Query block 0 must see only keys 0..T_local-1 even though KV blocks
    # from every device rotate past it.
    B, T, H, D = 1, 32, 2, 8
    q, k, v = qkv(B=B, T=T, H=H, D=D, seed=7)
    out_full = np.asarray(ring_attention(q, k, v, sp_mesh(4)))
    # Changing the LAST 3/4 of keys/values must not affect the first 1/4 of
    # causal outputs.
    k2 = k.at[:, T // 4 :].set(0.0)
    v2 = v.at[:, T // 4 :].set(0.0)
    out_cut = np.asarray(ring_attention(q, k2, v2, sp_mesh(4)))
    np.testing.assert_allclose(
        out_full[:, : T // 4], out_cut[:, : T // 4], rtol=1e-5, atol=1e-6
    )


def test_composes_with_gossip_peer_axis():
    """2-D mesh (peers=2, sp=4): ring attention inside each replica's sp
    sub-axis, gossip ppermute across the peers axis — the combined layout
    for long-context gossip training."""
    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from dpwa_tpu.ops.ring_attention import ring_attention_local

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("peers", "sp"))
    B, T, H, D = 1, 32, 2, 8
    ks = jax.random.split(jax.random.key(0), 6)
    # Peer-stacked q/k/v: [n_peers, B, T, H, D]
    q = jax.random.normal(ks[0], (2, B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (2, B, T, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (2, B, T, H, D), jnp.float32)

    def body(q, k, v):
        # local: q [1, B, T/4, H, D] -> run sp ring attention per peer
        out = ring_attention_local(q[0], k[0], v[0], axis_name="sp")
        # gossip the attention outputs across peers (stand-in for the
        # parameter exchange: proves the two collectives coexist)
        merged = 0.5 * out + 0.5 * jax.lax.ppermute(
            out, "peers", perm=[(0, 1), (1, 0)]
        )
        return merged[None]

    spec = P("peers", None, "sp", None, None)
    out = shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)

    want0 = full_attention_reference(q[0], k[0], v[0])
    want1 = full_attention_reference(q[1], k[1], v[1])
    merged = 0.5 * want0 + 0.5 * want1
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(merged), rtol=2e-4, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(out[1]), np.asarray(merged), rtol=2e-4, atol=2e-5
    )


def test_grouped_kv_matches_repeated():
    """GQA: grouped K/V stay small through the ring (expanded per block
    inside the kernel) and must equal attention over pre-repeated K/V."""
    B, T, H, KV, D = 2, 32, 8, 2, 16
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, KV, D), jnp.float32)
    mesh = sp_mesh(4)
    got = np.asarray(ring_attention(q, k, v, mesh))
    k_rep = jnp.repeat(k, H // KV, axis=2)
    v_rep = jnp.repeat(v, H // KV, axis=2)
    want = np.asarray(full_attention_reference(q, k_rep, v_rep))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
