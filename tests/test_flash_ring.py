"""Flash-ring attention (Pallas kernels in every ring hop) — CPU parity.

On this box the TPU kernels cannot run under pytest (forced-CPU mesh), so
these tests drive the flash-ring PATH with its jnp twin kernels
(``impl="flash"`` resolves to the twins off-TPU).  The twins share the
exact (o, lse) / global-residual contracts of the library Pallas kernels
(``jax.experimental.pallas.ops.tpu.flash_attention``'s ``p =
exp(s·scale − m)/l`` convention), so everything ABOVE the kernel — the
three-case ring causality, the logsumexp merge, the custom-vjp with
global residuals, dk/dv accumulation on the rotating block, GQA group
folding — is fully verified here; the TPU path swaps in kernels that are
library-tested against the same contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dpwa_tpu.ops.ring_attention import (
    full_attention_reference,
    ring_attention,
)


def qkv(B=2, T=32, H=4, D=16, seed=0, KV=None):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    kvh = KV or H
    k = jax.random.normal(ks[1], (B, T, kvh, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, kvh, D), jnp.float32)
    return q, k, v


def sp_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("sp",))


@pytest.mark.parametrize("n_sp", [2, 4, 8])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_ring_matches_full_attention(n_sp, causal):
    q, k, v = qkv(T=32)
    want = np.asarray(full_attention_reference(q, k, v, causal=causal))
    got = np.asarray(
        ring_attention(q, k, v, sp_mesh(n_sp), causal=causal, impl="flash")
    )
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_ring_gradients_match_autodiff(causal):
    """The custom-vjp (library bwd kernels fed GLOBAL residuals) must equal
    differentiating full attention — the core ring-flash identity."""
    q, k, v = qkv(B=1, T=16, H=2, D=8, seed=2)
    mesh = sp_mesh(4)

    g = jax.grad(
        lambda q, k, v: jnp.sum(
            ring_attention(q, k, v, mesh, causal=causal, impl="flash") ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(
            full_attention_reference(q, k, v, causal=causal) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=f"d{name}",
        )


def test_flash_ring_matches_xla_ring():
    """Same inputs, both ring implementations: identical outputs (both are
    exact attention; only the hop compute differs)."""
    q, k, v = qkv(T=64, seed=4)
    mesh = sp_mesh(8)
    a = np.asarray(ring_attention(q, k, v, mesh, impl="flash"))
    b = np.asarray(ring_attention(q, k, v, mesh, impl="xla"))
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_flash_ring_grouped_kv():
    """GQA through the flash ring: grouped K/V rotate, heads expand per
    hop, and dk/dv fold back to groups in the backward pass."""
    q, k, v = qkv(B=1, T=32, H=8, D=8, KV=2, seed=5)
    mesh = sp_mesh(4)
    got = np.asarray(ring_attention(q, k, v, mesh, impl="flash"))
    k_rep = jnp.repeat(k, 4, axis=2)
    v_rep = jnp.repeat(v, 4, axis=2)
    want = np.asarray(full_attention_reference(q, k_rep, v_rep))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    # Gradients: folded grouped dk/dv == summing the expanded reference.
    g = jax.grad(
        lambda q, k, v: jnp.sum(
            ring_attention(q, k, v, mesh, impl="flash") ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)

    def ref_loss(q, k, v):
        k_rep = jnp.repeat(k, 4, axis=2)
        v_rep = jnp.repeat(v, 4, axis=2)
        return jnp.sum(full_attention_reference(q, k_rep, v_rep) ** 2)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=f"d{name}",
        )


def test_flash_ring_first_block_causality():
    # Query block 0 must see only its own keys even though every KV block
    # rotates past it (the skip case must actually mask, not just weight).
    B, T, H, D = 1, 32, 2, 8
    q, k, v = qkv(B=B, T=T, H=H, D=D, seed=7)
    mesh = sp_mesh(4)
    out_full = np.asarray(ring_attention(q, k, v, mesh, impl="flash"))
    k2 = k.at[:, T // 4 :].set(0.0)
    v2 = v.at[:, T // 4 :].set(0.0)
    out_cut = np.asarray(ring_attention(q, k2, v2, mesh, impl="flash"))
    np.testing.assert_allclose(
        out_full[:, : T // 4], out_cut[:, : T // 4], rtol=1e-5, atol=1e-6
    )


def test_flash_ring_bf16_inputs():
    """bf16 q/k/v (the long-context training dtype): f32 accumulation
    inside, output back in bf16, close to the f32 reference."""
    q, k, v = qkv(B=1, T=32, H=2, D=8, seed=8)
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    mesh = sp_mesh(4)
    got = np.asarray(
        ring_attention(qb, kb, vb, mesh, impl="flash").astype(jnp.float32)
    )
    want = np.asarray(full_attention_reference(q, k, v))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_flash_ring_composes_with_peer_axis():
    """2-D (peers, sp) mesh: flash-ring inside each replica + gossip
    ppermute across peers — the long-context gossip layout."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from dpwa_tpu.ops.ring_attention import ring_attention_local

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("peers", "sp"))
    B, T, H, D = 1, 32, 2, 8
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (2, B, T, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (2, B, T, H, D), jnp.float32)

    def body(q, k, v):
        out = ring_attention_local(
            q[0], k[0], v[0], axis_name="sp", impl="flash"
        )
        merged = 0.5 * out + 0.5 * jax.lax.ppermute(
            out, "peers", perm=[(0, 1), (1, 0)]
        )
        return merged[None]

    spec = P("peers", None, "sp", None, None)
    out = shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
    merged = 0.5 * full_attention_reference(
        q[0], k[0], v[0]
    ) + 0.5 * full_attention_reference(q[1], k[1], v[1])
    for p in range(2):
        np.testing.assert_allclose(
            np.asarray(out[p]), np.asarray(merged), rtol=2e-4, atol=2e-5
        )


def test_private_pallas_api_signatures_pinned():
    """flash_ring.py calls three PRIVATE library functions the CPU suite
    cannot execute; pin their parameter lists so a jax upgrade that
    reorders or renames them fails HERE (on CPU) instead of only at TPU
    runtime inside a ring hop."""
    import inspect

    fa = pytest.importorskip(
        "jax.experimental.pallas.ops.tpu.flash_attention"
    )
    assert list(inspect.signature(fa._flash_attention_impl).parameters) == [
        "q", "k", "v", "ab", "segment_ids", "save_residuals", "causal",
        "sm_scale", "block_b", "block_q", "block_k_major", "block_k",
        "debug",
    ]
    assert list(inspect.signature(fa._flash_attention_bwd_dkv).parameters) == [
        "q", "k", "v", "ab", "segment_ids", "l", "m", "do", "di",
        "block_q_major", "block_q", "block_k_major", "block_k", "sm_scale",
        "causal", "mask_value", "debug",
    ]
    assert list(inspect.signature(fa._flash_attention_bwd_dq).parameters) == [
        "q", "k", "v", "ab", "segment_ids", "l", "m", "do", "di",
        "block_q_major", "block_k_major", "block_k", "sm_scale", "causal",
        "mask_value", "debug",
    ]
    assert hasattr(fa, "DEFAULT_MASK_VALUE")


def test_jnp_twins_match_library_reference():
    """The jnp twin kernels must reproduce the library's own reference
    implementation (same residual conventions the Pallas kernels honor) —
    this is the contract that lets the CPU tests stand in for the TPU
    kernels."""
    fa = pytest.importorskip(
        "jax.experimental.pallas.ops.tpu.flash_attention"
    )
    from dpwa_tpu.ops.flash_ring import _hop_fwd_jnp

    B, H, T, D = 1, 2, 16, 8
    ks = jax.random.split(jax.random.key(3), 3)
    q, k, v = (
        jax.random.normal(kk, (B, H, T, D), jnp.float32) for kk in ks
    )
    scale = 0.41
    for causal in (False, True):
        o_ref, l_ref, m_ref = fa.mha_reference_no_custom_vjp(
            q, k, v, None, None, causal=causal, sm_scale=scale,
            save_residuals=True,
        )
        o, lse = _hop_fwd_jnp(q, k, v, causal, scale)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(o_ref), rtol=2e-5, atol=2e-6
        )
        np.testing.assert_allclose(
            np.asarray(lse),
            np.asarray(m_ref + jnp.log(l_ref)),
            rtol=2e-5, atol=2e-6,
        )


def test_jnp_twin_q_chunking_is_exact():
    """Above _JNP_Q_CHUNK rows the twins process q in chunks (capping the
    score panel like the einsum hop's q-chunking); the chunked path must
    be bit-comparable to the one-panel math, fwd and bwd."""
    from dpwa_tpu.ops.flash_ring import (
        _JNP_Q_CHUNK,
        _hop_bwd_jnp,
        _hop_bwd_jnp_panel,
        _hop_fwd_jnp,
        _hop_fwd_jnp_panel,
    )

    B, H, D = 1, 2, 8
    scale = 0.3
    # Divisible AND remainder shapes: the non-divisible tail must go
    # through its own sub-chunk panel, never a full-T fallback.
    for T in (2 * _JNP_Q_CHUNK, _JNP_Q_CHUNK + 100):
        _check_chunking_shape(B, H, T, D, scale)


def _check_chunking_shape(B, H, T, D, scale):
    from dpwa_tpu.ops.flash_ring import (
        _hop_bwd_jnp,
        _hop_bwd_jnp_panel,
        _hop_fwd_jnp,
        _hop_fwd_jnp_panel,
    )

    ks = jax.random.split(jax.random.key(11), 5)
    q, k, v, do = (
        jax.random.normal(kk, (B, H, T, D), jnp.float32) for kk in ks[:4]
    )
    for causal in (False, True):
        o_c, lse_c = _hop_fwd_jnp(q, k, v, causal, scale)
        o_p, lse_p = _hop_fwd_jnp_panel(q, k, v, causal, scale, 0)
        np.testing.assert_allclose(
            np.asarray(o_c), np.asarray(o_p), rtol=1e-6, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(lse_c), np.asarray(lse_p), rtol=1e-6, atol=1e-6
        )
        di = jnp.sum(o_p * do, axis=-1)
        g_c = _hop_bwd_jnp(q, k, v, lse_p, do, di, causal, scale)
        g_p = _hop_bwd_jnp_panel(q, k, v, lse_p, do, di, causal, scale, 0)
        for a, b, name in zip(g_c, g_p, ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5,
                err_msg=name,
            )
