"""Ulysses (all-to-all head-sharded) sequence parallelism — CPU parity.

Built from differentiable collectives + library attention, so there is
no hand-written VJP to verify — parity with full attention (forward AND
autodiff gradients) plus integration with the 2-D gossip train step is
the whole contract.  Off-TPU the per-device attention is the dense
einsum; on TPU it is the same Pallas flash kernel as the single-device
model path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dpwa_tpu.ops.ring_attention import full_attention_reference
from dpwa_tpu.ops.ulysses import ulysses_attention_local


def qkv(B=1, T=32, H=4, D=8, seed=0, KV=None):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    kvh = KV or H
    k = jax.random.normal(ks[1], (B, T, kvh, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, kvh, D), jnp.float32)
    return q, k, v


def run_ulysses(q, k, v, sp, causal=True):
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    spec = P(None, "sp", None, None)
    return shard_map(
        lambda a, b, c: ulysses_attention_local(
            a, b, c, "sp", causal=causal
        ),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
    )(q, k, v)


@pytest.mark.parametrize("sp", [2, 4])
@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_full_attention(sp, causal):
    q, k, v = qkv(T=32)
    want = np.asarray(full_attention_reference(q, k, v, causal=causal))
    got = np.asarray(run_ulysses(q, k, v, sp, causal=causal))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_ulysses_gradients_match_autodiff():
    q, k, v = qkv(T=16, H=4, D=8, seed=2)
    sp = 4
    g = jax.grad(
        lambda q, k, v: jnp.sum(run_ulysses(q, k, v, sp) ** 2),
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
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6,
            err_msg=f"d{name}",
        )


@pytest.mark.parametrize("KV", [2, 1])
def test_ulysses_grouped_kv(KV):
    """KV % sp == 0 ships grouped K/V through the all-to-all; otherwise
    heads expand first.  Both must equal the expanded reference."""
    q, k, v = qkv(T=32, H=8, D=8, KV=KV, seed=5)
    sp = 2
    got = np.asarray(run_ulysses(q, k, v, sp))
    k_rep = jnp.repeat(k, 8 // KV, axis=2)
    v_rep = jnp.repeat(v, 8 // KV, axis=2)
    want = np.asarray(
        full_attention_reference(q, k_rep, v_rep, causal=True)
    )
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_ulysses_rejects_unshardable_heads():
    q, k, v = qkv(T=32, H=3, D=8)
    with pytest.raises(ValueError, match="divisible by sp"):
        run_ulysses(q, k, v, 2)


def test_ulysses_in_llama_sp_train_step():
    """sp_strategy="a2a" through the full 2-D gossip train step equals
    the unsharded reference trajectory (same bar the ring strategies
    clear)."""
    import optax

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.models.llama import Llama, LlamaConfig
    from dpwa_tpu.parallel.ici import IciTransport
    from dpwa_tpu.parallel.mesh import make_mesh
    from dpwa_tpu.train import (
        init_gossip_state,
        make_gossip_train_step,
        stack_params,
    )
    from dpwa_tpu.train_sp import (
        init_gossip_sp_state,
        make_gossip_sp_train_step,
        make_sp_mesh,
        sp_batch_sharding,
    )

    n_peers, sp, b, t = 2, 4, 2, 32
    base = dict(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64,
    )
    rng = np.random.default_rng(9)
    toks = rng.integers(0, 64, (n_peers, b, t + 1)).astype(np.int32)
    inputs, targets = toks[..., :-1], toks[..., 1:]

    cfg = make_local_config(n_peers, schedule="ring")
    opt = optax.sgd(0.1, momentum=0.9)
    model0 = Llama(LlamaConfig(**base))
    p0 = model0.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    stacked = stack_params(p0, n_peers)

    ref_transport = IciTransport(
        cfg, mesh=make_mesh(cfg, devices=jax.devices()[:n_peers])
    )
    ref_state = init_gossip_state(stacked, opt, ref_transport)

    def ref_loss(params, batch):
        x, y = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            model0.apply(params, x), y
        ).mean()

    ref_step = make_gossip_train_step(ref_loss, opt, ref_transport)

    sp_model = Llama(
        LlamaConfig(**base, sp_axis="sp", sp_strategy="a2a")
    )
    mesh = make_sp_mesh(cfg, sp)
    sp_transport = IciTransport(cfg, mesh=mesh)
    sp_state = init_gossip_sp_state(stacked, opt, sp_transport)

    def sp_loss(params, batch):
        x, y = batch
        losses = optax.softmax_cross_entropy_with_integer_labels(
            sp_model.apply(params, x), y
        )
        return losses.sum(), jnp.float32(losses.size)

    sp_step = make_gossip_sp_train_step(sp_loss, opt, sp_transport)
    sh = sp_batch_sharding(mesh)
    for k in range(3):
        ref_state, ref_losses, _ = ref_step(
            ref_state, (jnp.asarray(inputs), jnp.asarray(targets))
        )
        sp_state, sp_losses, _ = sp_step(
            sp_state,
            (jax.device_put(inputs, sh), jax.device_put(targets, sh)),
        )
        np.testing.assert_allclose(
            np.asarray(ref_losses), np.asarray(sp_losses),
            rtol=2e-4, atol=2e-5,
        )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-3, atol=3e-4
        ),
        ref_state.params,
        sp_state.params,
    )


def test_config_rejects_a2a_with_zigzag():
    from dpwa_tpu.models.llama import LlamaConfig

    with pytest.raises(ValueError, match="zigzag layout only applies"):
        LlamaConfig(sp_axis="sp", sp_strategy="a2a", sp_layout="zigzag")
