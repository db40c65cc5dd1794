"""Gossip + sequence-parallel training on a (peers, sp) 2-D mesh.

The correctness bar: the 2-D step (ring attention over ``sp``, gradient
psum, gossip over ``peers``) must produce the SAME training trajectory as
the plain 1-D gossip step running full attention on unsharded sequences —
sequence parallelism is a layout, not a different algorithm.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dpwa_tpu.config import make_local_config
from dpwa_tpu.models.llama import Llama, LlamaConfig
from dpwa_tpu.parallel.ici import IciTransport
from dpwa_tpu.parallel.mesh import make_mesh, peer_sharding
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

N_PEERS, SP, B, T = 2, 4, 2, 32

BASE_CFG = dict(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=64, max_seq_len=64,
)


def _data(seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, 64, (N_PEERS, B, T + 1)).astype(np.int32)
    return toks[..., :-1], toks[..., 1:]


def _init_params():
    mcfg = LlamaConfig(**BASE_CFG)  # sp_axis=None for init
    model = Llama(mcfg)
    p0 = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    return stack_params(p0, N_PEERS)


@pytest.mark.parametrize("wire", ["f32", "int8"])
def test_sp_matches_unsharded_training(wire):
    """2-D (peers x sp) trajectory equals the 1-D twin — including under
    the int8 stochastic-rounding wire: the exchange keys off the PEERS
    axis index only, so every sp-replicated copy of a leaf quantizes
    identically (a global-device-index key would silently desynchronize
    the sp replicas)."""
    inputs, targets = _data()
    cfg = make_local_config(N_PEERS, schedule="ring", wire_dtype=wire)
    opt = optax.sgd(0.1, momentum=0.9)
    stacked = _init_params()

    # --- Reference: 1-D gossip step, full attention, full sequences.
    ref_model = Llama(LlamaConfig(**BASE_CFG))
    ref_transport = IciTransport(
        cfg, mesh=make_mesh(cfg, devices=jax.devices()[:N_PEERS])
    )
    ref_state = init_gossip_state(stacked, opt, ref_transport)

    def ref_loss(params, batch):
        x, y = batch
        logits = ref_model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    ref_step = make_gossip_train_step(ref_loss, opt, ref_transport)

    # --- 2-D: same replicas, sequences sharded 4-way over sp.
    sp_model = Llama(LlamaConfig(**BASE_CFG, sp_axis="sp"))
    mesh = make_sp_mesh(cfg, SP)
    sp_transport = IciTransport(cfg, mesh=mesh)
    sp_state = init_gossip_sp_state(stacked, opt, sp_transport)

    def sp_loss(params, batch):
        x, y = batch  # this device's sequence block
        logits = sp_model.apply(params, x)
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        return losses.sum(), jnp.float32(losses.size)

    sp_step = make_gossip_sp_train_step(sp_loss, opt, sp_transport)
    sh = sp_batch_sharding(mesh)

    for k in range(3):
        ref_state, ref_losses, ref_info = ref_step(
            ref_state, (jnp.asarray(inputs), jnp.asarray(targets))
        )
        sp_state, sp_losses, sp_info = sp_step(
            sp_state,
            (
                jax.device_put(inputs, sh),
                jax.device_put(targets, sh),
            ),
        )
        np.testing.assert_array_equal(
            np.asarray(ref_info.partner), np.asarray(sp_info.partner)
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


def test_sp_mesh_shape_and_validation():
    cfg = make_local_config(2)
    mesh = make_sp_mesh(cfg, 4)
    assert dict(mesh.shape) == {"peers": 2, "sp": 4}
    with pytest.raises(RuntimeError, match="needs 16 devices"):
        make_sp_mesh(cfg, 8)
    # A 1-D transport is rejected by the sp step builder.
    t = IciTransport(cfg, mesh=make_mesh(cfg, devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match="no 'sp' axis"):
        make_gossip_sp_train_step(lambda p, b: (0.0, 1.0), optax.sgd(0.1), t)


def test_sp_rope_positions_are_global():
    """A model with sp_axis must see GLOBAL rope positions: compare its
    logits (through the sp step's forward) against the unsharded model —
    if positions restarted at 0 per block, logits diverge wildly."""
    inputs, targets = _data(seed=3)
    cfg = make_local_config(N_PEERS, schedule="ring")
    mesh = make_sp_mesh(cfg, SP)
    sp_model = Llama(LlamaConfig(**BASE_CFG, sp_axis="sp"))
    ref_model = Llama(LlamaConfig(**BASE_CFG))
    params = jax.tree.map(lambda v: v[0], _init_params())

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def fwd(x):
        return sp_model.apply(params, x[0])[None]

    out = shard_map(
        fwd, mesh=mesh,
        in_specs=P("peers", None, "sp"),
        out_specs=P("peers", None, "sp", None),
    )(jnp.asarray(inputs))
    want = ref_model.apply(params, jnp.asarray(inputs[0]))
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(want), rtol=2e-3, atol=2e-3
    )


def test_sp_lora_subset_exchange_matches_1d():
    """Config 5's actual long-context layout (BASELINE.json:11): LoRA
    adapters gossip over ``peers`` while sequences shard over ``sp``.
    Base weights must stay bit-identical to init (frozen AND never
    exchanged), and the whole trajectory must match the 1-D LoRA step."""
    from dpwa_tpu.models.llama import lora_filter, lora_optimizer
    from dpwa_tpu.train import init_params_per_peer
    from dpwa_tpu.utils.pytree import partition

    lcfg = dict(BASE_CFG, lora_rank=4)
    inputs, targets = _data(seed=5)
    cfg = make_local_config(N_PEERS, schedule="ring")

    init = lambda k: Llama(LlamaConfig(**lcfg)).init(
        k, jnp.zeros((1, 8), jnp.int32)
    )
    stacked = init_params_per_peer(init, jax.random.key(4), N_PEERS)
    opt = lora_optimizer(
        optax.adam(1e-2), jax.tree.map(lambda v: v[0], stacked)
    )

    # --- 1-D reference: full attention, LoRA-only exchange.
    ref_model = Llama(LlamaConfig(**lcfg))
    ref_transport = IciTransport(
        cfg, mesh=make_mesh(cfg, devices=jax.devices()[:N_PEERS])
    )
    ref_state = init_gossip_state(stacked, opt, ref_transport)

    def ref_loss(params, batch):
        x, y = batch
        logits = ref_model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    ref_step = make_gossip_train_step(
        ref_loss, opt, ref_transport, exchange_filter=lora_filter
    )

    # --- 2-D: ring attention over sp, LoRA-only exchange over peers.
    sp_model = Llama(LlamaConfig(**lcfg, sp_axis="sp"))
    mesh = make_sp_mesh(cfg, SP)
    sp_transport = IciTransport(cfg, mesh=mesh)
    sp_state = init_gossip_sp_state(stacked, opt, sp_transport)

    def sp_loss(params, batch):
        x, y = batch
        logits = sp_model.apply(params, x)
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        return losses.sum(), jnp.float32(losses.size)

    sp_step = make_gossip_sp_train_step(
        sp_loss, opt, sp_transport, exchange_filter=lora_filter
    )
    sh = sp_batch_sharding(mesh)

    initial = jax.tree.map(np.asarray, stacked)
    for _ in range(3):
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
    final = jax.tree.map(np.asarray, sp_state.params)
    _, init_rest = partition(initial, lora_filter)
    fin_sel, fin_rest = partition(final, lora_filter)
    # Base weights bit-identical on every peer.
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, b), init_rest, fin_rest
    )
    # Trajectory parity with the 1-D LoRA step (fp tolerance: the sp
    # forward sums in a different order).
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-3, atol=3e-4
        ),
        ref_state.params,
        sp_state.params,
    )
    assert any(
        not np.array_equal(a, b)
        for a, b in zip(
            jax.tree.leaves(partition(initial, lora_filter)[0]),
            jax.tree.leaves(fin_sel),
        )
    )


def test_sp_ranks_of_a_replica_stay_identical():
    """The step's shard_map is unchecked, so nothing proves statically that
    what leaves under ``P(peers)`` is the same on every sp rank.  Pin it:
    after a step (explicit gradient psum over ``sp``), the sp copies of
    every parameter shard are bit-identical."""
    inputs, targets = _data(seed=7)
    cfg = make_local_config(N_PEERS, schedule="ring")
    sp_model = Llama(LlamaConfig(**BASE_CFG, sp_axis="sp"))
    mesh = make_sp_mesh(cfg, SP)
    transport = IciTransport(cfg, mesh=mesh)
    state = init_gossip_sp_state(_init_params(), optax.sgd(0.1), transport)

    def sp_loss(params, batch):
        x, y = batch
        losses = optax.softmax_cross_entropy_with_integer_labels(
            sp_model.apply(params, x), y
        )
        return losses.sum(), jnp.float32(losses.size)

    step = make_gossip_sp_train_step(sp_loss, optax.sgd(0.1), transport)
    sh = sp_batch_sharding(mesh)
    state, losses, info = step(
        state, (jax.device_put(inputs, sh), jax.device_put(targets, sh))
    )
    assert np.all(np.isfinite(np.asarray(losses)))
    for leaf in jax.tree.leaves(state.params):
        by_peer = {}
        for shard in leaf.addressable_shards:
            by_peer.setdefault(shard.index[0].start, []).append(
                np.asarray(shard.data)
            )
        assert len(by_peer) == N_PEERS
        for copies in by_peer.values():
            assert len(copies) == SP
            for c in copies[1:]:
                np.testing.assert_array_equal(copies[0], c)


def test_sp_overlap_matches_unsharded_overlap():
    """overlap=True on the 2-D step: same trajectory as the 1-D overlap
    step (stale-publish exchange), sequences sharded over sp."""
    inputs, targets = _data(seed=9)
    cfg = make_local_config(N_PEERS, schedule="ring")
    opt = optax.sgd(0.1, momentum=0.9)
    stacked = _init_params()

    ref_model = Llama(LlamaConfig(**BASE_CFG))
    ref_transport = IciTransport(
        cfg, mesh=make_mesh(cfg, devices=jax.devices()[:N_PEERS])
    )
    ref_state = init_gossip_state(stacked, opt, ref_transport)

    def ref_loss(params, batch):
        x, y = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            ref_model.apply(params, x), y
        ).mean()

    ref_step = make_gossip_train_step(
        ref_loss, opt, ref_transport, overlap=True
    )

    sp_model = Llama(LlamaConfig(**BASE_CFG, sp_axis="sp"))
    mesh = make_sp_mesh(cfg, SP)
    sp_transport = IciTransport(cfg, mesh=mesh)
    sp_state = init_gossip_sp_state(stacked, opt, sp_transport)

    def sp_loss(params, batch):
        x, y = batch
        losses = optax.softmax_cross_entropy_with_integer_labels(
            sp_model.apply(params, x), y
        )
        return losses.sum(), jnp.float32(losses.size)

    sp_step = make_gossip_sp_train_step(
        sp_loss, opt, sp_transport, overlap=True
    )
    sh = sp_batch_sharding(mesh)
    for _ in range(3):
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


def test_sp_model_state_matches_1d():
    """model_state on the sp path: each sp rank computes statistics on its
    own block, the step pmeans them over sp — the trajectory (params AND
    state) must match the 1-D with_state step on full sequences."""
    from dpwa_tpu.train import make_gossip_train_step_with_state
    from dpwa_tpu.train_sp import make_gossip_sp_train_step_with_state

    V, D = 64, 16
    inputs, targets = _data(seed=11)
    cfg = make_local_config(N_PEERS, schedule="ring")
    opt = optax.sgd(0.1)

    k = jax.random.key(13)
    w0 = jax.random.normal(k, (V, D)) * 0.05
    stacked = stack_params({"w": w0}, N_PEERS)
    stacked_ms = stack_params({"h_mean": jnp.zeros(D)}, N_PEERS)

    def fwd(params, x):
        h = params["w"][x]  # [B, T_loc, D]
        logits = h @ params["w"].T
        return h, logits

    # --- 1-D reference on full sequences.
    ref_transport = IciTransport(
        cfg, mesh=make_mesh(cfg, devices=jax.devices()[:N_PEERS])
    )
    ref_state = init_gossip_state(stacked, opt, ref_transport, stacked_ms)

    def ref_loss(params, model_state, batch):
        x, y = batch
        h, logits = fwd(params, x)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()
        new_ms = {"h_mean": 0.9 * model_state["h_mean"] + 0.1 * h.mean((0, 1))}
        return loss, new_ms

    ref_step = make_gossip_train_step_with_state(ref_loss, opt, ref_transport)

    # --- 2-D: same math per block, stats pmean'd over sp.
    mesh = make_sp_mesh(cfg, SP)
    sp_transport = IciTransport(cfg, mesh=mesh)
    sp_state = init_gossip_sp_state(stacked, opt, sp_transport, stacked_ms)

    def sp_loss(params, model_state, batch):
        x, y = batch
        h, logits = fwd(params, x)
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        new_ms = {"h_mean": 0.9 * model_state["h_mean"] + 0.1 * h.mean((0, 1))}
        return (losses.sum(), jnp.float32(losses.size)), new_ms

    sp_step = make_gossip_sp_train_step_with_state(sp_loss, opt, sp_transport)
    sh = sp_batch_sharding(mesh)
    for _ in range(3):
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
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        (ref_state.params, ref_state.model_state),
        (sp_state.params, sp_state.model_state),
    )


def test_sp_flash_ring_matches_unsharded_training():
    """The flash-ring path (attn_impl="flash": custom-vjp hops, jnp twins
    on CPU) inside the FULL 2-D sp train step — gossip exchange, sp
    gradient sum, optimizer — must reproduce the unsharded reference
    step, exactly like the default einsum-hop path does.  This is the
    integration the ring-only parity tests cannot see."""
    inputs, targets = _data(seed=3)
    cfg = make_local_config(N_PEERS, schedule="ring")
    opt = optax.sgd(0.1, momentum=0.9)
    stacked = _init_params()

    ref_model = Llama(LlamaConfig(**BASE_CFG))
    ref_transport = IciTransport(
        cfg, mesh=make_mesh(cfg, devices=jax.devices()[:N_PEERS])
    )
    ref_state = init_gossip_state(stacked, opt, ref_transport)

    def ref_loss(params, batch):
        x, y = batch
        logits = ref_model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    ref_step = make_gossip_train_step(ref_loss, opt, ref_transport)

    sp_model = Llama(
        LlamaConfig(**BASE_CFG, sp_axis="sp", attn_impl="flash")
    )
    mesh = make_sp_mesh(cfg, SP)
    sp_transport = IciTransport(cfg, mesh=mesh)
    sp_state = init_gossip_sp_state(stacked, opt, sp_transport)

    def sp_loss(params, batch):
        x, y = batch
        logits = sp_model.apply(params, x)
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, y)
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


def test_sp_zigzag_matches_unsharded_training():
    """The zigzag (causal-load-balanced) layout through the FULL 2-D sp
    train step: tokens/targets zigzag-sharded, rope positions supplied by
    the model, attention through ops/zigzag_ring.py — must reproduce the
    unsharded reference trajectory exactly like the contiguous layout
    does (the layout changes work DISTRIBUTION, never math)."""
    from dpwa_tpu.ops.zigzag_ring import zigzag_shard

    inputs, targets = _data(seed=5)
    cfg = make_local_config(N_PEERS, schedule="ring")
    opt = optax.sgd(0.1, momentum=0.9)
    stacked = _init_params()

    ref_model = Llama(LlamaConfig(**BASE_CFG))
    ref_transport = IciTransport(
        cfg, mesh=make_mesh(cfg, devices=jax.devices()[:N_PEERS])
    )
    ref_state = init_gossip_state(stacked, opt, ref_transport)

    def ref_loss(params, batch):
        x, y = batch
        logits = ref_model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    ref_step = make_gossip_train_step(ref_loss, opt, ref_transport)

    sp_model = Llama(
        LlamaConfig(**BASE_CFG, sp_axis="sp", sp_layout="zigzag")
    )
    mesh = make_sp_mesh(cfg, SP)
    sp_transport = IciTransport(cfg, mesh=mesh)
    sp_state = init_gossip_sp_state(stacked, opt, sp_transport)

    def sp_loss(params, batch):
        x, y = batch
        logits = sp_model.apply(params, x)
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        return losses.sum(), jnp.float32(losses.size)

    sp_step = make_gossip_sp_train_step(sp_loss, opt, sp_transport)
    sh = sp_batch_sharding(mesh)
    # The ONLY caller-side difference from the contiguous layout: the
    # global sequence axis is zigzag-permuted before sharding.
    zz_inputs = np.asarray(zigzag_shard(jnp.asarray(inputs), SP, axis=2))
    zz_targets = np.asarray(zigzag_shard(jnp.asarray(targets), SP, axis=2))

    for k in range(3):
        ref_state, ref_losses, _ = ref_step(
            ref_state, (jnp.asarray(inputs), jnp.asarray(targets))
        )
        sp_state, sp_losses, _ = sp_step(
            sp_state,
            (jax.device_put(zz_inputs, sh), jax.device_put(zz_targets, sh)),
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
