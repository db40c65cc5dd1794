"""Gossip + sequence-parallel training: one SPMD program on a 2-D mesh.

Long-context is first-class (SURVEY.md §5): a ``(peers, sp)`` mesh runs
gossip data-parallelism across replicas while EACH replica's sequences
span its ``sp`` sub-axis via exact ring attention
(:mod:`dpwa_tpu.ops.ring_attention`).  The whole step — sp-sharded
forward/backward (ring-attention ppermutes inside), gradient ``psum``
over ``sp``, optax update, and the gossip ``ppermute`` over ``peers`` —
is ONE ``shard_map`` program.  Layout:

- params: ``P(peers)`` — sharded over replicas, replicated over ``sp``;
- batch:  ``[n_peers, B, T]`` with ``P(peers, None, sp)`` — every device
  holds its replica's contiguous sequence block;
- collectives: ring-attention ``ppermute`` + gradient ``psum`` ride the
  ``sp`` sub-axis (ICI-local when sp maps to intra-host chips), the
  pairing ``ppermute`` rides ``peers``.

The gossip semantics (schedule pools, participation/fault draws,
interpolation, pull mode, bf16 wire) are exactly
:func:`dpwa_tpu.parallel.ici.gossip_exchange_local` — replicated over the
``sp`` axis, every sp rank of a replica executes the identical exchange.
The step composes with the full 1-D feature set
(:mod:`dpwa_tpu.train`): ``exchange_filter`` (config 5's long-context
LoRA layout — adapters gossip over ``peers`` while the frozen base rides
only the sp collectives), ``model_state`` (sp-reduced so replicas stay
consistent), and ``overlap`` (ship the pre-update replica).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dpwa_tpu.config import DpwaConfig
from dpwa_tpu.interpolation import PeerMeta
from dpwa_tpu.parallel.ici import (
    ExchangeInfo,
    IciTransport,
    gossip_exchange_local,
)
from dpwa_tpu.parallel.mesh import PEER_AXIS
from dpwa_tpu.train import GossipTrainState
from dpwa_tpu.utils.pytree import combine as pytree_combine
from dpwa_tpu.utils.pytree import partition as pytree_partition

PyTree = Any
SP_AXIS = "sp"

# loss_fn(single_replica_params, local_batch_block) -> (loss_sum, count):
# the SUM of token losses over this device's sequence block and the
# number of tokens it covers; the step psums both over ``sp``.
SpLossFn = Callable[[PyTree, Any], Tuple[jnp.ndarray, jnp.ndarray]]


def make_sp_mesh(
    config: DpwaConfig, sp: int, devices=None, sp_axis: str = SP_AXIS
) -> Mesh:
    """A ``(peers, sp)`` mesh: ``len(config.nodes) * sp`` devices.

    The sp axis is innermost, so a replica's sequence blocks sit on
    CONTIGUOUS devices — on real hardware that keeps the per-hop
    ring-attention ppermute on neighboring chips (ICI)."""
    n = config.n_peers
    if devices is None:
        devices = jax.devices()
    if len(devices) < n * sp:
        raise RuntimeError(
            f"(peers={n}) x (sp={sp}) needs {n * sp} devices, have "
            f"{len(devices)}"
        )
    arr = np.asarray(devices[: n * sp]).reshape(n, sp)
    return Mesh(arr, (PEER_AXIS, sp_axis))


def init_gossip_sp_state(
    stacked_params: PyTree,
    optimizer: optax.GradientTransformation,
    transport: IciTransport,
    stacked_model_state: PyTree = None,
) -> GossipTrainState:
    """Identical to :func:`dpwa_tpu.train.init_gossip_state` — the peer
    sharding on a 2-D mesh replicates every leaf over ``sp`` for free."""
    from dpwa_tpu.train import init_gossip_state

    return init_gossip_state(
        stacked_params, optimizer, transport, stacked_model_state
    )


def _make_sp_step(
    loss_fn,
    optimizer: optax.GradientTransformation,
    transport: IciTransport,
    exchange_filter: Optional[Callable[[str], bool]],
    with_state: bool,
    overlap: bool,
    sp_axis: str,
):
    """Shared builder behind both public sp step factories.

    Mirrors :func:`dpwa_tpu.train._make_step` with the sp additions: the
    loss arrives as a (sum, count) pair psummed over ``sp``; gradients
    are psummed over ``sp`` too; and
    ``model_state`` is ``pmean``-ed over ``sp`` after the forward pass
    (each sp rank computes statistics on its own sequence block — the
    reduction is what keeps every rank of a replica bit-identical before
    the exchange)."""
    mesh, peers_axis = transport.mesh, transport.axis_name
    if sp_axis not in mesh.shape:
        raise ValueError(
            f"transport mesh {dict(mesh.shape)} has no {sp_axis!r} axis; "
            "build it with make_sp_mesh"
        )
    schedule, interp = transport.schedule, transport.interp
    if with_state:
        # loss_fn returns ((loss_sum, count), new_model_state); grad needs
        # a scalar primal, so fold count in with the aux.
        def _scalarized(params, model_state, batch):
            (loss_sum, count), new_ms = loss_fn(params, model_state, batch)
            return loss_sum, (count, new_ms)

        grad_fn = jax.value_and_grad(_scalarized, has_aux=True)
    else:
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    shard = lambda t: jax.tree.map(lambda v: v[0], t)
    unshard = lambda t: jax.tree.map(lambda v: v[None], t)

    def body(params, opt_state, model_state, clock, prev_loss, step, batch):
        params, opt_state = shard(params), shard(opt_state)
        old_params, old_model_state = params, model_state
        local_batch = shard(batch)
        if with_state:
            model_state = shard(model_state)
            (loss_sum, (count, new_model_state)), grads = grad_fn(
                params, model_state, local_batch
            )
            # Each sp rank saw only its sequence block: reduce the updated
            # statistics across ``sp`` so the replica stays consistent.
            new_model_state = jax.tree.map(
                lambda v: lax.pmean(v, sp_axis), new_model_state
            )
            old_model_state = model_state
        else:
            (loss_sum, count), grads = grad_fn(params, local_batch)
            new_model_state = ()
        # ``params`` enter replicated over ``sp``, so each rank holds the
        # gradient of every block's loss through ITS OWN copy (ring and
        # all-to-all cross-block terms arrive through the transposed
        # collectives); the gradient of the shared parameters is their sum
        # over ``sp``.  The map is unchecked (see below), so nothing
        # inserts that sum for us.
        grads = jax.tree.map(lambda g: lax.psum(g, sp_axis), grads)
        loss_sum = lax.psum(loss_sum, sp_axis)
        count = lax.psum(count, sp_axis)
        loss = (loss_sum / jnp.maximum(count, 1.0)).astype(jnp.float32)
        grads = jax.tree.map(
            lambda g: g / jnp.maximum(count, 1.0).astype(g.dtype), grads
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        clock = clock[0] + 1.0
        if overlap:
            # Ship the PRE-update replica with the PREVIOUS step's loss —
            # every collective operand is ready at step entry, so the
            # peers-axis ppermute needs nothing from this step's fwd/bwd
            # (same semantics as the 1-D overlap: one step of partner
            # staleness, exactly the reference's stale Rx publish).
            exchange_params, exchange_state = old_params, old_model_state
            meta = PeerMeta(clock, prev_loss[0])
        else:
            exchange_params, exchange_state = params, new_model_state
            meta = PeerMeta(clock, loss)
        if exchange_filter is not None:
            exchange_params, _ = pytree_partition(
                exchange_params, exchange_filter
            )
        (merged_sel, merged_state), (partner, alpha, part) = (
            gossip_exchange_local(
                (exchange_params, exchange_state), meta, step,
                schedule=schedule, interp=interp, axis_name=peers_axis,
            )
        )
        if overlap:
            # x_{k+1} = merge(x_k) + own update; model_state analogously
            # re-applies this step's statistics delta to the merge.
            if exchange_filter is not None:
                sel_updates, _ = pytree_partition(updates, exchange_filter)
                merged_sel = optax.apply_updates(merged_sel, sel_updates)
            else:
                merged_sel = optax.apply_updates(merged_sel, updates)
            merged_state = jax.tree.map(
                lambda m, new, old: m + (new - old),
                merged_state, new_model_state, old_model_state,
            )
        if exchange_filter is not None:
            _, rest = pytree_partition(params, exchange_filter)
            merged = pytree_combine(merged_sel, rest)
        else:
            merged = merged_sel
        return (
            unshard(merged),
            unshard(opt_state),
            unshard(merged_state),
            clock[None],
            loss[None],
            (partner[None], alpha[None], part[None]),
        )

    # A single spec is a valid pytree prefix for any batch structure whose
    # leaves are [n_peers, B, T] blocks.
    batch_spec = P(peers_axis, None, sp_axis)
    # Unchecked for the reason train._make_step gives (the attention hops
    # are library Pallas kernels on a TPU).  What the check used to supply
    # here is the gradient psum above.
    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(peers_axis),
            P(peers_axis),
            P(peers_axis),
            P(peers_axis),
            P(peers_axis),
            P(),
            batch_spec,
        ),
        out_specs=(
            P(peers_axis),
            P(peers_axis),
            P(peers_axis),
            P(peers_axis),
            P(peers_axis),
            (P(peers_axis), P(peers_axis), P(peers_axis)),
        ),
        check_vma=False,
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _step(state: GossipTrainState, batch):
        prev_loss = (
            state.loss
            if state.loss is not None
            else jnp.zeros_like(state.clock)
        )
        params, opt_state, model_state, clock, losses, info = mapped(
            state.params,
            state.opt_state,
            state.model_state if with_state else (),
            state.clock,
            prev_loss,
            state.step,
            batch,
        )
        new_state = GossipTrainState(
            params=params,
            opt_state=opt_state,
            clock=clock,
            step=state.step + 1,
            model_state=model_state if with_state else state.model_state,
            loss=losses,
        )
        return new_state, losses, ExchangeInfo(*info)

    # CPU run-ahead bound: reuse the transport's detection (see the
    # rationale comment in IciTransport.__init__).
    block_per_call = transport._block_per_call

    def train_step(state: GossipTrainState, batch):
        if not with_state and state.model_state is not None:
            raise ValueError(
                "state carries model_state but this step was built with "
                "make_gossip_sp_train_step, which would never update it; "
                "use make_gossip_sp_train_step_with_state instead"
            )
        if with_state and state.model_state is None:
            raise ValueError(
                "step built with make_gossip_sp_train_step_with_state but "
                "state.model_state is None; pass stacked_model_state to "
                "init_gossip_sp_state"
            )
        out = _step(state, batch)
        if block_per_call:
            jax.block_until_ready(out)
        return out

    return train_step


def make_gossip_sp_train_step(
    loss_fn: SpLossFn,
    optimizer: optax.GradientTransformation,
    transport: IciTransport,
    exchange_filter: Optional[Callable[[str], bool]] = None,
    overlap: bool = False,
    sp_axis: str = SP_AXIS,
):
    """Jitted ``train_step(state, batch) -> (state, losses, info)`` on a
    ``(peers, sp)`` mesh.

    ``transport`` must be an :class:`IciTransport` built over a 2-D mesh
    from :func:`make_sp_mesh`.  ``batch`` is a pytree of ``[n_peers, B,
    T]`` leaves (e.g. ``(inputs, targets)``; the host pre-shifts targets,
    so block boundaries need no cross-shard fix-up); ``T`` is sharded
    over ``sp``.  ``losses`` is the per-replica mean token loss,
    float32[n_peers].

    ``exchange_filter`` composes subset-pytree gossip with sp — config
    5's actual long-context layout (BASELINE.json:11): LoRA adapters
    gossip over ``peers`` while the frozen base weights never enter the
    collective.  ``overlap`` ships the pre-update replica exactly as in
    :func:`dpwa_tpu.train.make_gossip_train_step`."""
    return _make_sp_step(
        loss_fn, optimizer, transport, exchange_filter, with_state=False,
        overlap=overlap, sp_axis=sp_axis,
    )


def make_gossip_sp_train_step_with_state(
    loss_fn,
    optimizer: optax.GradientTransformation,
    transport: IciTransport,
    exchange_filter: Optional[Callable[[str], bool]] = None,
    overlap: bool = False,
    sp_axis: str = SP_AXIS,
):
    """Like :func:`make_gossip_sp_train_step`, for models with
    non-parameter variables.

    ``loss_fn(params, model_state, batch) -> ((loss_sum, count),
    new_model_state)``.  Each sp rank computes statistics on its own
    sequence block; the step ``pmean``s ``new_model_state`` over ``sp``
    so every rank of a replica stays bit-identical, then exchanges it
    alongside the (filtered) params with the same α, exactly as the 1-D
    :func:`dpwa_tpu.train.make_gossip_train_step_with_state`."""
    return _make_sp_step(
        loss_fn, optimizer, transport, exchange_filter, with_state=True,
        overlap=overlap, sp_axis=sp_axis,
    )


def sp_batch_sharding(mesh: Mesh, sp_axis: str = SP_AXIS) -> NamedSharding:
    """Sharding for ``[n_peers, B, T]`` batches: peers x sequence blocks."""
    return NamedSharding(mesh, P(PEER_AXIS, None, sp_axis))
