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

The step IS the 1-D step (:func:`dpwa_tpu.train.gossip_train_step` through
``train._make_step``); this module brings only what the ``sp`` axis adds, the
local gradients reduced over ``sp`` and the batch spec.  So the gossip round
(schedule pools, participation/fault draws, interpolation, pull mode, wire
encodings) is :func:`dpwa_tpu.parallel.ici.gossip_exchange_local`, replicated
over the ``sp`` axis — every sp rank of a replica executes the identical
exchange — and ``exchange_filter`` (config 5's long-context LoRA layout:
adapters gossip over ``peers`` while the frozen base rides only the sp
collectives), ``model_state`` (sp-reduced so replicas stay consistent) and
``overlap`` (ship the pre-update replica) come with it.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dpwa_tpu.config import DpwaConfig
from dpwa_tpu.parallel.ici import IciTransport
from dpwa_tpu.parallel.mesh import PEER_AXIS
from dpwa_tpu.train import _make_step, apply_gradients, init_gossip_state
from dpwa_tpu.utils import scopes

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


# The peer sharding on a 2-D mesh replicates every leaf over ``sp`` for free,
# so the 1-D initialiser serves as it is.
init_gossip_sp_state = init_gossip_state


def _make_sp_step(
    loss_fn,
    optimizer: optax.GradientTransformation,
    transport: IciTransport,
    exchange_filter: Optional[Callable[[str], bool]],
    with_state: bool,
    overlap: bool,
    sp_axis: str,
):
    """Shared builder behind both public sp step factories:
    :func:`dpwa_tpu.train._make_step` with this layout's two arguments, the
    local gradients below and the batch spec ``P(peers, None, sp)``.

    The loss arrives as a (sum, count) pair psummed over ``sp``; gradients
    are psummed over ``sp`` too; and ``model_state`` is ``pmean``-ed over
    ``sp`` after the forward pass (each sp rank computes statistics on its
    own sequence block — the reduction is what keeps every rank of a
    replica bit-identical before the exchange)."""
    mesh = transport.mesh
    if sp_axis not in mesh.shape:
        raise ValueError(
            f"transport mesh {dict(mesh.shape)} has no {sp_axis!r} axis; "
            "build it with make_sp_mesh"
        )
    scoped = scopes.scoped_loss(loss_fn)

    # grad needs a scalar primal, so fold count in with the aux.
    def scalarized(params, model_state, batch):
        if with_state:
            (loss_sum, count), new_ms = scoped(params, model_state, batch)
        else:
            (loss_sum, count), new_ms = scoped(params, batch), ()
        return loss_sum, (count, new_ms)

    grad_fn = jax.value_and_grad(scalarized, has_aux=True)
    over_sp = lambda reduce, t: jax.tree.map(lambda v: reduce(v, sp_axis), t)

    def update(params, opt_state, model_state, batch):
        (loss_sum, (count, new_model_state)), grads = grad_fn(
            params, model_state, batch
        )
        # Each sp rank saw only its sequence block: reduce the updated
        # statistics across ``sp`` so the replica stays consistent.
        new_model_state = over_sp(lax.pmean, new_model_state)
        # ``params`` enter replicated over ``sp``, so each rank holds the
        # gradient of every block's loss through ITS OWN copy (ring and
        # all-to-all cross-block terms arrive through the transposed
        # collectives); the gradient of the shared parameters is their sum
        # over ``sp``.  The map is unchecked (see train._make_step), so
        # nothing inserts that sum for us.
        grads, loss_sum, count = over_sp(lax.psum, (grads, loss_sum, count))
        count = jnp.maximum(count, 1.0)
        grads = jax.tree.map(lambda g: g / count.astype(g.dtype), grads)
        loss = (loss_sum / count).astype(jnp.float32)
        new_params, updates, opt_state = apply_gradients(
            optimizer, grads, opt_state, params
        )
        return new_params, updates, opt_state, new_model_state, loss

    return _make_step(
        update, transport, exchange_filter, with_state, overlap,
        # A single spec is a valid pytree prefix for any batch structure
        # whose leaves are [n_peers, B, T] blocks.
        batch_spec=P(transport.axis_name, None, sp_axis),
    )


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
