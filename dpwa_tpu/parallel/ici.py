"""ICI gossip transport: ``ppermute`` + fused merge inside ``shard_map``.

This replaces the reference's hot path end to end (SURVEY.md §3.2): where the
reference flattens params to numpy, pickles them through a TCP socket to a
peer's Rx thread, and merges on the CPU (reference ``dpwa/conn.py`` +
``dpwa/adapters/pytorch.py`` — mount empty), here every replica lives in HBM
as the per-device shard of a peer-stacked pytree and one jitted SPMD program
does, per step:

1. select the pairing in effect (``lax.switch`` over a small pool of static
   involutions — compiled once, step-indexed on device),
2. exchange parameters AND (clock, loss) metadata with the partner via
   ``lax.ppermute`` over ICI,
3. compute α from both sides' metadata (interpolation strategy) and the
   per-pair participation draw (emulating the reference's probabilistic
   fetch; SURVEY.md §7 design stance),
4. merge ``x ← (1−α)·x + α·x_peer`` — fused by XLA into the same program.

No host round-trips, no serialization, no copies: the "wire format" is the
collective itself.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dpwa_tpu.config import DpwaConfig
from dpwa_tpu.interpolation import Interpolation, PeerMeta
from dpwa_tpu.parallel.exchange import (
    ExchangeInfo,
    MeshLayout,
    gossip_exchange,
    round_rules,
)
from dpwa_tpu.parallel.mesh import PEER_AXIS, make_mesh
from dpwa_tpu.parallel.schedules import Schedule

PyTree = Any


def gossip_exchange_local(
    params: PyTree,
    meta: PeerMeta,
    step: jnp.ndarray,
    *,
    schedule: Schedule,
    interp: Interpolation,
    axis_name: str = PEER_AXIS,
) -> Tuple[PyTree, ExchangeInfo]:
    """The per-device gossip round: :func:`exchange.gossip_exchange` with one
    peer a position on ``axis_name``.  Call INSIDE shard_map/pjit over
    ``axis_name``; ``params`` leaves and ``meta`` scalars are this device's
    local (unstacked) values, and so is what it returns."""
    return gossip_exchange(
        params, meta, step, schedule=schedule, interp=interp,
        layout=MeshLayout(axis_name),
    )


class IciTransport:
    """On-device gossip over a ``peers`` mesh axis.

    Drop-in peer of :class:`dpwa_tpu.parallel.tcp.TcpTransport` behind the
    same exchange semantics (SURVEY.md §7 transports plugin interface), but
    SPMD: one process owns all replicas as a peer-stacked, peer-sharded
    pytree and :meth:`exchange` advances every replica's gossip round in a
    single XLA program.
    """

    def __init__(
        self,
        config: DpwaConfig,
        mesh: Optional[Mesh] = None,
        axis_name: str = PEER_AXIS,
    ):
        self.config = config
        self.schedule, self.interp = round_rules(config)
        self.axis_name = axis_name
        self.mesh = mesh if mesh is not None else make_mesh(config, axis_name=axis_name)
        (axis_size,) = (self.mesh.shape[axis_name],)
        if axis_size != config.n_peers:
            raise ValueError(
                f"mesh axis {axis_name!r} has size {axis_size} but config "
                f"names {config.n_peers} peers"
            )
        # XLA:CPU's in-process collectives rendezvous on a shared thread
        # pool; on thread-starved hosts, letting many in-flight steps queue
        # up deadlocks the pool (threads blocked in step k+j's rendezvous
        # starve the laggards of step k, which aborts after 40s).  Bounding
        # run-ahead to one step on CPU meshes removes the hazard; real TPU
        # meshes keep fully async dispatch.
        self._block_per_call = all(
            d.platform == "cpu" for d in self.mesh.devices.flat
        )
        self._exchange = self._build_exchange()

    def _build_exchange(self):
        schedule, interp, axis = self.schedule, self.interp, self.axis_name

        def body(params, meta, step):
            # shard_map hands us a leading peer axis of local size 1;
            # strip it so interpolation sees true scalars, then restore.
            merged, info = gossip_exchange_local(
                *jax.tree.map(lambda v: v[0], (params, meta)), step,
                schedule=schedule, interp=interp, axis_name=axis,
            )
            return jax.tree.map(lambda v: v[None], (merged, tuple(info)))

        mapped = shard_map(
            body, mesh=self.mesh, in_specs=(P(axis), P(axis), P()),
            out_specs=P(axis),
            check_vma=False,  # one setting for every map; see train._make_step
        )

        @jax.jit
        def exchange(params, meta, step):
            merged, info = mapped(params, meta, step)
            return merged, ExchangeInfo(*info)

        return exchange

    def exchange(
        self, params: PyTree, meta: PeerMeta, step
    ) -> Tuple[PyTree, ExchangeInfo]:
        """One gossip round over every replica.

        Args:
          params: pytree whose leaves are peer-stacked ``[n_peers, ...]``
            arrays (ideally already sharded with :func:`peer_sharding`).
          meta: :class:`PeerMeta` of ``[n_peers]`` float32 arrays.
          step: int — selects the pairing and the participation draw.
        """
        out = self._exchange(params, meta, jnp.asarray(step, jnp.int32))
        if self._block_per_call:
            jax.block_until_ready(out)
        return out
