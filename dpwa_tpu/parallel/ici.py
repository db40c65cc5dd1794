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

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dpwa_tpu.config import DpwaConfig
from dpwa_tpu.interpolation import Interpolation, PeerMeta, make_interpolation
from dpwa_tpu.parallel import schedules
from dpwa_tpu.parallel.mesh import PEER_AXIS, make_mesh
from dpwa_tpu.parallel.schedules import Schedule, participation_draw
from dpwa_tpu.utils import scopes

PyTree = Any


class ExchangeInfo(NamedTuple):
    """Per-peer diagnostics from one gossip round (stacked over peers)."""

    partner: jnp.ndarray  # int32[n] — pairing in effect this step
    alpha: jnp.ndarray  # float32[n] — merge coefficient actually applied
    participated: jnp.ndarray  # bool[n]


def _perm_pairs(perm) -> Tuple[Tuple[int, int], ...]:
    """ppermute (source, dest) pairs so device i receives from perm[i].

    Valid for pairwise involutions AND one-sided pull maps: ``ppermute``
    only requires each *destination* to appear once; a popular source may
    feed several pullers."""
    return tuple((int(perm[i]), int(i)) for i in range(len(perm)))


@scopes.scoped(scopes.EXCHANGE)
def gossip_exchange_local(
    params: PyTree,
    meta: PeerMeta,
    step: jnp.ndarray,
    *,
    schedule: Schedule,
    interp: Interpolation,
    axis_name: str = PEER_AXIS,
):
    """The per-device gossip body. Call INSIDE shard_map/pjit over
    ``axis_name``; ``params`` leaves and ``meta`` scalars are this device's
    local (unstacked) values.

    Returns (merged_params, (partner, alpha, participated)) for this device.
    """
    me = lax.axis_index(axis_name)
    pool = jnp.asarray(schedule.pool)  # [K, n] baked-in constant
    branch = schedule.branch_traced(step)
    partner = pool[branch, me]

    def make_branch(perm):
        pairs = _perm_pairs(perm)

        def apply(operand):
            return jax.tree.map(
                lambda v: lax.ppermute(v, axis_name, perm=pairs), operand
            )

        return apply

    # Compressed wire: only the SHIPPED copy is compressed — bf16 halves
    # the ICI/DCN bytes; int8 quarters them for real (the collective
    # moves the ``(int8 q, f32 scales)`` encoding, NOT a dequantized f32
    # copy — the receiver decodes after the ppermute); the local replica
    # and the merge math stay f32 (the partner's contribution arrives
    # rounded, scaled by α).  Stochastic rounding keeps the quantizer
    # unbiased (ops/quantize.py).
    decode_remote = None
    if schedule.wire_dtype == "bf16":
        wire_params = jax.tree.map(
            lambda v: v.astype(jnp.bfloat16)
            if v.dtype == jnp.float32
            else v,
            params,
        )
    elif schedule.wire_dtype == "int8":
        from dpwa_tpu.ops import quantize as qz

        # Each device quantizes ITS OWN copy (sender-keyed, per-leaf) —
        # the stacked twin derives the same (step, sender, leaf) keys and
        # dequantize commutes with its gather elementwise, so the two
        # transports stay bit-identical.
        leaves, treedef = jax.tree.flatten(params)
        enc = [
            qz.quantize(v, qz.wire_key(schedule.seed, step, me, leaf=i))
            if v.dtype == jnp.float32
            else v
            for i, v in enumerate(leaves)
        ]
        # (q, scales) tuples become subtrees: ppermute moves the int8
        # codes and the tiny f32 scale vectors as separate leaves.
        wire_params = jax.tree.unflatten(treedef, enc)

        def decode_remote(remote_tree):
            flat = jax.tree.leaves(remote_tree)
            out, j = [], 0
            for v in leaves:
                if v.dtype == jnp.float32:
                    q, s = flat[j], flat[j + 1]
                    j += 2
                    out.append(qz.dequantize(q, s, v.shape))
                else:
                    out.append(flat[j])
                    j += 1
            return jax.tree.unflatten(treedef, out)

    else:
        wire_params = params
    remote_params, remote_meta = lax.switch(
        branch,
        [make_branch(p) for p in schedule.pool],
        (wire_params, meta),
    )
    if decode_remote is not None:
        remote_params = decode_remote(remote_params)

    # Pull mode: the pull is one-sided, so the puller draws alone (the
    # reference's per-process fetch decision); pairwise: both members of a
    # pair share one draw keyed on min(i, partner).
    pair_id = me if schedule.mode == "pull" else jnp.minimum(me, partner)
    if schedule.fetch_probability >= 1.0:
        drawn = jnp.bool_(True)
    else:
        drawn = participation_draw(
            schedule.seed, step, pair_id, schedule.fetch_probability
        )
    if schedule.drop_probability > 0.0:
        # Fault injection: masked merge (α=0) is the SPMD form of the
        # reference's timed-out fetch (SURVEY.md §5).
        drawn = jnp.logical_and(
            drawn,
            jnp.logical_not(
                schedules.fault_draw(
                    schedule.seed, step, pair_id, schedule.drop_probability
                )
            ),
        )
    participated = jnp.logical_and(drawn, partner != me)
    alpha = jnp.where(participated, interp(meta, remote_meta), 0.0)
    alpha = alpha.astype(jnp.float32)

    def merge(x, y):
        a = alpha.astype(jnp.promote_types(x.dtype, jnp.float32))
        return ((1.0 - a) * x.astype(a.dtype) + a * y.astype(a.dtype)).astype(
            x.dtype
        )

    merged = jax.tree.map(merge, params, remote_params)
    return merged, (partner, alpha, participated)


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
        self.schedule = schedules.build_schedule(config)
        self.interp = make_interpolation(
            config.interpolation,
            max_abs_loss=(
                config.recovery.rescue_bound() if config.recovery.enabled else None
            ),
        )
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
            params1 = jax.tree.map(lambda v: v[0], params)
            meta1 = jax.tree.map(lambda v: v[0], meta)
            merged, (partner, alpha, part) = gossip_exchange_local(
                params1,
                meta1,
                step,
                schedule=schedule,
                interp=interp,
                axis_name=axis,
            )
            merged = jax.tree.map(lambda v: v[None], merged)
            return merged, (
                partner[None],
                alpha[None],
                part[None],
            )

        mapped = shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(self.axis_name), P(self.axis_name), P()),
            out_specs=(
                P(self.axis_name),
                (P(self.axis_name), P(self.axis_name), P(self.axis_name)),
            ),
            check_vma=False,  # one setting for every map; see train._make_step
        )

        @jax.jit
        def exchange(params, meta, step):
            merged, (partner, alpha, part) = mapped(params, meta, step)
            return merged, ExchangeInfo(partner, alpha, part)

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
