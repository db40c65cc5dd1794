"""One gossip round, written once, laid over peers two ways.

:func:`gossip_exchange` holds every decision of a round exactly once: the
pairing in effect, who draws for a pair, the participation and fault draws,
α from both sides' (clock, loss) metadata, the wire encoding of the shipped
copy and the merge ``x ← (1−α)·x + α·x_peer``.  What it does not know is
how peers are laid out.  A layout supplies four things and no more:

==================================  ==============================  ==========================
..                                  :class:`MeshLayout`             :class:`StackedLayout`
==================================  ==============================  ==========================
who am I (``whoami``)               ``lax.axis_index(axis)``        ``jnp.arange(n)``
run a per-peer function             call it                         ``jax.vmap`` it
the partner's tree (``fetch``)      ``lax.switch`` over ppermutes   ``v[partner]``
α against a leaf (``against``)      the scalar                      reshaped to ``[n, 1, ...]``
==================================  ==============================  ==========================

:mod:`dpwa_tpu.parallel.ici` binds the first (one peer a mesh position,
inside ``shard_map``), :mod:`dpwa_tpu.parallel.stacked` the second (n peers
on axis 0 of every leaf, one device).  ``tests/test_stacked.py`` holds the
two bit for bit against each other; with everything but the fetch shared,
that test is about the fetch.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dpwa_tpu.config import DpwaConfig
from dpwa_tpu.interpolation import Interpolation, PeerMeta, make_interpolation
from dpwa_tpu.parallel import schedules
from dpwa_tpu.parallel.schedules import Schedule
from dpwa_tpu.utils import scopes

PyTree = Any


class ExchangeInfo(NamedTuple):
    """Per-peer diagnostics from one gossip round (stacked over peers)."""

    partner: jnp.ndarray  # int32[n] — pairing in effect this step
    alpha: jnp.ndarray  # float32[n] — merge coefficient actually applied
    participated: jnp.ndarray  # bool[n]


def round_rules(config: DpwaConfig) -> Tuple[Schedule, Interpolation]:
    """The (schedule, interpolation) a transport runs its rounds by."""
    bound = config.recovery.rescue_bound() if config.recovery.enabled else None
    return schedules.build_schedule(config), make_interpolation(
        config.interpolation, max_abs_loss=bound
    )


def _perm_pairs(perm) -> Tuple[Tuple[int, int], ...]:
    """ppermute (source, dest) pairs so device i receives from perm[i].

    Valid for pairwise involutions AND one-sided pull maps: ``ppermute``
    only requires each *destination* to appear once; a popular source may
    feed several pullers."""
    return tuple((int(perm[i]), int(i)) for i in range(len(perm)))


class MeshLayout(NamedTuple):
    """One peer a position on mesh axis ``axis_name``: values are that
    device's own (unstacked) arrays and scalars.  Inside ``shard_map``."""

    axis_name: str

    def whoami(self, pool, branch):
        me = lax.axis_index(self.axis_name)
        return me, pool[branch, me]

    def per_peer(self, fn):
        return fn

    def fetch(self, operand, schedule, branch, partner):
        # ``lax.switch`` over a small pool of static permutations: compiled
        # once, step-indexed on device; the collective is the wire.
        def permute(pairs):
            return lambda tree: jax.tree.map(
                lambda v: lax.ppermute(v, self.axis_name, perm=pairs), tree
            )

        return lax.switch(
            branch, [permute(_perm_pairs(p)) for p in schedule.pool], operand
        )

    def against(self, alpha, x):
        return alpha


class StackedLayout(NamedTuple):
    """n peers on axis 0 of every leaf, one device: no collective, the
    partner arrives by a leading-axis gather XLA fuses into the merge."""

    def whoami(self, pool, branch):
        return jnp.arange(pool.shape[1]), pool[branch]

    def per_peer(self, fn):
        return jax.vmap(fn)

    def fetch(self, operand, schedule, branch, partner):
        return jax.tree.map(lambda v: v[partner], operand)

    def against(self, alpha, x):
        return alpha.reshape(alpha.shape + (1,) * (x.ndim - 1))


def _wire_codec(schedule: Schedule, step):
    """(encode(own_tree, sender), decode(own_tree, received)) for one peer's
    shipped copy; ``None`` where there is nothing to do.

    Compressed wire: only the SHIPPED copy is compressed — bf16 halves the
    ICI/DCN bytes; int8 quarters them for real (what moves is the ``(int8
    q, f32 scales)`` encoding, NOT a dequantized f32 copy — the receiver
    decodes after the fetch); the local replica and the merge math stay f32
    (the partner's contribution arrives rounded, scaled by α).  Stochastic
    rounding keeps the quantizer unbiased (ops/quantize.py)."""
    if schedule.wire_dtype == "bf16":
        # The merge widens what arrives; nothing to decode.
        return lambda tree, sender: jax.tree.map(
            lambda v: v.astype(jnp.bfloat16) if v.dtype == jnp.float32 else v,
            tree,
        ), None
    if schedule.wire_dtype == "int8":
        from dpwa_tpu.ops import quantize as qz

        # Each peer quantizes ITS OWN copy, keyed by who it is.
        return lambda tree, sender: qz.quantize_tree(
            tree, schedule.seed, step, sender
        ), qz.dequantize_tree
    return None, None


@scopes.scoped(scopes.EXCHANGE)
def gossip_exchange(
    params: PyTree,
    meta: PeerMeta,
    step: jnp.ndarray,
    *,
    schedule: Schedule,
    interp: Interpolation,
    layout,
) -> Tuple[PyTree, ExchangeInfo]:
    """One gossip round over ``params`` and ``meta`` as ``layout`` holds
    them.  Returns the merged tree and this round's :class:`ExchangeInfo`,
    in the same layout."""
    pool = jnp.asarray(schedule.pool)  # [K, n] baked-in constant
    branch = schedule.branch_traced(step)
    me, partner = layout.whoami(pool, branch)

    encode, decode = _wire_codec(schedule, step)
    shipped = params if encode is None else layout.per_peer(encode)(params, me)
    remote, remote_meta = layout.fetch(
        (shipped, meta), schedule, branch, partner
    )
    if decode is not None:
        remote = layout.per_peer(decode)(params, remote)

    # Pull mode: the pull is one-sided, so the puller draws alone (the
    # reference's per-process fetch decision); pairwise: both members of a
    # pair share one draw keyed on min(i, partner).
    pair_id = me if schedule.mode == "pull" else jnp.minimum(me, partner)
    participated = partner != me
    if schedule.fetch_probability < 1.0:
        participated &= layout.per_peer(
            lambda pid: schedules.participation_draw(
                schedule.seed, step, pid, schedule.fetch_probability
            )
        )(pair_id)
    if schedule.drop_probability > 0.0:
        # Fault injection: a masked merge (α=0) is the SPMD form of the
        # reference's timed-out fetch (SURVEY.md §5).
        participated &= ~layout.per_peer(
            lambda pid: schedules.fault_draw(
                schedule.seed, step, pid, schedule.drop_probability
            )
        )(pair_id)
    alpha = layout.per_peer(interp)(meta, remote_meta)
    alpha = jnp.where(participated, alpha, 0.0).astype(jnp.float32)

    def merge(x, y):
        a = layout.against(alpha, x).astype(
            jnp.promote_types(x.dtype, jnp.float32)
        )
        return ((1.0 - a) * x.astype(a.dtype) + a * y.astype(a.dtype)).astype(
            x.dtype
        )

    merged = jax.tree.map(merge, params, remote)
    return merged, ExchangeInfo(partner, alpha, participated)
