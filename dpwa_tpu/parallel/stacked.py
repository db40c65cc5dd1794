"""Single-device gossip over a stacked virtual-peer axis.

The SPMD transport (:mod:`dpwa_tpu.parallel.ici`) needs one device per peer.
This module provides the same gossip semantics on ONE device — every replica
lives in a ``[n_peers, ...]``-stacked pytree and the exchange is a batched
gather-merge instead of a ``ppermute`` — so a single TPU chip can train and
benchmark an N-peer gossip run (SURVEY.md §7: the dev/bench box has exactly
one chip; the driver's real meshes come later).

Semantics parity is exact, not approximate: the pairing pool, the per-pair
participation/fault draws (same counter-based threefry streams), the
interpolation α from exchanged (clock, loss) metadata, and the masked merge
all reproduce :func:`dpwa_tpu.parallel.ici.gossip_exchange_local` bit for
bit — ``tests/test_stacked.py`` asserts it against the multi-device path on
a forced-CPU mesh.  One jitted program still advances every replica's round;
there is simply no collective in it, only a leading-axis gather.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from dpwa_tpu.config import DpwaConfig
from dpwa_tpu.interpolation import PeerMeta, make_interpolation
from dpwa_tpu.parallel import schedules
from dpwa_tpu.parallel.ici import ExchangeInfo
from dpwa_tpu.parallel.schedules import participation_draw
from dpwa_tpu.utils import scopes
from dpwa_tpu.utils.pytree import combine as pytree_combine
from dpwa_tpu.utils.pytree import partition as pytree_partition

PyTree = Any


@scopes.scoped(scopes.EXCHANGE)
def stacked_gossip_exchange(
    params: PyTree,
    meta: PeerMeta,
    step: jnp.ndarray,
    *,
    schedule: schedules.Schedule,
    interp,
) -> Tuple[PyTree, ExchangeInfo]:
    """One gossip round over a ``[n, ...]``-stacked pytree, single device.

    The batched twin of
    :func:`dpwa_tpu.parallel.ici.gossip_exchange_local`: identical pool
    selection (:meth:`Schedule.branch_traced` — cyclic for ring/
    hierarchical, per-step threefry draw for random), identical per-pair
    threefry draws, identical α math — the partner's replica arrives by
    leading-axis gather (``x[partner]``, fused by XLA into the merge)
    instead of ``ppermute``.
    """
    n = schedule.n_peers
    me = jnp.arange(n)
    pool = jnp.asarray(schedule.pool)  # [K, n] baked-in constant
    branch = schedule.branch_traced(step)
    partner = pool[branch]  # [n]

    remote_meta = jax.tree.map(lambda v: v[partner], meta)
    # Pull mode: one-sided, puller draws alone; pairwise: shared pair draw.
    pair_id = me if schedule.mode == "pull" else jnp.minimum(me, partner)
    if schedule.fetch_probability >= 1.0:
        drawn = jnp.ones(n, jnp.bool_)
    else:
        drawn = jax.vmap(
            lambda pid: participation_draw(
                schedule.seed, step, pid, schedule.fetch_probability
            )
        )(pair_id)
    if schedule.drop_probability > 0.0:
        drawn = jnp.logical_and(
            drawn,
            jnp.logical_not(
                jax.vmap(
                    lambda pid: schedules.fault_draw(
                        schedule.seed, step, pid, schedule.drop_probability
                    )
                )(pair_id)
            ),
        )
    participated = jnp.logical_and(drawn, partner != me)
    alpha = jax.vmap(interp)(meta, remote_meta)
    alpha = jnp.where(participated, alpha, 0.0).astype(jnp.float32)

    if schedule.wire_dtype == "int8":
        from dpwa_tpu.ops.quantize import fake_quant_tree

        # Emulate the wire per SENDER: row s of every stacked leaf is
        # quantized with sender s's key (vmap over the peer axis), then
        # gathered by the receiver — the same (step, sender, leaf) key
        # derivation as the ICI transport, so the merges stay
        # bit-identical across the two.
        wire_params = jax.vmap(
            lambda row, s: fake_quant_tree(row, schedule.seed, step, s)
        )(params, me)
    else:
        wire_params = params

    def merge(x, xw):
        a = alpha.reshape((n,) + (1,) * (x.ndim - 1)).astype(
            jnp.promote_types(x.dtype, jnp.float32)
        )
        y = xw[partner]
        if schedule.wire_dtype == "bf16" and x.dtype == jnp.float32:
            # Emulate the wire: the partner's contribution is what would
            # have arrived over the fabric — bf16-rounded.  Keeps the
            # stacked path bit-matched to the ICI transport's merges.
            y = y.astype(jnp.bfloat16)
        return ((1.0 - a) * x.astype(a.dtype) + a * y.astype(a.dtype)).astype(
            x.dtype
        )

    merged = jax.tree.map(merge, params, wire_params)
    return merged, ExchangeInfo(partner, alpha, participated)


class StackedTransport:
    """Virtual-peer gossip on a single device.

    Drop-in peer of :class:`dpwa_tpu.parallel.ici.IciTransport` behind the
    same ``exchange(params, meta, step)`` surface, for hosts with fewer
    devices than peers.  The YAML config is the same one that drives the
    ICI and TCP transports (BASELINE.json:5 contract) — ``nodes:`` length
    sets the stacked-axis size; host/port entries are ignored.
    """

    def __init__(self, config: DpwaConfig):
        self.config = config
        self.schedule = schedules.build_schedule(config)
        self.interp = make_interpolation(
            config.interpolation,
            max_abs_loss=(
                config.recovery.rescue_bound() if config.recovery.enabled else None
            ),
        )
        schedule, interp = self.schedule, self.interp

        @jax.jit
        def exchange(params, meta, step):
            return stacked_gossip_exchange(
                params, meta, step, schedule=schedule, interp=interp
            )

        self._exchange = exchange

    def exchange(
        self, params: PyTree, meta: PeerMeta, step
    ) -> Tuple[PyTree, ExchangeInfo]:
        """One gossip round over every stacked replica.

        Args:
          params: pytree whose leaves are ``[n_peers, ...]`` arrays.
          meta: :class:`PeerMeta` of ``[n_peers]`` float32 arrays.
          step: int — selects the pairing and the participation draw.
        """
        return self._exchange(params, meta, jnp.asarray(step, jnp.int32))


class StackedTrainState(NamedTuple):
    """Stacked training state; every leaf's leading axis is n_peers.

    ``loss`` is each peer's most recent training loss — what the
    reference's Rx thread serves alongside the published vector; overlapped
    exchanges ship it as the metadata (see
    :class:`dpwa_tpu.train.GossipTrainState`)."""

    params: PyTree
    opt_state: PyTree
    clock: jnp.ndarray  # float32[n]
    step: jnp.ndarray  # int32 scalar
    model_state: PyTree = None
    loss: jnp.ndarray = None  # float32[n] — last step's per-peer loss


def init_stacked_state(
    stacked_params: PyTree,
    optimizer: optax.GradientTransformation,
    transport: StackedTransport,
    stacked_model_state: PyTree = None,
) -> StackedTrainState:
    n = transport.config.n_peers
    leading = {leaf.shape[0] for leaf in jax.tree.leaves(stacked_params)}
    if leading != {n}:
        raise ValueError(
            f"stacked params must have leading peer axis {n}, got {leading}"
        )
    # Own copies: the train step DONATES the state, so the state must not
    # alias arrays the caller still holds.  One program, not an op per
    # leaf: on an accelerator every new leaf shape is a compilation.
    @jax.jit
    def build(params, model_state):
        own = lambda t: jax.tree.map(jnp.copy, t)
        params = own(params)
        return params, jax.vmap(optimizer.init)(params), own(model_state)

    params, opt_state, model_state = build(stacked_params, stacked_model_state)
    return StackedTrainState(
        params=params,
        opt_state=opt_state,
        clock=jnp.zeros(n, jnp.float32),
        step=jnp.int32(0),
        model_state=model_state,
        loss=jnp.zeros(n, jnp.float32),
    )


def make_stacked_train_step(
    loss_fn,
    optimizer: optax.GradientTransformation,
    transport: StackedTransport,
    exchange_filter: Optional[Callable[[str], bool]] = None,
    with_state: bool = False,
    overlap: bool = False,
):
    """Jitted ``train_step(state, batch) -> (state, losses, info)`` on one
    device: vmapped per-peer forward/backward/optimizer followed by the
    stacked gossip exchange, all in one XLA program — the single-chip twin
    of :func:`dpwa_tpu.train.make_gossip_train_step`.

    ``batch`` is peer-stacked ``(x[n, b, ...], y[n, b])``; with
    ``with_state=True``, ``loss_fn(params, model_state, batch) ->
    (loss, new_model_state)`` as in
    :func:`dpwa_tpu.train.make_gossip_train_step_with_state`.

    The state is **donated**: each call consumes its input state's buffers
    and the caller must use the returned one (``state, … = step(state, …)``
    — the standard loop).  Without donation every in-flight step holds a
    full fresh copy of params + optimizer state, and a deep async dispatch
    queue (hundreds of steps) can swamp the HBM allocator.

    ``overlap=True`` exchanges the PRE-update replicas (with the previous
    step's losses as metadata) and applies the local updates to the merged
    result, exactly as :func:`dpwa_tpu.train.make_gossip_train_step`
    documents.  On one chip the gain is small (~1 % — a single core has
    no second engine to hide the gather behind); the mode exists here for
    layout parity with the ICI path, where the dependency-free collective
    genuinely overlaps compute.
    """
    grad_fn = jax.value_and_grad(
        scopes.scoped_loss(loss_fn), has_aux=with_state
    )
    schedule, interp = transport.schedule, transport.interp

    def check_state(state):
        # Same misuse guards as the SPMD twin (dpwa_tpu/train.py): silently
        # frozen BatchNorm stats are worse than an error.
        if not with_state and state.model_state is not None:
            raise ValueError(
                "state carries model_state but this step was built with "
                "with_state=False, which would never update it; pass "
                "with_state=True"
            )
        if with_state and state.model_state is None:
            raise ValueError(
                "step built with with_state=True but state.model_state is "
                "None; pass stacked_model_state to init_stacked_state"
            )

    def per_peer(params, opt_state, model_state, batch):
        if with_state:
            (loss, new_model_state), grads = grad_fn(
                params, model_state, batch
            )
        else:
            loss, grads = grad_fn(params, batch)
            new_model_state = ()
        with jax.named_scope(scopes.OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
        return new_params, updates, opt_state, new_model_state, loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _step(state: StackedTrainState, batch):
        model_state = state.model_state if with_state else ()
        params, updates, opt_state, new_model_state, losses = jax.vmap(
            per_peer
        )(state.params, state.opt_state, model_state, batch)
        clock = state.clock + 1.0
        # Overlap mode exchanges the pre-update replicas (state.params)
        # with the PREVIOUS step's losses — every exchanged operand is
        # ready at step entry, so the exchange's HBM reads never wait on
        # this step's fwd/bwd/optimizer; the local updates (and the
        # model-state delta) land on the merged result afterwards.
        if overlap:
            prev_loss = (
                state.loss
                if state.loss is not None
                else jnp.zeros_like(clock)
            )
            meta = PeerMeta(clock, prev_loss)
            exchange_params, exchange_state = state.params, model_state
        else:
            meta = PeerMeta(clock, losses.astype(jnp.float32))
            exchange_params, exchange_state = params, new_model_state
        if exchange_filter is not None:
            selected, _ = pytree_partition(exchange_params, exchange_filter)
            (merged_sel, merged_state), info = stacked_gossip_exchange(
                (selected, exchange_state), meta, state.step,
                schedule=schedule, interp=interp,
            )
            if overlap:
                sel_updates, _ = pytree_partition(updates, exchange_filter)
                with jax.named_scope(scopes.OPTIMIZER):
                    merged_sel = optax.apply_updates(merged_sel, sel_updates)
            _, rest = pytree_partition(params, exchange_filter)
            merged = pytree_combine(merged_sel, rest)
        else:
            (merged, merged_state), info = stacked_gossip_exchange(
                (exchange_params, exchange_state), meta, state.step,
                schedule=schedule, interp=interp,
            )
            if overlap:
                with jax.named_scope(scopes.OPTIMIZER):
                    merged = optax.apply_updates(merged, updates)
        if overlap:
            merged_state = jax.tree.map(
                lambda m, new, old: m + (new - old),
                merged_state, new_model_state, model_state,
            )
        new_state = StackedTrainState(
            params=merged,
            opt_state=opt_state,
            clock=clock,
            step=state.step + 1,
            model_state=merged_state if with_state else state.model_state,
            loss=losses,
        )
        return new_state, losses, info

    def train_step(state: StackedTrainState, batch):
        check_state(state)
        return _step(state, batch)

    return train_step
