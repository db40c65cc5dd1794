"""Single-device gossip over a stacked virtual-peer axis.

The SPMD transport (:mod:`dpwa_tpu.parallel.ici`) needs one device per peer.
This module provides the same gossip semantics on ONE device — every replica
lives in a ``[n_peers, ...]``-stacked pytree and the exchange is a batched
gather-merge instead of a ``ppermute`` — so a single TPU chip can train and
benchmark an N-peer gossip run (SURVEY.md §7: the dev/bench box has exactly
one chip; the driver's real meshes come later).

The semantics are the mesh transport's by construction: the round is
:func:`dpwa_tpu.parallel.exchange.gossip_exchange` and the step is
:func:`dpwa_tpu.train.gossip_train_step`, the same two functions the mesh
path runs, bound here to the stacked layout.  What differs is how a partner
arrives (no collective, only a leading-axis gather), and
``tests/test_stacked.py`` holds the two bit for bit against each other on a
forced-CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from dpwa_tpu.config import DpwaConfig
from dpwa_tpu.interpolation import PeerMeta
from dpwa_tpu.parallel import schedules
from dpwa_tpu.parallel.exchange import (
    ExchangeInfo,
    StackedLayout,
    gossip_exchange,
    round_rules,
)
from dpwa_tpu.train import gossip_train_step, local_update

PyTree = Any


def stacked_gossip_exchange(
    params: PyTree,
    meta: PeerMeta,
    step: jnp.ndarray,
    *,
    schedule: schedules.Schedule,
    interp,
) -> Tuple[PyTree, ExchangeInfo]:
    """One gossip round over a ``[n, ...]``-stacked pytree, single device:
    :func:`exchange.gossip_exchange` with n peers on axis 0 of every leaf.
    The partner's replica arrives by leading-axis gather (``x[partner]``,
    fused by XLA into the merge) instead of ``ppermute``."""
    return gossip_exchange(
        params, meta, step, schedule=schedule, interp=interp,
        layout=StackedLayout(),
    )


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
        self.schedule, self.interp = schedule, interp = round_rules(config)

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
    """The initial state of a stacked run.  **Takes ownership** of
    ``stacked_params`` and ``stacked_model_state`` (they are donated: do not
    use them afterwards)."""
    n = transport.config.n_peers
    leading = {leaf.shape[0] for leaf in jax.tree.leaves(stacked_params)}
    if leading != {n}:
        raise ValueError(
            f"stacked params must have leading peer axis {n}, got {leading}"
        )
    # The state OWNS what it is given: ``stacked_params`` and
    # ``stacked_model_state`` are donated, so the state's leaves are the
    # caller's buffers (no second copy of a model that fills the chip is
    # ever held) and the caller's arrays are deleted; a caller that still
    # needs its values copies them before the call.  One program, not an op
    # per leaf: on an accelerator every new leaf shape is a compilation.
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def build(params, model_state):
        return params, jax.vmap(optimizer.init)(params), model_state

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
    device: :func:`dpwa_tpu.train.gossip_train_step` with the per-peer local
    update under ``vmap`` and the stacked exchange, all in one XLA program.

    ``batch`` is peer-stacked ``(x[n, b, ...], y[n, b])``; ``loss_fn``,
    ``exchange_filter``, ``with_state`` and ``overlap`` as in
    :func:`dpwa_tpu.train.make_gossip_train_step` (``with_state=True`` is
    its ``_with_state`` factory).  The state is **donated**: the caller must
    use the returned one (``state, … = step(state, …)``).

    On one chip ``overlap=True`` gains little (~1 % — a single core has no
    second engine to hide the gather behind); the mode is here because the
    step body is the mesh path's, where the dependency-free collective
    genuinely overlaps compute.
    """
    return gossip_train_step(
        jax.vmap(local_update(loss_fn, optimizer, with_state)),
        functools.partial(
            stacked_gossip_exchange, schedule=transport.schedule,
            interp=transport.interp,
        ),
        exchange_filter=exchange_filter, overlap=overlap,
        with_state=with_state,
    )
