"""The SPMD gossip training loop.

The reference's hot loop (SURVEY.md §3.2) is::

    forward / loss.backward() / optimizer.step()   # local, per process
    adapter.update(loss)                           # publish, fetch, merge

Here the entire loop — per-peer forward/backward, optax update, AND the
gossip exchange — is **one jitted ``shard_map`` program** over the ``peers``
mesh axis (SURVEY.md §3.5).  Manual SPMD, deliberately: auto sharding
propagation through vmapped convolutions makes GSPMD introduce all-gathers
of the per-peer replicas, which is both a performance bug (the whole point
of gossip is that nothing is globally gathered) and a deadlock on
thread-starved CPU test meshes.  Inside ``shard_map`` every peer's
forward/backward/optimizer math is provably local; the **only** collective
in the compiled program is the pairing ``ppermute`` of the exchange.

Elasticity note: inside one SPMD program there are no independently
failing peers — a fault injected via ``fault_probability`` (or the chaos
harness on the TCP path) surfaces to this loop as an α = 0 round: the
replica keeps training on its own.  The peer-health control plane
(:mod:`dpwa_tpu.health` — suspicion, quarantine/backoff, probe
re-admission, fallback remap) lives on the multi-process TCP path, where
peers genuinely die and come back; its scoreboard state is observable via
metrics ``health`` records and the optional ``/healthz`` endpoint."""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from dpwa_tpu.interpolation import PeerMeta
from dpwa_tpu.parallel.ici import (
    ExchangeInfo,
    IciTransport,
    gossip_exchange_local,
)
from dpwa_tpu.parallel.mesh import peer_sharding, replicated_sharding
from dpwa_tpu.utils import scopes
from dpwa_tpu.utils.pytree import combine as pytree_combine
from dpwa_tpu.utils.pytree import partition as pytree_partition

PyTree = Any
# loss_fn(single_peer_params, (x, y)) -> scalar loss
LossFn = Callable[[PyTree, Tuple[jnp.ndarray, jnp.ndarray]], jnp.ndarray]


class GossipTrainState(NamedTuple):
    """Peer-stacked training state. Every leaf's leading axis is n_peers.

    ``model_state`` carries non-parameter model variables (e.g. BatchNorm
    ``batch_stats``); it is exchanged alongside params — running statistics
    are part of the replica and must gossip with the same α — but never
    touched by the optimizer.

    ``loss`` is each peer's most recent training loss — the value the
    reference's Rx thread serves alongside the published vector
    (SURVEY.md §3.3).  Overlapped exchanges ship it as the metadata so the
    collective has no dependency on the current step's forward pass."""

    params: PyTree
    opt_state: PyTree
    clock: jnp.ndarray  # float32[n] — steps trained, rides with exchanges
    step: jnp.ndarray  # int32 scalar — global schedule position
    model_state: PyTree = None
    loss: jnp.ndarray = None  # float32[n] — last step's per-peer loss


def init_gossip_state(
    stacked_params: PyTree,
    optimizer: optax.GradientTransformation,
    transport: IciTransport,
    stacked_model_state: PyTree = None,
) -> GossipTrainState:
    """Build state from peer-stacked params and shard it over the mesh."""
    n = transport.config.n_peers
    leading = {leaf.shape[0] for leaf in jax.tree.leaves(stacked_params)}
    if leading != {n}:
        raise ValueError(
            f"stacked params must have leading peer axis {n}, got {leading}"
        )
    sh = peer_sharding(transport.mesh, transport.axis_name)
    # The train step donates the state, so it must not alias arrays the
    # caller still holds (an array already in the target sharding would
    # otherwise come back as itself).  One call for each tree: a transfer
    # per leaf is a dispatch per leaf.
    put = lambda t: jax.device_put(t, sh, may_alias=False)
    params = put(stacked_params)
    return GossipTrainState(
        params=params,
        # Built from the sharded params as one program, so the optimizer
        # state is born on its own chip and never whole on the first.
        opt_state=jax.jit(jax.vmap(optimizer.init), out_shardings=sh)(params),
        clock=jax.device_put(jnp.zeros(n, jnp.float32), sh),
        # Committed and replicated, which is how the step hands it back: an
        # uncommitted scalar here gives the second call another input
        # signature, and the whole train step compiles a second time.
        step=jax.device_put(jnp.int32(0), replicated_sharding(transport.mesh)),
        model_state=put(stacked_model_state)
        if stacked_model_state is not None
        else None,
        loss=jax.device_put(jnp.zeros(n, jnp.float32), sh),
    )


def stack_params(params: PyTree, n_peers: int) -> PyTree:
    """Replicate one pytree n times along a new leading peer axis —
    identical warm start on every peer (the reference's default: every
    process builds the same model)."""
    return jax.tree.map(
        lambda v: jnp.broadcast_to(v[None], (n_peers,) + v.shape), params
    )


def init_params_per_peer(
    init_fn: Callable[[jax.Array], PyTree], key: jax.Array, n_peers: int
) -> PyTree:
    """Independent random init per peer (diverged cold start).

    Jitted: a flax ``init`` runs the model's forward pass, and op by op on
    an accelerator that is a compilation per layer (over two minutes for
    ResNet-50 on a v5e, against a 60 s compile of the train step).  As one
    program the forward pass is dead code and only the initializers run."""
    return jax.jit(jax.vmap(init_fn))(jax.random.split(key, n_peers))


def _make_step(
    loss_fn,
    optimizer: optax.GradientTransformation,
    transport: IciTransport,
    exchange_filter: Optional[Callable[[str], bool]],
    with_state: bool,
    overlap: bool = False,
):
    """Shared builder behind both public step factories.

    When ``with_state`` is False, ``model_state`` is threaded through as an
    empty pytree ``()`` — zero leaves, so it adds nothing to the compiled
    program — keeping one body/shard_map/_step implementation for both.

    ``overlap`` selects which params the exchange ships (see
    :func:`make_gossip_train_step`): post-update (default, the lock-step
    emulation) or pre-update ``x_k`` (the collective overlaps fwd/bwd)."""
    grad_fn = jax.value_and_grad(
        scopes.scoped_loss(loss_fn), has_aux=with_state
    )
    schedule, interp = transport.schedule, transport.interp
    axis, mesh = transport.axis_name, transport.mesh
    shard = lambda t: jax.tree.map(lambda v: v[0], t)
    unshard = lambda t: jax.tree.map(lambda v: v[None], t)

    def body(params, opt_state, model_state, clock, prev_loss, step, batch):
        # Local (per-device) values: strip the size-1 peer block axis.
        params, opt_state = shard(params), shard(opt_state)
        old_params, old_model_state = params, model_state
        if with_state:
            model_state = shard(model_state)
            old_model_state = model_state
            (loss, new_model_state), grads = grad_fn(
                params, model_state, shard(batch)
            )
        else:
            loss, grads = grad_fn(params, shard(batch))
            new_model_state = ()
        with jax.named_scope(scopes.OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        clock = clock[0] + 1.0
        if overlap:
            # Exchange the PRE-update replica with the PREVIOUS step's
            # loss (the last value this peer "published", exactly what the
            # reference's Rx thread would serve, SURVEY.md §3.3).  Every
            # collective operand — x_k, clock, stale loss — is ready at
            # step entry, so nothing gates the ppermute on this step's
            # fwd/bwd and XLA can overlap the DMA with compute.  The
            # model_state (fwd-produced) is also shipped stale; its
            # this-step delta is re-applied to the merge below.
            exchange_params, exchange_state = old_params, old_model_state
            meta = PeerMeta(clock, prev_loss[0])
        else:
            exchange_params, exchange_state = params, new_model_state
            meta = PeerMeta(clock, loss.astype(jnp.float32))
        if exchange_filter is not None:
            selected, _ = pytree_partition(exchange_params, exchange_filter)
            (merged_sel, merged_state), (partner, alpha, part) = (
                gossip_exchange_local(
                    (selected, exchange_state), meta, step,
                    schedule=schedule, interp=interp, axis_name=axis,
                )
            )
        else:
            (merged_sel, merged_state), (partner, alpha, part) = (
                gossip_exchange_local(
                    (exchange_params, exchange_state), meta, step,
                    schedule=schedule, interp=interp, axis_name=axis,
                )
            )
        if overlap:
            # x_{k+1} = merge(x_k) + own update: the merge contributed the
            # partner's pre-update replica (exactly what a free-running
            # reference peer would have pulled from a partner that had not
            # finished its step yet), the local gradient is never lost.
            # Model state gets the same treatment: merge(ms_k) + this
            # step's statistics delta.
            with jax.named_scope(scopes.OPTIMIZER):
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

    # Unchecked map (check_vma=False), on every shard_map in the package.
    # Under the checked map a ``pallas_call`` is refused unless its
    # ``out_shape`` carries ``vma``, and the flash kernels a Llama
    # ``loss_fn`` reaches on a TPU are the library's: they build their
    # ``out_shape`` themselves, so the other road (vma on the kernels'
    # outputs) would mean keeping copies of them.  Nothing here leaned on
    # the check: every differentiated operand varies over ``axis``.
    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(axis), P(axis), P(axis), P(axis), P(axis), P(), P(axis),
        ),
        out_specs=(
            P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
        ),
        check_vma=False,
    )

    # Donated: each call consumes the input state's buffers (the caller
    # rebinds `state, … = step(state, …)`).  Without donation every
    # in-flight step holds a fresh params+opt copy and a deep async
    # dispatch queue can swamp the HBM allocator.
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

    # Same CPU run-ahead bound as IciTransport.exchange (see the rationale
    # comment there) — reuse its detection so the rule lives in one place.
    block_per_call = transport._block_per_call

    def train_step(state: GossipTrainState, batch):
        if not with_state and state.model_state is not None:
            raise ValueError(
                "state carries model_state but this step was built with "
                "make_gossip_train_step, which would never update it; use "
                "make_gossip_train_step_with_state instead"
            )
        if with_state and state.model_state is None:
            raise ValueError(
                "step built with make_gossip_train_step_with_state but "
                "state.model_state is None; pass stacked_model_state to "
                "init_gossip_state"
            )
        out = _step(state, batch)
        if block_per_call:
            jax.block_until_ready(out)
        return out

    return train_step


def make_gossip_train_step(
    loss_fn: LossFn,
    optimizer: optax.GradientTransformation,
    transport: IciTransport,
    exchange_filter: Optional[Callable[[str], bool]] = None,
    overlap: bool = False,
):
    """Returns jitted ``train_step(state, batch) -> (state, losses, info)``.

    ``batch`` is a peer-stacked ``(x[n, b, ...], y[n, b])`` pair; ``losses``
    is float32[n] (per peer) and also becomes the metadata the
    loss-weighted interpolation sees, matching the reference's
    ``update(loss)`` argument.

    ``exchange_filter`` enables subset-pytree gossip (BASELINE.json:11, the
    LoRA config): only leaves whose path matches the predicate enter the
    collective; everything else never moves — neither over ICI nor DCN.

    ``overlap=True`` ships the PRE-update replica ``x_k`` through the
    collective with the PREVIOUS step's loss as metadata, and applies the
    local update to the merged result (``x_{k+1} = merge(x_k) +
    update_k``).  Every collective operand is then ready at step entry —
    nothing gates the ppermute on this step's fwd/bwd — so on a real
    multi-device mesh XLA can schedule the collective-permute's ICI DMA
    concurrently with compute instead of serializing it after the
    optimizer.  (On the single-chip stacked twin there is no second
    engine to hide the gather behind; measured recovery there is ~1 % —
    artifacts/stacked_exchange_profile.json.)  Semantically this is one
    step of partner staleness: exactly what a free-running reference
    process sees when it pulls from a peer that has not finished its
    current step (SURVEY.md §3.2/§3.3 — the Rx thread serves the last
    *published* vector and loss).  The doubly-stochastic
    mean-preservation property is unchanged.

    Raises at call time if ``state.model_state`` is set — that state would
    silently stop updating; use :func:`make_gossip_train_step_with_state`."""
    return _make_step(
        loss_fn, optimizer, transport, exchange_filter, with_state=False,
        overlap=overlap,
    )


def make_gossip_train_step_with_state(
    loss_fn,
    optimizer: optax.GradientTransformation,
    transport: IciTransport,
    exchange_filter: Optional[Callable[[str], bool]] = None,
    overlap: bool = False,
):
    """Like :func:`make_gossip_train_step`, for models with non-parameter
    variables (BatchNorm running stats etc., the reference's stock torch
    ResNets).

    ``loss_fn(params, model_state, batch) -> (loss, new_model_state)``.
    ``model_state`` is exchanged together with the (filtered) params —
    running statistics belong to the replica, so they merge with the same
    α — but the optimizer never sees it.  ``overlap`` as in
    :func:`make_gossip_train_step`: the PRE-step model_state ships (the
    post-step one is produced by the forward pass the collective must not
    wait on) and this step's statistics delta is re-applied to the merged
    result, mirroring the params' merge-then-update rule."""
    return _make_step(
        loss_fn, optimizer, transport, exchange_filter, with_state=True,
        overlap=overlap,
    )


def make_gossip_eval_fn(
    apply_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
    transport: IciTransport = None,
):
    """Returns jitted ``eval_fn(stacked_params, x, y) -> accuracy[n]``.

    Evaluates every peer's replica on the same (replicated) test set.  With
    a ``transport``, runs as shard_map so each replica is evaluated on its
    own device with zero collectives; without one, falls back to vmap."""

    def one(params, x, y):
        logits = apply_fn(params, x)
        return jnp.mean(jnp.argmax(logits, -1) == y)

    if transport is None:

        @jax.jit
        def eval_fn(stacked_params, x, y):
            return jax.vmap(lambda p: one(p, x, y))(stacked_params)

        return eval_fn

    axis, mesh = transport.axis_name, transport.mesh

    def body(stacked_params, x, y):
        params = jax.tree.map(lambda v: v[0], stacked_params)
        return one(params, x, y)[None]

    mapped = shard_map(
        body, mesh=mesh, in_specs=(P(axis), P(), P()), out_specs=P(axis),
        check_vma=False,  # apply_fn may reach a Pallas kernel; see _make_step
    )
    return jax.jit(mapped)


def make_host_train_step(
    loss_fn: Callable[[PyTree, Any, Any], Any],
    optimizer: optax.GradientTransformation,
):
    """Jitted single-replica host step: ``step_fn(params, opt_state, x,
    y) -> (params, opt_state, loss)``.

    The multi-PROCESS twin of :func:`make_gossip_train_step`: where the
    SPMD loop fuses every peer's fwd/bwd/optimizer and the exchange into
    one ``shard_map`` program, the chaos-certified harness
    (:mod:`dpwa_tpu.run`, docs/training.md) runs one OS process per
    peer — each takes this local step, then hands the result to
    ``DpwaTcpAdapter.update`` for the TCP exchange (the reference's
    ``loss.backward(); optimizer.step(); adapter.update(loss)`` shape).
    One definition serves the harness and the examples' ``--certify``
    arms, so the certified loop and the benched loop cannot drift."""

    @jax.jit
    def step_fn(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step_fn


def consensus_params(stacked_params: PyTree) -> PyTree:
    """Mean over the peer axis — the 'deployed' model after training.

    Gossip preserves this mean at every exchange (doubly-stochastic merges),
    so it is the natural final artifact."""
    return jax.tree.map(lambda v: v.mean(axis=0), stacked_params)


def slice_peer_state(state: GossipTrainState, peer: int) -> GossipTrainState:
    """One peer's view of a peer-stacked state, as host numpy.

    The bootstrap donor payload (``dpwa_tpu/recovery/``): every
    peer-stacked leaf is sliced at ``peer`` on its leading axis; the
    per-peer ``clock``/``loss`` vectors keep their full length (they are
    the gossip metadata every replica already shares each round), and
    the scalar ``step`` rides unchanged.  Pairs with
    :func:`land_peer_state`."""
    import numpy as np

    take = lambda t: jax.tree.map(lambda v: np.asarray(v)[peer], t)
    return GossipTrainState(
        params=take(state.params),
        opt_state=take(state.opt_state),
        clock=np.asarray(state.clock),
        step=np.asarray(state.step),
        model_state=(
            take(state.model_state) if state.model_state is not None else None
        ),
        loss=np.asarray(state.loss) if state.loss is not None else None,
    )


def land_peer_state(
    state: GossipTrainState, peer: int, slice_state: GossipTrainState
) -> GossipTrainState:
    """Write a fetched peer slice back into a peer-stacked state.

    The rejoiner's landing step: its own row of every stacked leaf is
    replaced with the donor slice, and ``clock``/``step`` adopt the
    donor's values so the next participation/pairing draws line up with
    the ring's schedule position."""
    import numpy as np

    def put(stacked, sl):
        return jax.tree.map(
            lambda v, s: jnp.asarray(np.asarray(v)).at[peer].set(
                jnp.asarray(s)
            ),
            stacked,
            sl,
        )

    return GossipTrainState(
        params=put(state.params, slice_state.params),
        opt_state=put(state.opt_state, slice_state.opt_state),
        clock=jnp.asarray(slice_state.clock),
        step=jnp.asarray(slice_state.step),
        model_state=(
            put(state.model_state, slice_state.model_state)
            if state.model_state is not None
            else None
        ),
        loss=(
            jnp.asarray(slice_state.loss)
            if slice_state.loss is not None
            else state.loss
        ),
    )
