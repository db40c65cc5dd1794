"""The SPMD gossip training loop.

The reference's hot loop (SURVEY.md §3.2) is::

    forward / loss.backward() / optimizer.step()   # local, per process
    adapter.update(loss)                           # publish, fetch, merge

Here the entire loop — per-peer forward/backward, optax update, AND the
gossip exchange — is one jitted program, written once
(:func:`gossip_train_step`) and laid over peers three ways: a ``shard_map``
over the ``peers`` mesh axis (this module, SURVEY.md §3.5), the same with
sequences sharded inside a peer (:mod:`dpwa_tpu.train_sp`), or a leading
array axis on one device (:mod:`dpwa_tpu.parallel.stacked`).  On a mesh it is
**one jitted ``shard_map`` program**.  Manual SPMD, deliberately: auto sharding
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


def apply_gradients(optimizer, grads, opt_state, params):
    """``(new_params, updates, opt_state)`` under ``dpwa.optimizer``: the
    optimizer's half of every local update."""
    with jax.named_scope(scopes.OPTIMIZER):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), updates, opt_state


def local_update(
    loss_fn, optimizer: optax.GradientTransformation, with_state: bool
):
    """One peer's plain local step: ``(params, opt_state, model_state,
    batch) -> (new_params, updates, opt_state, new_model_state, loss)``, the
    forward pass under ``dpwa.forward`` so the backward pass names itself.
    The stacked step ``vmap``s it; the mesh step calls it on the device's
    own arrays; a layout with its own gradients (:mod:`dpwa_tpu.train_sp`)
    writes its own around :func:`apply_gradients`.

    Without ``with_state``, ``model_state`` is threaded through as an empty
    pytree ``()`` — zero leaves, so it adds nothing to the compiled
    program — keeping one step body for both."""
    grad_fn = jax.value_and_grad(
        scopes.scoped_loss(loss_fn), has_aux=with_state
    )

    def update(params, opt_state, model_state, batch):
        # ``grad_fn`` is called from here and not through one more helper:
        # the chip's host traces the model's forward the slower the deeper
        # the Python frames above it (PERF.md section 6, PR 29).
        if with_state:
            (loss, new_model_state), grads = grad_fn(
                params, model_state, batch
            )
        else:
            (loss, grads), new_model_state = grad_fn(params, batch), ()
        new_params, updates, opt_state = apply_gradients(
            optimizer, grads, opt_state, params
        )
        return new_params, updates, opt_state, new_model_state, loss

    return update


def gossip_train_step(
    update, exchange, *, exchange_filter: Optional[Callable[[str], bool]],
    overlap: bool, with_state: bool, lay_out=lambda body: body,
    block_per_call: bool = False,
):
    """THE train step, behind every public step factory: local update, then
    the gossip round, over values whose peer layout it does not know.  After
    ``update`` every operation is elementwise on trees, so the same body
    serves one peer's arrays inside ``shard_map`` and ``[n, ...]`` stacks
    under plain ``jit``.

    ``update`` is :func:`local_update` laid over peers, ``exchange(tree,
    meta, step)`` the round in the same layout, and ``lay_out(body)`` what
    puts the body there (nothing for stacks; ``shard_map`` in
    :func:`_make_step`).  ``overlap`` selects which replica the exchange
    ships (see :func:`make_gossip_train_step`): post-update (default, the
    lock-step emulation) or pre-update ``x_k`` (the collective overlaps
    fwd/bwd)."""
    select = lambda tree: (
        tree if exchange_filter is None
        else pytree_partition(tree, exchange_filter)[0]
    )

    def body(params, opt_state, model_state, clock, prev_loss, step, batch):
        new_params, updates, opt_state, new_model_state, loss = update(
            params, opt_state, model_state, batch
        )
        clock = clock + 1.0
        if overlap:
            # Ship what was ready at step entry — x_k, its model_state, the
            # last loss this peer "published" — so nothing gates the
            # ppermute (or the gather's HBM reads) on this step's fwd/bwd.
            # Why, and what it means: make_gossip_train_step's docstring.
            shipped, shipped_state = params, model_state
            meta = PeerMeta(clock, prev_loss)
        else:
            shipped, shipped_state = new_params, new_model_state
            meta = PeerMeta(clock, loss.astype(jnp.float32))
        (merged, merged_state), info = exchange(
            (select(shipped), shipped_state), meta, step
        )
        if overlap:
            # x_{k+1} = merge(x_k) + own update: the local gradient is
            # never lost.  Model state gets the same treatment: merge(ms_k)
            # + this step's statistics delta.
            with jax.named_scope(scopes.OPTIMIZER):
                merged = optax.apply_updates(merged, select(updates))
            merged_state = jax.tree.map(
                lambda m, new, old: m + (new - old),
                merged_state, new_model_state, model_state,
            )
        if exchange_filter is not None:
            # Everything else never moves — neither over ICI nor DCN.
            _, rest = pytree_partition(new_params, exchange_filter)
            merged = pytree_combine(merged, rest)
        return merged, opt_state, merged_state, clock, loss, tuple(info)

    laid_out = lay_out(body)

    # Donated: each call consumes the input state's buffers (the caller
    # rebinds `state, … = step(state, …)`).  Without donation every
    # in-flight step holds a fresh params+opt copy and a deep async
    # dispatch queue can swamp the HBM allocator.
    @functools.partial(jax.jit, donate_argnums=(0,))
    def _step(state, batch):
        prev_loss = (
            jnp.zeros_like(state.clock) if state.loss is None else state.loss
        )
        params, opt_state, model_state, clock, losses, info = laid_out(
            state.params, state.opt_state,
            state.model_state if with_state else (),
            state.clock, prev_loss, state.step, batch,
        )
        new_state = state._replace(
            params=params, opt_state=opt_state, clock=clock,
            step=state.step + 1,
            model_state=model_state if with_state else state.model_state,
            loss=losses,
        )
        return new_state, losses, ExchangeInfo(*info)

    def train_step(state, batch):
        # Silently frozen BatchNorm statistics are worse than an error.
        if not with_state and state.model_state is not None:
            raise ValueError(
                "state carries model_state but this step was built without "
                "it and would never update it; build the step with model "
                "state (with_state=True or the *_with_state factory)"
            )
        if with_state and state.model_state is None:
            raise ValueError(
                "step built with model state but state.model_state is None; "
                "pass stacked_model_state when the state is initialised"
            )
        out = _step(state, batch)
        if block_per_call:
            jax.block_until_ready(out)
        return out

    return train_step


def _make_step(
    update,
    transport: IciTransport,
    exchange_filter: Optional[Callable[[str], bool]],
    with_state: bool,
    overlap: bool = False,
    batch_spec=None,
):
    """Shared builder behind the public mesh step factories, here and in
    :mod:`dpwa_tpu.train_sp`: :func:`gossip_train_step` with one peer a
    position on the transport's mesh axis.  ``update`` (here
    :func:`local_update`) and ``batch_spec`` (default: peers on axis 0) are
    what a layout with more axes inside a peer brings of its own."""
    axis = transport.axis_name

    def over_mesh(body):
        def per_device(params, opt_state, model_state, clock, loss, step, batch):
            # Local (per-device) values: strip the size-1 peer block axis.
            own = lambda t: jax.tree.map(lambda v: v[0], t)
            out = body(
                *own((params, opt_state, model_state, clock, loss)), step,
                own(batch),
            )
            return jax.tree.map(lambda v: v[None], out)

        # Unchecked map (check_vma=False), on every shard_map in the
        # package.  Under the checked map a ``pallas_call`` is refused
        # unless its ``out_shape`` carries ``vma``, and the flash kernels a
        # Llama ``loss_fn`` reaches on a TPU are the library's: they build
        # their ``out_shape`` themselves, so the other road (vma on the
        # kernels' outputs) would mean keeping copies of them.  Nothing
        # here leaned on the check: every differentiated operand varies
        # over ``axis``.
        return shard_map(
            per_device,
            mesh=transport.mesh,
            in_specs=(
                P(axis), P(axis), P(axis), P(axis), P(axis), P(),
                P(axis) if batch_spec is None else batch_spec,
            ),
            out_specs=P(axis),
            check_vma=False,
        )

    return gossip_train_step(
        update,
        functools.partial(
            gossip_exchange_local, schedule=transport.schedule,
            interp=transport.interp, axis_name=axis,
        ),
        exchange_filter=exchange_filter, overlap=overlap,
        with_state=with_state, lay_out=over_mesh,
        # CPU run-ahead bound as IciTransport.exchange (see the rationale
        # comment there) — reuse its detection so the rule lives in one
        # place.
        block_per_call=transport._block_per_call,
    )


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
    optimizer.  (On the single-chip stacked layout there is no second
    engine to hide the gather behind, and the deferred form recovers
    next to nothing there.)  Semantically this is one
    step of partner staleness: exactly what a free-running reference
    process sees when it pulls from a peer that has not finished its
    current step (SURVEY.md §3.2/§3.3 — the Rx thread serves the last
    *published* vector and loss).  The doubly-stochastic
    mean-preservation property is unchanged.

    Raises at call time if ``state.model_state`` is set — that state would
    silently stop updating; use :func:`make_gossip_train_step_with_state`."""
    return _make_step(
        local_update(loss_fn, optimizer, False), transport, exchange_filter,
        with_state=False, overlap=overlap,
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
        local_update(loss_fn, optimizer, True), transport, exchange_filter,
        with_state=True, overlap=overlap,
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

    :func:`local_update` for the multi-PROCESS path: where the SPMD loop
    fuses every peer's fwd/bwd/optimizer and the exchange into one
    program, the chaos-certified harness
    (:mod:`dpwa_tpu.run`, docs/training.md) runs one OS process per
    peer — each takes this local step, then hands the result to
    ``DpwaTcpAdapter.update`` for the TCP exchange (the reference's
    ``loss.backward(); optimizer.step(); adapter.update(loss)`` shape).
    One definition serves the harness and the examples' ``--certify``
    arms, so the certified loop and the benched loop cannot drift."""

    update = local_update(
        lambda params, batch: loss_fn(params, *batch), optimizer, False
    )

    @jax.jit
    def step_fn(params, opt_state, x, y):
        params, _, opt_state, _, loss = update(params, opt_state, (), (x, y))
        return params, opt_state, loss

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
