"""The comparison that decides ``correct``: one gossip step is a local
update, a permutation and a lerp.

Nothing here imports a step builder or an exchange body.  From the live state
and one batch, :func:`make_local_update` and :func:`merge` compute in plain ``jax.numpy``

    u_i  = optax_update(grad(loss_fn)(p_i, batch_i))            per peer
    p'_i = (1 - alpha_i) * u_i + alpha_i * u_{partner_i}        exchanged leaves

in float32 with ``jax.default_matmul_precision("highest")`` around the merge,
with ``partner`` and ``alpha`` taken from the ``ExchangeInfo`` the system's
own step returned for the same state.  :func:`compare` then holds every
exchanged leaf of the system's result to it.

The tolerance, per leaf, on root-mean-squares over the leaf:

    rms(p'_sys - p'_ref)  <=  A_UPDATE * rms(u - p)  +  B_PARAM * rms(p')

Reason.  The two sides compute the same gradient twice, by two programs
(``vmap`` or ``shard_map`` in the step, a loop over peers here), so they
differ by the bf16 rounding of activations, which reaches a parameter only
through ``lr x gradient``: a small share of the local update ``u - p``.  On
the v5e that share was at most 1.5 % (ResNet-50 stacked), 0.6 % (decoder) and
3.6 % (ResNet-50 across four chips, once in nine runs) of the update's rms
(my chip runs, PR 22); A_UPDATE is three times the worst seen.  The merge
itself is float32 arithmetic on float32 values, exact to a few roundings of
2^-24.  A bf16 wire would perturb the partner's half by about
alpha * 2^-9 / sqrt(3) = 6e-4 of ``rms(p)``, an int8 wire by more, and a
wrong partner or alpha by the distance between replicas; all of those are
far above B_PARAM, and above A_UPDATE of an update as long as one step moves
a leaf by less than about a tenth of its size (a leaf that is still little more than its own updates, such as a
bias or a LoRA B matrix just after a zero start, cannot tell; one leaf that
can is enough, because a wire rounds them all).  ``wire_margin`` in the report
is the perturbation a bf16 wire would cause over the tolerance, on the leaf
where that is largest, in this run.  Where one step moves a leaf by
several per cent of its size (LoRA adapters after 30 Adam steps) it falls
under 1; so ``run.py`` also calls the transport's exchange alone on the live
tree and holds it to ``merge`` with no update term (``moved`` = 0): nothing
but float32 rounding separates the two there, and a bf16 wire is hundreds of
times outside in every cell.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

A_UPDATE = 1e-1
B_PARAM = 2e-6
# The model against its plain float32 reference, on logits: the configuration
# computes in bfloat16 (8 bits of mantissa: 2^-9 a rounding, accumulated over
# some tens of layers' activations to about 1e-2 of the logits' rms), so 3e-2
# passes honest bf16 and fails a computation in a coarser type (fp8's 2^-4 a
# rounding) or with part of the mathematics left out.
MODEL_TOLERANCE = 3e-2


class Verdict(NamedTuple):
    ok: bool
    reasons: tuple  # human-readable, empty when ok
    worst_ratio: float  # largest error / tolerance over the leaves
    wire_margin: float  # largest bf16-wire perturbation / tolerance


def partition(tree, exchange_filter):
    """(exchanged leaves, the rest) as flat lists with their paths."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    picked, rest = [], []
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        chosen = exchange_filter is None or exchange_filter(name)
        (picked if chosen else rest).append((name, leaf))
    return picked, rest


def check_info(partner, alpha, participated, factor: float) -> list:
    """``partner`` is an involution with no fixed point where a peer
    participated, and ``alpha`` is 0 or the configured factor."""
    partner = np.asarray(partner)
    alpha = np.asarray(alpha, np.float64)
    participated = np.asarray(participated, bool)
    n = len(partner)
    reasons = []
    if partner.min() < 0 or partner.max() >= n:
        return [f"partner out of range: {partner.tolist()}"]
    if not np.array_equal(partner[partner], np.arange(n)):
        reasons.append(f"partner is not an involution: {partner.tolist()}")
    if np.any(participated & (partner == np.arange(n))):
        reasons.append(f"a participant is its own partner: {partner.tolist()}")
    expected = np.where(participated, factor, 0.0)
    if not np.allclose(alpha, expected, rtol=1e-6, atol=0):
        reasons.append(
            f"alpha {alpha.tolist()} is not {factor} where participated"
        )
    return reasons


def frozen_checksum(params, exchange_filter):
    """One uint32 a frozen leaf: the wrapping sum of its bit patterns.  Taken
    at init and after the last step, so no second copy of the base is held."""
    _, rest = partition(params, exchange_filter)

    def bits(v):
        width = {2: jnp.uint16, 4: jnp.uint32}[v.dtype.itemsize]
        return jnp.sum(
            jax.lax.bitcast_convert_type(v, width).astype(jnp.uint32),
            dtype=jnp.uint32,
        )

    return [bits(v) for _, v in rest]


def _rms(x):
    return jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32))))


def make_local_update(loss_fn, optimizer, exchange_filter):
    """Jitted ``(params, opt_state, batch) -> (u, moved)`` over peer-stacked
    trees: every peer's exchanged leaves after its own optimizer step and
    before any exchange, in ``partition`` order, and ``rms(u - p)`` of each
    leaf, the size of its local update.  The peers are walked one at a time
    (``lax.map``), so the check holds one peer's activations beside the live
    state, and shares neither ``vmap`` nor ``shard_map`` with the step."""

    def one_peer(params, opt_state, batch):
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        chosen = [
            exchange_filter is None
            or exchange_filter(jax.tree_util.keystr(path))
            for path, _ in flat
        ]
        leaves = [v for _, v in flat]

        def fill(trainable, others):
            it = iter(trainable)
            return treedef.unflatten(
                [next(it) if c else o for c, o in zip(chosen, others)]
            )

        grads = jax.grad(lambda tr: loss_fn(fill(tr, leaves), batch))(
            [v for v, c in zip(leaves, chosen) if c]
        )
        # Leaves outside the exchange are frozen in every first cell: the
        # optimizer never reads their gradient (set_to_zero), so zeros stand
        # in for it and the compiler drops them.
        full = fill(grads, [jnp.zeros_like(v) for v in leaves])
        updates, _ = optimizer.update(full, opt_state, params)
        new = optax.apply_updates(params, updates)
        return [v for v, c in zip(jax.tree.leaves(new), chosen) if c]

    def local(params, opt_state, batch):
        u = jax.lax.map(lambda a: one_peer(*a), (params, opt_state, batch))
        old = [v for _, v in partition(params, exchange_filter)[0]]
        moved = jnp.stack([
            _rms(x.astype(jnp.float32) - p.astype(jnp.float32))
            for x, p in zip(u, old)
        ])
        return u, moved

    return jax.jit(local)


@jax.jit
def merge(u_leaves, partner, alpha):
    """``(1 - alpha_i) * u_i + alpha_i * u_{partner_i}`` on every leaf, in
    float32 at the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        out = []
        for leaf in u_leaves:
            a = alpha.astype(jnp.float32).reshape(
                (-1,) + (1,) * (leaf.ndim - 1)
            )
            x = leaf.astype(jnp.float32)
            out.append((1.0 - a) * x + a * x[partner])
        return out


@jax.jit
def _leaf_stats(sys_leaves, ref_leaves, alpha):
    """Per leaf: rms error, rms size, rms of what a bf16 wire would change,
    and whether the system's leaf is finite."""

    def one(got, want):
        a = alpha.reshape((-1,) + (1,) * (want.ndim - 1))
        # Not astype(bfloat16).astype(float32): XLA:TPU may drop that pair
        # (xla_allow_excess_precision), and the margin would read 0.
        rounded = jax.lax.reduce_precision(want, exponent_bits=8, mantissa_bits=7)
        return jnp.stack([
            _rms(got.astype(jnp.float32) - want), _rms(want),
            _rms(a * (rounded - want)),
            jnp.all(jnp.isfinite(got)).astype(jnp.float32),
        ])

    return jnp.stack(
        [one(g, w) for g, w in zip(sys_leaves, ref_leaves)]
    )


def compare(sys_params, ref_leaves, moved, alpha, exchange_filter) -> Verdict:
    """Hold the system's exchanged leaves to the reference's."""
    picked, _ = partition(sys_params, exchange_filter)
    if len(picked) != len(ref_leaves):
        return Verdict(False, ("exchanged leaves differ in number",), 0.0, 0.0)
    stats = np.asarray(_leaf_stats(
        [v for _, v in picked], list(ref_leaves), alpha
    ), np.float64)
    moved = np.asarray(moved, np.float64)
    reasons, worst, margin = [], 0.0, 0.0
    for (name, _), (err, size, wire, finite), upd in zip(picked, stats, moved):
        tolerance = A_UPDATE * upd + B_PARAM * size
        if not finite or not err <= tolerance:
            reasons.append(
                f"{name}: rms error {err:.3e} > tolerance {tolerance:.3e} "
                f"(update {upd:.3e}, size {size:.3e})"
            )
        if tolerance > 0:
            worst = max(worst, err / tolerance)
            margin = max(margin, wire / tolerance)
    return Verdict(not reasons, tuple(reasons[:5]), worst, margin)


def make_model_check(apply_fn, reference_forward, reference_inputs):
    """Jitted ``(peer-stacked params, peer-stacked batch) -> (rms error, rms
    of the reference's logits)`` for peer 0 on the builder's sample: the
    program's model against the configuration's plain reference."""

    def check(params, batch):
        first = lambda tree: jax.tree.map(lambda v: v[0], tree)
        inputs = reference_inputs(first(batch))
        got = apply_fn(first(params), inputs).astype(jnp.float32)
        want = reference_forward(first(params), inputs)
        return _rms(got - want), _rms(want)

    return jax.jit(check)
