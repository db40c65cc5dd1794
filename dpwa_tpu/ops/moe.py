"""A dropless sparse-expert feed-forward: router, top-k, dispatch, grouped
matmuls with per-expert LoRA factors, weighted combine, load-balancing term.

The layer (the published ``OlmoeSparseMoeBlock``), for tokens ``x [N, D]``:

    p      = softmax_f32(x Wr)                 over all E experts
    (w, S) = top_k(p)                          no renormalisation of w
    y      = sum_{e in S} w_e * W_down_e(silu(W_gate_e x) * W_up_e x)

(:func:`route` also gates by sigmoid scores, renormalised over the chosen and
scaled: the DeepSeek-V3 gate.)  **A share of the experts** (``offset``):
the weights handed in are those of experts ``[offset, offset + held)`` of
the E the router chose among, one chip's share under expert parallelism,
and ``y`` sums over ``e in S`` that are held; ``w`` is what the gate gave,
normalised over all of S.  Nothing stands in for the absent experts.

**Dropless** means every one of the ``N x k`` (token, expert) assignments is
computed, at any routing skew: there is no capacity factor, no token is
dropped and no group is padded to a fixed size.  The assignments are sorted
by expert, the tokens' rows are gathered into that order (``[N x k, D]``, an
expert's rows contiguous), and each projection is a *grouped* matmul: row
``i`` is multiplied by the weights of the expert whose group it lies in,
``group_sizes [E]`` (summing to exactly ``N x k``) saying where groups end.

**The peer axis.**  ``parallel/stacked.py`` runs a peer's whole step under
``jax.vmap``, so everything here batches over replicas.  A grouped matmul
vmapped over ``n`` peers *is* a grouped matmul with ``n x E`` groups over
``n x N x k`` rows, and :func:`grouped_matmul` says so with a ``custom_vmap``
rule that folds the peer axis into the group axis by reshapes alone (each
peer's groups sum to its own row count, so the folded groups tile the folded
rows).  That is not an optimisation only: a Pallas kernel with scalar
prefetch batches by a loop over peers, and XLA:TPU refuses the batch
dimension that ``lax.ragged_dot``'s own batching rule would give it ("number
of batch dimensions should be 0").  Under ``shard_map``
(``train._make_step``) and in a plain loop the same functions run unbatched.

**Which grouped matmul.**  On a TPU, where the rows tile, the library's Pallas
``megablox`` kernels (``gmm`` forward and, with ``transpose_rhs``, to the
activations; ``tgmm`` to the weights) with tiles picked from the shapes by
:func:`_tiling`; everywhere else ``lax.ragged_dot``, which XLA:CPU expands
densely (fine at test sizes).  Nothing but the backend and the shapes
chooses: no argument, config field, flag or environment variable.  (A share
of the experts, :func:`held_matmul`, runs the kernels under ``vmap`` and
plain masked products unbatched: the comment above it says why.)  On the
v5e ``ragged_dot`` runs XLA's own grouped kernel at 70 TFLOP/s on the cell's
shapes, needs a 0.5 GB transposed copy of the kernels for the gradient to the
activations (48 TFLOP/s with it) and loses the instruction's ``op_name``, so
no scope finds it in a trace; ``megablox`` keeps its name and runs 107 to 113
TFLOP/s forward and to the activations at :func:`_tiling`'s tiles (88 to 103
at PR 27's ``(256, 1024, 1024)``, 9 at the library's default ``(128, 128,
128)``; PERF.md section 6, PR 27 and PR 45, has the sweeps).

**What the adapters share** (PR 30).  A rank-r factor's grouped matmul reads
or writes a whole sorted ``[N x k, D]`` array for r columns and runs at the
memory rate, so its cost is how often a wide array is read or written, and
:func:`moe_ffn` lets an adapter have a pass of its own only where the algebra
gives it no other.  (1) Gate's and up's A sides have one input, so they are
one grouped matmul by ``[A_gate | A_up]`` (:func:`gate_and_up`): one pass
over the rows forward, one ``tgmm`` to ``[E, D, 2r]`` and one ``gmm`` to the
rows backward, where there were two of each.  (2) The dispatch gathers the
sorted rows from the tokens themselves (``x[order // k]``), not from a
written ``jnp.repeat(x, k)``.  (3) The layer's output is linear in the down
projection, so the down adapter's B side is applied after the combine, in
token order: ``y += lora_scale x z B_all`` with ``z [N, E x r]`` holding
``w[n, j] x (hidden A_e)[n, j]`` at the columns of the expert e of choice j,
one dense matmul a peer in place of a grouped one that writes ``[N x k, D]``,
an add over it, and two backward calls that read the output's gradient.  The
same products, summed in another order; no precision changes.  What decides
is what is passed: (1) needs ``lora_a`` of one shape on both of gate and up
(else :func:`expert_projection` for each, as the dense-expert tests run it),
(3) an adapter on ``w_down``.

**The down projection and the combine, one rule** (PR 50, PR 51).  The
combine reads the experts' output where the frozen down projection wrote it,
sorted, and the two share one hand-written gradient
(:func:`_down_and_combine`).  Its residuals are ``hidden`` (the SwiGLU's
result, which the down adapter keeps anyway), the kernel, the routing weights,
``order`` and ``group_sizes``: neither the experts' output nor a copy of it in
token order, because a block under ``jax.checkpoint`` runs its forward pass
again to remake residuals, and whatever is one would be multiplied or gathered
a second time for a single reader.  Backward, ``d_y``'s own rows are gathered
into expert order (``picked``; nothing is widened to ``[N, k, D]``) and enter
one product, ``g = picked W_down^T``, unweighted.  The weight enters after it,
on the expert width: ``d_hidden = w x g``, the weights' gradient is the row
dot ``<g, hidden>`` and the kernel's own ``(w x hidden)^T picked``; ``picked``
is the one ``[N x k, D]`` array the rule writes.  A share's window
(:func:`_window`) keeps two rules: it is a checkpoint of its own, and its
combine a product by a placement matrix (:func:`_combine_rows`).

**The rows a share works on** (PR 34).  A share that holds ``held`` of the
router's ``E`` experts is sent ``N x k x held / E`` assignments on average,
and only an imbalance no shape can rule out sends it all ``N x k``.  So a
share walks its sorted rows a *window* at a time (:func:`_capped_ffn`):
:func:`row_cap` rows, four times that average, gathered, multiplied and
combined by one static program.  The held assignments are sorted first, so
the first window holds them all while the share's count is within the cap,
and it is the only one computed; a share sent more computes as many further
windows as its count needs and adds their parts.  How many is one scalar for
all peers (:func:`_most_of_peers`), so under ``vmap`` the walk stays one loop
and a peer inside its cap adds windows of empty groups.  Every held row
passes through the same grouped matmuls, in the same groups, whatever window
it falls in: nothing is dropped and nothing depends on the cap but time (and,
past the first window, the last bit: a group that straddles two windows has
its adapters' gradient rounded to ``dtype`` once a window, then added).
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax import custom_batching, lax

from dpwa_tpu.ops.wide import narrow
from dpwa_tpu.utils import scopes

HIGHEST = lax.Precision.HIGHEST
# [M, K] x [M, N] -> [G, K, N]: the rows of both operands are the ragged,
# contracted dimension (the weight gradient of a grouped matmul).
_RAGGED_CONTRACTING = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0],
    rhs_group_dimensions=[],
)


def _use_kernels(rows: int) -> bool:
    """The Pallas kernels need a TPU and rows that their row tile divides."""
    return jax.default_backend() == "tpu" and rows % 512 == 0


def _tiling(k: int, n: int, contracts_k: bool = True):
    """``(tm, tk, tn)`` for the ``megablox`` kernels from the two dense
    dimensions ``k x n`` of a group's weights (``gmm`` contracts ``k``; in
    ``tgmm`` they are the result's, ``contracts_k`` false, and the tile ``tk
    x tn`` is the float32 accumulator).  Each clause is a sweep on the v5e
    (PERF.md section 6: PR 27 and PR 45 at 65,536 rows in 128 groups, PR 44
    at 32,768 in 64 with an expert width of 1,792 = 7 x 256, PR 49 at 65,536
    in 128 with 896 = 7 x 128 over a hidden size of 2,304 = 18 x 128).

    A rank-16 adapter (one side under 128): 512 rows, its narrow side padded
    to one 128-lane tile, the wide side up to 2048 when it is contracted.

    The wide matmuls: 256 rows by a tile of the weights within 2,048 x 1,024
    values, filled ``k`` first.  A contracted ``k`` up to 2,304, the longest
    swept, is whole: in two tiles or three the weights' tile changes at every
    grid step and is read again for every tile of rows (3.00 to 3.13 ms a
    product in two tiles of 1,024 and 2.43 whole, PR 45; 3.07 in three of 768,
    2.98 in two of 1,152 and 2.38 whole at 2,304 x 896, PR 49); a longer one,
    and either side in ``tgmm`` (Mosaic refuses a whole 2,048 there), by its
    divisor up to 1,024.  ``n`` by the largest multiple of 128 that divides it
    within the tile and 2,304 (the accumulator is ``tm x tn``: Mosaic refuses
    256 x 8,192): 2,048 over a contracted 1,024 reads the rows once (2.55 ms
    against 2.68, PR 45), a whole 2,304 over a contracted 896 likewise (2.54
    against 2.67 in two of 1,152, PR 49), 896 over 1,792 pays no second tile
    that is a quarter empty (2.21 against 2.41, PR 44)."""
    lanes = lambda v: -(-v // 128) * 128
    if min(k, n) < 128:
        tk = min(lanes(k), 2048 if n < 128 else 1024)
        return 512, tk, min(lanes(n), 1024)
    k, n = lanes(k), lanes(n)
    # (A divisor under 512 pays more grid steps than a masked tile wastes.)
    divisor = lambda v, cap: max(
        (t for t in range(512, min(v, cap) + 1, 128) if v % t == 0),
        default=min(v, 1024),
    )
    tk = k if contracts_k and k <= 2304 else divisor(k, 1024)
    cap = min(2304, 2048 * 1024 // tk) if contracts_k else 1024
    return 256, tk, divisor(n, cap)


def _kernels():
    # The package's ``gmm`` attribute is its custom_vjp wrapper; the module
    # with the bare kernels is found by name.
    import importlib

    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm"
    )


def _folding_peers(fn):
    """``fn(a, b, group_sizes)`` with the vmap rule of the module docstring:
    peer-stacked operands have the peer axis folded into their first axis
    (rows, groups) by a reshape, ``[n, a, ...] -> [n * a, ...]``, one call is
    made, and the result is unfolded.  An operand the vmap did not batch is
    the same for every peer and is repeated."""
    fn = custom_batching.custom_vmap(fn)

    @fn.def_vmap
    def over_peers(axis_size, in_batched, *args):
        folded = [
            (a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape))
            .reshape((-1,) + a.shape[(2 if batched else 1):])
            for a, batched in zip(args, in_batched)
        ]
        out = fn(*folded)
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return fn


@_folding_peers
def _gmm(lhs, rhs, group_sizes):
    """``[M, K] x [G, K, N] -> [M, N]``, row i by the weights of its group."""
    if not _use_kernels(lhs.shape[0]):
        return lax.ragged_dot(lhs, rhs, group_sizes)
    return _kernels().gmm(
        lhs, rhs, group_sizes, lhs.dtype, _tiling(*rhs.shape[1:])
    )


@_folding_peers
def _gmm_transposed(grad, rhs, group_sizes):
    """``[M, N] x [G, K, N] -> [M, K]``: row i by its group's ``rhs^T``."""
    if not _use_kernels(grad.shape[0]):
        return lax.ragged_dot(grad, jnp.swapaxes(rhs, 1, 2), group_sizes)
    return _kernels().gmm(
        grad, rhs, group_sizes, grad.dtype, _tiling(*rhs.shape[:0:-1]),
        transpose_rhs=True,
    )


@_folding_peers
def _tgmm(lhs, grad, group_sizes):
    """``[M, K] x [M, N] -> [G, K, N]``: each group's ``lhs^T grad``."""
    if not _use_kernels(lhs.shape[0]):
        return lax.ragged_dot_general(
            lhs, grad, group_sizes, _RAGGED_CONTRACTING
        )
    return _kernels().tgmm(
        lhs.swapaxes(0, 1), grad, group_sizes, lhs.dtype,
        _tiling(lhs.shape[1], grad.shape[1], contracts_k=False),
    )


def _differentiable(gmm, gmm_transposed, tgmm):
    """A grouped matmul differentiable in ``lhs`` and ``rhs`` from its three
    products: forward, to the activations, to the weights.  The two of the
    backward pass are its ``to_rows`` and ``to_weights``, for a rule that
    writes the gradient of more than the matmul (:func:`_down_and_combine`)."""

    @jax.custom_vjp
    def matmul(lhs, rhs, group_sizes):
        return gmm(lhs, rhs, group_sizes)

    def fwd(lhs, rhs, group_sizes):
        return gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(residuals, grad):
        lhs, rhs, group_sizes = residuals
        with jax.named_scope(scopes.MOE_EXPERTS):
            d_lhs = gmm_transposed(grad, rhs, group_sizes)
            d_rhs = tgmm(lhs, grad, group_sizes)
        return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype), None

    matmul.defvjp(fwd, bwd)
    matmul.to_rows, matmul.to_weights = gmm_transposed, tgmm
    return matmul


grouped_matmul = _differentiable(_gmm, _gmm_transposed, _tgmm)
grouped_matmul.__doc__ = """``[M, K] x [G, K, N] -> [M, N]``: rows ``lhs`` sorted by group, row i
multiplied by ``rhs[g]`` of the group g it lies in; ``group_sizes [G]``
(int32) sums to M.  Differentiable in ``lhs`` and ``rhs``; under ``vmap``
the batch axis becomes more groups (module docstring)."""


# A share of the experts.  The rows bound for absent experts are sorted last
# and lie in no group, so ``group_sizes`` ends before the rows do, and folded
# over peers a peer's groups would no longer start where its rows do.
#
# Outside ``vmap`` the three products are written plainly: every held expert
# on every row, masked by the row's group (``G`` times the arithmetic of a
# grouped kernel, in XLA's own dots, accumulated in float32 and rounded once
# as a kernel's result is), and where the kernels could have run a warning
# says so.  That is the form a loop over peers runs, and it is chosen for
# what it holds, not for its speed: a caller that cuts one replica out of a
# stacked tree feeds XLA's dots the cut itself, where a kernel's operand has
# to be written out first, a whole replica's expert kernels beside the live
# state (PERF.md section 4), and nothing here can tell that caller from one
# that holds its replica whole.  ``vmap`` over a peer axis of one runs the
# kernels.
#
# Under ``vmap`` on a TPU the products are the ``megablox`` kernels.  They
# already serve weights that are a shard of the groups (``group_offset``: a
# leading group is skipped, and with more groups than weights the rows not
# visited come out zero), so the rule makes one call a peer on the folded
# rows: that peer's groups behind a leading group of the rows before them,
# the other peers' groups empty, each call writing into the result of the
# one before (``existing_out``, in place).  No array is sliced or stacked but
# ``tgmm``'s adapter-sized results.


def _membership(group_sizes, rows: int, dtype):
    """``[G, M]``: 1 where row m lies in group g; a row past the groups lies
    in none."""
    ends = jnp.cumsum(group_sizes)[:, None]
    row = jnp.arange(rows)[None, :]
    return ((row >= ends - group_sizes[:, None]) & (row < ends)).astype(dtype)


def _plain_gmm(lhs, rhs, group_sizes, transpose_rhs=False):
    """``[M, K] x [G, K, N] -> [M, N]`` (``rhs [G, N, K]`` transposed); a row
    past the groups comes out zero.  Accumulated in float32 and rounded once,
    as a kernel's result is."""
    member = _membership(group_sizes, lhs.shape[0], lhs.dtype)
    return narrow(jnp.einsum(
        "gm,mk,gnk->mn" if transpose_rhs else "gm,mk,gkn->mn",
        member, lhs, rhs, preferred_element_type=jnp.float32,
    ), lhs.dtype)


def _plain_tgmm(lhs, grad, group_sizes):
    """``[M, K] x [M, N] -> [G, K, N]``: each group's ``lhs^T grad``."""
    member = _membership(group_sizes, lhs.shape[0], lhs.dtype)
    return narrow(jnp.einsum(
        "gm,mk,mn->gkn", member, lhs, grad,
        preferred_element_type=jnp.float32,
    ), lhs.dtype)


def _kernel_gmm(lhs, rhs, group_sizes, skip, out, transpose_rhs=False):
    """The groups' rows of ``lhs``, which start at row ``skip``, by ``rhs``;
    the other rows are ``out``'s, or zero."""
    sizes = jnp.concatenate([jnp.full((1,), skip, jnp.int32), group_sizes])
    tiling = _tiling(*(rhs.shape[:0:-1] if transpose_rhs else rhs.shape[1:]))
    return _kernels().gmm(
        lhs, rhs, sizes, lhs.dtype, tiling, group_offset=jnp.int32(1),
        existing_out=out, transpose_rhs=transpose_rhs,
    )


def _kernel_tgmm(lhs, grad, group_sizes, skip):
    sizes = jnp.concatenate([jnp.full((1,), skip, jnp.int32), group_sizes])
    return _kernels().tgmm(
        lhs.swapaxes(0, 1), grad, sizes, lhs.dtype,
        _tiling(lhs.shape[1], grad.shape[1], contracts_k=False),
        group_offset=jnp.int32(1), num_actual_groups=group_sizes.shape[0],
    )


def _peer_by_peer(plain, kernel, rows_out: bool):
    """``plain(a, b, group_sizes)`` with the vmap rule of the comment above:
    ``kernel`` a peer on the folded rows where the kernels run, else
    ``plain`` a peer.  An operand the vmap did not batch is repeated."""
    def alone(a, b, group_sizes):
        if _use_kernels(a.shape[0]):
            warnings.warn(
                "held_matmul outside vmap runs every held expert on every "
                "row in XLA's own dots (the number of held experts times "
                "the arithmetic); vmap over a peer axis, of one if need "
                "be, runs the grouped kernels", stacklevel=2,
            )
        return plain(a, b, group_sizes)

    # (Three arguments exactly: custom_vmap would trace a keyword's default.)
    fn = custom_batching.custom_vmap(alone)

    @fn.def_vmap
    def over_peers(axis_size, in_batched, a, b, group_sizes):
        a, b, group_sizes = (
            x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, batched in zip((a, b, group_sizes), in_batched)
        )
        rows = a.shape[1]
        peers = range(axis_size)
        if not _use_kernels(axis_size * rows):
            return jnp.stack([
                plain(a[p], b[p], group_sizes[p]) for p in peers
            ]), True
        fold = lambda x: x.reshape((-1,) + x.shape[2:])
        if not rows_out:
            return jnp.stack([
                kernel(fold(a), fold(b), group_sizes[p], p * rows)
                for p in peers
            ]), True
        out = None
        for p in peers:
            own = jnp.zeros_like(group_sizes).at[p].set(group_sizes[p])
            out = kernel(fold(a), fold(b), fold(own), p * rows, out)
        return out.reshape((axis_size, rows) + out.shape[1:]), True

    return fn


held_matmul = _differentiable(
    _peer_by_peer(_plain_gmm, _kernel_gmm, True),
    _peer_by_peer(
        functools.partial(_plain_gmm, transpose_rhs=True),
        functools.partial(_kernel_gmm, transpose_rhs=True), True,
    ),
    _peer_by_peer(_plain_tgmm, _kernel_tgmm, False),
)
held_matmul.__doc__ = """:func:`grouped_matmul` for a share of the experts: ``group_sizes [G]``
may sum to less than M, and the rows past the groups come out zero (their
gradient too)."""


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows(x, order, inverse, k: int):
    """Tokens ``x [N, D]`` into expert order, ``[N x k, D]``: sorted row i is
    the token of flat assignment ``order[i]``, ``x[order // k]``, gathered
    from the tokens themselves (``jnp.repeat(x, k)`` is never written).  The
    gradient is a gather too: ``grad[inverse]`` is token-major, ``[N, k, D]``,
    and a token's k rows are summed."""
    return x[order // k]


def _dispatch_rows_fwd(x, order, inverse, k):
    return x[order // k], inverse


def _dispatch_rows_bwd(k, inverse, grad):
    per_choice = grad[inverse].reshape(-1, k, grad.shape[-1])
    return per_choice.sum(1), None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


def row_cap(assignments: int, held: int, n_routed: int):
    """The rows of one window of a share that holds ``held`` of ``n_routed``
    experts, of ``assignments`` = N x k a peer: four times what uniform
    routing sends it, rounded up to the 512 rows that :func:`_use_kernels`
    wants.  (Four, not two: a trained layer's routing drifts, and calls of
    2.3 times the uniform count on average, 4.5 at most, were seen where the
    router itself was frozen: PERF.md section 6, PR 34.)  None where that is
    no less than all the assignments (small shapes, a share that is most of
    the experts) or does not divide them: the share then works on all N x k
    rows at once."""
    cap = -(-4 * assignments * held // (n_routed * 512)) * 512
    return cap if cap < assignments and assignments % cap == 0 else None


def over_cap(held_rows, assignments: int, held: int, n_routed: int):
    """Whether a share sent ``held_rows`` assignments walks past its first
    window (int32, 0 or 1); 0 where the shapes give no cap."""
    cap = row_cap(assignments, held, n_routed) or assignments
    return (held_rows > cap).astype(jnp.int32)


@custom_batching.custom_vmap
def _most_of_peers(count):
    """``count`` as it is; under ``vmap`` over peers one count for them all,
    the largest, and *unbatched*: a loop bounded by it stays one loop (bounded
    by a batched count it runs every body masked, to the slowest peer)."""
    return count


@_most_of_peers.def_vmap
def _most_of_peers_over_peers(axis_size, in_batched, count):
    return jnp.max(count), False


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _combine_rows(out, row_weights, tokens, n: int, out_dtype):
    """The combine from sorted rows: ``y[t] = sum of row_weights[i] x out[i]``
    over the rows i with ``tokens[i] == t``, ``[n, D]``, as one product by the
    weights placed at ``[t, i]``: the terms and their float32 accumulation
    are the einsum's over a token's k choices (a choice with no row here adds
    nothing in either).  The gradient gathers: row i takes ``d_y[tokens[i]]``,
    nothing is widened to ``[N, k, D]``."""
    placed = jax.nn.one_hot(tokens, n, dtype=row_weights.dtype, axis=0) * (
        row_weights[None, :]
    )
    return jnp.dot(
        placed, out, precision=HIGHEST, preferred_element_type=out_dtype
    )


def _combine_rows_fwd(out, row_weights, tokens, n, out_dtype):
    return _combine_rows(out, row_weights, tokens, n, out_dtype), (
        out, row_weights, tokens
    )


def _combine_rows_bwd(n, out_dtype, residuals, grad):
    out, row_weights, tokens = residuals
    picked = grad[tokens]
    d_out = picked * row_weights[:, None].astype(grad.dtype)
    d_weights = jnp.sum(picked * out.astype(grad.dtype), -1)
    return d_out.astype(out.dtype), d_weights.astype(row_weights.dtype), None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _down_and_combine(hidden, kernel, down, weights, order, inverse,
                      group_sizes, matmul, out_dtype):
    """The frozen down projection of all N x k sorted rows and their combine,
    ``(y, down in token order)``: ``out = matmul(hidden, kernel)``, ``y[n] =
    sum_j weights[n, j] x out[inverse[n k + j]]``, the einsum over the rows
    gathered back to token order, and ``down [N x k, r]`` (or None) gathered
    likewise to ``[N, k, r]``.  ``hidden`` comes as the SwiGLU made it and is
    rounded here, where it enters the product; ``matmul`` is
    :func:`grouped_matmul` or :func:`held_matmul`.

    One gradient rule for the two, because together they need less than
    apart.  With ``picked[i] = d_y[order[i] // k]``, the tokens' gradient
    rows in expert order, and ``g = picked W_down^T`` group by group (the
    product to the rows, on the *unweighted* ``picked``):

        d_hidden[i] = w[i] x g[i]         d_w[i] = <g[i], hidden[i]>

    where the two rules apart made ``d_out = w x picked`` (an ``[N x k, D]``
    array written for one reader) and ``d_w[i] = <picked[i], out[i]>``, which
    kept ``out`` as a residual: a block under ``jax.checkpoint`` then ran the
    down projection again for that row dot alone.  Here the residuals are
    ``hidden`` (the down adapter's already), the kernel, the weights,
    ``order`` and ``group_sizes``; neither ``out`` nor ``picked`` outlives
    its one reader, and the row dot runs over the experts' width, not the
    model's.  The kernel's own gradient carries the weight on the narrow
    side too, ``(w x hidden)^T picked``.

    What is rounded: ``g`` leaves the product in the matmul type, as
    ``d_hidden`` did, and ``w x g`` goes back in the type ``hidden`` came in:
    a sum and a product rounded once each, where it was the product, then
    the sum (the float32 ``g`` of ISSUE 51 would save the first rounding for
    twice the bytes of an ``[N x k, F]`` array; at equal seeds and steps the
    step checks of the three cells that run this read as the two rules' did
    with the narrow one, PERF.md section 6, PR 51).  With ``out_dtype`` ``d_y`` is rounded where it enters the
    product, which the weights' gradient passes through now.  That gradient
    is summed in float32 and rounded once.  A gather of N x k single values
    costs more than one of 16-wide rows and nearly three sorts of as many
    pairs (PERF.md section 6, PR 50), so the weights come to sorted order as
    one more column of ``down``'s gradient, which takes the same way, and
    their gradient goes back to token order by a sort on the rows'
    assignments."""
    n, k = weights.shape
    with jax.named_scope(scopes.MOE_EXPERTS):
        out = matmul(narrow(hidden, kernel.dtype), kernel, group_sizes)
    to_tokens = lambda v: v[inverse].reshape(n, k, -1)
    with jax.named_scope(scopes.MOE_ROUTE):
        y = jnp.einsum(
            "nkd,nk->nd", to_tokens(out), weights,
            preferred_element_type=out_dtype,
        )
        return y, None if down is None else to_tokens(down)


def _down_and_combine_fwd(hidden, kernel, down, weights, order, inverse,
                          group_sizes, matmul, out_dtype):
    # (The narrow ``hidden`` is what the backward pass multiplies; an empty
    # array carries the type it came in.)
    like_hidden = jnp.zeros((0,), hidden.dtype)
    hidden = narrow(hidden, kernel.dtype)
    return _down_and_combine(
        hidden, kernel, down, weights, order, inverse, group_sizes, matmul,
        out_dtype,
    ), (hidden, kernel, weights, order, group_sizes, like_hidden)


def _down_and_combine_bwd(matmul, out_dtype, residuals, grads):
    hidden, kernel, weights, order, group_sizes, like_hidden = residuals
    grad, d_down = grads
    with jax.named_scope(scopes.MOE_ROUTE):
        narrow_columns = weights.reshape(-1, 1)
        if d_down is not None:
            narrow_columns = jnp.concatenate(
                [d_down.reshape(narrow_columns.shape[0], -1), narrow_columns],
                axis=-1,
            )[order]
            d_down = narrow_columns[:, :-1].astype(d_down.dtype)
        else:
            narrow_columns = narrow_columns[order]
        row_weights = narrow_columns[:, -1:].astype(jnp.float32)
        picked = narrow(grad, hidden.dtype)[order // weights.shape[1]]
    with jax.named_scope(scopes.MOE_EXPERTS):
        g = matmul.to_rows(picked, kernel, group_sizes).astype(jnp.float32)
        wide_hidden = hidden.astype(jnp.float32)
        d_hidden = (g * row_weights).astype(like_hidden.dtype)
        d_weights = jnp.sum(g * wide_hidden, -1).astype(weights.dtype)
        d_kernel = matmul.to_weights(
            (wide_hidden * row_weights).astype(hidden.dtype), picked,
            group_sizes,
        ).astype(kernel.dtype)
    with jax.named_scope(scopes.MOE_ROUTE):
        d_weights = lax.sort(  # (no two keys are equal)
            (order, d_weights), num_keys=1, is_stable=False
        )[1].reshape(weights.shape)
    return d_hidden, d_kernel, d_down, d_weights, None, None, None


_down_and_combine.defvjp(_down_and_combine_fwd, _down_and_combine_bwd)


@jax.custom_vjp
def _rows_to_choices(v, assignments, position):
    """A window of sorted rows, ``v [R, w]``, back in assignment order,
    ``[N x k, w]``: assignment a takes row ``position[a]`` of the window, or
    zero where that lies outside it.  The gradient is a gather of R rows,
    ``grad[assignments]``, those being the window's rows' own."""
    rows = v.shape[0]
    inside = (position >= 0) & (position < rows)
    return jnp.where(inside[:, None], v[jnp.clip(position, 0, rows - 1)], 0)


def _rows_to_choices_fwd(v, assignments, position):
    return _rows_to_choices(v, assignments, position), assignments


def _rows_to_choices_bwd(assignments, grad):
    return grad[assignments], None, None


_rows_to_choices.defvjp(_rows_to_choices_fwd, _rows_to_choices_bwd)


def router_scores(logits, scoring: str = "softmax"):
    """``[N, E]`` scores of the router logits: their softmax over the
    experts, or each logit's sigmoid."""
    if scoring == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def route(x, router_kernel, k: int, scoring: str = "softmax",
          norm_topk_prob: bool = False, scale: float = 1.0, bias=None,
          norm_eps: float = 0.0):
    """Router of ``x [N, D]``: ``(weights [N, k] float32, experts [N, k]
    int32, logits [N, E] float32)``.  Logits and scores are float32 at the
    highest matmul precision whatever ``x``'s type; the experts are the top k
    by score, or with a ``bias [E]`` by ``score + bias``; the weights are
    those experts' scores (never the bias), divided by their sum ``+
    norm_eps`` with ``norm_topk_prob``, times ``scale``."""
    logits = jnp.dot(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=HIGHEST,
    )
    scores = router_scores(logits, scoring)
    if bias is None:
        weights, experts = lax.top_k(scores, k)
    else:
        experts = lax.top_k(scores + bias.astype(jnp.float32), k)[1]
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        total = weights.sum(-1, keepdims=True)
        weights = weights / (total + norm_eps if norm_eps else total)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32), logits


def choices_moved(scores, experts):
    """The share of the ``experts [N, k]`` assignments that are not among the
    top k of ``scores [N, E]`` alone: what a router bias changed."""
    plain = lax.top_k(scores, experts.shape[-1])[1]
    kept = jnp.any(experts[:, :, None] == plain[:, None, :], axis=-1)
    return 1.0 - kept.mean()


def assignment_counts(experts, n_experts: int):
    """``[E]`` int32: how many of the ``experts [N, k]`` assignments went to
    each expert; sums to N x k."""
    return jnp.zeros((n_experts,), jnp.int32).at[experts.reshape(-1)].add(1)


def dispatch_plan(experts, n_experts: int, offset=None):
    """From ``experts [N, k]``: ``(order, inverse, group_sizes)``.  ``order
    [N x k]`` lists the flat assignments (token i's j-th choice is i*k + j)
    sorted by expert, stably; ``inverse`` is its inverse permutation;
    ``group_sizes [E]`` counts each expert's assignments and sums to N x k:
    nothing is dropped and nothing padded.

    With ``offset`` the ``n_experts`` are the share ``[offset, offset +
    n_experts)`` held here: the assignments to them come first, by expert,
    every one of them (the order is of all N x k, so no imbalance can drop
    one); those bound for absent experts are sorted behind them and counted
    in no group, so ``group_sizes`` sums to what landed here."""
    if offset is not None:
        local = experts - offset
        experts = jnp.where((local >= 0) & (local < n_experts), local, n_experts)
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32)
    )
    if offset is not None:
        return order, inverse, assignment_counts(experts, n_experts + 1)[:-1]
    return order, inverse, assignment_counts(experts, n_experts)


def expert_projection(rows, weights, group_sizes, lora_scale, dtype,
                      matmul=grouped_matmul):
    """One projection of every expert on its own rows: the frozen kernel's
    grouped matmul plus ``lora_scale x (rows A_e) B_e``.  ``weights`` is
    ``(kernel [E, K, N], lora_a [E, K, r] | None, lora_b [E, r, N] | None)``;
    ``matmul`` is :func:`held_matmul` where the experts are a share."""
    kernel, lora_a, lora_b = weights
    out = matmul(rows, kernel.astype(dtype), group_sizes)
    if lora_a is not None:
        down = matmul(rows, lora_a.astype(dtype), group_sizes)
        out = out + matmul(down, lora_b.astype(dtype), group_sizes) * lora_scale
    return out


def gate_and_up(rows, w_gate, w_up, group_sizes, lora_scale, dtype,
                matmul=grouped_matmul):
    """Both input-side projections of every expert on its own rows, as
    :func:`expert_projection` twice gives them.  Where both carry adapters of
    one rank their A sides are one grouped matmul: ``rows`` is read once for
    ``[A_gate | A_up]`` (``[E, D, 2r]``; the kernels pad a narrow side to 128
    lanes, so rank 2r costs a call what rank r does), and backward once to
    the adapters and once to the rows, where two adapters made two passes
    over the same wide array each way."""
    a_gate, a_up = w_gate[1], w_up[1]
    if a_gate is None or a_up is None or a_gate.shape != a_up.shape:
        return tuple(
            expert_projection(rows, w, group_sizes, lora_scale, dtype, matmul)
            for w in (w_gate, w_up)
        )
    a_cat = jnp.concatenate([a_gate, a_up], axis=-1).astype(dtype)
    down_cat = matmul(rows, a_cat, group_sizes)
    return tuple(
        matmul(rows, kernel.astype(dtype), group_sizes)
        + matmul(down, lora_b.astype(dtype), group_sizes) * lora_scale
        for down, (kernel, _, lora_b)
        in zip(jnp.split(down_cat, 2, axis=-1), (w_gate, w_up))
    )


def _entering(dtype, out_dtype):
    """How a value comes to a matmul: converted to ``dtype``, and with an
    ``out_dtype`` by a rounding the compiler keeps (``ops/wide.narrow``)."""
    if out_dtype is None:
        return lambda v: v.astype(dtype)
    return lambda v: narrow(v, dtype)


def _down_adapter_on_tokens(down, routed, lora_b, lora_scale, dtype,
                            out_dtype=None):
    """The down adapter's B side after the combine.  The layer's output is
    linear in the down projection, so the adapter's share of it is
    ``lora_scale x z B_all``: given ``down [N, k, r]`` (``hidden A_e``, back
    in token order), ``z[n, e] = w[n, j] down[n, j]`` for the j with
    ``experts[n, j] == e`` (zero elsewhere: ``[N, E x r]``), and ``B_all`` is
    ``lora_b`` as ``[E x r, D]``: one dense matmul a peer, where a grouped
    one wrote ``[N x k, D]`` only to be added, gathered and summed over k."""
    weights, experts = routed
    n_experts, r, d_out = lora_b.shape
    enter = _entering(dtype, out_dtype)
    placed = jax.nn.one_hot(experts, n_experts, dtype=dtype) * (
        enter(weights)[..., None]
    )
    z = jnp.einsum("nke,nkr->ner", placed, down)
    return jnp.dot(
        z.reshape(-1, n_experts * r),
        enter(lora_b).reshape(n_experts * r, d_out),
        preferred_element_type=out_dtype,
    ) * lora_scale


def moe_ffn(x, routed, w_gate, w_up, w_down, lora_scale: float, dtype,
            offset=None, out_dtype=None, n_routed=None):
    """The expert layer on tokens ``x [N, D]`` given ``routed = (weights,
    experts)`` of :func:`route`: dispatch, SwiGLU experts, combine.  Each of
    ``w_gate / w_up / w_down`` is a triple as in :func:`expert_projection`.
    What the adapters share is chosen by what is passed (the module
    docstring's last paragraph but one).

    With ``offset`` the weights are those of the experts ``[offset, offset +
    E)`` alone, a share of those the router chose among, and the result is
    their part of the layer: an assignment to an absent expert is sorted
    behind the held ones, enters no grouped matmul, and adds zero to the
    combine.  ``n_routed`` is how many experts the router chose among; with
    it the share works on its sorted rows a window of :func:`row_cap` at a
    time, as many windows as hold its assignments (the module docstring's
    last paragraph); without it, or where the shapes give no cap, on all
    N x k rows at once.  Both are exact.

    The grouped matmuls take and give ``dtype``.  With ``out_dtype`` the
    SwiGLU between them and the combine are computed in that type and the
    result is of it: a value is rounded to ``dtype`` where it enters a matmul
    and nowhere else (``ops/wide.py``)."""
    weights, experts = routed
    held = w_gate[0].shape[0]
    with jax.named_scope(scopes.MOE_ROUTE):
        plan = dispatch_plan(experts, held, offset)
    how = (lora_scale, dtype, offset is not None, out_dtype)
    cap = None
    if offset is not None:  # an absent expert's index matches no column
        experts = experts - offset
        if n_routed is not None:
            cap = row_cap(experts.size, held, n_routed)
    operands = (x, weights, experts, plan, (w_gate, w_up, w_down))
    if cap is None:
        return _all_rows(how, *operands)
    return _capped_ffn(how, cap, *operands)


def _matmul_of(how):
    """:func:`held_matmul` where the experts are a share."""
    return held_matmul if how[2] else grouped_matmul


def _swiglu_rows(rows, group_sizes, w_gate, w_up, how):
    """The experts' SwiGLU on their sorted rows, ``[rows, F]`` in the type it
    is computed in (``out_dtype``, else ``dtype``): not yet rounded for the
    down projection."""
    lora_scale, dtype, _, out_dtype = how
    gate, up = gate_and_up(
        rows, w_gate, w_up, group_sizes, lora_scale, dtype, _matmul_of(how)
    )
    if out_dtype is None:
        return jax.nn.silu(gate) * up
    return jax.nn.silu(gate.astype(out_dtype)) * up.astype(out_dtype)


def _down_adapter_rows(hidden, group_sizes, w_down, how):
    """``hidden A_down`` on the sorted rows, ``hidden`` in ``dtype``; None
    without an adapter on ``w_down``."""
    if w_down[1] is None:
        return None
    return _matmul_of(how)(hidden, w_down[1].astype(how[1]), group_sizes)


def _swiglu_experts(rows, group_sizes, w_gate, w_up, w_down, how):
    """The experts on their sorted rows: ``(hidden, out, down)``, the SwiGLU's
    result, its down projection by the frozen kernels, and ``hidden A_down``
    (None without an adapter on ``w_down``).  A window alone reads ``out``
    (:func:`_window`, whose combine is a product by a placement matrix with a
    rule of its own); over all rows at once the down projection and the
    combine are one rule and ``out`` is nobody's operand
    (:func:`_down_and_combine`)."""
    hidden = narrow(_swiglu_rows(rows, group_sizes, w_gate, w_up, how), how[1])
    out = _matmul_of(how)(hidden, w_down[0].astype(how[1]), group_sizes)
    return hidden, out, _down_adapter_rows(hidden, group_sizes, w_down, how)


def _all_rows(how, x, weights, experts, plan, expert_weights):
    """:func:`moe_ffn` given its dispatch ``plan``, over all N x k sorted
    rows at once.  ``experts`` count from the first one held.  The frozen
    down projection and the combine are one call with one gradient rule
    (:func:`_down_and_combine`), which keeps ``hidden`` and not the experts'
    output: a recomputed forward pass has then neither the down projection
    nor the gather back to token order to make."""
    lora_scale, dtype, _, out_dtype = how
    order, inverse, group_sizes = plan
    w_gate, w_up, w_down = expert_weights
    enter = _entering(dtype, out_dtype)
    with jax.named_scope(scopes.MOE_ROUTE):
        rows = _dispatch_rows(enter(x), order, inverse, experts.shape[1])
    with jax.named_scope(scopes.MOE_EXPERTS):
        hidden = _swiglu_rows(rows, group_sizes, w_gate, w_up, how)
        down = _down_adapter_rows(
            narrow(hidden, dtype), group_sizes, w_down, how
        )
    # (It names its own scopes, forward and backward: the product under
    # ``dpwa.moe.experts``, the gathers and the sum under ``.route``.)
    y, down = _down_and_combine(
        hidden, w_down[0].astype(dtype), down, enter(weights), order, inverse,
        group_sizes, _matmul_of(how), out_dtype,
    )
    if down is None:
        return y
    with jax.named_scope(scopes.MOE_EXPERTS):
        return y + _down_adapter_on_tokens(
            down, (weights, experts), w_down[2], lora_scale, dtype, out_dtype,
        )


def _cut_to_window(group_sizes, start, cap: int):
    """How many rows of each group lie among the sorted rows ``[start, start
    + cap)``: group sizes again, summing to at most ``cap``, whose first
    group starts at the window's first row."""
    ends = jnp.cumsum(group_sizes)
    in_window = lambda row: jnp.clip(row, start, start + cap)
    return in_window(ends) - in_window(ends - group_sizes)


def _window(how, cap, start, x, weights, experts, plan, expert_weights):
    """The part of a share's layer that its sorted rows ``[start, start +
    cap)`` make: those rows gathered from the tokens, each group cut to what
    of it lies in the window, the experts on them, their weighted sum into
    the tokens.  The parts of all windows that hold a held assignment add up
    to :func:`_all_rows`'s result."""
    lora_scale, dtype, _, out_dtype = how
    order, inverse, group_sizes = plan
    n, k = experts.shape
    enter = _entering(dtype, out_dtype)
    with jax.named_scope(scopes.MOE_ROUTE):
        assignments = lax.dynamic_slice(order, (start,), (cap,))
        tokens = assignments // k  # of each row of the window
        sizes = _cut_to_window(group_sizes, start, cap)
        # A product by 0 and 1 moves whole rows exactly (each a value times
        # 1, accumulated in float32), and so does its transpose, the
        # gradient: a sum of a token's rows where a scatter-add would stand.
        rows = jnp.dot(
            jax.nn.one_hot(tokens, n, dtype=dtype), enter(x), precision=HIGHEST
        )
    with jax.named_scope(scopes.MOE_EXPERTS):
        _, out, down = _swiglu_experts(rows, sizes, *expert_weights, how)
    with jax.named_scope(scopes.MOE_ROUTE):
        y = _combine_rows(
            out, enter(weights).reshape(-1)[assignments], tokens, n, out_dtype
        )
        if down is None:
            return y
        down = _rows_to_choices(down, assignments, inverse - start)
    with jax.named_scope(scopes.MOE_EXPERTS):
        return y + _down_adapter_on_tokens(
            down.reshape(n, k, -1), (weights, experts), expert_weights[2][2],
            lora_scale, dtype, out_dtype,
        )


# One window's program and its gradient, each under ``jax.jit``: every window
# of every expert layer of a model calls them with the same shapes, so they
# are traced, differentiated and lowered once (XLA inlines them).
_window_part = jax.jit(_window, static_argnums=(0, 1))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _window_cotangents(how, cap, start, operands, grad):
    """What ``grad`` on a window's part sends back to ``(x, weights, expert
    weights)``.  Nothing of the window's forward is kept: it is a checkpoint
    of its own, recomputed here for the pullback."""
    x, weights, experts, plan, expert_weights = operands
    recomputed = jax.checkpoint(
        lambda x, weights, expert_weights: _window(
            how, cap, start, x, weights, experts, plan, expert_weights
        ), prevent_cse=False,  # inside a loop's body already
    )
    return jax.vjp(recomputed, x, weights, expert_weights)[1](grad)


def _over_windows(cap: int, plan, part):
    """``part(start)`` summed over the windows that hold a held assignment of
    any peer (none: zeros).  One loop whatever the count, so that
    a model holds the window's program once; its body has one name, so a
    trace shows a further window as the body's instructions run once more
    in that step, and ``held_over_cap`` says which calls had one."""
    windows = _most_of_peers(-(-plan[2].sum() // cap))
    nothing = jax.tree.map(jnp.zeros_like, jax.eval_shape(part, jnp.int32(0)))
    return lax.fori_loop(
        0, windows,
        lambda w, total: jax.tree.map(jnp.add, total, part(w * cap)),
        nothing,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _capped_ffn(how, cap, x, weights, experts, plan, expert_weights):
    """A share's layer where its shapes give a row cap: walked a window of
    ``cap`` sorted rows at a time (:func:`_over_windows`).

    The walk's length depends on the routing, so it has no derivative of its
    own, and a loop that kept each window's residuals would hold all N x k
    rows again.  The gradient is written by hand instead: the residuals are
    the operands, and the backward pass walks the same windows, each
    recomputing its forward for its cotangents, which add up (in the
    parameters' own type) as the parts did."""
    operands = (x, weights, experts, plan, expert_weights)
    return _over_windows(
        cap, plan, lambda start: _window_part(how, cap, start, *operands)
    )


def _capped_ffn_fwd(how, cap, *operands):
    return _capped_ffn(how, cap, *operands), operands


def _capped_ffn_bwd(how, cap, operands, grad):
    d_x, d_weights, d_expert_weights = _over_windows(
        cap, operands[3],
        lambda start: _window_cotangents(how, cap, start, operands, grad),
    )
    return d_x, d_weights, None, None, d_expert_weights


_capped_ffn.defvjp(_capped_ffn_fwd, _capped_ffn_bwd)


def load_balancing_loss(counts, prob_means):
    """``E x sum_e f_e P_e`` pooled over layers: ``counts [L, E]`` are each
    layer's assignments an expert (``f_e`` their share of all L x N x k) and
    ``prob_means [L, E]`` each layer's mean router probability (``P_e`` their
    mean).  1.0 under uniform routing.  (The Hugging Face code sums the same
    product over the k slots and so returns k times this; the coefficient is
    the configuration's.)  Gradients flow through ``P_e`` alone."""
    counts = counts.astype(jnp.float32)
    f = counts.sum(0) / counts.sum()
    return counts.shape[-1] * jnp.sum(f * prob_means.mean(0))


def routing_stats(experts, n_experts: int, share=None) -> dict:
    """What a routing did, for tests and chip runs: assignments an expert,
    the fullest expert over the mean, and the assignments dropped (the
    dispatch has nowhere to drop one: N x k less the groups' sum).  For a
    ``share = (offset, held)`` of the experts also ``held``, the assignments
    that land on it, its row ``cap`` (:func:`row_cap`; N x k where the shapes
    give none) and ``over_cap``: whether this routing sends the share past
    its first window."""
    group_sizes = assignment_counts(experts, n_experts)
    stats = dict(
        assignments=group_sizes,
        max_over_mean=group_sizes.max() * n_experts / experts.size,
        dropped=experts.size - group_sizes.sum(),
    )
    if share is not None:
        offset, held = share
        here = group_sizes[offset:offset + held].sum()
        stats.update(
            held=here,
            cap=row_cap(experts.size, held, n_experts) or experts.size,
            over_cap=over_cap(here, experts.size, held, n_experts),
        )
    return stats
