"""A dropless sparse-expert feed-forward: router, top-k, dispatch, grouped
matmuls with per-expert LoRA factors, weighted combine, load-balancing term.

The layer (the published ``OlmoeSparseMoeBlock``), for tokens ``x [N, D]``:

    p      = softmax_f32(x Wr)                 over all E experts
    (w, S) = top_k(p)                          no renormalisation of w
    y      = sum_{e in S} w_e * W_down_e(silu(W_gate_e x) * W_up_e x)

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
chooses: no argument, config field, flag or environment variable.  On the
v5e ``ragged_dot`` runs XLA's own grouped kernel at 70 TFLOP/s on the cell's
shapes, needs a 0.5 GB transposed copy of the kernels for the gradient to the
activations (48 TFLOP/s with it) and loses the instruction's ``op_name``, so
no scope finds it in a trace; ``megablox`` at ``(256, 1024, 1024)`` runs 89 to
100 TFLOP/s in all three and keeps its name; at its default ``(128, 128,
128)`` it runs 9 (PERF.md section 6, PR 27, has the sweep).

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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import custom_batching, lax

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


def _tiling(k: int, n: int):
    """``(tm, tk, tn)`` for the ``megablox`` kernels from the two dense
    dimensions, by the sweep on the v5e at 65,536 rows in 128 groups
    (PERF.md section 6, PR 27): 256 rows by up to 1024 x 1024 for the wide
    matmuls; for a rank-16 adapter (one side under 128) 512 rows, its narrow
    side padded to one 128-lane tile, and the wide side up to 2048 when it is
    contracted.  Larger tiles are refused by Mosaic (scoped VMEM)."""
    lanes = lambda v: -(-v // 128) * 128
    narrow = min(k, n) < 128
    return (
        512 if narrow else 256,
        min(lanes(k), 2048 if n < 128 else 1024),
        min(lanes(n), 1024),
    )


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
        _tiling(lhs.shape[1], grad.shape[1]),
    )


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """``[M, K] x [G, K, N] -> [M, N]``: rows ``lhs`` sorted by group, row i
    multiplied by ``rhs[g]`` of the group g it lies in; ``group_sizes [G]``
    (int32) sums to M.  Differentiable in ``lhs`` and ``rhs``; under ``vmap``
    the batch axis becomes more groups (module docstring)."""
    return _gmm(lhs, rhs, group_sizes)


def _grouped_matmul_fwd(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(residuals, grad):
    lhs, rhs, group_sizes = residuals
    with jax.named_scope(scopes.MOE_EXPERTS):
        d_lhs = _gmm_transposed(grad, rhs, group_sizes)
        d_rhs = _tgmm(lhs, grad, group_sizes)
    return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype), None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation and its inverse, so that the gradient
    is a gather too (``g[inverse]``) and not a scatter-add."""
    return x[perm]


def _permute_rows_fwd(x, perm, inverse):
    return x[perm], inverse


def _permute_rows_bwd(inverse, grad):
    return grad[inverse], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


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


def route(x, router_kernel, k: int):
    """Router of ``x [N, D]``: ``(weights [N, k] float32, experts [N, k]
    int32, logits [N, E] float32)``.  Logits and softmax are float32 at the
    highest matmul precision whatever ``x``'s type; the top-k weights are the
    softmax's own values, not renormalised."""
    logits = jnp.dot(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=HIGHEST,
    )
    weights, experts = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return weights, experts.astype(jnp.int32), logits


def assignment_counts(experts, n_experts: int):
    """``[E]`` int32: how many of the ``experts [N, k]`` assignments went to
    each expert; sums to N x k."""
    return jnp.zeros((n_experts,), jnp.int32).at[experts.reshape(-1)].add(1)


def dispatch_plan(experts, n_experts: int):
    """From ``experts [N, k]``: ``(order, inverse, group_sizes)``.  ``order
    [N x k]`` lists the flat assignments (token i's j-th choice is i*k + j)
    sorted by expert, stably; ``inverse`` is its inverse permutation;
    ``group_sizes [E]`` counts each expert's assignments and sums to N x k:
    nothing is dropped and nothing padded."""
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32)
    )
    return order, inverse, assignment_counts(experts, n_experts)


def expert_projection(rows, weights, group_sizes, lora_scale, dtype):
    """One projection of every expert on its own rows: the frozen kernel's
    grouped matmul plus ``lora_scale x (rows A_e) B_e``.  ``weights`` is
    ``(kernel [E, K, N], lora_a [E, K, r] | None, lora_b [E, r, N] | None)``."""
    kernel, lora_a, lora_b = weights
    out = grouped_matmul(rows, kernel.astype(dtype), group_sizes)
    if lora_a is not None:
        down = grouped_matmul(rows, lora_a.astype(dtype), group_sizes)
        out = out + grouped_matmul(
            down, lora_b.astype(dtype), group_sizes
        ) * lora_scale
    return out


def gate_and_up(rows, w_gate, w_up, group_sizes, lora_scale, dtype):
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
            expert_projection(rows, w, group_sizes, lora_scale, dtype)
            for w in (w_gate, w_up)
        )
    a_cat = jnp.concatenate([a_gate, a_up], axis=-1).astype(dtype)
    down_cat = grouped_matmul(rows, a_cat, group_sizes)
    return tuple(
        grouped_matmul(rows, kernel.astype(dtype), group_sizes)
        + grouped_matmul(down, lora_b.astype(dtype), group_sizes) * lora_scale
        for down, (kernel, _, lora_b)
        in zip(jnp.split(down_cat, 2, axis=-1), (w_gate, w_up))
    )


def _down_adapter_on_tokens(down, routed, lora_b, lora_scale, dtype):
    """The down adapter's B side after the combine.  The layer's output is
    linear in the down projection, so the adapter's share of it is
    ``lora_scale x z B_all``: given ``down [N, k, r]`` (``hidden A_e``, back
    in token order), ``z[n, e] = w[n, j] down[n, j]`` for the j with
    ``experts[n, j] == e`` (zero elsewhere: ``[N, E x r]``), and ``B_all`` is
    ``lora_b`` as ``[E x r, D]``: one dense matmul a peer, where a grouped
    one wrote ``[N x k, D]`` only to be added, gathered and summed over k."""
    weights, experts = routed
    n_experts, r, d_out = lora_b.shape
    placed = jax.nn.one_hot(experts, n_experts, dtype=dtype) * (
        weights.astype(dtype)[..., None]
    )
    z = jnp.einsum("nke,nkr->ner", placed, down)
    return jnp.dot(
        z.reshape(-1, n_experts * r),
        lora_b.astype(dtype).reshape(n_experts * r, d_out),
    ) * lora_scale


def moe_ffn(x, routed, w_gate, w_up, w_down, lora_scale: float, dtype):
    """The expert layer on tokens ``x [N, D]`` given ``routed = (weights,
    experts)`` of :func:`route`: dispatch, SwiGLU experts, combine.  Each of
    ``w_gate / w_up / w_down`` is a triple as in :func:`expert_projection`.
    What the adapters share is chosen by what is passed (the module
    docstring's last paragraph)."""
    weights, experts = routed
    n, k = experts.shape
    with jax.named_scope(scopes.MOE_ROUTE):
        order, inverse, group_sizes = dispatch_plan(experts, w_gate[0].shape[0])
        rows = _dispatch_rows(x.astype(dtype), order, inverse, k)
    kernel_down, a_down, b_down = w_down
    with jax.named_scope(scopes.MOE_EXPERTS):
        gate, up = gate_and_up(
            rows, w_gate, w_up, group_sizes, lora_scale, dtype
        )
        hidden = jax.nn.silu(gate) * up
        out = grouped_matmul(hidden, kernel_down.astype(dtype), group_sizes)
        if a_down is not None:
            down = grouped_matmul(hidden, a_down.astype(dtype), group_sizes)
    with jax.named_scope(scopes.MOE_ROUTE):
        to_tokens = lambda v: _permute_rows(v, inverse, order).reshape(n, k, -1)
        y = jnp.einsum("nkd,nk->nd", to_tokens(out), weights.astype(dtype))
        if a_down is None:
            return y
        down = to_tokens(down)
    with jax.named_scope(scopes.MOE_EXPERTS):
        return y + _down_adapter_on_tokens(
            down, routed, b_down, lora_scale, dtype
        )


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


def routing_stats(experts, n_experts: int) -> dict:
    """What a routing did, for tests and chip runs: assignments an expert,
    the fullest expert over the mean, and the assignments dropped (the
    dispatch has nowhere to drop one: N x k less the groups' sum)."""
    group_sizes = assignment_counts(experts, n_experts)
    return dict(
        assignments=group_sizes,
        max_over_mean=group_sizes.max() * n_experts / experts.size,
        dropped=experts.size - group_sizes.sum(),
    )
