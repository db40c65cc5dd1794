"""EVA attention: exact inside a window, chunk summaries of every earlier
window, one softmax over both (the published EvaByte block's
``attention_class: "eva"``), with the core's gradient written by hand.

Per head, with ``s = D^-1/2``, a window of ``W`` positions and chunks of ``C``
(``W / C`` chunks a window), for ``q``, ``k``, ``v`` ``[T, D]`` (rope applied).
Every array here has its heads before its positions, ``[B, h, T, D]``: what
the kernels read a window of, and what the summaries pool rows of; the model
turns ``q``, ``k``, ``v`` once on the way in and ``o`` once on the way out.

*Summaries* (:func:`chunk_summaries`).  Chunk ``c`` holds positions ``C c ..
C c + C - 1``; with the head's learned ``phi``, ``mu`` ``[D]``

    a_j    = softmax over j in chunk c of (s k_j . phi)
    ksum_c = sum_j a_j k_j + mu         vsum_c = sum_j a_j v_j

*Core* (:func:`eva_attention`).  Query ``t`` lies in window ``w = t // W``.
It sees the positions ``j <= t`` of its own window exactly, and of every
window before ``w`` every chunk's summary (``(W / C) w`` of them; none of its
own window, none in window 0), under **one** softmax:

    o_t = (sum_j e^{s q_t.k_j} v_j + sum_c e^{s q_t.ksum_c} vsum_c)
          / (sum_j e^{s q_t.k_j} + sum_c e^{s q_t.ksum_c})

Written out, the scores are ``T x (W + T / C)`` a head (12 GB a layer at the
published sizes and T 16,384): nothing here writes them.

**On a TPU** the core is two Pallas kernels over a grid of (sequence, head,
window).  A grid step holds one window's ``q``, ``k``, ``v`` ``[W, D]`` and
the head's whole ``ksum``, ``vsum`` ``[T / C, D]`` in VMEM (256 kB each at T
16,384: fetched once a head, their block index does not move with the
window) and walks the window in blocks of :func:`sub_block` rows.  A query
block visits its own window's key blocks up to the diagonal (the causal mask
on the diagonal block only), then the summaries of windows ``< w``, one
window's ``W / C`` at a time in a loop of ``w`` turns: no mask inside a
block, and the summaries of later windows are never multiplied.  One running
maximum, sum and accumulator in VMEM serve both kinds of key.  The forward
kernel writes ``o`` and the log-sum-exp of each query as one row ``[1, T]``
a head.  The backward kernel recomputes a block's probabilities from that
row, in the transposed orientation (keys on sublanes, queries on lanes), so
that the row broadcasts as it lies and ``dk``, ``dv``, ``dksum``, ``dvsum``
need no transpose; ``dq`` takes one.  ``dksum`` and ``dvsum`` of a head are
float32 blocks that stay in VMEM over the head's windows.  Scores, maximum,
sums and accumulators are float32, matmul operands the type ``q`` came in
(``mixedp_attn``).  The gradient to ``ksum`` and ``vsum`` flows on through
:func:`chunk_summaries` by JAX.

**Off the TPU**, or where ``T`` is no multiple of the window or the shapes
miss the kernels' tiling, the same function is its plain twin: a loop over
windows with dense ``[W, W + (W / C) w]`` scores a window, under autodiff.

**Under ``vmap``** (the stacked step's peer axis) a ``custom_vmap`` rule folds
the peer axis into the kernels' sequence axis (``ops/ssm.folding_peers``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dpwa_tpu.ops.ssm import folding_peers, vmem_limit, vmem_need
from dpwa_tpu.utils import scopes

F32 = jnp.float32
# A masked score: far below any real one and finite, so that no row's
# maximum is ever infinite.
MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128
_TRANS_B = (((1,), (1,)), ((), ()))  # a [m, d] x b [n, d] -> [m, n]


@functools.partial(jax.checkpoint, static_argnums=(4,))
def chunk_summaries(k, v, phi, mu, chunk: int):
    """``ksum``, ``vsum`` ``[B, h, T // chunk, D]`` of ``k``, ``v`` ``[B, h,
    T, D]`` with ``phi``, ``mu`` ``[h, D]``: a softmax over each chunk's
    positions of ``D^-1/2 k . phi`` (float32), the keys and values pooled by
    it, ``mu`` added to the pooled key alone.  Results in ``k``'s type.  A
    last chunk that ``T`` cuts short has no summary: it lies in the last
    window, whose summaries no query sees.

    Under ``jax.checkpoint``: the backward pass keeps ``k`` and ``v`` as they
    came and pools them again, where autodiff would keep their float32
    copies in chunks (1.07 GB a layer at 2 x 16,384 x 4,096) for work that
    is one pass over memory."""
    with jax.named_scope(scopes.ATTN_EVA.summaries):
        B, h, T, D = k.shape
        n = T // chunk
        chunks = lambda z: z[:, :, :n * chunk].reshape(B, h, n, chunk, D).astype(
            F32
        )
        per_head = lambda z: z.astype(F32)[:, None, None, :]  # [h, 1, 1, D]
        kc, vc = chunks(k), chunks(v)
        logits = (kc * per_head(phi)).sum(-1) * D ** -0.5
        a = jax.nn.softmax(logits, axis=3)[..., None]
        ksum = (a * kc).sum(3) + mu.astype(F32)[:, None, :]
        return ksum.astype(k.dtype), (a * vc).sum(3).astype(v.dtype)


def sub_block(window: int) -> int:
    """Rows of queries, and of keys, a turn of the kernels' loops holds: 512
    where that divides the window (a ``[512, 512]`` float32 tile of scores
    is 1 MB of VMEM, and a diagonal block wastes half of itself: 25 % of the
    local work at four blocks a window), else 256, 128, or the window."""
    return next((b for b in (512, 256, 128) if window % b == 0), window)


def _use_kernels(T: int, D: int, window: int, chunk: int) -> bool:
    return (
        jax.default_backend() == "tpu" and T % window == 0
        and D % LANES == 0 and window % LANES == 0 and window % chunk == 0
        and (window // chunk) % LANES == 0
    )


def eva_attention(q, k, v, ksum, vsum, *, window: int, chunk: int):
    """``o [B, h, T, D]`` of the core in the module docstring, for ``q``,
    ``k``, ``v`` ``[B, h, T, D]`` and ``ksum``, ``vsum`` ``[B, h, T / chunk,
    D]``; differentiable in all five.  ``o`` has ``q``'s type."""
    with jax.named_scope(scopes.ATTN_EVA.core):
        if _use_kernels(q.shape[2], q.shape[3], window, chunk):
            return kernel_eva_attention(q, k, v, ksum, vsum, window, chunk)
        return plain_eva_attention(q, k, v, ksum, vsum, window, chunk)


def plain_eva_attention(q, k, v, ksum, vsum, window: int, chunk: int):
    """The core a window at a time with dense float32 scores, gradients by
    autodiff: what runs off the TPU, for any ``T`` that ``chunk`` divides."""
    T, D = q.shape[2], q.shape[3]
    per_window, scale = window // chunk, D ** -0.5
    wide = lambda z: z.astype(F32)
    out = []
    for w in range(-(-T // window)):
        here = slice(w * window, min((w + 1) * window, T))
        qw, kw, vw = (wide(z[:, :, here]) for z in (q, k, v))
        seen = slice(0, w * per_window)
        local = jnp.einsum("bhtd,bhsd->bhts", qw, kw) * scale
        n = local.shape[-1]
        local = jnp.where(jnp.tril(jnp.ones((n, n), bool)), local, -jnp.inf)
        remote = jnp.einsum(
            "bhtd,bhcd->bhtc", qw, wide(ksum[:, :, seen])
        ) * scale
        p = jax.nn.softmax(jnp.concatenate([local, remote], -1), -1)
        out.append(
            jnp.einsum("bhts,bhsd->bhtd", p[..., :n], vw)
            + jnp.einsum("bhtc,bhcd->bhtd", p[..., n:], wide(vsum[:, :, seen]))
        )
    return jnp.concatenate(out, 2).astype(q.dtype)


# The kernels.  ``q``, ``k``, ``v`` (``o``, ``do``) are ``[S, h, T, D]`` over S
# sequences and ``ksum``, ``vsum`` ``[S, h, T / chunk, D]``, as the module's
# functions take them; the log-sum-exp and ``di = sum_d do o`` are ``[S, h, 1,
# T]`` float32 rows.


def _lanes(column, width: int):
    """``column [rows, 128]``, one value a row repeated along the lanes, as
    ``[rows, width]``."""
    if width % LANES == 0:
        return jnp.tile(column, (1, width // LANES))
    return jnp.broadcast_to(column[:, :1], (column.shape[0], width))


def _forward_kernel(
    q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, lse_ref,
    m_ref, l_ref, acc_ref, *, block, per_window, scale,
):
    w = pl.program_id(2)
    window, d = q_ref.shape
    shape = (block, block)
    on_or_under = (
        lax.broadcasted_iota(jnp.int32, shape, 1)
        <= lax.broadcasted_iota(jnp.int32, shape, 0)
    )

    def attend(q, keys, values, mask):
        """One block of keys of either kind into the running softmax."""
        s = lax.dot_general(
            q, keys, _TRANS_B, preferred_element_type=F32
        ) * scale
        if mask is not None:
            s = jnp.where(mask, s, MASKED)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, s.shape[1]))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = _lanes(alpha, d) * acc_ref[...] + jnp.dot(
            p.astype(values.dtype), values, preferred_element_type=F32
        )
        m_ref[...] = m_next

    for i in range(window // block):
        rows = pl.ds(i * block, block)
        q = q_ref[rows, :]
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for j in range(i + 1):
            keys = pl.ds(j * block, block)
            attend(
                q, k_ref[keys, :], v_ref[keys, :],
                on_or_under if j == i else None,
            )

        def earlier_window(c, carry, q=q):
            seen = pl.ds(pl.multiple_of(c * per_window, per_window), per_window)
            attend(q, ks_ref[seen, :], vs_ref[seen, :], None)
            return carry

        lax.fori_loop(0, w, earlier_window, 0)
        total = l_ref[...]
        o_ref[rows, :] = (acc_ref[...] / _lanes(total, d)).astype(o_ref.dtype)
        # One value a row along the lanes -> one row of the block's queries.
        lse_ref[:, rows] = (m_ref[...] + jnp.log(total)).T[:1]


def _backward_kernel(
    q_ref, k_ref, v_ref, ks_ref, vs_ref, do_ref, lse_ref, di_ref,
    dq_ref, dk_ref, dv_ref, dks_ref, dvs_ref, dq_acc,
    *, block, per_window, scale,
):
    # Everything here is transposed: a block of keys on the sublanes, a
    # block of queries on the lanes.
    w = pl.program_id(2)
    window, d = q_ref.shape
    blocks = window // block

    @pl.when(w == 0)
    def _():
        dks_ref[...] = jnp.zeros_like(dks_ref)
        dvs_ref[...] = jnp.zeros_like(dvs_ref)

    dq_acc[...] = jnp.zeros_like(dq_acc)
    shape = (block, block)
    on_or_under = (
        lax.broadcasted_iota(jnp.int32, shape, 0)
        <= lax.broadcasted_iota(jnp.int32, shape, 1)
    )

    def pair(keys, values, i, mask):
        """``(d keys, d values)`` that query block ``i`` gives a block of
        keys of either kind; its ``dq`` goes to the accumulator."""
        rows = pl.ds(i * block, block)
        q, do = q_ref[rows, :], do_ref[rows, :]
        st = lax.dot_general(
            keys, q, _TRANS_B, preferred_element_type=F32
        ) * scale
        if mask is not None:
            st = jnp.where(mask, st, MASKED)
        pt = jnp.exp(st - lse_ref[:, rows])
        d_values = jnp.dot(pt.astype(do.dtype), do, preferred_element_type=F32)
        dpt = lax.dot_general(
            values, do, _TRANS_B, preferred_element_type=F32
        )
        dst = pt * (dpt - di_ref[:, rows]) * scale
        d_keys = jnp.dot(dst.astype(q.dtype), q, preferred_element_type=F32)
        dq_acc[rows, :] += jnp.dot(
            dst.T.astype(keys.dtype), keys, preferred_element_type=F32
        )
        return d_keys, d_values

    def gathered(keys, values, first, masked):
        """The sum of :func:`pair` over the query blocks ``first ..``."""
        d_keys = jnp.zeros(keys.shape, F32)
        d_values = jnp.zeros(values.shape, F32)
        for i in range(first, blocks):
            dk, dv = pair(
                keys, values, i, on_or_under if masked and i == first else None
            )
            d_keys, d_values = d_keys + dk, d_values + dv
        return d_keys, d_values

    for j in range(blocks):
        rows = pl.ds(j * block, block)
        d_keys, d_values = gathered(k_ref[rows, :], v_ref[rows, :], j, True)
        dk_ref[rows, :] = d_keys.astype(dk_ref.dtype)
        dv_ref[rows, :] = d_values.astype(dv_ref.dtype)

    def earlier_window(c, carry):
        seen = pl.ds(pl.multiple_of(c * per_window, per_window), per_window)
        d_keys, d_values = gathered(ks_ref[seen, :], vs_ref[seen, :], 0, False)
        dks_ref[seen, :] += d_keys
        dvs_ref[seen, :] += d_values
        return carry

    lax.fori_loop(0, w, earlier_window, 0)
    dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _specs(q, ksum, window: int):
    """``(grid, a window's block, a head's summaries, a window's row)``."""
    seqs, heads, steps, d = q.shape
    return (
        (seqs, heads, steps // window),
        pl.BlockSpec((None, None, window, d), lambda s, h, w: (s, h, w, 0)),
        pl.BlockSpec(
            (None, None, ksum.shape[2], d), lambda s, h, w: (s, h, 0, 0)
        ),
        pl.BlockSpec((None, None, 1, window), lambda s, h, w: (s, h, 0, w)),
    )


def _kernel_call(
    kernel, name, interpret, grid, window, chunk, scratch, tiles, operands,
    *, in_specs, out_specs, out_shape,
):
    """One ``pallas_call`` of ``kernel`` over ``grid`` with float32
    ``scratch`` (shapes), and the VMEM limit its shapes come to with
    ``tiles`` float32 score tiles alive in a turn of its loops."""
    block, d = sub_block(window), operands[0].shape[-1]
    need = vmem_need(
        scratch + tiles * [(block, block)], in_specs + out_specs,
        [v.dtype for v in (*operands, *out_shape)],
    )
    return pl.pallas_call(
        functools.partial(
            kernel, block=block, per_window=window // chunk, scale=d ** -0.5
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(shape, F32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(need),
        ),
        interpret=interpret,
        name=name,
    )(*operands)


def _forward_call(interpret, window, chunk, q, k, v, ksum, vsum):
    """``(o [S, h, T, D], lse [S, h, 1, T])``."""
    grid, seq, summaries, row = _specs(q, ksum, window)
    block, d = sub_block(window), q.shape[-1]
    return _kernel_call(
        _forward_kernel, "dpwa_eva_attention_fwd", interpret, grid, window,
        chunk, 2 * [(block, LANES)] + [(block, d)], 3, (q, k, v, ksum, vsum),
        in_specs=[seq, seq, seq, summaries, summaries],
        out_specs=[seq, row],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(q.shape[:2] + (1, q.shape[2]), F32),
        ],
    )


def _backward_call(interpret, window, chunk, q, k, v, ksum, vsum, do, lse, di):
    """``dq``, ``dk``, ``dv`` in ``q``'s type and ``dksum``, ``dvsum``
    float32, each in its argument's shape."""
    grid, seq, summaries, row = _specs(q, ksum, window)
    like = lambda z, dtype: jax.ShapeDtypeStruct(z.shape, dtype)
    return _kernel_call(
        _backward_kernel, "dpwa_eva_attention_bwd", interpret, grid, window,
        chunk, [(window, q.shape[-1])], 6,
        (q, k, v, ksum, vsum, do, lse, di),
        in_specs=[seq, seq, seq, summaries, summaries, seq, row, row],
        out_specs=[seq, seq, seq, summaries, summaries],
        out_shape=[
            like(q, q.dtype), like(k, k.dtype), like(v, v.dtype),
            like(ksum, F32), like(vsum, F32),
        ],
    )


@functools.lru_cache(maxsize=None)
def _differentiable(interpret: bool, window: int, chunk: int):
    """:func:`eva_attention`'s signature on the two kernels."""
    forward = folding_peers(
        functools.partial(_forward_call, interpret, window, chunk)
    )
    backward = folding_peers(
        functools.partial(_backward_call, interpret, window, chunk)
    )

    @jax.custom_vjp
    def core(q, k, v, ksum, vsum):
        return forward(q, k, v, ksum, vsum)[0]

    def fwd(q, k, v, ksum, vsum):
        o, lse = forward(q, k, v, ksum, vsum)
        return o, (q, k, v, ksum, vsum, o, lse)

    def bwd(residuals, do):
        *inputs, o, lse = residuals
        # A custom gradient's instructions carry no name of the forward's.
        with jax.named_scope(scopes.ATTN_EVA.core):
            di = (do.astype(F32) * o.astype(F32)).sum(-1)  # [B, h, T]
            grads = backward(*inputs, do, lse, di[:, :, None])
            return tuple(g.astype(z.dtype) for g, z in zip(grads, inputs))

    core.defvjp(fwd, bwd)
    return core


def kernel_eva_attention(q, k, v, ksum, vsum, window: int, chunk: int):
    """:func:`eva_attention` by the Pallas kernels."""
    return _differentiable(False, window, chunk)(q, k, v, ksum, vsum)


def interpreted_eva_attention(q, k, v, ksum, vsum, window: int, chunk: int):
    """The same kernels run by the Pallas interpreter, for tests off the TPU."""
    return _differentiable(True, window, chunk)(q, k, v, ksum, vsum)
