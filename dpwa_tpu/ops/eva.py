"""EVA attention: exact inside a window, chunk summaries of every earlier
window, one softmax over both (the published EvaByte block's
``attention_class: "eva"``), with the core's gradient written by hand.

Per head, with ``s = D^-1/2``, a window of ``W`` positions and chunks of ``C``
(``W / C`` chunks a window), for ``q``, ``k``, ``v`` ``[T, D]`` (rope applied).
Every array of the EVA core has its heads before its positions, ``[B, h, T,
D]``: what the kernels read a window of, and what the summaries pool rows of;
the model turns ``q``, ``k``, ``v`` once on the way in and ``o`` once on the
way out.  (Causal attention on the same kernels, below, takes ``v`` and
gives ``o`` with positions first.)

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
on the diagonal block only), then what the windows ``< w`` hand over, at most
a block's rows a turn (one window's ``W / C`` summaries) in a loop of ``w``
turns: no mask inside a block, and what later windows hand over is never
multiplied.  One running maximum, sum and accumulator in VMEM serve both
kinds of key.  The forward kernel writes ``o`` and the log-sum-exp of each
query as one row ``[1, T]`` a head.  The backward kernel recomputes a block's
probabilities from that row, in the transposed orientation (keys on
sublanes, queries on lanes), so that the row broadcasts as it lies and
``dk``, ``dv``, ``dksum``, ``dvsum`` need no transpose; ``dq`` takes one.
``dksum`` and ``dvsum`` of a head are float32 blocks that stay in VMEM over
the head's windows.  Scores, maximum, sums and accumulators are float32,
matmul operands the type ``q`` came in (``mixedp_attn``).  The gradient to
``ksum`` and ``vsum`` flows on through :func:`chunk_summaries` by JAX.

**Off the TPU**, or where ``T`` is no multiple of the window or the shapes
miss the kernels' tiling, the same function is its plain twin: a loop over
windows with dense ``[W, W + (W / C) w]`` scores a window, under autodiff.

**Causal attention on the same two kernels** (:func:`causal_attention`; what
``ops/ulysses.single_device_attention`` runs on a TPU wherever
:func:`causal_kernels_take` the shape).  With ``ksum = k``, ``vsum = v`` and a
chunk of one position the core above *is* causal softmax attention: "of
every window before ``w`` every chunk's summary" is then every earlier key.
So the kernels take what the earlier windows hand over as their parameter:
summaries, ``W / C`` rows a window beside the window's own ``k``, ``v``; or
the keys and values themselves, ``W`` rows a window, 512 a turn, and then no
operand of the window's own: its rows are read out of the head's whole ``k``,
``v`` ``[T, D]``, and its ``dk``, ``dv`` are added into the head's whole
float32 block, where the later windows' queries add theirs.  Grouped keys are
read as they are: query head ``h`` takes block ``h // (H / KV)``, which stays
in VMEM over the group's heads (laid one after another in the grid), and the
group's ``dk``, ``dv`` are summed there in float32 and rounded once.  The
window is a function of ``T`` (:func:`causal_window`: one window up to 2,048
positions, two at 4,096), the scale an argument, and the two calls are named
``flash_attention_fwd_dpwa`` / ``flash_mha_bwd_dpwa``: the prefixes by which a
trace's readers know a flash-attention kernel.  ``q`` and ``k`` are read
heads first, as the rope leaves them; ``v`` and ``do`` are read, and ``o`` and
``dv`` written, as ``[B, T, h D]``, a head a block of ``D`` lanes, as the
projections beside them hold them: the same tiles, and no turn of those four
(:func:`causal_attention` says why each lies as it does).

**A band beside "every earlier window"** (``causal_attention(..., window=W)``:
a model's sliding window, query ``t`` sees the keys ``t - W + 1 .. t``, its
own among them).  The grid's window stays :func:`causal_window` of ``T``: the
model's window and the grid's are two things.  A block of queries visits the
key blocks that hold a key of its band and no other: its own block under the
diagonal's mask, then the ``(W + block - 2) // block`` blocks behind it, the
last of them (and any that the band's far edge crosses: a second diagonal)
under that edge's mask, the ones between unmasked; at a window of 1,024 in
blocks of 512, three key blocks a query block whatever ``T`` (21 block pairs a
head at T 4,096 where the whole triangle has 36).  The blocks behind are read
out of the head's whole ``k``, ``v`` by their rows: of the query's own grid
window where it has them, of the grid window before under ``pl.when`` there
is one.  The backward kernel walks the same pairs from the keys' side (a key
block is seen by its own query block and the next few), adds ``dk``, ``dv``
into the group's whole float32 block as ever, and so needs no second pass.
The two calls carry a tail of their own on the same prefixes
(``flash_attention_fwd_dpwa_window`` / ``flash_mha_bwd_dpwa_window``).  With
``window=None`` nothing of this is traced: the program is the one above, to
the text.

**Under ``vmap``** (the stacked step's peer axis) a ``custom_vmap`` rule folds
the peer axis into the kernels' sequence axis (``ops/ssm.folding_peers``).
"""

from __future__ import annotations

import contextlib
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


# The kernels.  ``q`` is ``[S, h, T, D]`` over S sequences (``o``, ``do`` too
# in the EVA core; ``[S, T, h D]`` in causal attention, like its ``v``); the
# log-sum-exp and ``di = sum_d do o`` are ``[S, h, 1, T]`` float32 rows.  What
# a query's earlier windows hand over is a head's *whole* operand, ``per_window``
# rows a window:
#
# - summaries (the EVA core): ``ksum``, ``vsum`` ``[S, h, T / chunk, D]``,
#   ``window / chunk`` rows a window, beside the window's own block of ``k``,
#   ``v`` ``[S, h, T, D]``;
# - the keys themselves (causal attention): ``k [S, kv, T, D]``, ``v [S, T,
#   kv D]``, ``window`` rows a window, and no block of the window's own: its
#   rows lie in the head's, ``h // (h / kv)`` where the keys are grouped.


def _lanes(column, width: int):
    """``column [rows, 128]``, one value a row repeated along the lanes, as
    ``[rows, width]``."""
    if width % LANES == 0:
        return jnp.tile(column, (1, width // LANES))
    return jnp.broadcast_to(column[:, :1], (column.shape[0], width))


def _own_window(refs, w, window: int):
    """Views of the head's whole ``refs``: the rows of window ``w``."""
    here = pl.ds(pl.multiple_of(w * window, window), window)
    return [ref.at[here] for ref in refs]


def _earlier(w, per_window: int, turn: int):
    """``(turns, rows of a turn)`` of the loop over what the ``w`` earlier
    windows hand over, ``turn`` rows of the head's whole operand at a time."""
    turns = w if turn == per_window else w * (per_window // turn)
    return turns, lambda c: pl.ds(pl.multiple_of(c * turn, turn), turn)


def _band_reach(band: int, block: int, rows: int) -> int:
    """How many blocks of keys behind a query block's own the band of
    ``band`` keys (the query's own among them) still touches: query row ``r``
    of a block sees key ``c`` of the block ``m`` before it iff ``0 <= m block
    + r - c < band``, which some pair satisfies up to this ``m``; no further
    than a head of ``rows`` keys has blocks."""
    return min((band + block - 2) // block, rows // block - 1)


def _band_mask(ahead, m: int, band: int, block: int):
    """Which pairs of a query block and the key block ``m`` before it lie in
    the band, from ``ahead`` = key index - query index inside the blocks;
    None where all do.  Block 0 is cut by the diagonal, and a block is cut by
    the band's far edge where its first key lies ``band`` or more behind the
    query block's last row."""
    mask = ahead <= 0 if m == 0 else None
    if (m + 1) * block > band:
        inside = ahead > m * block - band
        mask = inside if mask is None else mask & inside
    return mask


def _block_before(w, blocks: int, back: int, block: int):
    """The rows, in a head's whole operand, of the block ``back`` blocks
    before the first of window ``w`` (``blocks`` blocks a window)."""
    return pl.ds(pl.multiple_of((w * blocks - back) * block, block), block)


def _forward_kernel(q_ref, *refs, block, per_window, scale, band=None):
    *own, ks_ref, vs_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    w = pl.program_id(2)
    window, d = q_ref.shape
    k_ref, v_ref = own or _own_window((ks_ref, vs_ref), w, window)
    turns, seen = _earlier(w, per_window, min(per_window, block))
    shape = (block, block)
    if band is None:
        on_or_under = (
            lax.broadcasted_iota(jnp.int32, shape, 1)
            <= lax.broadcasted_iota(jnp.int32, shape, 0)
        )
    else:
        ahead = (
            lax.broadcasted_iota(jnp.int32, shape, 1)
            - lax.broadcasted_iota(jnp.int32, shape, 0)
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
        if band is None:
            for j in range(i + 1):
                keys = pl.ds(j * block, block)
                attend(
                    q, k_ref[keys, :], v_ref[keys, :],
                    on_or_under if j == i else None,
                )

            def earlier_window(c, carry, q=q):
                rows = seen(c)
                attend(q, ks_ref[rows, :], vs_ref[rows, :], None)
                return carry

            lax.fori_loop(0, turns, earlier_window, 0)
        else:
            # The diagonal block first (every row has a key there), then the
            # blocks behind it as far as the band reaches: of this window
            # where it has them, else of the windows before, if there are.
            blocks = window // block
            for m in range(_band_reach(band, block, ks_ref.shape[0]) + 1):
                mask = _band_mask(ahead, m, band, block)
                if m <= i:
                    keys = pl.ds((i - m) * block, block)
                    attend(q, k_ref[keys, :], v_ref[keys, :], mask)
                    continue

                @pl.when(w * blocks >= m - i)
                def _(q=q, mask=mask, back=m - i):
                    keys = _block_before(w, blocks, back, block)
                    attend(q, ks_ref[keys, :], vs_ref[keys, :], mask)

        total = l_ref[...]
        o_ref[rows, :] = (acc_ref[...] / _lanes(total, d)).astype(o_ref.dtype)
        # One value a row along the lanes -> one row of the block's queries.
        lse_ref[:, rows] = (m_ref[...] + jnp.log(total)).T[:1]


def _backward_kernel(
    q_ref, *refs, block, per_window, scale, group, own, band=None
):
    # Everything here is transposed: a block of keys on the sublanes, a
    # block of queries on the lanes.
    w = pl.program_id(2)
    window, d = q_ref.shape
    blocks = window // block
    if own:
        (k_ref, v_ref, ks_ref, vs_ref, do_ref, lse_ref, di_ref,
         dq_ref, dk_ref, dv_ref, dks_ref, dvs_ref, dq_acc) = refs
    else:
        (ks_ref, vs_ref, do_ref, lse_ref, di_ref,
         dq_ref, dks_ref, dvs_ref, dq_acc) = refs
        k_ref, v_ref, dk_ref, dv_ref = _own_window(
            (ks_ref, vs_ref, dks_ref, dvs_ref), w, window
        )
    turns, seen = _earlier(w, per_window, min(per_window, block))
    # The float32 blocks of a head's whole operand stay in VMEM over the
    # head's windows and, where ``group`` query heads share them, over those.
    first = w == 0
    if group > 1:
        first = jnp.logical_and(first, pl.program_id(1) % group == 0)

    @pl.when(first)
    def _():
        dks_ref[...] = jnp.zeros_like(dks_ref)
        dvs_ref[...] = jnp.zeros_like(dvs_ref)

    dq_acc[...] = jnp.zeros_like(dq_acc)
    shape = (block, block)
    if band is None:
        on_or_under = (
            lax.broadcasted_iota(jnp.int32, shape, 0)
            <= lax.broadcasted_iota(jnp.int32, shape, 1)
        )
    else:
        ahead = (
            lax.broadcasted_iota(jnp.int32, shape, 0)
            - lax.broadcasted_iota(jnp.int32, shape, 1)
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

    def gathered(keys, values, seen_by):
        """The sum of :func:`pair` over ``seen_by``: (query block, mask)s."""
        d_keys = jnp.zeros(keys.shape, F32)
        d_values = jnp.zeros(values.shape, F32)
        for i, mask in seen_by:
            dk, dv = pair(keys, values, i, mask)
            d_keys, d_values = d_keys + dk, d_values + dv
        return d_keys, d_values

    if band is None:
        under = lambda j: [
            (i, on_or_under if i == j else None) for i in range(j, blocks)
        ]
    else:
        # A block of keys is seen by its own query block and the ``reach``
        # after it: key block ``j`` of this window by this window's, a key
        # block ``back`` before the window by the window's first ones.
        reach = _band_reach(band, block, ks_ref.shape[0])
        under = lambda j: [
            (i, _band_mask(ahead, i - j, band, block))
            for i in range(max(j, 0), min(j + reach, blocks - 1) + 1)
        ]

    for j in range(blocks):
        rows = pl.ds(j * block, block)
        d_keys, d_values = gathered(k_ref[rows, :], v_ref[rows, :], under(j))
        if own:
            dk_ref[rows, :] = d_keys.astype(dk_ref.dtype)
            dv_ref[rows, :] = d_values.astype(dv_ref.dtype)
        else:
            dk_ref[rows, :] += d_keys
            dv_ref[rows, :] += d_values

    if band is None:
        def earlier_window(c, carry):
            rows = seen(c)
            d_keys, d_values = gathered(
                ks_ref[rows, :], vs_ref[rows, :],
                [(i, None) for i in range(blocks)],
            )
            dks_ref[rows, :] += d_keys
            dvs_ref[rows, :] += d_values
            return carry

        lax.fori_loop(0, turns, earlier_window, 0)
    else:
        for back in range(1, reach + 1):
            @pl.when(w * blocks >= back)
            def _(back=back):
                rows = _block_before(w, blocks, back, block)
                d_keys, d_values = gathered(
                    ks_ref[rows, :], vs_ref[rows, :], under(-back)
                )
                dks_ref[rows, :] += d_keys
                dvs_ref[rows, :] += d_values

    dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _layout(window: int, q, k, v, *summaries, backward: bool):
    """``(float32 scratch shapes, float32 score tiles alive in a turn, what
    pallas_call is told of grid, blocks and results)`` for the forward or the
    backward kernel, from its operands' shapes and types alone (arrays or
    ``jax.ShapeDtypeStruct``), as the kernel takes them: ``q [S, h, T, D]``;
    with ``summaries`` the EVA core's five operands, all heads first; without
    them causal attention's ``k [S, kv, T, D]`` and ``v [S, T, kv D]`` are
    the head's whole operand, ``v`` (and ``o``, ``do``, ``dv``) with a head
    as a block of ``D`` lanes (:func:`_kernel_call` says why)."""
    seqs, heads, steps, d = q.shape
    ksum, vsum = summaries or (k, v)
    group = heads // ksum.shape[1]
    block = sub_block(window)
    seq = pl.BlockSpec((None, None, window, d), lambda s, h, w: (s, h, w, 0))
    row = pl.BlockSpec((None, None, 1, window), lambda s, h, w: (s, h, 0, w))
    # A head's whole operand: its block index does not move with the window,
    # nor with the query head inside a group, so it is fetched once.
    whole = pl.BlockSpec(
        (None, None, ksum.shape[2], d),
        (lambda s, h, w: (s, h, 0, 0)) if group == 1
        else (lambda s, h, w: (s, h // group, 0, 0)),
    )
    like = lambda z, dtype: jax.ShapeDtypeStruct(z.shape, dtype)
    if summaries:
        out, keys, o = seq, [seq, seq, whole, whole], like(q, q.dtype)
    else:
        # The same tiles out of [S, T, h D]: a window of a head, a head's whole.
        out = pl.BlockSpec((None, window, d), lambda s, h, w: (s, w, h))
        keys = [whole, pl.BlockSpec(
            (None, steps, d), lambda s, h, w: (s, 0, h // group)
        )]
        o = jax.ShapeDtypeStruct((seqs, steps, heads * d), q.dtype)
    grid = (seqs, heads, steps // window)
    if not backward:
        return 2 * [(block, LANES)] + [(block, d)], 3, dict(
            grid=grid,
            in_specs=[seq, *keys],
            out_specs=[out, row],
            out_shape=[o, jax.ShapeDtypeStruct((seqs, heads, 1, steps), F32)],
        )
    own = bool(summaries) * [like(k, k.dtype), like(v, v.dtype)]
    return [(window, d)], 6, dict(
        grid=grid,
        in_specs=[seq, *keys, out, row, row],
        out_specs=[seq, *keys],
        out_shape=[like(q, q.dtype), *own, like(ksum, F32), like(vsum, F32)],
    )


def _vmem_need(window: int, scratch, tiles, call, operands) -> int:
    block = sub_block(window)
    return vmem_need(
        scratch + tiles * [(block, block)],
        call["in_specs"] + call["out_specs"],
        [z.dtype for z in (*operands, *call["out_shape"])],
    )


# (forward, backward) by whether the earlier windows hand over summaries.
# The causal calls' names lie under the two prefixes by which a trace's
# readers know a flash-attention kernel (``benchmark/tracered.FLASH_KERNEL``).
KERNEL_NAMES = {
    True: ("dpwa_eva_attention_fwd", "dpwa_eva_attention_bwd"),
    False: ("flash_attention_fwd_dpwa", "flash_mha_bwd_dpwa"),
}
# The causal calls with a band: the same two prefixes, and a tail of their
# own by which a reader tells them from the calls over the whole triangle.
BAND_KERNEL_NAMES = tuple(
    name + "_window" for name in KERNEL_NAMES[False]
)


def _kernel_call(
    backward, interpret, window, per_window, scale, band, *operands
):
    """One ``pallas_call`` of the forward or the backward kernel on
    ``operands`` (``q k v``, the summaries where there are any, and the
    backward kernel's ``do lse di``), with the VMEM limit its shapes come to
    with its score tiles alive in a turn of its loops.  Forward: ``(o, lse
    [S, h, 1, T])``.  Backward: the gradients of ``q k v`` and of the
    summaries, each in its argument's shape: with summaries ``dq dk dv`` in
    ``q``'s type and ``dksum dvsum`` float32; without them ``dq`` and float32
    ``dk dv``.

    With summaries every operand is heads first, ``o`` too.  Without them
    (causal attention) ``q``, ``k`` are heads first and ``v``, ``do`` (and so
    ``o``, ``dv``) ``[S, T, h, D]``, each as :func:`causal_attention` says its
    neighbour in a model holds it; the positions-first four go to the kernel
    as ``[S, T, h D]`` by a reshape, where a head is a block of ``D`` lanes:
    the same tiles in VMEM."""
    keys = operands[:len(operands) - 3 * backward]
    own = len(keys) == 5
    if not own:
        lanes = lambda z: z.reshape(*z.shape[:2], -1)
        q, k, v, *rest = operands
        keys = (q, k, lanes(v))
        operands = (*keys, *map(lanes, rest[:1]), *rest[1:])  # rest: do lse di
    group = operands[0].shape[1] // operands[1].shape[1]
    scratch, tiles, call = _layout(window, *keys, backward=backward)
    static = dict(
        block=sub_block(window), per_window=per_window, scale=scale, band=band
    )
    if backward:
        static.update(group=group, own=own)
    results = pl.pallas_call(
        functools.partial(
            _backward_kernel if backward else _forward_kernel, **static
        ),
        **call,
        scratch_shapes=[pltpu.VMEM(shape, F32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            # The query heads of a group add into one float32 block.
            dimension_semantics=(
                "parallel",
                "arbitrary" if backward and group > 1 else "parallel",
                "arbitrary",
            ),
            vmem_limit_bytes=vmem_limit(
                _vmem_need(window, scratch, tiles, call, operands)
            ),
        ),
        interpret=interpret,
        name=(
            KERNEL_NAMES[own] if band is None else BAND_KERNEL_NAMES
        )[backward],
    )(*operands)
    if own:
        return results
    seqs, heads, steps, d = q.shape
    if not backward:
        o, lse = results
        return o.reshape(seqs, steps, heads, d), lse
    dq, dk, dv = results
    return dq, dk, dv.reshape(v.shape)


def _row_sums(do, o):
    """``di [B, h, 1, T]`` float32, ``sum_D do o`` as one row a head, of ``do``
    and ``o`` ``[B, T, h, D]`` with ``T`` a multiple of 8.  The positions are
    summed in their groups of eight: ``[B, T / 8, 8, h, D]`` is how the tiles
    of a float32 ``[B, T, h D]`` lie in memory (eight positions by 128 lanes,
    a head after a head), so one fusion reads ``do`` and ``o`` where the
    output projection and the kernel left them.  Summed as ``[B, T, h, D]``
    XLA:TPU first copies both, in float32, to tiles of heads by lanes; and
    without the barrier it moves the peers' unfolding of ``o`` under the
    widening, widens ``o`` alone in a pass of its own, in the forward pass,
    and keeps that for the backward pass (0.54 GB of the T 512 step; PERF.md
    section 6, PR 54).  What is turned is the result, a 128th of ``o``."""
    B, T, h, D = do.shape
    do, o = lax.optimization_barrier(
        tuple(z.reshape(B, T, h * D) for z in (do, o))
    )
    lying = lambda z: z.reshape(B, T // 8, 8, h, D).astype(F32)
    di = (lying(do) * lying(o)).sum(-1)
    return di.transpose(0, 3, 1, 2).reshape(B, h, 1, T)


@functools.lru_cache(maxsize=None)
def _differentiable(
    interpret: bool, window: int, per_window: int, scale: float, scope,
    jitted: bool, band=None,
):
    """``core(q, k, v, *summaries) -> o`` on the two kernels, differentiable
    in every operand.  A custom gradient's instructions carry no name of the
    forward's: ``scope`` names them where the forward has one of its own.
    ``jitted`` puts each kernel call under ``jax.jit``, so that a model's
    layers share one traced and lowered body a kernel, as the library's
    ``flash_attention`` does for its own (four layers' unrolled block pairs
    traced apart cost a step's set-up 5 s: PERF.md section 6, PR 46)."""
    calls = (
        functools.partial(
            _kernel_call, backward, interpret, window, per_window, scale,
            band,
        )
        for backward in (False, True)
    )
    forward, backward = (
        folding_peers(jax.jit(call) if jitted else call) for call in calls
    )

    @jax.custom_vjp
    def core(*inputs):
        return forward(*inputs)[0]

    def fwd(*inputs):
        o, lse = forward(*inputs)
        return o, (inputs, o, lse)

    def bwd(residuals, do):
        inputs, o, lse = residuals
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            if len(inputs) == 5:  # the EVA core, heads first all round
                di = (do.astype(F32) * o.astype(F32)).sum(-1)  # [B, h, T]
                grads = backward(*inputs, do, lse, di[:, :, None])
            else:
                dq, dk, dv = backward(*inputs, do, lse, _row_sums(do, o))
                # ``dq`` and ``dk`` go back through the rope, which widens
                # them and wants them laid out its way.  Left to itself at
                # several sequences a peer, XLA:TPU widens first, in a pass of
                # its own under the peers' unfolding, and turns the float32
                # copy; held here as they left the kernel they are turned in
                # their own type and widened in the rope's fusion (a third
                # of the bytes; PERF.md section 6, PR 54).  Not ``dv``: it
                # goes to a matmul as it lies.
                grads = (*lax.optimization_barrier((dq, dk)), dv)
            return tuple(g.astype(z.dtype) for g, z in zip(grads, inputs))

    core.defvjp(fwd, bwd)
    return core


def kernel_eva_attention(
    q, k, v, ksum, vsum, window: int, chunk: int, interpret: bool = False
):
    """:func:`eva_attention` by the Pallas kernels."""
    # Not ``jitted``: the EvaByte step compiles to its accepted text; the PR
    # that moves that cell can take the shorter set-up with it.
    return _differentiable(
        interpret, window, window // chunk, q.shape[-1] ** -0.5,
        scopes.ATTN_EVA.core, False,
    )(q, k, v, ksum, vsum)


def interpreted_eva_attention(q, k, v, ksum, vsum, window: int, chunk: int):
    """The same kernels run by the Pallas interpreter, for tests off the TPU."""
    return kernel_eva_attention(q, k, v, ksum, vsum, window, chunk, True)


# Causal attention on the same two kernels: every earlier key is "a summary"
# of itself (``ksum = k``, ``vsum = v``, a chunk of one position).

# What a call may ask Mosaic for: half of the 128 MiB a v5e core has.
VMEM_CEILING = 64 * 2 ** 20


def causal_window(T: int) -> int:
    """The window causal attention over ``T`` positions runs in: the largest
    multiple of the lanes that divides ``T``, within 2,048 positions and
    eight of its blocks (a window's block pairs are unrolled in the kernels:
    36 at eight).  One window of ``T`` up to 2,048 where its blocks allow,
    two of 2,048 at 4,096; ``T`` itself where the lanes do not divide it."""
    fitting = [
        w for w in range(LANES, min(T, 2048) + 1, LANES)
        if T % w == 0 and w // sub_block(w) <= 8
    ]
    return max(fitting, default=T)


def causal_kernels_take(
    T: int, d: int, heads: int, kv_heads: int, dtype, band=None
) -> bool:
    """Whether the kernels run causal attention of ``heads`` query heads on
    ``kv_heads`` heads of keys and values, all of size ``d``, over ``T``
    positions: ``d`` and ``T`` in multiples of the lanes, ``kv_heads``
    dividing ``heads``, and the backward call, whose float32 ``dk``, ``dv``
    of a whole head grow with ``T``, inside :data:`VMEM_CEILING`.  A ``band``
    (a query sees its last ``band`` keys, its own among them) is a positive
    multiple of the lanes; the blocks a call holds in VMEM are the same with
    one and without."""
    window = causal_window(T)
    if d % LANES or T % LANES or T % window or heads % kv_heads:
        return False
    if band is not None and (band < LANES or band % LANES):
        return False
    shaped = lambda h: jax.ShapeDtypeStruct((1, h, T, d), dtype)
    q, k, rows = shaped(heads), shaped(kv_heads), jax.ShapeDtypeStruct(
        (1, heads, 1, T), F32
    )
    v = jax.ShapeDtypeStruct((1, T, kv_heads * d), dtype)
    need = _vmem_need(
        window, *_layout(window, q, k, v, backward=True), (q, k, v, q, rows, rows)
    )
    return vmem_limit(need) <= VMEM_CEILING


def causal_attention(
    q, k, v, sm_scale: float, interpret: bool = False, window=None
):
    """``o [B, T, h, D]`` of causal softmax attention with scores ``sm_scale
    q . k``, for ``q [B, h, T, D]``, ``k [B, kv, T, D]`` and ``v [B, T, kv,
    D]`` that :func:`causal_kernels_take`; differentiable in all three, each
    gradient in its argument's layout (``dk``, ``dv`` summed over a group's
    query heads in float32 inside the kernel).  With a ``window`` query ``t``
    sees the keys ``t - window + 1 .. t`` alone (the band of the module
    docstring).  ``interpret`` runs the kernels by the Pallas interpreter,
    off the TPU.

    **Why two layouts.**  A window of a head is the same ``[window, D]`` tile
    in VMEM out of ``[B, h, T, D]`` or out of ``[B, T, h D]`` (a block of
    ``D`` lanes), so each operand is taken where its neighbour in a model
    holds it, and no pass over memory is spent on turning it.  ``v`` comes
    from its projection, ``o`` goes to the output projection, ``do`` comes
    from that one's gradient and ``dv`` goes to ``v``'s: positions first.
    ``q`` and ``k`` come from the rope and ``dq``, ``dk`` go back through it,
    and the rope's product over ``D`` is compiled by XLA:TPU as a convolution
    that writes (and reads) heads first at a sequence a peer, so there the
    caller's turn of ``q`` and ``k`` is a layout XLA picks and no pass; at
    eight sequences a peer it writes positions last and a pass is paid to
    whatever layout the kernel asks, this or another.  Taken positions first
    too, ``q`` and ``k`` cost a pass a tensor more at T 4,096 (PERF.md section
    6, PR 54).  A caller with no rope (the Jamba layer) pays its turn of
    ``q`` and ``k`` as before."""
    grid_window = causal_window(q.shape[2])
    return _differentiable(
        interpret, grid_window, grid_window, float(sm_scale), None, True,
        None if window is None else int(window),
    )(q, k, v)
