"""The sequence operations of a Mamba-1 mixer, three entry points: the causal
depthwise convolution (:func:`causal_conv1d`, plain ``jax.numpy``, also the
gated short convolution's), the same convolution with silu behind it
(:func:`conv_silu`) and the selective scan (:func:`selective_scan`), the last
two with their gradients written by hand.

The scan, per sequence, with ``x``, ``delta`` ``[T, E]``, ``A`` ``[E, N]``,
``Bm``, ``Cm`` ``[T, N]``, ``D`` ``[E]`` and a state ``s`` ``[E, N]``:

    s_t = exp(delta_t (x) A) * s_{t-1} + (delta_t * x_t) (x) Bm_t      s_0 = 0
    y_t = s_t . Cm_t + D * x_t

The decay ``exp(delta_t[e] A[e, n])`` depends on channel *and* state index,
so the recurrence has no matmul form, and the state over time is ``T x E x N``
values (1.34 GB a sequence at 4096 x 5120 x 16): nothing here writes it out.

**On a TPU** the scan is two Pallas kernels.  Both lay the state out as ``[N,
channel block]`` float32, channels on lanes, and walk time inside the kernel,
a chunk of ``chunk_length(T)`` steps a grid step, over a grid of (sequence,
channel block, chunk) whose last axis runs in order with the state kept in
VMEM between chunks.  A block is ``channel_block(E)`` channels, 1,024 at the
published 5,120, and a turn of the kernels' loops 16 time steps: both from
sweeps of the kernels alone on a v5e (PERF.md section 6, PR 35 and PR 40),
both functions of the shape, and each call asks Mosaic for the VMEM its own
shapes come to (:func:`vmem_limit`).  The forward kernel writes ``y`` and the
state *entering* each chunk (``[T / chunk, N, E]``, the only residual beside
the inputs).  The backward kernel walks the chunks last to first: it
recomputes a chunk's states from its boundary into VMEM, then walks the chunk
backwards carrying ``dL/ds`` the other way.  ``dBm`` and ``dCm`` are sums over
channels: the kernel writes one partial sum a channel block and they are added
outside.  Both of the forward kernel's products carry a ``checkpoint_name``
(:data:`KEPT`): a ``jax.checkpoint`` whose policy saves those names has
nothing left to run the forward kernel for when it recomputes, because the
backward kernel reads the kept states and the inputs alone
(``models/llama.py`` gives its Mamba blocks that policy).
There is no division by a cumulative decay anywhere (``exp(-sum delta A)``
overflows float32 inside one chunk at the published initial values), and the
recurrence, ``exp`` and every accumulation are float32 whatever type ``x``
has.

**Off the TPU** the same function is its plain twin, a ``lax.scan`` a token
under autodiff.  The backend decides, as in ``single_device_attention``.

**Under ``vmap``** (the stacked step's peer axis) a ``custom_vmap`` rule
folds the peer axis into the kernels' sequence axis, ``A`` and ``D`` indexed
by the peer a sequence belongs to; unbatched (a loop over peers) it is the
same kernels on one peer.  Both do the same arithmetic in the same order.

**The convolution with silu**, ``y = silu(b + sum_j w[j] x_{t-(K-1)+j})``
with ``x_{<0} = 0``, is a pass over ``x`` each way and nothing else, so on a
TPU it is a kernel each way (the bottom of this file) built as the scan's
are: a grid of (sequence, channel block, chunk) with the chunks in order,
the peers folded by the same rule with ``w`` and ``b`` a peer's own.  Around
the plain form XLA writes a float32 copy of ``x`` forward and, from the
transposes of its pad and slices, four float32 arrays of ``x``'s size
backward (PERF.md section 6, PR 56); the kernels read and write the
activation type and widen in VMEM, and they read ``x`` where it lies: handed
``in_proj``'s whole product they pick the blocks of its leading half.  The forward kernel keeps the last rows
of a chunk for the next one (zeros at a sequence's start); the backward
kernel walks the chunks last to first, makes the pre-activation again from
``x``, and writes one array, ``dx_t = sum_j w[j] (g silu')_{t+(K-1)-j}``,
keeping ``g silu'`` of a chunk's first rows for the chunk before it.  ``dw``
and ``db`` are the plain form's own, beside the kernel: a step that trains
neither (the LoRA cells) has XLA drop them.  The residuals are the three
arguments.  Off the TPU, and at shapes the blocks do not divide, it is
``silu(causal_conv1d(...))`` under autodiff.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import custom_batching, lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dpwa_tpu.utils import scopes

F32 = jnp.float32
# What the forward kernel alone produces, by the names a checkpoint policy
# can keep them under: the scan's output and the states entering each chunk.
KEPT_OUTPUT = "dpwa.ssm.scan.y"
KEPT_STATES = "dpwa.ssm.scan.states"
KEPT = (KEPT_OUTPUT, KEPT_STATES)


def causal_conv1d(x, w, b):
    """Depthwise causal convolution over time: ``out_t = b + sum_j w[j] *
    x_{t - (K - 1) + j}`` with ``x_{<0} = 0``.  ``x [..., T, E]``, ``w [K,
    E]``, ``b [E]``; ``K`` shifted multiply-adds in float32, the result in
    ``x``'s type."""
    taps, steps = w.shape[0], x.shape[-2]
    wide = x.astype(F32)
    padded = jnp.pad(wide, [(0, 0)] * (x.ndim - 2) + [(taps - 1, 0), (0, 0)])
    out = b.astype(F32)
    for j in range(taps):
        out = out + w[j].astype(F32) * lax.slice_in_dim(
            padded, j, j + steps, axis=-2
        )
    return out.astype(x.dtype)


def chunk_length(steps: int) -> int:
    """Time steps a grid step walks: the largest of 128, 64, ... 8 that
    divides ``steps`` (0: no chunk does, and the kernels are not used)."""
    return next((c for c in (128, 64, 32, 16, 8) if steps % c == 0), 0)


def channel_block(channels: int) -> int:
    """Channels a grid step holds the state of: 1,024 where that divides
    them (sixteen vector registers of state at ``N`` 16), else 512, 256,
    128, or all of them where no multiple of the lane width does.

    What a grid step and a time step cost whatever the channel count (the
    pipeline's turn, the casts' set-up, the two column picks, the row
    addressing) is paid once a block, so the widest block wins until its
    scratch outgrows the VMEM: on a v5e at the published widths (one layer's
    call under ``vmap``, 2 x 1 x 4096 x 5120, forward / forward + backward
    ms, 8 steps a turn) 256 channels 3.42 / 12.20, 512 2.53 / 8.74, 1,024
    2.20 / 7.58 (PERF.md section 6, PR 35), and by another harness 512 3.07
    / 10.33, 1,024 2.74 / 9.24 (section 6, PR 40).  Past 1,024 nothing
    divides 5,120, and the backward kernel's scratch doubles again
    (:func:`vmem_limit`)."""
    return next(
        (c for c in (1024, 512, 256, 128) if channels % c == 0), channels
    )


def _unroll(chunk: int) -> int:
    """Time steps a turn of the kernels' loops holds.  On a v5e at the
    published widths and 512 channels a block 8 took three quarters of the
    time of 4, which took a third of 1 (PERF.md section 6, PR 35); at 1,024
    channels (forward / forward + backward ms of one layer's call, PR 40's
    harness) 8 reads 2.74 / 9.24, 16 2.56 / 8.49 and 32 2.42 / 8.39, the
    kernels' compile 3.3, 5.2 and 11 s (PERF.md section 6, PR 40): 16,
    because the backward kernel gains nothing more from 32 and pays twice
    the compile; in the Jamba cell 16 costs 3 s of set-up from the cache and
    buys 1.2 % of the step.  The arithmetic and its order are the same at
    any value."""
    return min(chunk, 16)


def _use_kernels(steps: int, channels: int) -> bool:
    return (
        jax.default_backend() == "tpu" and channels % 128 == 0
        and chunk_length(steps) > 0
    )


def selective_scan(x, delta, A, Bm, Cm, D):
    """``y [B, T, E]`` of the recurrence in the module docstring, for ``x``,
    ``delta [B, T, E]``, ``A [E, N]``, ``Bm``, ``Cm [B, T, N]``, ``D [E]``;
    differentiable in all six.  ``y`` has ``x``'s type; each gradient has its
    argument's."""
    if _use_kernels(x.shape[1], x.shape[2]):
        return kernel_scan(x, delta, A, Bm, Cm, D)
    return plain_scan(x, delta, A, Bm, Cm, D)


def plain_scan(x, delta, A, Bm, Cm, D):
    """The recurrence a token at a time (``lax.scan``), float32, gradients by
    autodiff: what runs off the TPU."""
    wide = lambda v: v.astype(F32)
    A, D = wide(A), wide(D)

    def step(s, inputs):
        x_t, d_t, b_t, c_t = inputs  # [B, E], [B, E], [B, N], [B, N]
        s = jnp.exp(d_t[..., None] * A) * s + (
            (d_t * x_t)[..., None] * b_t[:, None, :]
        )
        return s, (s * c_t[:, None, :]).sum(-1) + D * x_t

    over_time = lambda v: jnp.swapaxes(wide(v), 0, 1)
    s0 = jnp.zeros((x.shape[0],) + A.shape, F32)
    _, y = lax.scan(step, s0, tuple(map(over_time, (x, delta, Bm, Cm))))
    return jnp.swapaxes(y, 0, 1).astype(x.dtype)


# The kernels.  Their arguments are in "kernel layout": ``x``, ``delta`` (and
# ``dy``) ``[S, T, E]`` over S sequences; ``a [G, N, E]`` and ``d [G, 1, E]``
# over G groups of S / G sequences each (the peers of a stacked step), ``A``
# transposed so that channels lie on lanes; ``bt``, ``ct [S, N, T]`` float32,
# transposed so that a time step's ``N`` values are a column.


def _column(rows, pick):
    """Column ``t`` of ``rows [N, chunk]`` as ``[N, 1]``, ``pick`` being the
    mask of lane ``t``: a select and a lane reduction, no dynamic lane
    index."""
    return jnp.sum(jnp.where(pick, rows, 0.0), axis=1, keepdims=True)


def _advance(s, a, d_t, x_t, b_t):
    """One step of the recurrence on ``s [N, Eb]``: ``d_t``, ``x_t [1, Eb]``
    rows, ``b_t [N, 1]`` a column.  Forward and recomputation share it, so a
    recomputed state is the forward's to the bit."""
    return jnp.exp(d_t * a) * s + (d_t * x_t) * b_t


def _walk(steps: int, unroll: int, step, carry):
    """``carry = step(t, carry)`` for t in 0..steps-1, ``unroll`` steps a turn
    of the loop (Mosaic unrolls a ``fori_loop`` wholly or not at all)."""

    def turn(i, carry):
        for u in range(unroll):
            carry = step(i * unroll + u, carry)
        return carry

    return lax.fori_loop(0, steps // unroll, turn, carry)


def _forward_kernel(
    x_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref, y_ref, hs_ref,
    s_ref, xf_ref, df_ref, yf_ref, *, chunk, unroll,
):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    hs_ref[...] = s_ref[...]
    # Rows are read one at a time below: from float32 copies, whose rows are
    # whole sublanes whatever type came in.
    xf_ref[...] = x_ref[...].astype(F32)
    df_ref[...] = dt_ref[...].astype(F32)
    a, bt, ct = a_ref[...], bt_ref[...], ct_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, bt.shape, 1)

    def step(t, s):
        row = pl.ds(t, 1)
        pick = lane == t
        s = _advance(
            s, a, df_ref[row, :], xf_ref[row, :], _column(bt, pick)
        )
        yf_ref[row, :] = jnp.sum(
            s * _column(ct, pick), axis=0, keepdims=True
        )
        return s

    s_ref[...] = _walk(chunk, unroll, step, s_ref[...])
    y_ref[...] = (yf_ref[...] + d_ref[...] * xf_ref[...]).astype(y_ref.dtype)


def _backward_kernel(
    x_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref, hs_ref, dy_ref,
    dx_ref, ddt_ref, da_ref, dbt_ref, dct_ref, dd_ref,
    g_ref, states_ref, xf_ref, df_ref, dyf_ref, dxf_ref, ddf_ref,
    *, chunk, unroll,
):
    # Grid step c of the last axis is chunk (chunks - 1 - c): the index maps
    # turn the order round, and ``g_ref`` carries a_{t+1} * dL/ds_{t+1} from
    # the chunk after this one.
    @pl.when(pl.program_id(2) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    xf_ref[...] = x_ref[...].astype(F32)
    df_ref[...] = dt_ref[...].astype(F32)
    dyf_ref[...] = dy_ref[...].astype(F32)
    a, bt, ct = a_ref[...], bt_ref[...], ct_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, bt.shape, 1)

    # The chunk's states again, from the state that entered it:
    # ``states_ref[t]`` is s_{t-1}, ``states_ref[t + 1]`` is s_t.
    states_ref[0] = hs_ref[...]

    def recompute(t, s):
        row = pl.ds(t, 1)
        s = _advance(
            s, a, df_ref[row, :], xf_ref[row, :], _column(bt, lane == t)
        )
        states_ref[t + 1] = s
        return s

    _walk(chunk, unroll, recompute, hs_ref[...])

    def step(i, carry):
        h, d_a, d_bt, d_ct = carry
        t = chunk - 1 - i
        row = pl.ds(t, 1)
        pick = lane == t
        d_t, x_t, dy_t = df_ref[row, :], xf_ref[row, :], dyf_ref[row, :]
        b_t = _column(bt, pick)
        decay = jnp.exp(d_t * a)
        g = dy_t * _column(ct, pick) + h  # dL/ds_t
        d_ct = jnp.where(
            pick, jnp.sum(dy_t * states_ref[t + 1], axis=1, keepdims=True),
            d_ct,
        )
        d_bt = jnp.where(
            pick, jnp.sum(g * (d_t * x_t), axis=1, keepdims=True), d_bt
        )
        d_u = jnp.sum(g * b_t, axis=0, keepdims=True)  # to delta_t * x_t
        d_decay = g * states_ref[t] * decay  # dL/d(delta_t A), elementwise
        ddf_ref[row, :] = (
            jnp.sum(d_decay * a, axis=0, keepdims=True) + d_u * x_t
        )
        dxf_ref[row, :] = d_u * d_t
        return decay * g, d_a + d_decay * d_t, d_bt, d_ct

    zeros = jnp.zeros_like(bt)
    h, d_a, d_bt, d_ct = _walk(
        chunk, unroll, step, (g_ref[...], jnp.zeros_like(a), zeros, zeros)
    )
    g_ref[...] = h
    da_ref[...] += d_a
    dbt_ref[...] = d_bt
    dct_ref[...] = d_ct
    dd_ref[...] += jnp.sum(dyf_ref[...] * xf_ref[...], axis=0, keepdims=True)
    dx_ref[...] = (dxf_ref[...] + d_ref[...] * dyf_ref[...]).astype(
        dx_ref.dtype
    )
    ddt_ref[...] = ddf_ref[...].astype(ddt_ref.dtype)


def _grid(x, a):
    """``(sequences, channel blocks, chunks), (chunk, block), sequences a
    group`` for ``x [S, T, E]`` and ``a [G, N, E]``."""
    seqs, steps, channels = x.shape
    chunk, block = chunk_length(steps), channel_block(channels)
    return (
        (seqs, channels // block, steps // chunk), (chunk, block),
        seqs // a.shape[0],
    )


# Mosaic's own scoped limit on a v5e, of the 128 MiB a core has.
DEFAULT_VMEM_LIMIT = 16 * 2 ** 20


def _forward_scratch(chunk: int, n: int, block: int) -> list:
    """The forward kernel's float32 scratch, as shapes: the state, and a
    chunk's rows of ``x``, ``delta`` and ``y``."""
    return [(n, block)] + 3 * [(chunk, block)]


def _backward_scratch(chunk: int, n: int, block: int) -> list:
    """The backward kernel's: the state's gradient, a chunk's recomputed
    states with the one that entered it, and a chunk's rows of ``x``,
    ``delta``, ``dy``, ``dx`` and ``ddelta``."""
    return [(n, block), (chunk + 1, n, block)] + 5 * [(chunk, block)]


def vmem_need(scratch, specs, types) -> int:
    """Bytes of VMEM a call holds by its shapes: its float32 ``scratch``,
    and the block of every operand and result (``specs`` with their
    ``types``) twice, the pipeline fetching one grid step's while the kernel
    works on another's."""
    return sum(4 * math.prod(shape) for shape in scratch) + 2 * sum(
        math.prod(d for d in spec.block_shape if d is not None)
        * jnp.dtype(t).itemsize
        for spec, t in zip(specs, types)
    )


def vmem_limit(need: int) -> int:
    """What a call asks Mosaic for: a quarter over what its shapes need, for
    what the compiler adds of its own (spilled registers, the loops'
    temporaries), and never under Mosaic's default.  At 1,024 channels a
    block, a chunk of 128 and ``N`` 16 the backward call's shapes come to
    15.3 MB and Mosaic allots 16.2 (compile-only, PERF.md section 6, PR
    40): under the default by 0.6 MB, so the call names its own limit, 19.1
    MB, and a wider state (``N`` 32) or another row buffer moves the limit
    with it instead of failing on the chip."""
    return max(DEFAULT_VMEM_LIMIT, need + need // 4)


def _kernel_call(
    kernel, name, interpret, grid, scratch, operands,
    *, in_specs, out_specs, out_shape, aliases=None,
):
    """One ``pallas_call`` of ``kernel`` over ``grid``, its last axis walked
    in order, with float32 ``scratch`` (shapes) and the VMEM limit its own
    shapes come to; ``aliases`` maps an operand to the result written over
    it."""
    need = vmem_need(
        scratch, in_specs + out_specs,
        [v.dtype for v in (*operands, *out_shape)],
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(shape, F32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(need),
        ),
        input_output_aliases=aliases or {},
        interpret=interpret,
        name=name,
    )(*operands)


def _own_spec(rows: int, block: int, per: int):
    """The block of a ``[G, rows, E]`` operand that belongs to sequence
    ``s``'s group, ``per`` sequences a group (a peer's own parameters)."""
    return pl.BlockSpec((None, rows, block), lambda s, j, c: (s // per, 0, j))


def folding_peers(fn):
    """``fn`` over kernel-layout arrays with the ``vmap`` rule of the module
    docstring: each operand's peer axis is folded into its leading axis
    (sequences, or groups) by a reshape, one call is made, and the results
    (all led by sequences) are unfolded.  An operand the ``vmap`` did not
    batch is the same for every peer and is repeated."""
    fn = custom_batching.custom_vmap(fn)

    @fn.def_vmap
    def over_peers(axis_size, in_batched, *args):
        folded = [
            (v if batched else jnp.broadcast_to(v, (axis_size,) + v.shape))
            .reshape((-1,) + v.shape[(2 if batched else 1):])
            for v, batched in zip(args, in_batched)
        ]
        outs = fn(*folded)
        return tuple(
            o.reshape((axis_size, -1) + o.shape[1:]) for o in outs
        ), (True,) * len(outs)

    return fn


def _forward_call(interpret: bool, x, delta, a, bt, ct, d):
    """``(y [S, T, E], states [S, chunks, N, E])``: the scan, and the state
    entering each chunk."""
    grid, (chunk, block), per = _grid(x, a)
    n = a.shape[1]
    seq = pl.BlockSpec((None, chunk, block), lambda s, j, c: (s, c, j))
    col = pl.BlockSpec((None, n, chunk), lambda s, j, c: (s, 0, c))
    return _kernel_call(
        functools.partial(_forward_kernel, chunk=chunk, unroll=_unroll(chunk)),
        "dpwa_selective_scan_fwd", interpret, grid,
        _forward_scratch(chunk, n, block), (x, delta, a, bt, ct, d),
        in_specs=[
            seq, seq,
            _own_spec(n, block, per),
            col, col,
            _own_spec(1, block, per),
        ],
        out_specs=[
            seq,
            pl.BlockSpec(
                (None, None, n, block), lambda s, j, c: (s, c, 0, j)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((grid[0], grid[2], n, x.shape[2]), F32),
        ],
    )


def _backward_call(interpret: bool, x, delta, a, bt, ct, d, states, dy):
    """The six gradients, each led by sequences: ``dx``, ``ddelta [S, T,
    E]``, ``da [S, N, E]`` (a sequence's own sum over time), ``dbt``, ``dct
    [S, channel blocks, N, T]`` (partial sums a channel block), ``dd [S, 1,
    E]``."""
    grid, (chunk, block), per = _grid(x, a)
    n, last = a.shape[1], grid[2] - 1
    seq = pl.BlockSpec((None, chunk, block), lambda s, j, c: (s, last - c, j))
    col = pl.BlockSpec((None, n, chunk), lambda s, j, c: (s, 0, last - c))
    partial_col = pl.BlockSpec(
        (None, None, n, chunk), lambda s, j, c: (s, j, 0, last - c)
    )
    seqs, steps, channels = x.shape
    return _kernel_call(
        functools.partial(
            _backward_kernel, chunk=chunk, unroll=_unroll(chunk)
        ),
        "dpwa_selective_scan_bwd", interpret, grid,
        _backward_scratch(chunk, n, block),
        (x, delta, a, bt, ct, d, states, dy),
        in_specs=[
            seq, seq,
            _own_spec(n, block, per),
            col, col,
            _own_spec(1, block, per),
            pl.BlockSpec(
                (None, None, n, block), lambda s, j, c: (s, last - c, 0, j)
            ),
            seq,
        ],
        out_specs=[
            seq, seq,
            pl.BlockSpec((None, n, block), lambda s, j, c: (s, 0, j)),
            partial_col, partial_col,
            pl.BlockSpec((None, 1, block), lambda s, j, c: (s, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(delta.shape, delta.dtype),
            jax.ShapeDtypeStruct((seqs, n, channels), F32),
            jax.ShapeDtypeStruct((seqs, grid[1], n, steps), F32),
            jax.ShapeDtypeStruct((seqs, grid[1], n, steps), F32),
            jax.ShapeDtypeStruct((seqs, 1, channels), F32),
        ],
    )


def _named_bits(value, name: str):
    """``value`` with ``checkpoint_name`` on an integer view of it.
    ``jax.checkpoint`` rounds a floating-point array that it saves and the
    forward pass goes on to use to the array's own type (``reduce_precision``,
    against excess precision in what XLA fused before it), and then holds
    the rounded copy beside the array: at the Jamba cell's shapes that put
    ``y`` twice at the step's memory peak, 0.18 GB over all blocks.  A
    kernel's output has no excess precision, it is written to memory in its
    type, so the name goes on its bits, which are saved as they are.

    This leans on jax 0.9.0's ``ad_checkpoint._insert_reduce_precision``,
    which skips a residual whose type is not ``np.inexact``; it is no
    documented promise.  ``tests/test_bringup.py`` compiles the step with the
    name on the float and holds that it passes the memory bound this view
    keeps: when that fails after an upgrade, name ``y`` itself here."""
    bits = jnp.dtype(f"uint{8 * value.dtype.itemsize}")
    return lax.bitcast_convert_type(
        checkpoint_name(lax.bitcast_convert_type(value, bits), name),
        value.dtype,
    )


def _differentiable(interpret: bool):
    """:func:`selective_scan`'s signature on the two kernels."""
    forward = folding_peers(functools.partial(_forward_call, interpret))
    backward = folding_peers(functools.partial(_backward_call, interpret))

    def laid_out(A, Bm, Cm, D):
        over_lanes = lambda v: jnp.swapaxes(v.astype(F32), 1, 2)
        return (
            A.astype(F32).T[None], over_lanes(Bm), over_lanes(Cm),
            D.astype(F32)[None, None],
        )

    @jax.custom_vjp
    def scan(x, delta, A, Bm, Cm, D):
        return forward(x, delta, *laid_out(A, Bm, Cm, D))[0]

    def fwd(x, delta, A, Bm, Cm, D):
        y, states = forward(x, delta, *laid_out(A, Bm, Cm, D))
        # The states go nowhere but to the backward rule: saved as they are.
        states = checkpoint_name(states, KEPT_STATES)
        return _named_bits(y, KEPT_OUTPUT), (x, delta, A, Bm, Cm, D, states)

    def bwd(residuals, dy):
        x, delta, A, Bm, Cm, D, states = residuals
        # A custom gradient's instructions carry no name of the forward's.
        with jax.named_scope(scopes.SSM_SCAN):
            dx, ddelta, da, dbt, dct, dd = backward(
                x, delta, *laid_out(A, Bm, Cm, D), states, dy
            )
            back = lambda partials, like: jnp.swapaxes(
                partials.sum(1), 1, 2
            ).astype(like.dtype)
            return (
                dx, ddelta, da.sum(0).T.astype(A.dtype), back(dbt, Bm),
                back(dct, Cm), dd.sum((0, 1)).astype(D.dtype),
            )

    scan.defvjp(fwd, bwd)
    return scan


kernel_scan = _differentiable(interpret=False)
kernel_scan.__doc__ = """:func:`selective_scan` by the Pallas kernels."""
# The same kernels run by the Pallas interpreter, for tests off the TPU.
interpreted_scan = _differentiable(interpret=True)


# The convolution with silu.  Kernel layout again: ``x [S, T, C >= E]``, ``g``
# and the results ``[S, T, E]``; ``w [G, K, E]`` and ``b [G, 1, E]`` float32
# over G groups of S / G sequences each.  A grid step holds ``conv_chunk(T)``
# rows of ``conv_block(E)`` channels behind the HALO rows before them.

# Rows kept of the chunk before: one whole tile of a bfloat16 array, two of a
# float32 one, so that every copy of them is aligned; the taps reach K - 1.
HALO = 16


def conv_chunk(steps: int) -> int:
    """Rows a grid step of the convolution's kernels holds: the largest of
    512, 256, ... 16 that divides ``steps`` (0: none does, and the kernels
    are not used)."""
    return next(
        (c for c in (512, 256, 128, 64, 32, HALO) if steps % c == 0), 0
    )


def conv_block(channels: int) -> int:
    """Channels a grid step of the convolution's kernels holds."""
    return next(
        (c for c in (1024, 512, 256, 128) if channels % c == 0), channels
    )


def _use_conv_kernels(steps: int, channels: int, taps: int) -> bool:
    return (
        jax.default_backend() == "tpu" and channels % 128 == 0
        and conv_chunk(steps) > 0 and taps <= HALO
    )


def conv_silu(x, w, b):
    """``silu(causal_conv1d(x, w, b))`` for ``x [..., T, E]``, ``w [K, E]``,
    ``b [E]``, differentiable in all three, the result in ``x``'s type.  On
    a TPU one kernel a pass, with the gradient written by hand: the sum and
    silu in float32, one rounding at the end (what XLA made of the plain
    form inside one fusion, where it drops the rounding between the two).
    Elsewhere the plain form under autodiff, which rounds the sum first.

    ``x`` may be wider than ``w``, ``[..., T, C]`` with ``C > E``: its
    leading ``E`` channels are convolved and the rest is not read (its
    gradient is zero).  A caller whose ``x`` is the leading part of a wider
    product hands the product over, and the kernels pick their blocks out of
    it where a slice would be a copy."""
    if _use_conv_kernels(x.shape[-2], w.shape[-1], w.shape[0]):
        return kernel_conv_silu(x, w, b)
    return plain_conv_silu(x, w, b)


def plain_conv_silu(x, w, b):
    """What runs off the TPU, and what the kernels are held to."""
    return jax.nn.silu(causal_conv1d(x[..., :w.shape[-1]], w, b))


# What a turn of the kernels' loops works on, rows by lanes.  A whole chunk
# as one expression is some hundred vector registers an operand, which Mosaic
# spills one by one (a store a bundle); a tile's eight stay in registers.  A
# slice that starts between two tiles of sublanes is held in one register
# more than its rows fill, and so is what is computed from it: the more rows
# a tile has the less that costs.
TILE = (64, 128)


def _over_columns(block: int, column) -> None:
    """``column(lanes)`` for every column of TILE lanes of a grid step's
    block, a turn of a loop each: the kernel's text holds one column's
    instructions."""
    width = min(block, TILE[1])

    def turn(i, carry):
        column(pl.ds(pl.multiple_of(i * width, width), width))
        return carry

    lax.fori_loop(0, block // width, turn, None)


def _row_tiles(chunk: int):
    """``(first row, rows)`` of a column's tiles, in order."""
    rows = min(chunk, TILE[0])
    return [(at, rows) for at in range(0, chunk, rows)]


def _sigmoid(v):
    """``1 / (1 + exp(-v))`` as ``(1 + tanh(v / 2)) / 2``: one transcendental
    and no division, which Mosaic would refine over a dozen instructions."""
    return 0.5 * jnp.tanh(0.5 * v) + 0.5


def _taps(rows_ref, w_ref, first: int, rows: int, lanes, out, flip=False):
    """``out + sum_j w[j] * rows_ref[first + j + t]`` for ``t`` in ``rows``
    (``w[K - 1 - j]`` with ``flip``), float32."""
    taps = w_ref.shape[0]
    for j in range(taps):
        out = out + w_ref[pl.ds(taps - 1 - j if flip else j, 1), lanes] * (
            rows_ref[pl.ds(first + j, rows), lanes]
        )
    return out


def _conv_silu_forward_kernel(x_ref, w_ref, b_ref, y_ref, rows_ref, *, chunk):
    taps, block = w_ref.shape[0], y_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():  # zeros before a sequence, not the sequence before
        rows_ref[pl.ds(0, HALO), :] = jnp.zeros((HALO, block), F32)

    def column(lanes):
        for at, rows in _row_tiles(chunk):
            here = pl.ds(at, rows)
            rows_ref[pl.ds(HALO + at, rows), lanes] = (
                x_ref[here, lanes].astype(F32)
            )
            # out_t = b + sum_j w[j] * x_{t - (K - 1) + j} and silu of it,
            # float32 to the one rounding of the result.
            pre = _taps(
                rows_ref, w_ref, HALO + at - (taps - 1), rows, lanes,
                b_ref[:, lanes],
            )
            y_ref[here, lanes] = (pre * _sigmoid(pre)).astype(y_ref.dtype)
        rows_ref[pl.ds(0, HALO), lanes] = rows_ref[pl.ds(chunk, HALO), lanes]

    _over_columns(block, column)


def _conv_silu_backward_kernel(
    x_ref, before_ref, g_ref, w_ref, b_ref, dx_ref, rows_ref, h_ref, head_ref,
    *, chunk,
):
    # Grid step c of the last axis is chunk (chunks - 1 - c), as in the
    # scan's backward kernel.  ``before_ref`` is the HALO rows of ``x`` before
    # this chunk; ``head_ref`` carries the first HALO rows of ``g silu'`` of
    # the chunk after it.
    step, taps, block = pl.program_id(2), w_ref.shape[0], dx_ref.shape[1]

    @pl.when(step == 0)
    def _():  # nothing flows back from past a sequence's end
        head_ref[...] = jnp.zeros_like(head_ref)

    first = step == pl.num_programs(2) - 1  # zeros before a sequence

    def column(lanes):
        rows_ref[pl.ds(0, HALO), lanes] = jnp.where(
            first, 0.0, before_ref[:, lanes].astype(F32)
        )
        rows_ref[pl.ds(HALO, chunk), lanes] = x_ref[:, lanes].astype(F32)
        h_ref[pl.ds(chunk, HALO), lanes] = head_ref[:, lanes]
        # Last tile first: a tile's ``dx`` reads the K - 1 rows after it.
        for at, rows in reversed(_row_tiles(chunk)):
            here = pl.ds(at, rows)
            # The forward's pre-activation again, then silu's derivative.
            pre = _taps(
                rows_ref, w_ref, HALO + at - (taps - 1), rows, lanes,
                b_ref[:, lanes],
            )
            sig = _sigmoid(pre)
            h_ref[here, lanes] = g_ref[here, lanes].astype(F32) * (
                sig * (1.0 + pre * (1.0 - sig))
            )
            # dx_t = sum_j w[j] * (g silu')_{t + (K - 1) - j}
            dx_ref[here, lanes] = _taps(
                h_ref, w_ref, at, rows, lanes, 0.0, flip=True
            ).astype(dx_ref.dtype)
        head_ref[:, lanes] = h_ref[pl.ds(0, HALO), lanes]

    _over_columns(block, column)


def _conv_grid(x, w):
    """As :func:`_grid`, for the convolution's kernels: the channels are
    ``w``'s, the leading ones of ``x``."""
    seqs, steps, channels = *x.shape[:2], w.shape[-1]
    chunk, block = conv_chunk(steps), conv_block(channels)
    return (
        (seqs, channels // block, steps // chunk), (chunk, block),
        seqs // w.shape[0],
    )


def _conv_silu_forward_call(interpret: bool, x, w, b):
    """``(y [S, T, E],)``."""
    grid, (chunk, block), per = _conv_grid(x, w)
    seq = pl.BlockSpec((None, chunk, block), lambda s, j, c: (s, c, j))
    return _kernel_call(
        functools.partial(_conv_silu_forward_kernel, chunk=chunk),
        "dpwa_conv_silu_fwd", interpret, grid, [(HALO + chunk, block)],
        (x, w, b),
        in_specs=[
            seq, _own_spec(w.shape[1], block, per), _own_spec(1, block, per),
        ],
        out_specs=[seq],
        out_shape=[jax.ShapeDtypeStruct(x.shape[:2] + w.shape[-1:], x.dtype)],
    )


def _conv_silu_backward_call(interpret: bool, x, g, w, b):
    """``(dx [S, T, E],)`` in ``x``'s type, written over ``g``: a block of
    ``g`` is read once, before its block of ``dx`` is written."""
    grid, (chunk, block), per = _conv_grid(x, w)
    last, halos = grid[2] - 1, chunk // HALO
    seq = pl.BlockSpec((None, chunk, block), lambda s, j, c: (s, last - c, j))
    before = pl.BlockSpec(
        (None, HALO, block),
        lambda s, j, c: (s, jnp.maximum((last - c) * halos - 1, 0), j),
    )
    return _kernel_call(
        functools.partial(_conv_silu_backward_kernel, chunk=chunk),
        "dpwa_conv_silu_bwd", interpret, grid,
        2 * [(HALO + chunk, block)] + [(HALO, block)], (x, x, g, w, b),
        in_specs=[
            seq, before, seq, _own_spec(w.shape[1], block, per),
            _own_spec(1, block, per),
        ],
        out_specs=[seq], out_shape=[jax.ShapeDtypeStruct(g.shape, x.dtype)],
        aliases={2: 0},
    )


def _differentiable_conv_silu(interpret: bool):
    """:func:`conv_silu`'s signature on the two kernels."""
    # Under ``jit``, so that a program with a call a layer traces and lowers
    # each kernel once: a call's ``chunk`` and ``block`` are read when its
    # shapes are first traced.
    def jitted(call, name):
        call = functools.partial(call, interpret)
        call.__name__ = name  # what ``jit`` puts in the instructions' names
        return jax.jit(call)

    forward = folding_peers(jitted(_conv_silu_forward_call, "conv_silu_fwd"))
    backward = folding_peers(
        jitted(_conv_silu_backward_call, "conv_silu_bwd")
    )
    sequences = lambda v: v.reshape((-1,) + v.shape[-2:])
    laid_out = lambda w, b: (w.astype(F32)[None], b.astype(F32)[None, None])

    @jax.custom_vjp
    def conv(x, w, b):
        return forward(sequences(x), *laid_out(w, b))[0].reshape(
            x.shape[:-1] + w.shape[-1:]
        )

    def fwd(x, w, b):
        return conv(x, w, b), (x, w, b)

    def bwd(residuals, g):
        x, w, b = residuals
        # A custom gradient's instructions carry no name of the forward's.
        with jax.named_scope(scopes.SSM_PARTS.conv):
            dx = backward(sequences(x), sequences(g), *laid_out(w, b))[0]
            # The parameters' are plain sums beside the kernel: where both
            # leaves are frozen nothing reads them and XLA drops them.
            dw, db = jax.vjp(
                lambda w, b: plain_conv_silu(x, w, b), w, b
            )[1](g)
            # Nothing flows to the channels of ``x`` that were not read.
            unread = [(0, 0)] * (g.ndim - 1) + [(0, x.shape[-1] - g.shape[-1])]
            return jnp.pad(dx.reshape(g.shape), unread), dw, db

    conv.defvjp(fwd, bwd)
    return conv


kernel_conv_silu = _differentiable_conv_silu(interpret=False)
kernel_conv_silu.__doc__ = """:func:`conv_silu` by the Pallas kernels."""
interpreted_conv_silu = _differentiable_conv_silu(interpret=True)
