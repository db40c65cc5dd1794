"""Zigzag ring attention: causal load balance for the sp ring.

With CONTIGUOUS sequence blocks, causal ring attention is inherently
imbalanced: device 0's queries can only attend to its own block, so it
skips every later hop, while device n-1 attends to everything — per-step
wall clock is set by device n-1, wasting up to ~2× of the ring's compute
on causal workloads.  The standard fix (the "zigzag"/"striped" layout of
public ring-attention implementations) shards the sequence as 2n chunks
and gives device i chunks ``(i, 2n-1-i)`` — an early stripe ``e`` and a
late stripe ``l``.  Then at EVERY hop, every device computes exactly two
half-length attention panels (three on the diagonal hop):

- ``e_i × e_j``: full if ``j < i``, causal-diagonal if ``j == i``,
  skipped if ``j > i`` (a future chunk);
- ``l_i × e_j``: ALWAYS full — every late stripe sees every early chunk;
- ``l_i × l_j``: full if ``j > i``, causal-diagonal if ``j == i``,
  skipped if ``j < i``.

Work per (device, hop) is constant → perfectly balanced causal ring.

Each panel runs through the same per-hop flash kernels (and jnp twins)
as :mod:`dpwa_tpu.ops.flash_ring`, and the backward pass uses the same
global-residual trick per stripe (the library bwd kernels fed
``l = 1, m = global LSE`` produce exact global gradients restricted to
the held panel).  Forward + gradients are CPU-verified against full
attention in ``tests/test_zigzag_ring.py``.

Callers shard their data with :func:`zigzag_shard` (tokens, targets —
loss terms are pointwise, so only attention cares about the order) and
feed rope the matching :func:`zigzag positions <zigzag_positions>`;
``Llama(LlamaConfig(sp_axis=..., sp_layout="zigzag"))`` does both
internally (models/llama.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from dpwa_tpu.ops.flash_ring import (
    _NEG_INF,
    _expand_kv as _expand,
    _hop_bwd_jnp,
    _hop_bwd_pallas,
    _hop_fwd_jnp,
    _hop_fwd_pallas,
    _resolve_impl,
)

# ---------------------------------------------------------------------------
# Layout helpers (host/global side).
# ---------------------------------------------------------------------------


def zigzag_order(sp: int):
    """Global chunk order such that CONTIGUOUS sharding over ``sp``
    devices hands device i chunks ``(i, 2n-1-i)``: [0, 2n-1, 1, 2n-2, ...]
    grouped per device."""
    order = []
    for i in range(sp):
        order.append(i)
        order.append(2 * sp - 1 - i)
    return order


def zigzag_shard(x, sp: int, axis: int = 1):
    """Permute a GLOBAL sequence axis into zigzag chunk order, so that a
    plain contiguous ``P(axis_name)`` sharding yields each device its
    ``(i, 2n-1-i)`` stripes.  Inverse: :func:`zigzag_unshard`."""
    T = x.shape[axis]
    if T % (2 * sp):
        raise ValueError(f"sequence length {T} not divisible by 2*sp={2*sp}")
    chunks = jnp.split(x, 2 * sp, axis=axis)
    return jnp.concatenate([chunks[c] for c in zigzag_order(sp)], axis=axis)


def zigzag_unshard(x, sp: int, axis: int = 1):
    """Inverse of :func:`zigzag_shard`."""
    chunks = jnp.split(x, 2 * sp, axis=axis)
    inv = [0] * (2 * sp)
    for pos, c in enumerate(zigzag_order(sp)):
        inv[c] = pos
    return jnp.concatenate([chunks[inv[c]] for c in range(2 * sp)], axis=axis)


def zigzag_positions_local(T_local: int, axis_name: str) -> jnp.ndarray:
    """This device's GLOBAL rope positions under the zigzag layout
    (call inside shard_map): concat(chunk i, chunk 2n-1-i)."""
    n = lax.axis_size(axis_name)
    i = lax.axis_index(axis_name)
    C = T_local // 2
    return jnp.concatenate(
        [jnp.arange(C) + i * C, jnp.arange(C) + (2 * n - 1 - i) * C]
    )


# Pallas eligibility is decided per half-stripe by flash_ring's
# _resolve_impl/flash_ring_supported on the (B, C, H, D) panel shape —
# one predicate for both ring layouts.

# ---------------------------------------------------------------------------
# The balanced causal ring (call inside shard_map).
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def zigzag_ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = "sp",
    impl: Optional[str] = None,
) -> jnp.ndarray:
    """Causal ring attention over ``axis_name`` with the zigzag layout.

    q/k/v: this device's stripes, ``[B, T_local, H, D]`` with the first
    half = global chunk ``i`` and the second half = global chunk
    ``2n-1-i`` (produce with :func:`zigzag_shard` + contiguous sharding).
    Grouped K/V heads allowed.  Causal by construction — that is the
    layout's entire purpose; use
    :func:`dpwa_tpu.ops.flash_ring.ring_flash_attention_local` for
    non-causal."""
    out, _ = _zz_fwd_parts(q, k, v, axis_name, impl)
    return out


def _zz_fwd_parts(q, k, v, axis_name, impl):
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    C = T // 2
    scale = float(1.0 / (D ** 0.5))
    which = _resolve_impl(impl, (B, C, H, D))
    hop_fwd = _hop_fwd_pallas if which == "pallas" else _hop_fwd_jnp

    qh = q.transpose(0, 2, 1, 3)  # [B, H, T, D]
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    qe, ql = qh[:, :, :C], qh[:, :, C:]
    shift = [(j, (j + 1) % n) for j in range(n)]

    oz = (qe * 0.0).astype(jnp.float32)  # [B, H, C, D] stripe zeros
    lz = oz.sum(-1) + _NEG_INF  # [B, H, C]

    def merge(acc_o, acc_l, o_i, lse_i):
        lse_new = jnp.logaddexp(acc_l, lse_i)
        w_old = jnp.exp(jnp.minimum(acc_l - lse_new, 0.0))
        w_new = jnp.exp(jnp.minimum(lse_i - lse_new, 0.0))
        return acc_o * w_old[..., None] + o_i * w_new[..., None], lse_new

    def body(carry, hop):
        k_cur, v_cur, oe, le, ol, ll = carry
        src = (me - hop) % n
        ke, kl = k_cur[:, :, :C], k_cur[:, :, C:]
        ve, vl = v_cur[:, :, :C], v_cur[:, :, C:]

        def panel(qs, ks, vs, diag):
            return hop_fwd(qs, _expand(ks, H), _expand(vs, H), diag, scale)

        # e_i × e_src: past chunk full / diagonal causal / future skip.
        o_e, lse_e = lax.cond(
            src > me,
            lambda _: (oz, lz),
            lambda _: lax.cond(
                src == me,
                lambda __: panel(qe, ke, ve, True),
                lambda __: panel(qe, ke, ve, False),
                _,
            ),
            None,
        )
        oe, le = merge(oe, le, o_e, lse_e)
        # l_i × e_src: every late stripe sees every early chunk.
        o_l1, lse_l1 = panel(ql, ke, ve, False)
        ol, ll = merge(ol, ll, o_l1, lse_l1)
        # l_i × l_src: reversed ordering — late chunks DESCEND with i.
        o_l2, lse_l2 = lax.cond(
            src < me,
            lambda _: (oz, lz),
            lambda _: lax.cond(
                src == me,
                lambda __: panel(ql, kl, vl, True),
                lambda __: panel(ql, kl, vl, False),
                _,
            ),
            None,
        )
        ol, ll = merge(ol, ll, o_l2, lse_l2)

        k_nxt = lax.ppermute(k_cur, axis_name, perm=shift)
        v_nxt = lax.ppermute(v_cur, axis_name, perm=shift)
        return (k_nxt, v_nxt, oe, le, ol, ll), None

    (k_f, v_f, oe, le, ol, ll), _ = lax.scan(
        body, (kh, vh, oz, lz, oz, lz), jnp.arange(n)
    )
    out = jnp.concatenate([oe, ol], axis=2)  # [B, H, T, D]
    lse = jnp.concatenate([le, ll], axis=2)  # [B, H, T]
    return out.transpose(0, 2, 1, 3).astype(q.dtype), (out, lse)


def _zz_fwd(q, k, v, axis_name, impl):
    result, (out32, lse) = _zz_fwd_parts(q, k, v, axis_name, impl)
    return result, (q, k, v, out32, lse)


def _zz_bwd(axis_name, impl, res, g):
    q, k, v, out32, lse = res
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    C = T // 2
    KV = k.shape[2]
    rep = H // KV
    scale = float(1.0 / (D ** 0.5))
    which = _resolve_impl(impl, (B, C, H, D))
    hop_bwd = _hop_bwd_pallas if which == "pallas" else _hop_bwd_jnp

    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    do = g.transpose(0, 2, 1, 3).astype(jnp.float32)
    di = jnp.sum(out32 * do, axis=-1)  # [B, H, T]
    qe, ql = qh[:, :, :C], qh[:, :, C:]
    lse_e, lse_l = lse[:, :, :C], lse[:, :, C:]
    do_e, do_l = do[:, :, :C], do[:, :, C:]
    di_e, di_l = di[:, :, :C], di[:, :, C:]
    shift = [(j, (j + 1) % n) for j in range(n)]

    dq0 = (qe * 0.0).astype(jnp.float32)  # [B, H, C, D]
    dkv0 = (kh[:, :, :C] * 0.0).astype(jnp.float32)  # grouped [B, KV, C, D]

    def fold(t):
        return t.reshape(B, KV, rep, C, D).sum(2) if rep > 1 else t

    def body(carry, hop):
        k_cur, v_cur, dk_cur, dv_cur, dqe, dql = carry
        src = (me - hop) % n
        ke, kl = k_cur[:, :, :C], k_cur[:, :, C:]
        ve, vl = v_cur[:, :, :C], v_cur[:, :, C:]

        def panel_bwd(qs, ks, vs, lse_s, do_s, di_s, diag):
            dq_i, dk_i, dv_i = hop_bwd(
                qs, _expand(ks, H), _expand(vs, H),
                lse_s, do_s, di_s, diag, scale,
            )
            return dq_i, fold(dk_i), fold(dv_i)

        def zeros(_):
            return dq0, dkv0, dkv0

        # e_i × e_src
        dq_e, dk_e, dv_e = lax.cond(
            src > me,
            zeros,
            lambda _: lax.cond(
                src == me,
                lambda __: panel_bwd(qe, ke, ve, lse_e, do_e, di_e, True),
                lambda __: panel_bwd(qe, ke, ve, lse_e, do_e, di_e, False),
                _,
            ),
            None,
        )
        # l_i × e_src (always)
        dq_l1, dk_e2, dv_e2 = panel_bwd(
            ql, ke, ve, lse_l, do_l, di_l, False
        )
        # l_i × l_src
        dq_l2, dk_l, dv_l = lax.cond(
            src < me,
            zeros,
            lambda _: lax.cond(
                src == me,
                lambda __: panel_bwd(ql, kl, vl, lse_l, do_l, di_l, True),
                lambda __: panel_bwd(ql, kl, vl, lse_l, do_l, di_l, False),
                _,
            ),
            None,
        )
        dqe = dqe + dq_e
        dql = dql + dq_l1 + dq_l2
        dk_new = dk_cur + jnp.concatenate([dk_e + dk_e2, dk_l], axis=2)
        dv_new = dv_cur + jnp.concatenate([dv_e + dv_e2, dv_l], axis=2)
        k_nxt = lax.ppermute(k_cur, axis_name, perm=shift)
        v_nxt = lax.ppermute(v_cur, axis_name, perm=shift)
        dk_nxt = lax.ppermute(dk_new, axis_name, perm=shift)
        dv_nxt = lax.ppermute(dv_new, axis_name, perm=shift)
        return (k_nxt, v_nxt, dk_nxt, dv_nxt, dqe, dql), None

    dk_init = jnp.concatenate([dkv0, dkv0], axis=2)  # [B, KV, T, D]
    (k_f, v_f, dk, dv, dqe, dql), _ = lax.scan(
        body, (kh, vh, dk_init, dk_init, dq0, dq0), jnp.arange(n)
    )
    dq = jnp.concatenate([dqe, dql], axis=2)
    return (
        dq.transpose(0, 2, 1, 3).astype(q.dtype),
        dk.transpose(0, 2, 1, 3).astype(k.dtype),
        dv.transpose(0, 2, 1, 3).astype(v.dtype),
    )


zigzag_ring_attention_local.defvjp(_zz_fwd, _zz_bwd)
