"""Ring attention: sequence-parallel exact attention over a mesh axis.

Long-context support (first-class, per the rebuild mandate; the reference
itself never touches model internals — SURVEY.md §5 "Long-context").  The
sequence is sharded into contiguous blocks over a mesh axis ``sp`` —
orthogonal to the gossip ``peers`` axis, so a 2-D mesh ``(peers, sp)`` runs
gossip-DP across replicas while each replica's long sequences span its
``sp`` sub-mesh.

Algorithm (Liu et al. 2023 ring attention; same math as blockwise/flash):
each device holds Q/K/V for its block; K/V blocks rotate around the ring
with ``lax.ppermute`` while a numerically-stable online softmax accumulates
(running max ``m``, denominator ``l``, weighted sum ``o``).  After
``sp``-many hops every query has attended to every key, with communication
overlapped block-by-block and O(T_local²) peak memory.  Exact — not an
approximation; verified against full attention in tests."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _block_attn(q, k, v, scale, qpos, kpos, causal):
    """One Q-block × K-block partial attention. Returns (scores_max, exp
    scores @ v, exp scores row-sums).

    Grouped-query attention: ``k``/``v`` may carry fewer heads than ``q``
    (H % KV == 0); they are expanded HERE, per block, so the ring carries
    (and each hop ppermutes) only the small grouped K/V — GQA's whole
    point on a long-context fabric."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    if causal:
        mask = kpos[None, None, None, :] <= qpos[None, None, :, None]
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # [B,H,T]
    # Guard fully-masked rows (no valid keys in this block yet).
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    o = jnp.einsum("bhts,bshd->bthd", p, v)
    l = jnp.sum(p, axis=-1)  # [B,H,T]
    return m, o, l


def _auto_q_chunk(T: int) -> int:
    """Default query-chunk length: the largest power-of-two divisor of T
    capped at 256, or 0 (no chunking) for short blocks.  Chunking caps the
    per-hop score materialization at ``[B, H, chunk, T]`` instead of
    ``[B, H, T, T]``; 256 keeps the MXU-side matmuls large."""
    if T <= 512:
        return 0
    c = 256
    while c > 1 and T % c:
        c //= 2
    return c if c > 1 else 0


def _merge_partials(m, l, o, m_blk, l_blk, o_blk):
    """Online-softmax combine of two (max, denom, weighted-sum) partials."""
    m_new = jnp.maximum(m, m_blk)
    c_old = jnp.exp(m - m_new)
    c_blk = jnp.exp(m_blk - m_new)
    c_old = jnp.where(jnp.isfinite(c_old), c_old, 0.0)
    c_blk = jnp.where(jnp.isfinite(c_blk), c_blk, 0.0)
    l_new = l * c_old + l_blk * c_blk
    o_new = (
        o * c_old.transpose(0, 2, 1)[..., None]
        + o_blk * c_blk.transpose(0, 2, 1)[..., None]
    )
    return m_new, l_new, o_new


def ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = "sp",
    causal: bool = True,
    q_chunk: Optional[int] = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """Call INSIDE shard_map over ``axis_name``.

    Args:
      q, k, v: this device's sequence block, ``[B, T_local, H, D]``;
        device i holds global positions ``[i*T_local, (i+1)*T_local)``.
      q_chunk: query-chunk length for the flash-style inner loop.  None
        picks :func:`_auto_q_chunk`; 0 disables chunking.  With a chunk
        of C the per-hop peak is the ``[B, H, C, T_local]`` score panel —
        never the full ``[B, H, T_local, T_local]`` block — and the hop
        body is rematerialized (``jax.checkpoint``), so the backward pass
        recomputes score panels instead of carrying sp-many of them as
        scan residuals.  Long-context memory is O(T_local) activations.
      impl: "auto" runs every hop through the Pallas flash kernel on TPU
        when shapes allow (:mod:`dpwa_tpu.ops.flash_ring` — VMEM score
        tiles, never HBM panels); "flash" requests the same (on a TPU
        with ineligible shapes it falls back to THIS module's chunked
        einsum hop, never the flash-ring jnp twin, whose per-hop
        [B,H,T,T] panel would be a memory regression at long T; off-TPU
        it forces the twin — the CPU parity tests' hook); "xla" keeps
        the q-chunked einsum hop.  An EXPLICIT ``q_chunk`` pins the
        einsum hop too — it tunes a knob only that path has.
    Returns the local block of the attention output, ``[B, T_local, H, D]``.
    """
    if impl != "xla" and q_chunk is None:
        from dpwa_tpu.ops.flash_ring import (
            flash_ring_supported,
            ring_flash_attention_local,
        )

        on_tpu = jax.default_backend() == "tpu"
        if (on_tpu and flash_ring_supported(q.shape)) or (
            not on_tpu and impl == "flash"
        ):
            # Kernel choice (pallas vs jnp twin) auto-resolves by backend
            # inside flash_ring.
            return ring_flash_attention_local(q, k, v, axis_name, causal)
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    if q_chunk is None:
        q_chunk = _auto_q_chunk(T)
    if q_chunk and T % q_chunk:
        raise ValueError(f"q_chunk {q_chunk} must divide T_local {T}")
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    q32 = q.astype(jnp.float32)
    qpos = me * T + jnp.arange(T)

    shift = [(j, (j + 1) % n) for j in range(n)]  # rotate kv around the ring

    def hop_attn(k_cur, v_cur, m, l, o, kpos):
        """One hop's partial attention + combine, optionally q-chunked."""
        k32, v32 = k_cur.astype(jnp.float32), v_cur.astype(jnp.float32)
        if not q_chunk:
            m_blk, o_blk, l_blk = _block_attn(
                q32, k32, v32, scale, qpos, kpos, causal
            )
            return _merge_partials(m, l, o, m_blk, l_blk, o_blk)

        nc = T // q_chunk
        # Stack per-chunk slices: scan materializes ONE chunk's score
        # panel at a time (sequential, not vmapped — that is the point).
        qs = q32.reshape(B, nc, q_chunk, H, D).transpose(1, 0, 2, 3, 4)
        qps = qpos.reshape(nc, q_chunk)
        ms = m.reshape(B, H, nc, q_chunk).transpose(2, 0, 1, 3)
        ls = l.reshape(B, H, nc, q_chunk).transpose(2, 0, 1, 3)
        os_ = o.reshape(B, nc, q_chunk, H, D).transpose(1, 0, 2, 3, 4)

        def chunk_step(_, xs):
            qc, qpc, mc, lc, oc = xs
            m_blk, o_blk, l_blk = _block_attn(
                qc, k32, v32, scale, qpc, kpos, causal
            )
            mc, lc, oc = _merge_partials(mc, lc, oc, m_blk, l_blk, o_blk)
            return None, (mc, lc, oc)

        _, (ms, ls, os_) = lax.scan(
            jax.checkpoint(chunk_step), None, (qs, qps, ms, ls, os_)
        )
        m = ms.transpose(1, 2, 0, 3).reshape(B, H, T)
        l = ls.transpose(1, 2, 0, 3).reshape(B, H, T)
        o = os_.transpose(1, 0, 2, 3, 4).reshape(B, T, H, D)
        return m, l, o

    def body(carry, hop):
        k_cur, v_cur, m, l, o = carry
        src = (me - hop) % n  # whose block we currently hold
        kpos = src * T + jnp.arange(T)
        m, l, o = hop_attn(k_cur, v_cur, m, l, o, kpos)
        k_nxt = lax.ppermute(k_cur, axis_name, perm=shift)
        v_nxt = lax.ppermute(v_cur, axis_name, perm=shift)
        return (k_nxt, v_nxt, m, l, o), None

    # Initial accumulators must carry the same varying-over-axis type as
    # their per-hop updates (shard_map VMA typing) — derive them from q so
    # they inherit q's full axis-varying set (works on multi-axis meshes,
    # e.g. peers × sp).
    zeros_bht = (q32 * 0.0).sum(-1).transpose(0, 2, 1)  # [B, H, T]
    m0 = zeros_bht - jnp.inf
    l0 = zeros_bht
    o0 = q32 * 0.0
    # Remat the hop: the backward pass re-runs each hop's score math from
    # the (small) K/V carry instead of keeping sp-many score panels alive.
    (k_f, v_f, m, l, o), _ = lax.scan(
        jax.checkpoint(body), (k, v, m0, l0, o0), jnp.arange(n)
    )
    l = jnp.maximum(l, 1e-20)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("axis_name", "causal", "mesh", "q_chunk", "impl")
)
def _jit_ring(q, k, v, mesh, axis_name, causal, q_chunk, impl):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    body = functools.partial(
        ring_attention_local, axis_name=axis_name, causal=causal,
        q_chunk=q_chunk, impl=impl,
    )
    spec = P(None, axis_name, None, None)
    # Unchecked: on a TPU the hop is a library Pallas kernel whose
    # out_shape carries no vma (see train._make_step).
    return shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh,
    axis_name: str = "sp",
    causal: bool = True,
    q_chunk: Optional[int] = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """Global-view convenience: q/k/v ``[B, T, H, D]`` sharded (or shardable)
    along T over ``mesh``'s ``axis_name``; returns the same layout."""
    return _jit_ring(q, k, v, mesh, axis_name, causal, q_chunk, impl)


def full_attention_reference(q, k, v, causal=True):
    """O(T²) single-device reference used by the parity tests."""
    B, T, H, D = q.shape
    s = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s.astype(jnp.float32), -1)
    return jnp.einsum("bhts,bshd->bthd", p, v).astype(q.dtype)
