"""int8 stochastic-rounding wire compression for gossip exchanges.

``protocol.wire_dtype: int8`` compresses the SHIPPED replica to one byte
per element plus one f32 scale per :data:`CHUNK` elements — 3.9x fewer
wire bytes than f32 (the bf16 wire halves them; this quarters them), with
the local replica and all merge arithmetic staying f32.  The reference
has no compression at all (its wire is pickled f64/f32 numpy — SURVEY.md
§2 "TCP transport" row; mount empty); bf16 and int8 wires are rebuild
extensions motivated by the DCN/TCP fabric being the gossip
bottleneck.

Scheme: per-chunk absmax scaling, ``scale = max|chunk| / 127``, and
**stochastic rounding** ``q = floor(v/scale + u)``, ``u ~ U[0,1)``.
Stochastic rounding is the load-bearing choice: it makes the quantizer
unbiased (``E[q·scale] = v`` exactly), so repeated gossip averaging sees
zero-mean noise instead of a systematic pull toward the int8 grid —
deterministic rounding at α=0.5 freezes any coordinate pair whose gap is
under one grid step, a real convergence failure mode at consensus time
when replicas are already close.

Two implementations with one contract:

- the jittable JAX path (:func:`fake_quant_wire`) used by the SPMD
  transports to emulate the wire in-graph — keyed on
  ``(seed, step, sender)`` so the ICI and stacked transports produce
  BIT-IDENTICAL merges (same guarantee the bf16 wire has);
- the numpy path (:func:`quantize_np` / :func:`dequantize_np`) used by
  the TCP transport's publish/fetch codec — keyed on
  ``(seed, clock, sender)`` via ``numpy.random.Philox``.  The two RNGs
  differ, so TCP merges match the SPMD ones in distribution, not bits
  (documented non-goal; the bf16 wire's determinism comes free from
  rounding, stochastic rounding priced it in).
"""

from __future__ import annotations

import math
from typing import Tuple

import numpy as np

CHUNK = 256  # f32 scale per 256 int8 elements: 1.6 % metadata overhead

# Domain-separation constant so wire-quantization draws never collide
# with the participation/fault streams (schedules.participation_draw /
# fault_draw fold different data but share the schedule seed).
_WIRE_SALT = 0x51A7

# Separate salt for the top-k selection's tie-break stream: selection and
# value quantization run at the same (seed, clock, sender) and must not
# share a dither sequence.
_TOPK_SALT = 0x70CC


def _n_chunks(n: int) -> int:
    return max(1, math.ceil(n / CHUNK))


# --------------------------------------------------------------------------
# JAX path (SPMD transports; jit/shard_map-safe, static shapes)
# --------------------------------------------------------------------------


def wire_key(seed: int, step, sender, leaf: int = 0):
    """Per-(step, sender, leaf) threefry key for the shipped-copy
    quantization — the leaf index keeps same-shaped pytree leaves from
    sharing rounding noise."""
    import jax

    key = jax.random.key(seed ^ _WIRE_SALT)
    key = jax.random.fold_in(jax.random.fold_in(key, step), sender)
    return jax.random.fold_in(key, leaf)


def quantize(v, key) -> Tuple["jax.Array", "jax.Array"]:  # noqa: F821
    """f32 array (any shape) -> (int8[K, CHUNK], f32 scales[K])."""
    import jax
    import jax.numpy as jnp

    flat = v.reshape(-1)
    n = flat.shape[0]
    k = _n_chunks(n)
    padded = jnp.pad(flat, (0, k * CHUNK - n))
    chunks = padded.reshape(k, CHUNK)
    scale = jnp.max(jnp.abs(chunks), axis=1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    r = chunks / safe[:, None]
    u = jax.random.uniform(key, chunks.shape, dtype=chunks.dtype)
    q = jnp.clip(jnp.floor(r + u), -127, 127).astype(jnp.int8)
    q = jnp.where(scale[:, None] > 0, q, jnp.int8(0))
    return q, scale.astype(jnp.float32)


def dequantize(q, scale, shape):
    """(int8[K, CHUNK], f32[K]) -> f32 array of ``shape``."""
    import jax.numpy as jnp

    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    n = math.prod(shape) if shape else 1
    return flat[:n].reshape(shape)


def fake_quant_wire(v, seed: int, step, sender, leaf: int = 0):
    """Quantize-dequantize ``v`` exactly as the wire would — the in-graph
    emulation the SPMD transports apply to the SHIPPED copy (f32 leaves
    only; callers gate on dtype)."""
    q, scale = quantize(v, wire_key(seed, step, sender, leaf))
    return dequantize(q, scale, v.shape)


def quantize_tree(params, seed: int, step, sender):
    """One peer's shipped copy as the wire carries it: every f32 leaf of
    ``params`` becomes its ``(int8 q, f32 scales)`` pair, keyed (seed, step,
    sender, leaf) — the leaf's flatten-order index keeps same-shaped leaves
    from sharing rounding noise.  A pair is a subtree, so the codes and the
    tiny scale vectors move as separate leaves; other dtypes ride as they
    are.  Both SPMD layouts ship THIS tree
    (:func:`dpwa_tpu.parallel.exchange.gossip_exchange`)."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten(params)
    out = [
        quantize(v, wire_key(seed, step, sender, leaf=i))
        if v.dtype == jnp.float32
        else v
        for i, v in enumerate(leaves)
    ]
    return jax.tree.unflatten(treedef, out)


def dequantize_tree(like, received):
    """What the receiver reads out of a :func:`quantize_tree` tree; ``like``
    is any tree of the shipped one's shapes and dtypes (its own replica)."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda v, w: dequantize(*w, v.shape) if v.dtype == jnp.float32 else w,
        like, received,
    )


def fake_quant_tree(params, seed: int, step, sender):
    """Quantize-dequantize every f32 leaf exactly as the wire would: the
    reference the transports' int8 merges are tested against."""
    return dequantize_tree(params, quantize_tree(params, seed, step, sender))


# --------------------------------------------------------------------------
# numpy path (TCP transport codec; free-running host processes)
# --------------------------------------------------------------------------


def _np_key_words(seed: int, clock: float, sender: int) -> Tuple[int, int]:
    """One logical 128-bit key for both host codecs: (seed, sender) in
    one u64 word, the publish clock in the other.

    The clock word is the full IEEE-754 bit pattern, not ``int(clock)``:
    free-running publishers stamp fractional clocks, and truncation would
    hand e.g. clock 1.0 and 1.5 the same dither stream, breaking the
    documented per-(seed, clock, sender) uniqueness.  (Decode never
    derives the key — scales ride the payload — so only stream
    distinctness is at stake.)"""
    k0 = ((seed ^ _WIRE_SALT) & 0xFFFFFFFF) | ((sender & 0xFFFFFFFF) << 32)
    k1 = int(np.float64(clock).view(np.uint64))
    return k0, k1


def _np_rng(seed: int, clock: float, sender: int) -> np.random.Generator:
    return np.random.Generator(
        np.random.Philox(key=list(_np_key_words(seed, clock, sender)))
    )


def quantize_np(
    vec: np.ndarray, seed: int, clock: float, sender: int,
    impl: str = "auto",
) -> Tuple[np.ndarray, np.ndarray]:
    """f32[n] -> (int8[n], f32 scales[K]) with stochastic rounding.

    ``impl="auto"`` uses the native single-pass kernel
    (``native.quantize_sr``, splitmix64 dither) when the library is
    available — the codec is memory-bandwidth work, and numpy's
    ``Generator.random`` alone costs more than the int8 byte saving on
    a cheap fabric — with this numpy/Philox path as the fallback.  The
    two dither streams differ, so ``impl="numpy"`` pins this path where
    a test needs it; both satisfy the same contract (unbiased, error
    < 1 grid step, deterministic per (seed, clock, sender))."""
    flat = np.ascontiguousarray(vec, dtype=np.float32).reshape(-1)
    if impl == "auto":
        from dpwa_tpu import native

        out = native.quantize_sr(
            flat, CHUNK, *_np_key_words(seed, clock, sender)
        )
        if out is not None:
            return out
    n = flat.shape[0]
    k = _n_chunks(n)
    padded = np.zeros(k * CHUNK, np.float32)
    padded[:n] = flat
    chunks = padded.reshape(k, CHUNK)
    scale = (np.max(np.abs(chunks), axis=1) / 127.0).astype(np.float32)
    safe = np.where(scale > 0, scale, 1.0).astype(np.float32)
    u = _np_rng(seed, clock, sender).random(
        chunks.shape, dtype=np.float32
    )
    q = np.clip(np.floor(chunks / safe[:, None] + u), -127, 127).astype(
        np.int8
    )
    q[scale == 0, :] = 0
    return q.reshape(-1)[:n].copy(), scale


def dequantize_np(
    q: np.ndarray, scale: np.ndarray, impl: str = "auto"
) -> np.ndarray:
    """(int8[n], f32[K]) -> f32[n] (native one-pass decode when
    available; the two impls are bit-identical here — no RNG)."""
    if q.shape[0] > 0 and scale.shape[0] != _n_chunks(q.shape[0]):
        # Checked HERE for both impls: the native kernel would read past
        # a short scales buffer, and numpy's broadcasting would silently
        # smear one scale across every chunk.
        raise ValueError(
            f"scales has {scale.shape[0]} entries; "
            f"{_n_chunks(q.shape[0])} expected for n={q.shape[0]}"
        )
    if impl == "auto":
        from dpwa_tpu import native

        out = native.dequantize(
            np.ascontiguousarray(q),
            np.ascontiguousarray(scale, dtype=np.float32),
            CHUNK,
        )
        if out is not None:
            return out
    n = q.shape[0]
    k = _n_chunks(n)
    padded = np.zeros(k * CHUNK, np.int8)
    padded[:n] = q
    out = padded.reshape(k, CHUNK).astype(np.float32) * scale[:, None]
    return out.reshape(-1)[:n].copy()


# TCP wire payload for dtype code 4 (int8-chunked):
#   u64 n_elems | f32 scales[ceil(n/CHUNK)] | int8 q[n]
_LEN = np.dtype("<u8")


def _le_view(raw: np.ndarray, dtype: str) -> np.ndarray:
    """Reinterpret a contiguous uint8 slice as little-endian ``dtype``
    WITHOUT copying (numpy views are fine at unaligned offsets).  On a
    little-endian host ``np.dtype("<f4")`` IS the native dtype, so the
    view is the final array; only a big-endian host pays a byte-swapping
    ``astype`` — the zero-copy decode contract is LE-host-only, which is
    every deployment target (tests/test_zerocopy.py pins the LE case)."""
    out = raw.view(dtype)
    if out.dtype.byteorder == "<" and out.dtype.itemsize > 1:
        out = out.astype(out.dtype.newbyteorder("="))  # pragma: no cover
    return out


def encode_int8_payload(
    vec: np.ndarray, seed: int, clock: float, sender: int
) -> np.ndarray:
    q, scale = quantize_np(vec, seed, clock, sender)
    n = q.shape[0]
    kb = 4 * scale.shape[0]
    buf = np.empty(8 + kb + n, np.uint8)
    buf[:8].view("<u8")[0] = n
    buf[8:8 + kb].view("<f4")[:] = scale
    buf[8 + kb:] = q.view(np.uint8)
    return buf


def decode_int8_payload(buf: np.ndarray) -> np.ndarray:
    """uint8 payload -> f32[n]; raises ValueError on malformed payloads
    (callers treat that as a skipped fetch).

    Zero-copy discipline: the length/scale fields are read as views
    straight out of ``buf`` (which may alias a receive-ring buffer); the
    only payload-sized allocation is the dequantized f32 output itself.
    """
    raw = np.ascontiguousarray(buf, dtype=np.uint8)
    if raw.size < 8:
        raise ValueError("int8 wire payload shorter than its length field")
    n = int(raw[:8].view("<u8")[0])
    k = _n_chunks(n)
    if raw.size != 8 + 4 * k + n:
        raise ValueError(
            f"int8 wire payload size {raw.size} != {8 + 4 * k + n} "
            f"expected for n={n}"
        )
    scale = _le_view(raw[8:8 + 4 * k], "<f4")
    q = raw[8 + 4 * k:].view(np.int8)
    return dequantize_np(q, scale)


def int8_payload_views(buf: np.ndarray):
    """``(n, scales_view, q_view)`` of an int8 wire body WITHOUT
    dequantizing — the raw operands of the device engine's fused
    dequant-lerp kernel (dpwa_tpu/device/kernels.py), validated exactly
    like :func:`decode_int8_payload` but with the dense f32 output never
    materialized: both returned arrays are views into ``buf``."""
    raw = np.ascontiguousarray(buf, dtype=np.uint8)
    if raw.size < 8:
        raise ValueError("int8 wire payload shorter than its length field")
    n = int(raw[:8].view("<u8")[0])
    k = _n_chunks(n)
    if raw.size != 8 + 4 * k + n:
        raise ValueError(
            f"int8 wire payload size {raw.size} != {8 + 4 * k + n} "
            f"expected for n={n}"
        )
    return n, _le_view(raw[8:8 + 4 * k], "<f4"), raw[8 + 4 * k:].view(np.int8)


# --------------------------------------------------------------------------
# Top-k delta codec (TCP wire payload code 5)
# --------------------------------------------------------------------------
#
# Ships only the k largest-magnitude *changed* coordinates since the last
# publish, against an error-feedback accumulator, so a coordinate whose
# delta missed the cut this round keeps its full residual score and wins a
# later round — nothing is ever silently dropped (Stich et al.-style
# memory/error feedback, adapted to gossip's averaging merge).
#
# Payload layout (code 5):
#   u64 n | u32 k | u8 value_code | u32 idx[k] (strictly increasing) | values
# where value_code 0 ships f32 values (4k bytes) and value_code 1 ships the
# int8-chunked block f32 scales[ceil(k/CHUNK)] + int8 q[k].
#
# The shipped values are ABSOLUTE coordinates ``vec[idx]``, not deltas: the
# receiver rebuilds its estimate of the sender by overwriting its OWN
# replica at idx (``est = local.copy(); est[idx] = values``) and merges
# that densified estimate exactly like a dense payload.  Absolute values
# make the codec stateless on the receive side (no mirror to keep in sync
# across skipped fetches, restarts, or partner remaps) and make honest
# payloads look like the local replica to the trust plane (cosine ≈ +1 on
# the selected coordinates), so the PR 4 hard bounds screen sparse frames
# with no new thresholds.

TOPK_VALUE_F32 = 0
TOPK_VALUE_INT8 = 1


def topk_nbytes(n: int, k: int, value_dtype: str = "int8") -> int:
    """Exact on-wire payload bytes for a top-k frame (header + indices +
    value block) — used by ``_wire_nbytes`` / ``tree_wire_bytes`` so
    logged GB/s reflects the compressed traffic."""
    k = max(1, min(int(k), int(n))) if n else 0
    vals = 4 * k if value_dtype == "f32" else 4 * _n_chunks(k) + k
    return 13 + 4 * k + vals


def topk_k(n: int, fraction: float) -> int:
    """k for a given vector length and ``protocol.topk_fraction`` —
    clamped to [1, n] so degenerate fractions still make progress."""
    return max(1, min(int(n), int(round(float(fraction) * int(n)))))


def topk_select(
    delta: np.ndarray, k: int, seed: int, clock: float, sender: int
) -> np.ndarray:
    """Indices (sorted ascending) of the k largest-|delta| coordinates.

    Ties at the selection boundary are broken by a Philox draw keyed on
    (seed, clock, sender) — the host-path counterpart of the threefry
    keying the JAX codec uses, same convention as :func:`quantize_np` —
    then by index, so reruns are bit-identical and peers with identical
    deltas still make independent, unbiased boundary choices."""
    n = delta.shape[0]
    k = max(1, min(int(k), n))
    if k == n:
        return np.arange(n, dtype=np.uint32)
    score = np.abs(delta)
    part = np.argpartition(score, n - k)
    thresh = score[part[n - k]]
    above = np.nonzero(score > thresh)[0]
    need = k - above.shape[0]
    if need <= 0:
        # More strictly-above entries than k can't happen (partition
        # invariant), but guard the == 0 edge exactly.
        idx = above[:k]
    else:
        at = np.nonzero(score == thresh)[0]
        tie = np.random.Generator(
            np.random.Philox(
                key=list(_np_key_words(seed ^ _TOPK_SALT, clock, sender))
            )
        ).random(at.shape[0])
        order = np.lexsort((at, tie))
        idx = np.concatenate([above, at[order[:need]]])
    return np.sort(idx).astype(np.uint32)


class TopkPayload:
    """A decoded sparse frame: ``n`` total coordinates, sorted ``indices``
    (u32[k]) and f32 ``values`` — absolute sender coordinates, already
    dequantized when the value block was int8.  ``value_dtype`` records
    which block arrived (for per-codec accounting/baselines) and
    ``nbytes`` the on-wire payload size."""

    __slots__ = ("n", "indices", "values", "value_dtype", "nbytes")

    def __init__(self, n, indices, values, value_dtype="f32", nbytes=0):
        self.n = int(n)
        self.indices = np.ascontiguousarray(indices, dtype=np.uint32)
        self.values = np.ascontiguousarray(values, dtype=np.float32)
        self.value_dtype = value_dtype
        self.nbytes = int(nbytes)

    @property
    def k(self) -> int:
        return self.indices.shape[0]

    def densify(self, local: np.ndarray) -> np.ndarray:
        """Rebuild the sender estimate against the receiver's own
        replica: ``est = local.copy(); est[indices] = values``."""
        local = np.ascontiguousarray(local, dtype=np.float32).reshape(-1)
        if local.shape[0] != self.n:
            raise ValueError(
                f"top-k payload is for n={self.n} but local replica has "
                f"{local.shape[0]} elements"
            )
        out = local.copy()
        out[self.indices] = self.values
        return out


class TopkEncoder:
    """Sender-side error-feedback state for the top-k wire.

    ``base`` is this sender's record of what the ring has been told about
    each coordinate.  Each publish scores coordinates by
    ``|vec - base|`` (the residual: real movement PLUS anything previous
    rounds dropped or rounded away), ships the top-k as absolute values,
    and overwrites ``base`` only at the shipped indices with the values
    as they decode on the wire — so quantization error also stays in the
    score and un-shipped coordinates accumulate until they win."""

    def __init__(self, fraction: float, value_dtype: str = "int8"):
        self.fraction = float(fraction)
        self.value_dtype = value_dtype
        self.base: np.ndarray | None = None

    def reset(self) -> None:
        self.base = None

    def retune(self, fraction: float) -> None:
        """Swap the shipped fraction AND drop the error-feedback base.

        ``base`` records what the ring was told under the OLD rung; a
        codec change invalidates that record (the receivers that decode
        the next frame may have merged dense/bf16 frames meanwhile, and
        a stale residual would re-ship coordinates the new rung already
        covers — the "stale topk memory" failure the tune plane's
        reset-on-rung-change rule exists to prevent).  The next encode
        rebuilds ``base`` from zeros, exactly like a fresh encoder."""
        self.fraction = float(fraction)
        self.reset()

    def encode(
        self, vec: np.ndarray, seed: int, clock: float, sender: int
    ) -> np.ndarray:
        """f32[n] -> uint8 payload (code 5 body)."""
        flat = np.ascontiguousarray(vec, dtype=np.float32).reshape(-1)
        n = flat.shape[0]
        if self.base is None or self.base.shape[0] != n:
            self.base = np.zeros(n, np.float32)
        k = topk_k(n, self.fraction)
        idx = topk_select(flat - self.base, k, seed, clock, sender)
        vals = flat[idx]
        if self.value_dtype == "int8":
            q, scale = quantize_np(vals, seed, clock, sender)
            shipped = dequantize_np(q, scale)
            code = TOPK_VALUE_INT8
            sb = 4 * scale.shape[0]
            vb = sb + k
        else:
            q = scale = None
            sb = 0
            shipped = vals
            code = TOPK_VALUE_F32
            vb = 4 * k
        self.base[idx] = shipped
        # One preallocated buffer, header and blocks written through
        # views — no per-section tobytes round-trips, no concatenate.
        buf = np.empty(13 + 4 * k + vb, np.uint8)
        buf[:8].view("<u8")[0] = n
        buf[8:12].view("<u4")[0] = k
        buf[12] = code
        buf[13:13 + 4 * k].view("<u4")[:] = idx
        vstart = 13 + 4 * k
        if code == TOPK_VALUE_INT8:
            buf[vstart:vstart + sb].view("<f4")[:] = scale
            buf[vstart + sb:] = q.view(np.uint8)
        else:
            buf[vstart:].view("<f4")[:] = vals
        return buf


def decode_topk_payload(buf: np.ndarray) -> TopkPayload:
    """uint8 payload -> :class:`TopkPayload`; raises ValueError on ANY
    malformed input — truncated index list, k > n, out-of-range /
    unsorted / duplicate indices, or a value block whose length lies —
    so the transport classifies the frame CORRUPT instead of crashing."""
    raw = np.ascontiguousarray(buf, dtype=np.uint8)
    if raw.size < 13:
        raise ValueError("top-k wire payload shorter than its header")
    n = int(raw[:8].view("<u8")[0])
    k = int(raw[8:12].view("<u4")[0])
    code = int(raw[12])
    if n < 1 or k < 1:
        raise ValueError(f"top-k wire payload with n={n}, k={k}")
    if k > n:
        raise ValueError(f"top-k wire payload claims k={k} > n={n}")
    if code not in (TOPK_VALUE_F32, TOPK_VALUE_INT8):
        raise ValueError(f"top-k wire payload with value_code={code}")
    vals_nbytes = 4 * k if code == TOPK_VALUE_F32 else 4 * _n_chunks(k) + k
    expect = 13 + 4 * k + vals_nbytes
    if raw.size != expect:
        raise ValueError(
            f"top-k wire payload size {raw.size} != {expect} expected "
            f"for n={n}, k={k}, value_code={code}"
        )
    idx = _le_view(raw[13:13 + 4 * k], "<u4")
    if int(idx[-1]) >= n:
        raise ValueError(
            f"top-k wire payload index {int(idx[-1])} out of range for "
            f"n={n}"
        )
    if k > 1 and not np.all(idx[1:] > idx[:-1]):
        raise ValueError(
            "top-k wire payload indices not strictly increasing"
        )
    body = raw[13 + 4 * k:]
    if code == TOPK_VALUE_F32:
        # Values stay a VIEW into the receive buffer — the ownership
        # contract (docs/transport.md) is that the buffer's lease was
        # detached before these views escape.
        vals = _le_view(body, "<f4")
        vdtype = "f32"
    else:
        kc = _n_chunks(k)
        scale = _le_view(body[:4 * kc], "<f4")
        vals = dequantize_np(body[4 * kc:].view(np.int8), scale)
        vdtype = "int8"
    return TopkPayload(n, idx, vals, value_dtype=vdtype, nbytes=raw.size)
