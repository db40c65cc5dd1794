"""Buffered zero-copy ingest: ``recv_into`` reads, the receive-buffer
ring, and scatter-gather sends.

This module is the single landing place for the frame hot path's data
movement (docs/transport.md "The zero-copy landing zone").  Before it
existed, every byte crossed Python 4-5 times per hop: ``_recv_n`` grew a
bytearray chunk-by-chunk (once in the gossip fetch, duplicated in the
state-transfer fetch), the serve path concatenated ``header + payload``
before ``sendall``, and the codec decoders round-tripped through
``.tobytes()``.  The three primitives here remove those copies:

- :func:`recv_exact_into` — the one buffered read loop.  Fills a
  caller-supplied buffer via ``sock.recv_into(view[filled:])`` with the
  exact cumulative-deadline / per-byte-budget / progress-cell semantics
  the old ``_recv_exact`` had (same exception types and messages, so
  outcome classification upstream is unchanged).
- :class:`BufferRing` — a preallocated, size-classed pool of receive
  buffers.  Fetchers lease a buffer per frame, decode views directly out
  of it, and either *release* it back to the ring (payload fully
  consumed, e.g. int8 dequantize materialized a fresh f32 array),
  *detach* it (decoded views escape to the caller; ownership transfers
  to the views and the refcount keeps the buffer alive), or *recycle*
  it onto one owning escaping object (detach semantics now, automatic
  return to the pool when the owner dies — the dense-frame path, where
  a plain detach would pin the ring's hit rate at zero).
- :func:`sendall_segments` — scatter-gather egress.  ``socket.sendmsg``
  over ``[header, payload, digest, obs]`` so headers are never
  concatenated onto multi-MB payloads, with partial-send completion and
  a per-segment ``sendall`` fallback where ``sendmsg`` is unavailable.

Ownership rule (enforced by tests/test_zerocopy.py): a memoryview of a
leased buffer must never outlive the lease unless the lease was
detached.  Releasing while views escape would let the ring hand the
same bytes to the next frame and corrupt a decoded vector in place.

The module also keeps the process-wide rx copy tally behind
``wire_snapshot()``'s ``copies_per_frame`` column: decoders report how
many payload-sized copies a frame's decode performed (0 for dense f32 /
top-k f32 views, 1 for an int8 dequantize or a bf16 upcast).
"""

from __future__ import annotations

import ctypes
import errno
import socket
import threading
import time
import weakref
from typing import List, Optional, Sequence, Union

Buffer = Union[bytearray, memoryview]

# Smallest size class: header-ish reads don't each get a 1 MiB buffer.
_MIN_CLASS = 4096
# Free buffers kept per size class; beyond this, released buffers are
# dropped and the allocator reclaims them.  Gossip is one frame per
# peer per round, so a handful per class covers hedged + prefetch legs.
_MAX_FREE_PER_CLASS = 4
# Lease views start 64-byte aligned (one cacheline): dense f32 payloads
# land at offset 0 of their lease, so the decoded vector view is dlpack-
# eligible and crosses to the device by pointer adoption instead of a
# staging copy (dpwa_tpu/device/handoff.py's ALIGN — the two constants
# are the same contract).  bytearray gives no alignment promise of its
# own (pymalloc is 8-byte, large mallocs 16), so each pooled buffer
# carries LEASE_ALIGN slack and the lease view starts at the first
# aligned byte.
LEASE_ALIGN = 64


def _aligned_offset(buf: bytearray) -> int:
    """Offset of the first LEASE_ALIGN-aligned byte of ``buf`` (stable
    for the buffer's lifetime — CPython never relocates a bytearray's
    storage unless it is resized, and pooled buffers never are)."""
    base = ctypes.addressof(ctypes.c_char.from_buffer(buf))
    return (-base) % LEASE_ALIGN


def recv_exact_into(
    sock: socket.socket,
    n: int,
    deadline: Optional[float] = None,
    per_byte_s: float = 0.0,
    progress: Optional[list] = None,
    out: Optional[Buffer] = None,
) -> memoryview:
    """Read exactly ``n`` bytes into ``out`` (allocated if ``None``).

    Returns a writable memoryview of the first ``n`` bytes of ``out``.
    Deadline / per-byte / progress semantics are the gossip fetch
    contract (see the old ``_recv_exact`` docstring, now in
    tcp.py:_recv_exact which wraps this): ``deadline`` is a
    ``time.monotonic`` instant the WHOLE read must finish by,
    ``per_byte_s`` grows the budget with bytes actually received, and
    ``progress`` (a single-cell ``[int]``) survives the timeout this
    function raises so the caller can tell ``slow`` from ``timeout``.
    """
    if out is None:
        out = bytearray(n)
    view = memoryview(out)[:n]
    filled = 0
    while filled < n:
        if deadline is not None:
            remaining = deadline + filled * per_byte_s - time.monotonic()
            if remaining <= 0:
                raise socket.timeout("cumulative fetch deadline exceeded")
            sock.settimeout(remaining)
        cap = min(1 << 20, n - filled)
        got = sock.recv_into(view[filled : filled + cap])
        if not got:
            raise ConnectionError("peer closed mid-message")
        filled += got
        if progress is not None:
            progress[0] += got
    return view


class Lease:
    """One checked-out ring buffer.  ``view`` is sized to the request;
    call :meth:`release` when every decoded view of it is dead, or
    :meth:`detach` when views escape to the caller."""

    __slots__ = ("_ring", "_buf", "view", "_done")

    def __init__(self, ring: "BufferRing", buf: bytearray, n: int) -> None:
        self._ring = ring
        self._buf = buf
        off = _aligned_offset(buf)
        self.view = memoryview(buf)[off:off + n]
        self._done = False

    def release(self) -> None:
        """Return the buffer to the ring for reuse.  Idempotent."""
        if self._done:
            return
        self._done = True
        self.view.release()
        self._ring._put(self._buf)

    def detach(self) -> None:
        """Transfer ownership to the escaping views: the buffer is never
        pooled again; the views' refcounts keep it alive.  Idempotent."""
        if self._done:
            return
        self._done = True
        self._ring._forget(self._buf)

    def recycle(self, owner: object) -> None:
        """Transfer ownership to ``owner`` AND return the buffer to the
        ring once ``owner`` is garbage-collected (``weakref.finalize``).

        The pooled alternative to :meth:`detach` for the dense frame
        path, where every escaping view hangs off one ndarray's
        ``.base`` chain: a plain detach means every small gossip frame
        costs a fresh allocation (the ring's hit rate pins at zero —
        the small-class waste the copy leg's KiB cells expose), while
        recycle makes the next lease of that class a pool hit.

        ONLY safe when ``owner`` transitively owns every escaping view
        of the buffer (an ``np.frombuffer`` result does: derived slices
        keep it alive through ``.base``).  Payload objects whose member
        views can be extracted and outlive them (top-k / shard frames)
        must keep using :meth:`detach` — pooling while a stray view
        aliases the bytes would hand the next frame the same storage
        and corrupt a decoded vector in place.  Idempotent."""
        if self._done:
            return
        self._done = True
        # The lease view is NOT released here: the owner's views export
        # it (frombuffer holds a buffer export; releasing would raise
        # BufferError).  It dies with the owner.
        ring, buf = self._ring, self._buf
        # The buffer stays accounted as leased until the owner dies;
        # _recycle then both decrements and (capacity permitting) pools.
        weakref.finalize(owner, ring._recycle, buf)


class BufferRing:
    """Size-classed pool of receive buffers (powers of two ≥ 4 KiB).

    ``lease(n)`` hands back a :class:`Lease` whose ``view`` is exactly
    ``n`` bytes of a pooled (or freshly allocated) buffer, starting on
    a ``LEASE_ALIGN`` boundary (the device-handoff dlpack contract —
    each buffer carries the slack to guarantee it).  Stats feed
    the ``ring_occupancy`` health column: occupancy is the fraction of
    ring-managed bytes currently leased out — near zero when fetchers
    release promptly, climbing when decoded views pin buffers."""

    def __init__(
        self,
        min_class: int = _MIN_CLASS,
        max_free_per_class: int = _MAX_FREE_PER_CLASS,
    ) -> None:
        self._min_class = max(int(min_class), 16)
        self._max_free = max(int(max_free_per_class), 0)
        self._lock = threading.Lock()
        self._free: dict = {}  # class size -> [bytearray, ...]
        self._leased_bytes = 0
        self._hits = 0
        self._misses = 0
        self._recycled = 0

    def _class_for(self, n: int) -> int:
        size = self._min_class
        while size < n:
            size <<= 1
        return size

    def lease(self, n: int) -> Lease:
        if n < 0:
            raise ValueError(f"cannot lease {n} bytes")
        size = self._class_for(max(n, 1))
        with self._lock:
            pool = self._free.get(size)
            if pool:
                buf = pool.pop()
                self._hits += 1
            else:
                buf = None
                self._misses += 1
            self._leased_bytes += size
        if buf is None:
            # LEASE_ALIGN slack so the lease view can start on the first
            # aligned byte whatever base address the allocator hands out.
            buf = bytearray(size + LEASE_ALIGN)
        return Lease(self, buf, n)

    def _put(self, buf: bytearray) -> None:
        size = len(buf) - LEASE_ALIGN
        with self._lock:
            self._leased_bytes -= size
            pool = self._free.setdefault(size, [])
            if len(pool) < self._max_free:
                pool.append(buf)

    def _forget(self, buf: bytearray) -> None:
        with self._lock:
            self._leased_bytes -= len(buf) - LEASE_ALIGN

    def _recycle(self, buf: bytearray) -> None:
        """Finalizer target for :meth:`Lease.recycle`: the recycled
        lease's owner died, so the buffer comes home to the pool."""
        with self._lock:
            self._recycled += 1
        self._put(buf)

    def stats(self) -> dict:
        with self._lock:
            pooled = sum(
                len(b) - LEASE_ALIGN
                for p in self._free.values()
                for b in p
            )
            leased = self._leased_bytes
            total = leased + pooled
            return {
                "leased_bytes": leased,
                "pooled_bytes": pooled,
                "occupancy": (leased / total) if total else 0.0,
                "hits": self._hits,
                "misses": self._misses,
                "recycled": self._recycled,
            }


# Process-wide default ring + rx copy tally.  One ring per process is
# the right granularity: fetch legs, hedges, and prefetch threads all
# share it, and the health columns are per-process anyway.
_DEFAULT_RING = BufferRing()
_RX_LOCK = threading.Lock()
_RX_FRAMES = 0
_RX_COPIES = 0


def default_ring() -> BufferRing:
    return _DEFAULT_RING


def note_rx_frame(copies: int) -> None:
    """Record one decoded frame and how many payload-sized copies its
    decode performed (0 = view straight out of the receive buffer)."""
    global _RX_FRAMES, _RX_COPIES
    with _RX_LOCK:
        _RX_FRAMES += 1
        _RX_COPIES += max(int(copies), 0)


def rx_stats() -> dict:
    """Snapshot for ``wire_snapshot()``: mean payload copies per decoded
    frame plus the default ring's occupancy."""
    with _RX_LOCK:
        frames = _RX_FRAMES
        copies = _RX_COPIES
    ring = _DEFAULT_RING.stats()
    return {
        "frames": frames,
        "copies": copies,
        "copies_per_frame": (copies / frames) if frames else 0.0,
        "ring_occupancy": ring["occupancy"],
    }


def reset_rx_stats() -> None:
    """Test hook: zero the process-wide tally."""
    global _RX_FRAMES, _RX_COPIES
    with _RX_LOCK:
        _RX_FRAMES = 0
        _RX_COPIES = 0


# errnos some platforms use to refuse sendmsg on connected TCP sockets.
_SENDMSG_UNSUPPORTED = {
    getattr(errno, "ENOTSUP", None),
    getattr(errno, "EOPNOTSUPP", None),
    getattr(errno, "ENOSYS", None),
} - {None}


def sendall_segments(
    sock: socket.socket, segments: Sequence[Buffer]
) -> None:
    """Send every segment, in order, without concatenating them.

    Uses ``socket.sendmsg`` (scatter-gather, one syscall for header +
    payload + trailers) and completes partial sends by advancing
    memoryviews — fully-sent segments are dropped, a partially-sent
    head is sliced, never copied.  Where ``sendmsg`` is missing or the
    platform refuses it, falls back to per-segment ``sendall``, which
    preserves byte order and blocking/timeout semantics exactly.
    """
    segs: List[memoryview] = [
        memoryview(s).cast("B") for s in segments if len(s)
    ]
    if not segs:
        return
    sendmsg = getattr(sock, "sendmsg", None)
    if sendmsg is None:
        for seg in segs:
            sock.sendall(seg)
        return
    while segs:
        try:
            sent = sendmsg(segs)
        except OSError as exc:
            if exc.errno in _SENDMSG_UNSUPPORTED:
                for seg in segs:
                    sock.sendall(seg)
                return
            raise
        while sent > 0 and segs:
            head = segs[0]
            if sent >= len(head):
                sent -= len(head)
                segs.pop(0)
            else:
                segs[0] = head[sent:]
                sent = 0
