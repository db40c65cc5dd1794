"""TCP gossip transport — the reference-equivalent CPU path.

Reproduces the reference's transport semantics (SURVEY.md §2 "TCP transport",
§3.2/§3.3 call stacks; reference file ``dpwa/conn.py`` — mount empty,
reconstructed): every worker process runs an **Rx thread** that serves the
node's most recently *published* flattened parameter vector (plus clock/loss
metadata) to any peer that connects; the training thread, once per step,
publishes its own vector, picks a partner, connects, fetches the partner's
blob with a timeout, and merges on the CPU.  A fetch that times out is simply
skipped — training continues (the reference's implicit elasticity,
SURVEY.md §5 "Failure detection").

Differences from the reference, on purpose:

- **No pickle.**  The wire format is a fixed ``struct`` header + raw
  little-endian float bytes — deserializing untrusted peers with pickle is an
  RCE; a framed binary format is also faster.
- **Deterministic rendezvous.**  Peer selection delegates to the same
  :mod:`~dpwa_tpu.parallel.schedules` pool the ICI transport compiles in, and
  participation uses the identical threefry draw — so with a lock-step driver
  the TCP and ICI paths produce bit-comparable merges (SURVEY.md §4 parity).
  Set ``schedule: random`` + ``fetch_probability < 1`` and run free-running
  processes to recover the reference's fully asynchronous behavior.

This path exists for capability parity (true multi-process elasticity on
non-TPU hosts) and as the baseline the ICI path is benchmarked against
(BASELINE.json:5 — ≥50× averaging throughput target).
"""

from __future__ import annotations

import socket
import threading
import time
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from dpwa_tpu import native
from dpwa_tpu.config import (
    DEFAULT_MIN_WIRE_MB_PER_S,
    DpwaConfig,
    FlowctlConfig,
)
# flowctl imports config + detector only — no cycle with this module.
from dpwa_tpu.flowctl import AdmissionController, DeadlineEstimator
# detector/scoreboard import config + schedules only — no cycle; chaos
# (which imports THIS module) is loaded lazily inside TcpTransport.
from dpwa_tpu.health.detector import Outcome
from dpwa_tpu.health.scoreboard import PeerState, Scoreboard
from dpwa_tpu.interpolation import PeerMeta, make_interpolation
from dpwa_tpu.parallel.schedules import Schedule, build_schedule

# Every magic, struct layout, payload code, and size clamp on the wire
# comes from the protocol_constants registry (with its back-compat
# ledger); dpwalint's wire-protocol checker rejects inline literals.
# The old underscored names are kept as module-level aliases because
# chaos/recovery/test code imports them from here.
from dpwa_tpu.parallel import protocol_constants as _pc
# Zero-copy data movement: the shared recv_into loop, the receive-buffer
# ring every fetch leases from, and scatter-gather sends
# (docs/transport.md "The zero-copy landing zone").
from dpwa_tpu.parallel import ingest as _ingest

# Gossip blob wire: request is the 5-byte magic; response is
# BLOB_HDR (magic version dtype clock loss nbytes) + nbytes of payload.
_REQ = _pc.BLOB_REQ
_MAGIC = _pc.BLOB_MAGIC
_HDR = _pc.BLOB_HDR
_DTYPES = {
    _pc.PAYLOAD_F32: np.dtype("<f4"),
    _pc.PAYLOAD_F64: np.dtype("<f8"),
    _pc.PAYLOAD_U16: np.dtype("<u2"),
}
try:  # bf16 wire code (protocol.wire_dtype: bf16) — ml_dtypes ships w/ jax
    import ml_dtypes

    _DTYPES[_pc.PAYLOAD_BF16] = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover - ml_dtypes is a jax dependency
    ml_dtypes = None
_DTYPE_CODES = {v: k for k, v in _DTYPES.items()}
# Codec payloads (int8-chunked, top-k delta) are NOT flat numpy dtypes —
# see the notes on PAYLOAD_INT8_CHUNKED / PAYLOAD_TOPK_DELTA in
# protocol_constants.py for their body layouts and decode ownership.
_INT8_CHUNKED = _pc.PAYLOAD_INT8_CHUNKED
_TOPK_DELTA = _pc.PAYLOAD_TOPK_DELTA
_SHARD = _pc.PAYLOAD_SHARD

# Outcomes the self-tuning wire counts as wire-bound evidence
# regardless of measured spans: the link (or the peer behind it) could
# not deliver a timely frame, which is exactly what escalating
# compression relieves.  Hard-failure outcomes (refused/corrupt/
# poisoned/untrusted) stay OUT — fewer bytes do not fix a dead or
# byzantine peer, and the scoreboard owns those.
_TUNE_SOFT_OUTCOMES = frozenset(
    (Outcome.BUSY, Outcome.SLOW, Outcome.STALE, Outcome.TIMEOUT)
)
_PAYLOAD_CODES = _pc.CODEC_PAYLOAD_CODES
_MAX_BLOB = _pc.MAX_BLOB_BYTES

# Probe-before-commit bound for the payload ring lease: advertisements
# above the threshold read a probe's worth of real bytes before the
# full-size buffer is allocated, so a peer that lies about nbytes and
# hangs up costs a 64 KiB lease, not a multi-GB upfront allocation
# (the old grow-by-chunk loop had the same received-bytes-proportional
# property; _MAX_BLOB alone is a 16 GiB bound).
_PROBE_THRESHOLD = 1 << 20
_PROBE_BYTES = 1 << 16

# STATE transfer wire (crash recovery, dpwa_tpu/recovery/): a restarted
# worker bootstraps a donor's full serialized train state over the same
# one-shot socket discipline as the gossip fetch — request, one framed
# response, close.  Layout + resumability notes: protocol_constants.py
# (STATE_HDR_FMT, BACK_COMPAT["state_one_chunk_per_connection"]).
_STATE_REQ = _pc.STATE_REQ
_STATE_REQ_BODY = _pc.STATE_REQ_BODY
_STATE_MAGIC = _pc.STATE_MAGIC
_STATE_HDR = _pc.STATE_HDR
_MAX_STATE_CHUNK = _pc.MAX_STATE_CHUNK_BYTES

# RELAY probe wire (epidemic membership, dpwa_tpu/membership/): before a
# node promotes a suspect to quarantined on its own evidence alone, it
# asks K drawn healthy peers to header-probe the suspect FOR it — an
# asymmetric fault (my link to the suspect is down, yours is not) then
# yields "alive" votes that avert a false quarantine.  The response's
# ``outcome`` byte indexes _RELAY_OUTCOMES — the relay's CLASSIFIED
# result of its own probe_header_classified against the target.
_RELAY_REQ = _pc.RELAY_REQ
_RELAY_BODY = _pc.RELAY_BODY
_RELAY_MAGIC = _pc.RELAY_MAGIC
_RELAY_HDR = _pc.RELAY_HDR
# The wire contract is the NAME tuple in protocol_constants; this maps
# each code onto the health-detector Outcome enum and must stay aligned
# (asserted below — drift would misclassify relay votes).
_RELAY_OUTCOMES = (
    Outcome.SUCCESS,
    Outcome.TIMEOUT,
    Outcome.REFUSED,
    Outcome.SHORT_READ,
    Outcome.CORRUPT,
    Outcome.BUSY,
)
assert tuple(_RELAY_OUTCOMES) == _pc.RELAY_OUTCOME_NAMES
_MAX_RELAY_TIMEOUT_MS = _pc.MAX_RELAY_TIMEOUT_MS

# BUSY shed frame (flowctl admission, dpwa_tpu/flowctl/): when the Rx
# server refuses work — connection cap, token bucket, in-flight-bytes
# ceiling — it answers this tiny frame instead of silently dropping.
# Why it is deliberately SHORTER than the blob header:
# BACK_COMPAT["busy_nack_short_frame"] in protocol_constants.py.
_BUSY_MAGIC = _pc.BUSY_MAGIC
_BUSY_HDR = _pc.BUSY_HDR


def _busy_frame(retry_hint_ms: int = 0) -> bytes:
    """The DPWB shed reply: explicit 'loaded, come back later'."""
    return _BUSY_HDR.pack(
        _BUSY_MAGIC, 1, min(max(int(retry_hint_ms), 0), 0xFFFF)
    )
# Default deadline floor for the payload read (bytes/s): the fetch
# budget grows at this rate per byte RECEIVED, so a healthy peer
# streaming a large replica is never killed by a fixed timeout_ms sized
# for the rendezvous (100 MB at 500 ms would otherwise fail FOREVER,
# silently disabling gossip), while a trickling peer — orders of
# magnitude below any real fabric — still gets dropped promptly.
# Derived from the config default (one source of truth); configurable
# per deployment via ``protocol.min_wire_mb_per_s`` (slow-WAN fabrics
# must lower it).
_MIN_WIRE_BANDWIDTH = DEFAULT_MIN_WIRE_MB_PER_S * 1e6


def _recv_exact(
    sock: socket.socket,
    n: int,
    deadline: Optional[float] = None,
    per_byte_s: float = 0.0,
    progress: Optional[list] = None,
    out: Optional[bytearray] = None,
) -> memoryview:
    """Read exactly ``n`` bytes (thin wrapper over
    :func:`dpwa_tpu.parallel.ingest.recv_exact_into` — the one buffered
    read loop both the gossip fetch and the state transfer share).

    With ``deadline`` (a ``time.monotonic`` instant) the WHOLE read must
    finish by that wall-clock point: the socket timeout is re-derived from
    the remaining budget before every ``recv``.  A plain ``settimeout``
    restarts on each successful recv, so a peer trickling one byte at a
    time could pin the caller indefinitely — precisely the slow peer the
    skip semantics exist for.

    ``per_byte_s`` grows the deadline with bytes ACTUALLY RECEIVED (not
    the advertised size): a healthy stream earns budget as it flows,
    while a peer that advertised a huge payload and then stalls is still
    dropped at the base deadline — trusting the advertisement up front
    would let a malicious 16 GiB header pin the fetch for minutes.

    ``progress`` (a single-cell ``[int]`` list) accumulates the bytes
    received across a SEQUENCE of reads, surviving the timeout this
    function raises — the caller's classifier uses it to tell a peer
    that streamed something and lapsed (``slow``) from one that never
    answered at all (``timeout``).

    ``out`` is an optional destination buffer (bytearray or writable
    memoryview, at least ``n`` bytes); the bytes land there via
    ``recv_into`` and the returned memoryview aliases it — the zero-copy
    ingest path (a fresh bytearray is allocated when omitted).  Returns
    a memoryview, which compares equal to ``bytes`` by content; callers
    needing an owning copy take ``bytes(view)`` explicitly."""
    return _ingest.recv_exact_into(sock, n, deadline, per_byte_s, progress, out)


def _frame_segments(
    vec: np.ndarray,
    clock: float,
    loss: float,
    code: Optional[int] = None,
    digest: Optional[bytes] = None,
    obs: Optional[bytes] = None,
) -> Tuple[bytes, ...]:
    """The wire frame as ordered segments ``(header, payload[, digest]
    [, obs])`` — the one definition of the wire format, shared by the
    Python and native Rx servers.  Serve paths send the tuple via
    scatter-gather (:func:`ingest.sendall_segments`) so the header is
    never concatenated onto a multi-MB payload; :func:`_frame` joins it
    for consumers that need one contiguous byte string.

    ``code`` overrides the dtype byte for structured payloads
    (``_INT8_CHUNKED``: ``vec`` is the already-encoded uint8 buffer).

    ``digest`` (a serialized membership digest) rides as an OPTIONAL
    trailing section AFTER the nbytes payload: the header's ``nbytes``
    still counts only the vector, so a pre-membership fetcher reads
    exactly header + payload and never sees the trailer, while a
    digest-aware fetcher attempts a tolerant trailing read — version-
    gated wire compatibility in both directions (docs/membership.md).

    ``obs`` (a serialized ``DPWT`` observability section: trace id +
    replica sketch, dpwa_tpu/obs/wire.py) rides the same way, AFTER the
    digest when both are present.  Ordering matters for back-compat:
    a digest-aware pre-obs fetcher reads the digest it wants, then its
    next read fails the DPWM magic check on the DPWT header and stops
    harmlessly; obs-aware fetchers dispatch trailers by magic
    (:func:`_read_trailers`) and handle every presence combination."""
    vec = np.ascontiguousarray(vec)
    if code is None:
        # Exact-dtype lookup first (covers bf16, whose custom numpy dtype
        # has no byte-order variants), then the byte-order-normalized
        # form, then an f32 fallback.
        code = _DTYPE_CODES.get(vec.dtype)
        if code is None:
            try:
                code = _DTYPE_CODES.get(
                    np.dtype(vec.dtype.newbyteorder("<"))
                )
            except (TypeError, ValueError):  # pragma: no cover
                code = None
        if code is None:
            vec = vec.astype("<f4")
            code = _DTYPE_CODES[np.dtype("<f4")]
    # The one deliberate copy on the publish path: the frame must
    # snapshot the replica — the training thread mutates ``vec`` right
    # after publish, and serving a live view would tear frames mid-send.
    data = vec.tobytes()  # dpwalint: ignore[zerocopy-tobytes] -- publish-time snapshot; serving a view of the live replica would tear frames
    header = _HDR.pack(_MAGIC, 1, code, float(clock), float(loss), len(data))
    if digest or obs:
        segs = [header, data]
        if digest:
            segs.append(digest)
        if obs:
            segs.append(obs)
        return tuple(segs)
    return (header, data)


def _frame(
    vec: np.ndarray,
    clock: float,
    loss: float,
    code: Optional[int] = None,
    digest: Optional[bytes] = None,
    obs: Optional[bytes] = None,
) -> bytes:
    """:func:`_frame_segments` joined into one contiguous byte string —
    for the native server's ``publish_framed``, the chaos mutators, and
    golden-frame tests."""
    return b"".join(_frame_segments(vec, clock, loss, code, digest, obs))


class PeerServer:
    """The Rx thread: serves this node's latest published blob.

    Mirrors the reference's always-on listener (SURVEY.md §3.3): the training
    thread and the Rx thread share only the publish buffer, guarded by a
    lock."""

    # Optional hook consulted by the relay-probe handler: a callable
    # (target_index) -> bool that returns True when this node's OWN link
    # to the target is blocked (the chaos harness wires it so injected
    # partitions constrain relays exactly like real ones).
    relay_guard = None

    # Optional serve-span hook (obs.trace): a callable
    # (trace_id, nbytes, dur_s) invoked after each served blob, wired by
    # the transport to Tracer.note_serve so the serving side of an
    # exchange lands in the cross-peer round trace.  The trace id is
    # stored WITH the payload under the publish lock, so a served frame
    # and the id reported for it can never come from different rounds.
    obs_serve_hook = None

    def __init__(
        self,
        host: str,
        port: int,
        flowctl: Optional[FlowctlConfig] = None,
    ):
        self._lock = threading.Lock()
        # Pre-framed (header, payload[, digest][, obs]) segments; served
        # via scatter-gather so publish never joins them into one blob.
        self._segments: Optional[Tuple[bytes, ...]] = None
        self._payload_nbytes = 0
        self._payload_trace_id: Optional[str] = None
        self._state: Optional[bytes] = None  # serialized bootstrap state
        self._state_gen = 0
        # Serving-side flow control (dpwa_tpu/flowctl/): connection cap,
        # per-remote token pacing, in-flight-bytes ceiling, slow-loris
        # eviction.  Defaults apply when no config is passed; admission
        # is skipped entirely when the block is disabled.
        self.flowctl = flowctl if flowctl is not None else FlowctlConfig()
        self.admission = (
            AdmissionController(self.flowctl)
            if self.flowctl.enabled
            else None
        )
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self.port = self._sock.getsockname()[1]  # resolves port=0 to real port
        self._sock.listen(16)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._serve, name=f"dpwa-rx:{self.port}", daemon=True
        )
        self._thread.start()

    def publish(
        self,
        vec: np.ndarray,
        clock: float,
        loss: float,
        code: Optional[int] = None,
        digest: Optional[bytes] = None,
        obs: Optional[bytes] = None,
        trace_id: Optional[str] = None,
    ) -> None:
        segments = _frame_segments(vec, clock, loss, code, digest, obs)
        with self._lock:
            self._segments = segments
            self._payload_nbytes = sum(len(s) for s in segments)
            self._payload_trace_id = trace_id

    @property
    def _payload(self) -> Optional[bytes]:
        """The published frame as one contiguous byte string — the
        pre-segment representation, kept for the chaos harness and
        tests.  Lock-free: a single attribute read of the segments tuple
        is atomic, and the tuple itself is immutable."""
        segs = self._segments
        return b"".join(segs) if segs is not None else None

    def publish_state(self, blob: bytes) -> None:
        """Expose a serialized train state for peer-assisted bootstrap.

        ``blob`` is whatever :mod:`dpwa_tpu.recovery.state_transfer`
        packed; the server is agnostic — it chunks bytes.  Each publish
        bumps the generation, so an in-flight transfer against the old
        blob restarts instead of splicing."""
        with self._lock:
            # dpwalint: ignore[zerocopy-tobytes] -- publish-time snapshot: served views must outlive the caller's buffer
            self._state = bytes(blob)
            self._state_gen = (self._state_gen + 1) & 0xFFFFFFFF

    def _serve(self) -> None:
        try:
            # close() may already have closed the listener before this
            # thread got scheduled; EBADF here is a clean shutdown, not an
            # error to surface.
            self._sock.settimeout(0.2)
        except OSError:
            return
        while not self._stop.is_set():
            try:
                conn, addr = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            host = addr[0] if addr else ""
            if self.admission is not None:
                ok, retry_ms = self.admission.admit(host)
                if not ok:
                    # Shed EXPLICITLY: the tiny DPWB frame tells a
                    # flowctl-aware fetcher "loaded, retry later" (low-
                    # weight busy outcome); an old fetcher sees EOF short
                    # of a full header and classifies its existing reset
                    # path.  Either way the accept loop stays free.
                    self._shed(conn, retry_ms)
                    continue
            worker = threading.Thread(
                target=self._conn_worker,
                args=(conn, host),
                name=f"dpwa-rx-conn:{self.port}",
                daemon=True,
            )
            worker.start()

    def _shed(self, conn: socket.socket, retry_ms: int) -> None:
        """Best-effort busy reply + close (never blocks the accept loop)."""
        try:
            conn.settimeout(0.5)
            conn.sendall(_busy_frame(retry_ms))
        except OSError:
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _conn_worker(self, conn: socket.socket, host: str) -> None:
        """One admitted connection, on its own thread: under admission
        the handler count is bounded by ``max_connections``, so thread-
        per-connection cannot run away — and a relay probe serving
        synchronously no longer pins every other fetcher behind it."""
        try:
            # Handler budget derived from the flowctl block (one source
            # of truth with the request-read eviction deadline) instead
            # of the old hard-coded 5.0 s.
            conn.settimeout(self.flowctl.request_timeout_ms / 1000.0)
            self._handle(conn)
        except OSError:
            pass
        finally:
            if self.admission is not None:
                self.admission.release(host)
            try:
                conn.close()
            except OSError:
                pass

    def _handle(self, conn: socket.socket) -> None:
        """Serve one accepted connection.  Split out of the accept loop
        so the chaos harness (health/chaos.py) can wrap per-connection
        behavior without duplicating the listener."""
        fc = self.flowctl
        deadline = per_byte = None
        if fc.enabled:
            # Slow-loris discipline on the REQUEST read: cumulative
            # deadline, extended per byte received at the minimum ingest
            # rate — a client trickling its request is evicted, not
            # waited on (the same _recv_exact mechanics the fetch side
            # uses against trickling servers).
            deadline = time.monotonic() + fc.request_timeout_ms / 1000.0
            per_byte = (
                1.0 / fc.min_ingest_bytes_per_s
                if fc.min_ingest_bytes_per_s > 0
                else 0.0
            )
        body = None
        try:
            req = _recv_exact(conn, len(_REQ), deadline, per_byte or 0.0)
            if req == _STATE_REQ:
                body = _recv_exact(
                    conn, _STATE_REQ_BODY.size, deadline, per_byte or 0.0
                )
        except socket.timeout:
            if self.admission is not None:
                self.admission.note_eviction()
            return
        if req == _STATE_REQ:
            offset, max_chunk = _STATE_REQ_BODY.unpack(body)
            self._handle_state(conn, offset, max_chunk)
            return
        if req == _RELAY_REQ:
            self._handle_relay(conn)
            return
        if req != _REQ:
            return
        self._serve_blob(conn)

    def _serve_blob(self, conn: socket.socket) -> None:
        """Send the published frame under the in-flight-bytes ceiling.

        Scatter-gather: the (header, payload, trailers) segments go out
        via one ``sendmsg`` instead of being concatenated first — the
        serve path never allocates payload-sized scratch."""
        with self._lock:
            segments = self._segments
            nbytes = self._payload_nbytes
            trace_id = self._payload_trace_id
        if segments is None:
            return
        adm = self.admission
        if adm is not None and not adm.reserve_bytes(nbytes):
            # Ceiling crossed: shed this send explicitly rather than
            # queue unbounded payload bytes behind slow readers.
            try:
                conn.sendall(_busy_frame(self.flowctl.busy_retry_ms))
            except OSError:
                pass
            return
        hook = self.obs_serve_hook
        t0 = time.monotonic() if hook is not None else 0.0
        try:
            _ingest.sendall_segments(conn, segments)
        finally:
            if adm is not None:
                adm.release_bytes(nbytes)
            if hook is not None and trace_id is not None:
                try:
                    hook(trace_id, nbytes, time.monotonic() - t0)
                except Exception:
                    pass  # observability must never break a serve

    def _handle_relay(self, conn: socket.socket) -> None:
        """Serve one relayed header probe: probe the requested target
        ourselves and report the CLASSIFIED outcome plus the target's
        publish clock.  The probe runs on this Rx thread with a clamped
        budget — relays are drawn from healthy peers and one header
        probe is the cheapest thing on this wire, so the serving stall
        is bounded and rare."""
        body = _recv_exact(conn, _RELAY_BODY.size)
        target, port, timeout_ms, hostlen = _RELAY_BODY.unpack(body)
        host = (
            str(_recv_exact(conn, hostlen), "ascii", "replace")
            if hostlen
            else "127.0.0.1"
        )
        timeout_ms = min(max(int(timeout_ms), 1), _MAX_RELAY_TIMEOUT_MS)
        guard = self.relay_guard
        if guard is not None and guard(int(target)):
            outcome, clock = Outcome.REFUSED, None
        else:
            outcome, clock = probe_header_classified(host, port, timeout_ms)
        conn.sendall(
            _RELAY_HDR.pack(
                _RELAY_MAGIC,
                1,
                _RELAY_OUTCOMES.index(outcome),
                float(clock) if clock is not None else -1.0,
            )
        )

    def _handle_state(
        self, conn: socket.socket, offset: int, max_chunk: int
    ) -> None:
        """Serve one STATE chunk at ``offset``.  No published state is a
        well-formed empty transfer (total = 0): the client reads it as
        'this donor has nothing for you' and tries the next candidate —
        distinct from a protocol failure, which would accrue suspicion
        against an innocent peer."""
        with self._lock:
            blob = self._state if self._state is not None else b""
            gen = self._state_gen
        total = len(blob)
        off = min(max(offset, 0), total)
        n = min(max(max_chunk, 0), total - off, _MAX_STATE_CHUNK)
        # A VIEW of the published blob, not a slice copy: ``blob`` is an
        # immutable bytes object and a re-publish replaces the object,
        # so the view stays valid for the duration of the send.
        chunk = memoryview(blob)[off : off + n]
        header = _STATE_HDR.pack(
            _STATE_MAGIC, 1, gen, total, off, len(chunk), zlib.crc32(chunk)
        )
        _ingest.sendall_segments(conn, (header, chunk))

    def close(self) -> None:
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass
        self._thread.join(timeout=2.0)


class NativePeerServer:
    """Rx server backed by the C++ serve loop (native/rx_server.cpp).

    Same protocol and publish semantics as :class:`PeerServer`; the serve
    thread is native, so fetches from peers cost this process zero GIL
    time — under free-running training the Python Rx thread otherwise
    competes with fwd/bwd for the interpreter."""

    def __init__(self, host: str, port: int):
        from dpwa_tpu import native

        self._srv = native.NativeRxServer(host, port)
        self.port = self._srv.port

    def publish(
        self,
        vec: np.ndarray,
        clock: float,
        loss: float,
        code: Optional[int] = None,
        digest: Optional[bytes] = None,
        obs: Optional[bytes] = None,
        trace_id: Optional[str] = None,
    ) -> None:
        # The native loop serves the framed bytes verbatim, so the
        # digest/obs trailers ride along without the C++ side knowing.
        # trace_id is accepted-and-ignored: serve-side spans need the
        # Python server's hook (the transport forces it when obs.trace).
        self._srv.publish_framed(_frame(vec, clock, loss, code, digest, obs))

    def publish_state(self, blob: bytes) -> None:
        raise RuntimeError(
            "the native Rx server only speaks the blob protocol; STATE "
            "serving needs the Python server (TcpTransport selects it "
            "automatically when recovery.enabled)"
        )

    def close(self) -> None:
        self._srv.close()


def make_peer_server(
    host: str, port: int, flowctl: Optional[FlowctlConfig] = None
):
    """Native Rx server when the toolchain allows, Python thread otherwise.

    ``DPWA_NATIVE_RX=0`` forces the Python server (debugging / parity
    tests).  ``flowctl`` configures the Python server's admission plane;
    the native C++ loop speaks only the blob protocol and ignores it
    (``TcpTransport`` forces the Python server when ``flowctl.enabled``
    so admission is actually in force)."""
    import os

    if os.environ.get("DPWA_NATIVE_RX", "1") != "0":
        try:
            return NativePeerServer(host, port)
        except (RuntimeError, OSError):
            pass  # no toolchain / bind raced: identical Python fallback
    return PeerServer(host, port, flowctl=flowctl)


def _recv_trailing(
    sock: socket.socket, n: int, deadline: float
) -> Optional[memoryview]:
    """Best-effort exact read for an OPTIONAL trailing section.

    Returns None — never raises — on timeout/EOF/reset: a peer that
    closed right after its payload simply has no trailer, which is the
    normal pre-membership wire and must not look like a failure."""
    try:
        return _recv_exact(sock, n, deadline)
    except (socket.timeout, ConnectionError, OSError):
        return None


def _read_digest_trailer(
    sock: socket.socket, budget_s: float = 0.25
) -> Optional[bytes]:
    """Read the optional membership-digest trailer after a payload.

    Two-phase tolerant read (fixed digest header, then the entry block
    the header's count implies); ANY malformation — missing bytes, bad
    magic, absurd count — yields None rather than an error, because an
    old-format peer legitimately serves no trailer.  The budget is small
    and fixed: the digest is ~11 B/peer and the peer has already proven
    responsive by streaming the whole payload."""
    from dpwa_tpu.membership.digest import (
        HEADER_SIZE,
        header_entries_nbytes,
    )

    deadline = time.monotonic() + budget_s
    head = _recv_trailing(sock, HEADER_SIZE, deadline)
    if head is None:
        return None
    nbytes = header_entries_nbytes(head)
    if nbytes is None:
        return None
    body = _recv_trailing(sock, nbytes, deadline)
    if body is None:
        return None
    # join, not +: _recv_trailing hands back memoryviews now, and the
    # digest contract returns owning bytes (tiny — ~11 B/peer).
    return b"".join((head, body))


def _read_trailers(
    sock: socket.socket,
    want_digest: bool,
    want_obs: bool,
    budget_s: float = 0.25,
) -> Tuple[Optional[bytes], Optional[bytes]]:
    """Magic-dispatched tolerant read of ALL optional trailing sections.

    A served frame may carry, after the payload: a membership digest
    (``DPWM``) and/or an observability section (``DPWT``), in that
    order.  Reading them naively in sequence breaks when the local node
    wants only one of them — e.g. membership off + obs on against a peer
    serving both would consume the digest header while looking for the
    obs magic and lose the section boundary.  So: read a 4-byte magic
    tolerantly, dispatch on it, repeat; stop on anything unrecognized.
    Sections the caller doesn't want are still consumed (the socket is
    about to close — the bytes are free) but returned as None.

    Returns ``(digest_bytes, obs_bytes)``; each None when absent,
    malformed, or unwanted.  Never raises."""
    from dpwa_tpu.membership.digest import (
        DIGEST_MAGIC,
        HEADER_SIZE,
        header_entries_nbytes,
    )
    from dpwa_tpu.obs.wire import (
        OBS_HEADER_SIZE,
        OBS_MAGIC,
        header_sketch_count,
        values_size,
    )

    deadline = time.monotonic() + budget_s
    digest = obs = None
    # Bounded dispatch: one section per known magic, tiny loop cap so a
    # hostile peer streaming valid-looking sections can't pin us here.
    for _ in range(4):
        magic = _recv_trailing(sock, 4, deadline)
        if magic is None:
            break
        if magic == DIGEST_MAGIC and digest is None:
            rest = _recv_trailing(sock, HEADER_SIZE - 4, deadline)
            if rest is None:
                break
            head = b"".join((magic, rest))
            nbytes = header_entries_nbytes(head)
            if nbytes is None:
                break
            body = _recv_trailing(sock, nbytes, deadline)
            if body is None:
                break
            digest = b"".join((head, body))
        elif magic == OBS_MAGIC and obs is None:
            rest = _recv_trailing(sock, OBS_HEADER_SIZE - 4, deadline)
            if rest is None:
                break
            head = b"".join((magic, rest))
            n = header_sketch_count(head)
            if n is None:
                break
            body = _recv_trailing(sock, values_size(n), deadline)
            if body is None:
                break
            obs = b"".join((head, body))
        else:
            break
    return (digest if want_digest else None, obs if want_obs else None)


def fetch_blob_full(
    host: str,
    port: int,
    timeout_ms: int,
    min_bandwidth_bps: float = _MIN_WIRE_BANDWIDTH,
    want_digest: bool = False,
    sock_box: Optional[list] = None,
    want_obs: bool = False,
    lease_box: Optional[list] = None,
) -> Tuple[
    Optional[Tuple[np.ndarray, float, float]], str, float, int,
    Optional[bytes], Optional[bytes],
]:
    """:func:`fetch_blob` plus the classified outcome the health
    subsystem feeds on, plus the optional trailing sections.

    Returns ``(result, outcome, latency_s, payload_bytes_received,
    digest, obs)`` where ``result`` is ``(vec, clock, loss)`` or None,
    ``digest`` is the raw membership-digest trailer bytes and ``obs``
    the raw DPWT observability trailer bytes (each only attempted when
    ``want_digest`` / ``want_obs`` and the payload fetch succeeded; None
    whenever the peer served no valid section) and ``outcome``
    is one of :class:`dpwa_tpu.health.detector.Outcome`:

    - ``refused`` — the connect itself failed (peer process gone);
    - ``timeout`` — the cumulative deadline expired with NOTHING received
      (connect, request, or a header that never started);
    - ``slow`` — the cumulative deadline expired with bytes already
      flowing: the peer is alive and serving, just not fast enough for
      the budget (low detector weight — soft-degrades, never
      quarantines);
    - ``busy`` — the peer answered the tiny ``DPWB`` shed frame: loaded
      but honest (same low weight as ``slow``);
    - ``short_read`` — the peer closed or reset mid-frame;
    - ``corrupt`` — bad magic/version/dtype, oversize advertisement, or
      an int8 payload that failed to decode;
    - ``success`` — a full, valid frame.

    ``sock_box`` (a plain list) receives the connected socket as soon as
    it exists: a hedging caller running this fetch on a thread closes it
    to cancel the losing leg promptly instead of waiting out its
    deadline.

    ``lease_box`` (a plain list) opts into explicit receive-buffer
    ownership: the payload's ring :class:`~dpwa_tpu.parallel.ingest
    .Lease` is appended on success and the CALLER must ``release()`` it
    once every view of the decoded vector is dead — the allocation-flat
    steady state (the tracemalloc tier-1 test drives this).
    Without it, leases whose decode produced escaping views (dense /
    top-k / shard) are detached — correct but unpooled — and fully
    consumed payloads (int8) are released here.

    ``timeout_ms`` is a CUMULATIVE wall-clock budget enforced via a
    monotonic deadline threaded through :func:`_recv_exact` — not a
    per-recv timer a trickling peer could keep resetting.  It covers
    connect + request + header outright; the payload read then earns
    ``1 / min_bandwidth_bps`` extra seconds per byte received (default:
    the module floor derived from ``DEFAULT_MIN_WIRE_MB_PER_S``; the
    transport passes ``protocol.min_wire_mb_per_s``), so the budget
    scales with the replica actually flowing instead of rejecting every
    blob larger than bandwidth × timeout_ms — and a peer that merely
    ADVERTISES a huge payload earns nothing."""
    t0 = time.monotonic()
    deadline = t0 + timeout_ms / 1000.0
    nbytes_rx = 0
    # Total bytes received across header + payload, surviving a raised
    # timeout: >0 at deadline lapse means the peer was STREAMING, which
    # classifies as ``slow`` (soft evidence) rather than ``timeout``.
    rx = [0]
    # The payload's ring lease, once taken: every non-success exit must
    # release it back to the ring (the except arms below do).
    lease = None
    try:
        sock = socket.create_connection(
            (host, port), timeout=timeout_ms / 1000.0
        )
    except socket.timeout:
        return None, Outcome.TIMEOUT, time.monotonic() - t0, 0, None, None
    except (ConnectionError, OSError):
        # Refused, unreachable, reset during handshake: no peer process
        # is answering on that port.
        return None, Outcome.REFUSED, time.monotonic() - t0, 0, None, None
    if sock_box is not None:
        sock_box.append(sock)
    try:
        with sock:
            # The request send draws from the SAME cumulative budget as
            # the reads: create_connection leaves only the connect
            # timeout on the socket, which restarts the clock — a peer
            # that accepts but never reads (full Rx backlog) would get a
            # fresh window for sendall on top of a spent deadline.
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise socket.timeout(
                    "cumulative fetch deadline exceeded before request"
                )
            sock.settimeout(remaining)
            sock.sendall(_REQ)
            # Magic peek: 4 bytes decide DPWB (busy shed) vs DPWA (blob
            # header).  An old server never sends DPWB, so the peek is
            # just the header's first read split in two — both halves
            # land in ONE scratch buffer so the header is never
            # reassembled by concatenation.
            hdr_buf = bytearray(max(_HDR.size, _BUSY_HDR.size))
            peek = _recv_exact(sock, 4, deadline, progress=rx, out=hdr_buf)
            if peek == _BUSY_MAGIC:
                _recv_exact(
                    sock, _BUSY_HDR.size - 4, deadline, progress=rx,
                    out=memoryview(hdr_buf)[4:],
                )
                _m, bversion, _retry_ms = _BUSY_HDR.unpack_from(hdr_buf, 0)
                if bversion != 1:
                    return (
                        None, Outcome.CORRUPT, time.monotonic() - t0, 0,
                        None, None,
                    )
                return None, Outcome.BUSY, time.monotonic() - t0, 0, None, None
            _recv_exact(
                sock, _HDR.size - 4, deadline, progress=rx,
                out=memoryview(hdr_buf)[4:],
            )
            magic, version, code, clock, loss, nbytes = _HDR.unpack_from(
                hdr_buf, 0
            )
            if magic != _MAGIC or version != 1 or (
                code not in _DTYPES and code not in _PAYLOAD_CODES
            ):
                return (
                    None, Outcome.CORRUPT, time.monotonic() - t0, 0, None,
                    None,
                )
            if nbytes > _MAX_BLOB:
                return (
                    None, Outcome.CORRUPT, time.monotonic() - t0, 0, None,
                    None,
                )
            # Payload lands straight in a ring buffer via recv_into —
            # no chunk-grow bytearray, no final bytes() copy.  For a
            # large advertisement the full-size lease is deferred behind
            # a small probe read: the old grow-by-chunk loop only ever
            # allocated in proportion to bytes actually RECEIVED, so a
            # peer that advertises gigabytes and hangs up must not cost
            # a huge upfront allocation here either.
            per_byte = 1.0 / min_bandwidth_bps
            pre = 0
            if nbytes > _PROBE_THRESHOLD:
                lease = _ingest.default_ring().lease(_PROBE_BYTES)
                _recv_exact(
                    sock, _PROBE_BYTES, deadline, per_byte,
                    progress=rx, out=lease.view,
                )
                try:
                    full = _ingest.default_ring().lease(nbytes)
                except (MemoryError, OverflowError):
                    # Advertised size within _MAX_BLOB but beyond this
                    # host: a frame this process can never hold is
                    # malformed from its point of view.
                    lease.release()
                    lease = None
                    return (
                        None, Outcome.CORRUPT,
                        time.monotonic() - t0, rx[0], None, None,
                    )
                full.view[:_PROBE_BYTES] = lease.view
                lease.release()
                lease = full
                pre = _PROBE_BYTES
            else:
                lease = _ingest.default_ring().lease(nbytes)
            # The probe already earned its per-byte budget: shift the
            # deadline so the cumulative contract spans both reads.
            data = _recv_exact(
                sock, nbytes - pre, deadline + pre * per_byte,
                per_byte, progress=rx, out=lease.view[pre:],
            )
            data = lease.view
            nbytes_rx = len(data)
            # Payload-sized copies this decode performs (0 = the decoded
            # vector is a view into the ring buffer); feeds the
            # copies_per_frame health column.
            copies = 0
            escapes = True
            if code == _TOPK_DELTA:
                # Sparse top-k frame: validated and decoded here (the
                # full malformed-input taxonomy — truncated index list,
                # k > n, unsorted/duplicate indices, lying value-block
                # length — classifies as CORRUPT, never crashes), but
                # NOT densified: only the transport holds the local
                # replica the indices splice into, so the TopkPayload
                # object rides the vector slot up to TcpTransport.fetch.
                from dpwa_tpu.ops.quantize import decode_topk_payload

                try:
                    vec = decode_topk_payload(
                        np.frombuffer(data, dtype=np.uint8)
                    )
                except ValueError:
                    lease.release()
                    return (
                        None, Outcome.CORRUPT,
                        time.monotonic() - t0, nbytes_rx, None, None,
                    )
                # f32 value blocks decode as views into the buffer; an
                # int8 block materializes fresh f32 values (one copy).
                copies = 0 if vec.value_dtype == "f32" else 1
            elif code == _SHARD:
                # Sharded frame: one contiguous slice of the replica in
                # any inner encoding.  Decoded and validated here (lying
                # k, out-of-range shard_idx, truncated preamble, inner
                # bodies that fail their own codec — all CORRUPT, never
                # a crash) but NOT densified: like top-k, only the
                # transport holds the replica the slice merges into, so
                # the ShardPayload object rides the vector slot.
                from dpwa_tpu.ops.shard import decode_shard_payload

                try:
                    vec = decode_shard_payload(
                        np.frombuffer(data, dtype=np.uint8)
                    )
                except ValueError:
                    lease.release()
                    return (
                        None, Outcome.CORRUPT,
                        time.monotonic() - t0, nbytes_rx, None, None,
                    )
                # Dense-f32 inner slices (and top-k f32 value blocks)
                # stay views; bf16/int8 inners materialize f32.
                if vec.inner_code == _pc.PAYLOAD_F32:
                    copies = 0
                elif vec.inner_code == _TOPK_DELTA:
                    copies = 0 if vec.inner.value_dtype == "f32" else 1
                else:
                    copies = 1
            elif code == _INT8_CHUNKED:
                # Receiver-side dequantize: the wire moved 1 byte/elem
                # (+ scales); the merge math runs on the f32 decode.
                from dpwa_tpu.ops.quantize import decode_int8_payload

                try:
                    vec = decode_int8_payload(
                        np.frombuffer(data, dtype=np.uint8)
                    )
                except ValueError:
                    # malformed payload == skipped fetch
                    lease.release()
                    return (
                        None, Outcome.CORRUPT,
                        time.monotonic() - t0, nbytes_rx, None, None,
                    )
                # Dequantize materialized a fresh f32 vector: the wire
                # bytes are fully consumed, nothing views the buffer.
                copies = 1
                escapes = False
            else:
                try:
                    # A VIEW over the ring buffer, not .copy(): the
                    # lease below keeps the bytes alive for exactly as
                    # long as the vector does.
                    vec = np.frombuffer(data, dtype=_DTYPES[code])
                except ValueError:
                    # Payload length not a multiple of the advertised
                    # dtype's itemsize: malformed frame.
                    lease.release()
                    return (
                        None, Outcome.CORRUPT,
                        time.monotonic() - t0, nbytes_rx, None, None,
                    )
                # f32 merges straight off the view; bf16/f64/u16 pay
                # their one upcast copy downstream in _weigh_remote.
                copies = 0 if _DTYPES[code] == np.dtype("<f4") else 1
            # Optional trailing sections (epidemic-membership digest,
            # DPWT observability): attempted only after a fully valid
            # payload (a frame that failed above carries no trustworthy
            # trailer), tolerant of their absence, dispatched by magic
            # so every presence combination parses.
            if want_digest or want_obs:
                digest, obs = _read_trailers(sock, want_digest, want_obs)
            else:
                digest = obs = None
            # Buffer ownership handoff (docs/transport.md): the caller
            # takes the lease explicitly (lease_box), or the views keep
            # the escaped buffer alive, or — payload fully consumed —
            # the buffer goes straight back to the ring.  Dense frames
            # escape as ONE ndarray whose .base chain owns every derived
            # view, so their lease is *recycled* (pooled again when the
            # vector dies) instead of detached — otherwise every frame
            # in the small-frame regime (LoRA adapters) costs a fresh
            # allocation and the ring's hit rate pins at zero.  Top-k /
            # shard payload objects stay detached: their member views
            # can be extracted and outlive the payload wrapper.
            if lease_box is not None:
                lease_box.append(lease)
            elif not escapes:
                lease.release()
            elif code in (_TOPK_DELTA, _SHARD):
                lease.detach()
            else:
                lease.recycle(vec)
            lease = None
            _ingest.note_rx_frame(copies)
            return (
                (vec, clock, loss), Outcome.SUCCESS,
                time.monotonic() - t0, nbytes_rx, digest, obs,
            )
    except socket.timeout:
        # Bytes flowed and the budget still lapsed: a live-but-slow peer
        # (trickle, overload) — soft evidence, not a death mark.
        if lease is not None:
            lease.release()
        outcome = Outcome.SLOW if rx[0] > 0 else Outcome.TIMEOUT
        return None, outcome, time.monotonic() - t0, nbytes_rx, None, None
    except (ConnectionError, OSError):
        # Accepted, then closed/reset mid-frame: the peer process is
        # alive enough to accept but served a broken stream.
        if lease is not None:
            lease.release()
        return (
            None, Outcome.SHORT_READ, time.monotonic() - t0, nbytes_rx, None,
            None,
        )


def fetch_blob_ex(
    host: str,
    port: int,
    timeout_ms: int,
    min_bandwidth_bps: float = _MIN_WIRE_BANDWIDTH,
) -> Tuple[
    Optional[Tuple[np.ndarray, float, float]], str, float, int
]:
    """:func:`fetch_blob_full` without the trailing sections — the
    4-tuple ``(result, outcome, latency_s, nbytes_rx)`` shape the
    health subsystem and existing callers consume."""
    return fetch_blob_full(host, port, timeout_ms, min_bandwidth_bps)[:4]


def fetch_blob(
    host: str,
    port: int,
    timeout_ms: int,
    min_bandwidth_bps: float = _MIN_WIRE_BANDWIDTH,
) -> Optional[Tuple[np.ndarray, float, float]]:
    """Connect to a peer's Rx thread and pull its latest blob.

    Returns None on timeout / refused connection / malformed reply — the
    caller skips the merge and keeps training, like the reference.  Thin
    wrapper over :func:`fetch_blob_ex`, which additionally classifies
    the failure for the health subsystem; see it for deadline
    semantics."""
    return fetch_blob_ex(host, port, timeout_ms, min_bandwidth_bps)[0]


def fetch_state_chunk(
    host: str,
    port: int,
    offset: int,
    max_chunk: int,
    timeout_ms: int,
    min_bandwidth_bps: float = _MIN_WIRE_BANDWIDTH,
    out: Optional[memoryview] = None,
) -> Tuple[Optional[Tuple[memoryview, int, int]], str, float, int]:
    """Fetch one STATE chunk: ``(result, outcome, latency_s, nbytes_rx)``
    where ``result`` is ``(chunk_view, total_len, generation)`` or None.

    Same cumulative-deadline discipline as :func:`fetch_blob_ex`: the
    budget covers connect + request + header outright and the chunk read
    earns per-byte extension.  A CRC mismatch or malformed header is
    ``corrupt``; the caller (:func:`fetch_state`) decides whether to
    resume, restart, or give up.

    ``out`` (a writable memoryview) receives the chunk bytes in place —
    :func:`fetch_state` passes a window of its preassembled blob so
    chunks land at their final offset with no accumulation copy.  A
    server-advertised ``chunk_len`` that would overflow ``out`` is
    ``corrupt`` (the blob shrank or the donor is lying).  The returned
    chunk is a memoryview either way; it compares equal to ``bytes``."""
    t0 = time.monotonic()
    deadline = t0 + timeout_ms / 1000.0
    nbytes_rx = 0
    try:
        sock = socket.create_connection(
            (host, port), timeout=timeout_ms / 1000.0
        )
    except socket.timeout:
        return None, Outcome.TIMEOUT, time.monotonic() - t0, 0
    except (ConnectionError, OSError):
        return None, Outcome.REFUSED, time.monotonic() - t0, 0
    try:
        with sock:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise socket.timeout(
                    "cumulative state-fetch deadline exceeded before request"
                )
            sock.settimeout(remaining)
            sock.sendall(
                _STATE_REQ + _STATE_REQ_BODY.pack(offset, max_chunk)
            )
            raw = _recv_exact(sock, _STATE_HDR.size, deadline)
            magic, version, gen, total, off, chunk_len, crc = (
                _STATE_HDR.unpack(raw)
            )
            if (
                magic != _STATE_MAGIC
                or version != 1
                or total > _MAX_BLOB
                or chunk_len > max(total - off, 0)
                or (out is not None and chunk_len > len(out))
            ):
                return None, Outcome.CORRUPT, time.monotonic() - t0, 0
            data = _recv_exact(
                sock, chunk_len, deadline, 1.0 / min_bandwidth_bps,
                out=out,
            )
            nbytes_rx = len(data)
            if zlib.crc32(data) != crc or off != min(max(offset, 0), total):
                # A clamped offset means the blob shrank under us (the
                # donor re-published): same remedy as a bad chunk —
                # the transfer-level loop restarts.
                return None, Outcome.CORRUPT, time.monotonic() - t0, nbytes_rx
            return (
                (data, total, gen), Outcome.SUCCESS,
                time.monotonic() - t0, nbytes_rx,
            )
    except socket.timeout:
        return None, Outcome.TIMEOUT, time.monotonic() - t0, nbytes_rx
    except (ConnectionError, OSError):
        return None, Outcome.SHORT_READ, time.monotonic() - t0, nbytes_rx


def fetch_state(
    host: str,
    port: int,
    timeout_ms: int,
    chunk_bytes: int = 1 << 20,
    max_retries: int = 8,
    min_bandwidth_bps: float = _MIN_WIRE_BANDWIDTH,
) -> Tuple[Optional[bytes], str, float, int]:
    """Full resumable STATE transfer from a donor peer.

    Loops :func:`fetch_state_chunk` from offset 0, each chunk on a fresh
    one-shot connection (a short read or timeout resumes at the last
    acknowledged offset — bytes already banked are never refetched);
    ``max_retries`` bounds the total number of failed chunk attempts
    across the transfer.  A generation change or corrupt chunk restarts
    the transfer from zero (also charged as a retry).  Returns
    ``(blob | None, outcome, latency_s, nbytes_received)`` — an empty
    blob (donor has no published state) comes back as ``(b"", success)``
    for the caller to interpret; ``outcome`` on failure is the LAST
    chunk's classification."""
    t0 = time.monotonic()
    # Chunks land DIRECTLY at their final offset: the first successful
    # chunk learns ``total`` and sizes the blob once; every later chunk
    # recv_into's a window of it — no chunk-grow accumulation buffer,
    # no per-chunk splice copy (the tcp.py:1021 twin of the old
    # _recv_n loop, now shared via ingest.recv_exact_into).
    blob: Optional[bytearray] = None
    filled = 0
    total: Optional[int] = None
    gen: Optional[int] = None
    retries = 0
    nbytes_rx = 0
    while True:
        window = (
            memoryview(blob)[filled:] if blob is not None else None
        )
        got, outcome, _lat, nrx = fetch_state_chunk(
            host, port, filled, chunk_bytes, timeout_ms,
            min_bandwidth_bps, out=window,
        )
        nbytes_rx += nrx
        if got is None:
            # A refused connect means the donor process is gone — no
            # point burning the remaining retries against it.
            if outcome == Outcome.REFUSED or retries >= max_retries:
                return None, outcome, time.monotonic() - t0, nbytes_rx
            retries += 1
            if outcome == Outcome.CORRUPT:
                blob, filled = None, 0
                total = gen = None
            continue
        data, tot, g = got
        if gen is not None and (g != gen or tot != total):
            # Donor re-published mid-transfer: splicing chunks from two
            # different blobs would hand the bootstrap a frankenstate.
            if retries >= max_retries:
                return None, Outcome.CORRUPT, time.monotonic() - t0, nbytes_rx
            retries += 1
            blob, filled = None, 0
            total = gen = None
            continue
        gen, total = g, tot
        if blob is None:
            # First chunk of a (re)started transfer: size the blob from
            # the donor's advertisement and bank what just arrived.
            blob = bytearray(total)
            blob[: len(data)] = data
            filled = len(data)
        else:
            # ``data`` IS blob[filled:filled+len] (recv_into'd there).
            filled += len(data)
        if filled >= total:
            # bytes() here is the public immutable-contract copy, not a
            # frame-path one — bootstrap runs once per restart.
            return (
                bytes(memoryview(blob)[:total]), Outcome.SUCCESS,  # dpwalint: ignore[zerocopy-tobytes] -- one-shot bootstrap transfer returns owning bytes by contract
                time.monotonic() - t0, nbytes_rx,
            )
        if not len(data):
            # Zero-byte chunk while bytes remain: malformed server.
            if retries >= max_retries:
                return None, Outcome.CORRUPT, time.monotonic() - t0, nbytes_rx
            retries += 1


def probe_header_classified(
    host: str, port: int, timeout_ms: int = 100
) -> Tuple[str, Optional[float]]:
    """Header-only liveness probe with the CLASSIFIED outcome.

    Same wire exchange as :func:`probe_header` but the failure mode is
    reported as a :class:`~dpwa_tpu.health.detector.Outcome` string —
    the membership layer treats "nothing listening" (``refused``) very
    differently from "listening but serving garbage" (``corrupt``), and
    relays forward exactly this classification to the asking node."""
    deadline = time.monotonic() + timeout_ms / 1000.0
    try:
        sock = socket.create_connection(
            (host, port), timeout=timeout_ms / 1000.0
        )
    except socket.timeout:
        return Outcome.TIMEOUT, None
    except (ConnectionError, OSError):
        return Outcome.REFUSED, None
    try:
        with sock:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return Outcome.TIMEOUT, None
            sock.settimeout(remaining)
            sock.sendall(_REQ)
            hdr_buf = bytearray(max(_HDR.size, _BUSY_HDR.size))
            peek = _recv_exact(sock, 4, deadline, out=hdr_buf)
            if peek == _BUSY_MAGIC:
                # A shedding server answers probes with DPWB too: the
                # peer is ALIVE but loaded — the caller records the
                # low-weight busy outcome, never a hard failure.
                _recv_exact(
                    sock, _BUSY_HDR.size - 4, deadline,
                    out=memoryview(hdr_buf)[4:],
                )
                _m, bversion, _retry = _BUSY_HDR.unpack_from(hdr_buf, 0)
                if bversion != 1:
                    return Outcome.CORRUPT, None
                return Outcome.BUSY, None
            _recv_exact(
                sock, _HDR.size - 4, deadline, out=memoryview(hdr_buf)[4:]
            )
            magic, version, code, clock, _loss, nbytes = _HDR.unpack_from(
                hdr_buf, 0
            )
            if (
                magic != _MAGIC
                or version != 1
                or (code not in _DTYPES and code not in _PAYLOAD_CODES)
                or nbytes > _MAX_BLOB
            ):
                return Outcome.CORRUPT, None
            return Outcome.SUCCESS, float(clock)
    except socket.timeout:
        return Outcome.TIMEOUT, None
    except (ConnectionError, OSError):
        return Outcome.SHORT_READ, None


def probe_header_ex(
    host: str, port: int, timeout_ms: int = 100
) -> Tuple[bool, Optional[float]]:
    """:func:`probe_header` plus the probed frame's publish clock.

    The clock rides the header for free, and re-admission wants it: a
    readmitted peer whose clock is far AHEAD of ours means we are the
    stale replica (we were partitioned while it kept training) — the
    freshness check behind ``recovery.max_clock_lag``.  Thin wrapper
    over :func:`probe_header_classified`, which keeps the failure
    taxonomy."""
    outcome, clock = probe_header_classified(host, port, timeout_ms)
    return outcome == Outcome.SUCCESS, clock


def relay_probe(
    relay_host: str,
    relay_port: int,
    target_index: int,
    target_host: str,
    target_port: int,
    probe_timeout_ms: int,
    timeout_ms: int,
) -> Tuple[str, Optional[str], Optional[float]]:
    """Ask a relay peer to header-probe ``target`` on our behalf.

    The SWIM indirect-probe leg: returns ``(relay_outcome,
    probe_outcome, clock)`` where ``relay_outcome`` classifies OUR
    connection to the relay (it feeds the relay's own health record),
    ``probe_outcome`` is the relay's classified
    :func:`probe_header_classified` result against the target (None
    whenever the relay leg itself failed), and ``clock`` is the
    target's publish clock as the relay saw it (None when unknown).

    ``timeout_ms`` must comfortably exceed ``probe_timeout_ms``: the
    relay performs its probe synchronously before answering."""
    deadline = time.monotonic() + timeout_ms / 1000.0
    try:
        sock = socket.create_connection(
            (relay_host, relay_port), timeout=timeout_ms / 1000.0
        )
    except socket.timeout:
        return Outcome.TIMEOUT, None, None
    except (ConnectionError, OSError):
        return Outcome.REFUSED, None, None
    try:
        with sock:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return Outcome.TIMEOUT, None, None
            sock.settimeout(remaining)
            host_b = target_host.encode("ascii", "replace")[:255]
            sock.sendall(
                _RELAY_REQ
                + _RELAY_BODY.pack(
                    target_index & 0xFFFF,
                    target_port & 0xFFFF,
                    int(probe_timeout_ms) & 0xFFFFFFFF,
                    len(host_b),
                )
                + host_b
            )
            raw = _recv_exact(sock, _RELAY_HDR.size, deadline)
            magic, version, code, clock = _RELAY_HDR.unpack(raw)
            if (
                magic != _RELAY_MAGIC
                or version != 1
                or code >= len(_RELAY_OUTCOMES)
            ):
                return Outcome.CORRUPT, None, None
            return (
                Outcome.SUCCESS,
                _RELAY_OUTCOMES[code],
                float(clock) if clock >= 0 else None,
            )
    except socket.timeout:
        return Outcome.TIMEOUT, None, None
    except (ConnectionError, OSError):
        return Outcome.SHORT_READ, None, None


def probe_header(host: str, port: int, timeout_ms: int = 100) -> bool:
    """Cheap liveness probe: connect, request, validate the HEADER only.

    The re-admission check for a quarantined peer — it answers "is a
    live dpwa Rx serving a well-formed frame there?" without pulling the
    payload (a full replica would cost the quarantined-peer path the
    very bandwidth quarantine exists to save).  The connection is
    abandoned after the header; the Rx side's sendall into a closed
    socket is its normal ``OSError -> close`` path."""
    return probe_header_ex(host, port, timeout_ms)[0]


def _host_merge(
    vec: np.ndarray, remote_vec: np.ndarray, alpha: float
) -> np.ndarray:
    """Host-side ``(1-α)·vec + α·remote`` — native single-pass axpy on
    the f32 fast path (numpy takes three passes + temps)."""
    if vec.dtype == np.float32 and remote_vec.dtype == np.float32:
        return native.merge_out(
            np.ascontiguousarray(vec),
            np.ascontiguousarray(remote_vec),
            alpha,
        )
    return (
        (1.0 - alpha) * vec.astype(np.float32)
        + alpha * remote_vec.astype(np.float32)
    ).astype(vec.dtype)


class _OverlappedExchange:
    """In-flight overlapped gossip round: the fetch runs on a daemon
    thread while the owner computes its local step.

    ``finish(pre_vec, update)`` joins the fetch and returns
    ``(merged_plus_update, alpha, partner)`` where
    ``merged_plus_update = (1-α)·pre + α·remote + update`` — identical
    algebra to the SPMD ``overlap=True`` step (merge the PRE-update
    replicas, land the local update on the merged result).  A skipped
    round (self-pair, masked, fetch timeout) returns
    ``pre_vec + update`` with α = 0."""

    def __init__(
        self, transport: "TcpTransport", clock, loss, step,
        expected_nbytes: int = 0,
    ):
        self._t = transport
        self._clock, self._loss = clock, loss
        self._step = step
        # Gossip replicas are symmetric: the partner's payload is the
        # same size (in WIRE bytes) as what we just published.  Sizes
        # the join backstop the same way fetch_blob's deadline scales.
        self._expected_nbytes = expected_nbytes
        self.sched_partner, self.partner, self.remapped = (
            transport._resolve_partner(step)
        )
        # Participation is gated on the ORIGINAL schedule pairing (same
        # threefry draw as the ICI path); a health remap changes only
        # WHO gets fetched, never WHETHER this round merges.
        self._participates = (
            self.partner != transport.me
            and transport.schedule.participates(step, transport.me)
        )
        # dpwalint: double_buffered(_got) -- handoff by join ordering: the fetch thread is the only writer, and finish() joins it before reading
        self._got = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        if not self._participates:
            return

        def _fetch():
            self._got = self._t.fetch(self.partner, step=self._step)

        self._thread = threading.Thread(target=_fetch, daemon=True)
        self._thread.start()

    def finish(
        self, pre_vec: np.ndarray, update: Optional[np.ndarray] = None
    ) -> Tuple[np.ndarray, float, int]:
        if self._thread is not None:
            # The fetch itself is bounded by timeout_ms plus the
            # per-byte-received extension (fetch_blob's scaled deadline);
            # the join backstop must allow the same worst case — a fixed
            # 2.5 s join would abandon large-replica fetches the deadline
            # deliberately tolerates, silently skipping every merge.
            # ``_expected_nbytes`` is the WIRE size of the partner frame
            # (int8/bf16-aware: the deadline earns budget only for bytes
            # actually on the wire, so sizing from the f32 replica would
            # inflate the backstop 4x under int8), and timeout_ms appears
            # exactly once: the deadline already folds it in, so the
            # slack term is a fixed 1 s for thread scheduling, not a
            # second copy of the timeout.  A timed-out join skips the
            # round like any other failed fetch.  With flowctl enabled
            # the fetch may run TWO sequential budgets (primary deadline
            # up to flowctl.max_ms, then a hedge leg with its own), so
            # the backstop doubles the larger of the two ceilings.
            fc = self._t.config.flowctl
            base_s = self._t.config.protocol.timeout_ms / 1000.0
            if fc.enabled:
                base_s = 2.0 * max(base_s, fc.max_ms / 1000.0)
            self._thread.join(
                timeout=1.0
                + base_s
                + self._expected_nbytes
                / (self._t.config.protocol.min_wire_mb_per_s * 1e6)
            )
        got = self._got if self._thread is not None else None
        # The overlapped path never runs _round, so the membership round
        # boundary lands here — after the fetch (and its digest merge)
        # has been joined.
        self._t._membership_end_round(self._step)
        if got is None:
            merged, alpha = pre_vec, 0.0
        else:
            remote_vec, alpha = self._t._weigh_remote(
                got, self._clock, self._loss
            )
            merged = self._t._merge_remote(pre_vec, remote_vec, alpha)
        if update is not None:
            merged = merged + update
        return merged, alpha, self.partner


# Device-side merging lives in dpwa_tpu/device/ (docs/device.md): the
# single-slot jitted lerp that used to sit here (_LERP_CACHE) became the
# engine's keyed LRU jit cache, and the per-frame jnp.asarray upload
# became the zero-copy handoff.  The import stays deferred to the device
# substrates so this module remains importable (and its CPU exchange
# usable) without touching a JAX backend.
def _merge_engine():
    from dpwa_tpu.device import default_engine

    return default_engine()


class TcpTransport:
    """Per-process gossip transport with the reference's update semantics.

    One instance per worker process; ``name`` selects this node's entry in
    the shared YAML ``nodes:`` list (exactly the reference's CLI contract,
    SURVEY.md §3.1)."""

    def __init__(self, config: DpwaConfig, name: str):
        self.config = config
        self.me = config.node_index(name)
        # Hierarchical gossip (docs/hierarchy.md): a ``topology:`` block
        # swaps in the two-level island×wide-area pool — intra-island
        # slots everyone works, wide-area slots only the elected island
        # leaders work (non-leaders self-pair, and a self-pair never
        # fetches).  No block -> the flat pool, bit-identical to before
        # the topology grammar existed.  Deferred import: hier pulls in
        # the election machinery only topology users need.
        self.topology = None
        if config.topology.enabled:
            from dpwa_tpu.hier.schedule import build_hier_schedule
            from dpwa_tpu.hier.topology import Topology

            self.topology = Topology.from_config(config)
            self.schedule: Schedule = build_hier_schedule(config)
        else:
            self.schedule = build_schedule(config)
        # Content-trust plane (dpwa_tpu/trust/): screens every decoded
        # REMOTE payload and damps/rejects the merge.  Deferred import —
        # trust pulls in the screening jit machinery this module must
        # not require at import time.
        self.trust = None
        if config.trust.enabled:
            from dpwa_tpu.trust.manager import TrustManager

            self.trust = TrustManager(
                len(config.nodes), self.me, config.trust
            )
        # The CURRENT exchange's trust damping, read by the interpolation
        # through a zero-arg callable: fetch() writes it (fetch thread in
        # the overlapped path), _weigh_remote reads it AFTER the fetch is
        # joined, so the handoff is ordered.  1.0 (fully trusted) is a
        # bit-exact no-op on alpha.
        # dpwalint: double_buffered(_pending_trust_scale) -- written by the fetch leg before finish() joins it; _weigh_remote reads strictly after the join
        self._pending_trust_scale = 1.0
        # Local replica view for screening + the zero-energy guard:
        # stashed by publish() (publish always precedes fetch in a round).
        # dpwalint: double_buffered(_local_vec) -- swap-on-publish: _publish rebinds a fresh array, readers see the old or new ref, never a torn write; straddling prefetches re-screen via _last_clock
        self._local_vec: Optional[np.ndarray] = None
        # dpwalint: double_buffered(_local_norm) -- rebound alongside _local_vec under the same swap-on-publish discipline
        self._local_norm: Optional[float] = None
        self.interp = make_interpolation(
            config.interpolation,
            max_abs_loss=(
                config.recovery.rescue_bound() if config.recovery.enabled else None
            ),
            trust_scale=(
                self._trust_alpha_scale if self.trust is not None else None
            ),
        )
        self._wire_bf16 = config.protocol.wire_dtype == "bf16"
        self._wire_int8 = config.protocol.wire_dtype == "int8"
        if self._wire_bf16 and ml_dtypes is None:  # pragma: no cover
            raise RuntimeError("wire_dtype bf16 requires ml_dtypes")
        # Top-k delta codec (protocol.wire_codec: topk): the published
        # frame carries only the k largest-|residual| coordinates; the
        # encoder's error-feedback base guarantees dropped coordinates
        # accumulate and ship later.  Takes precedence over wire_dtype
        # for the gossip frame (the value-block precision is
        # protocol.topk_values); STATE/relay verbs are unaffected.
        self._wire_topk = config.protocol.wire_codec == "topk"
        self._topk_encoder = None
        if self._wire_topk:
            from dpwa_tpu.ops.quantize import TopkEncoder

            self._topk_encoder = TopkEncoder(
                config.protocol.topk_fraction,
                config.protocol.topk_values,
            )
        # Sharded gossip (shard.k > 1, docs/wire.md): each publish ships
        # ONE contiguous shard of the replica — the one the per-epoch
        # shard_draw permutation assigns to the publish clock — wrapped
        # in the code-6 preamble around the inner wire_dtype/wire_codec
        # encoding, and the merge touches only that slice.  k == 1 (or
        # an absent shard: block) keeps every branch below untaken and
        # the frames byte-identical to a pre-shard build.
        self._shard_k = config.shard.k
        self._shard_on = config.shard.k > 1
        # Top-k-within-shard keeps one error-feedback encoder PER shard:
        # the base tracks "what the ring was told about this slice", and
        # slices ship on independent cadences, so a shared base would
        # smear one shard's residuals into another's selection.
        self._shard_topk_encoders: Dict[int, object] = {}
        # Per-epoch shard-visit permutation memo (one threefry draw per
        # k rounds instead of per publish): (epoch, perm ndarray).
        self._shard_perm: Optional[Tuple[int, np.ndarray]] = None
        # The CURRENT fetch's shard slice bounds, consumed by
        # _merge_remote so every merge substrate lerps ONLY [lo, hi)
        # and copies the other k-1 slices bit-exactly ((1-a)x + ax is
        # NOT x in f32).  None for dense/topk/full-vector fetches.
        # dpwalint: double_buffered(_pending_shard) -- written by the fetch leg alongside _pending_trust_scale before finish() joins it; the merge reads strictly after the join
        self._pending_shard: Optional[Tuple[int, int]] = None
        # Device merge mode (docs/device.md): exchange_on_device flips
        # _sparse_consume around its _round so _consume_fetch keeps
        # sparse frames SPARSE — no host densify; the fused scatter /
        # dynamic-slice kernels splice on the device instead.  The
        # pending support rides next to _pending_shard under the same
        # double-buffer discipline.
        # dpwalint: double_buffered(_pending_topk) -- written by the fetch leg alongside _pending_shard before finish() joins it; the device merge reads strictly after the join
        self._pending_topk: Optional[Tuple[np.ndarray, np.ndarray]] = None
        # dpwalint: double_buffered(_sparse_consume) -- flipped by the round driver strictly before the fetch starts and restored strictly after finish() joins it; the fetch leg only reads inside that window
        self._sparse_consume = False
        # Device-resident replica handle, cached across rounds so the
        # host mirror (lazy readback) survives between exchanges.
        self._dev_replica = None
        # Per-shard wire accounting under _stats_lock: frames and bytes
        # per shard index, behind wire_snapshot()["shard"] and the
        # health_report --wire coverage columns.
        self._shard_tally: Dict[int, Dict[str, int]] = {}
        # Per-publish wire accounting: actual on-wire payload bytes vs
        # the dense f32 size, behind the ``compression_ratio`` health
        # column.  Guarded by _stats_lock:
        # the training thread tallies while the healthz / metrics-scrape
        # threads read multi-key snapshots (unlocked, a scrape could see
        # frames from one publish and bytes from another — or hit a dict
        # mutated mid-iteration).
        self._stats_lock = threading.Lock()
        self._wire_tally = {"frames": 0, "wire_bytes": 0, "dense_bytes": 0}
        # Double-buffered prefetch pipeline (protocol.overlap_prefetch):
        # round t+1's partner fetch streams on a background slot while
        # round t's decode -> trust-screen -> merge runs.  One slot:
        # {step, sched, partner, remapped, expected_nbytes, thread, box,
        #  t_end} — thread is None when the slot round does not
        # participate (self-pair / masked).
        self._prefetch_on = config.protocol.overlap_prefetch
        self._prefetch_slot: Optional[dict] = None
        self._pipe_last_entry: Optional[float] = None
        self._overlap = {
            "rounds": 0, "prefetched": 0, "straddled": 0,
            "fetch_s": 0.0, "join_wait_s": 0.0,
            "inflight_s": 0.0, "round_s": 0.0,
        }
        # Observability plane (dpwa_tpu/obs/, docs/observability.md):
        # round tracer, replica-sketch board, /metrics registry.  All
        # None when the obs: block is off — the hot path then takes no
        # obs branches, adds no timing calls, and publishes frames
        # bit-identical to an obs-free build.
        obs_cfg = config.obs
        self.tracer = None
        if obs_cfg.trace:
            from dpwa_tpu.obs.trace import Tracer

            self.tracer = Tracer(
                self.me,
                every=obs_cfg.trace_every,
                path=obs_cfg.trace_path,
                max_records=obs_cfg.trace_max_records,
            )
        self.sketchboard = None
        if obs_cfg.sketch:
            from dpwa_tpu.obs.sketch import SketchBoard

            self.sketchboard = SketchBoard(self.me, k=obs_cfg.sketch_k)
        # Published DPWT sections and fetch-side trailer reads gate on
        # either facility (the trace id is free once the section exists).
        self._obs_wire = obs_cfg.trace or obs_cfg.sketch
        self._trace_id: Optional[str] = None
        self._obs_trailer_cache: Optional[Tuple[int, bytes]] = None
        self.metrics_registry = None
        if obs_cfg.metrics:
            from dpwa_tpu.obs.prometheus import MetricsRegistry

            self.metrics_registry = MetricsRegistry()
        # Incident plane + black-box flight recorder (docs/incidents.md):
        # online detectors over the signals the other planes already
        # produce, correlated into open→update→resolved incidents, plus
        # a bounded last-N-rounds ring dumped on crash/incident/demand.
        # Both None when off — the round boundary then takes no extra
        # branches and no timing calls (zero-cost-when-disabled).
        self.incidents = None
        if obs_cfg.incidents:
            from dpwa_tpu.obs.incidents import IncidentPlane

            self.incidents = IncidentPlane(
                self.me, len(config.nodes), obs_cfg,
                topology=self.topology,
            )
        self.flight = None
        if obs_cfg.recorder:
            from dpwa_tpu.obs.recorder import FlightRecorder

            self.flight = FlightRecorder(
                self.me,
                rounds=obs_cfg.recorder_rounds,
                path=obs_cfg.recorder_path,
            )
            self.flight.arm_crash_dump()
        # Event interception: when the incident plane (or recorder) is
        # armed the round hook drains membership/trust events so
        # detectors see them the round they happen; adapters keep seeing
        # every event through pop_*_events reading these buffers.
        self._membership_event_buf: list = []
        self._trust_event_buf: list = []
        self._obs_round_entry_t: Optional[float] = None
        spec = config.nodes[self.me]
        # Fetcher-side flow control: the per-peer latency estimator that
        # derives adaptive cumulative deadlines and hedge launch points.
        # None when the flowctl block is disabled — every fetch then
        # runs on the static protocol.timeout_ms exactly as before.
        self._estimator: Optional[DeadlineEstimator] = (
            DeadlineEstimator(
                config.flowctl, timeout_ms=config.protocol.timeout_ms
            )
            if config.flowctl.enabled
            else None
        )
        # Kept when chaos is on so the FETCHING side can honor injected
        # partitions (the serving side cannot know who is connecting).
        self._chaos_engine = None
        if config.chaos.enabled:
            # Chaos wraps the Rx server (fault injection needs
            # per-connection control of the serve path); the import is
            # deferred because health.chaos imports this module.  Both
            # Rx servers inject: the threaded wrapper rewrites frames in
            # its serve loop, the reactor subclass rewrites them at
            # _serve_blob time — same pure mutation functions, so the
            # served bytes are identical (tests/test_fleet.py pins it).
            from dpwa_tpu.health.chaos import (
                ChaosEngine,
                ChaosPeerServer,
                ChaosReactorPeerServer,
            )

            self._chaos_engine = ChaosEngine(config.chaos, self.me)
            if config.protocol.rx_server == "reactor":
                self.server = ChaosReactorPeerServer(
                    spec.host, spec.port, self._chaos_engine,
                    flowctl=config.flowctl,
                )
            else:
                self.server = ChaosPeerServer(
                    spec.host, spec.port, self._chaos_engine,
                    flowctl=config.flowctl,
                )
        elif config.protocol.rx_server == "reactor":
            # Single-threaded event-loop Rx (docs/transport.md): same
            # wire bytes and admission semantics as PeerServer, with
            # the connection cap lifted to flowctl.reactor_max_
            # connections.  Deferred import: reactor.py imports this
            # module for the frame builders.
            from dpwa_tpu.parallel.reactor import ReactorPeerServer

            self.server = ReactorPeerServer(
                spec.host, spec.port, flowctl=config.flowctl
            )
        elif (
            config.recovery.enabled
            or config.flowctl.enabled
            or config.obs.trace
            or (config.health.enabled and config.membership.enabled)
        ):
            # STATE serving (peer-assisted bootstrap), the RELAY probe
            # verb (indirect membership probing), and flowctl admission
            # (DPWB shedding, token pacing, loris eviction) live in the
            # Python Rx server only — the native C++ loop speaks just
            # the blob protocol.  Same forcing rationale as chaos.
            self.server = PeerServer(
                spec.host, spec.port, flowctl=config.flowctl
            )
        else:
            self.server = make_peer_server(
                spec.host, spec.port, flowctl=config.flowctl
            )
        if self.tracer is not None and hasattr(
            self.server, "obs_serve_hook"
        ):
            # Serve-side spans: only the Python Rx servers (threaded
            # PeerServer and the reactor — both expose the hook attr)
            # can time their sends (obs.trace forces them above).
            # Under chaos the serve path bypasses _serve_blob and the
            # wrapper has no hook, so chaos runs trace the fetcher
            # side only.
            self.server.obs_serve_hook = self.tracer.note_serve
        self._ports = {
            i: (n.host, n.port) for i, n in enumerate(config.nodes)
        }
        # Peer-health control plane: every fetch outcome feeds the
        # scoreboard; quarantined partners are remapped in
        # _resolve_partner.  health.enabled=False restores the seed's
        # raw skip-on-timeout behavior exactly.
        self.scoreboard: Optional[Scoreboard] = (
            Scoreboard(
                len(config.nodes), self.me, config.health,
                seed=self.schedule.seed,
            )
            if config.health.enabled
            else None
        )
        # Epidemic membership rides on the scoreboard: digests merge
        # into the same per-peer records the fetch outcomes feed.
        self.membership = None
        if self.scoreboard is not None and config.membership.enabled:
            from dpwa_tpu.membership.manager import MembershipManager

            leader_board = None
            if self.topology is not None:
                # The board's seed must be the topology's leader_seed —
                # the SAME draw build_hier_schedule compiled the term-0
                # wide-area slots from — so digest-adopted successions
                # and the static pool agree on who term 0's leaders are.
                from dpwa_tpu.hier.leader import LeaderBoard

                leader_board = LeaderBoard(
                    self.topology, seed=config.topology.leader_seed
                )
            self.membership = MembershipManager(
                len(config.nodes), self.me, self.scoreboard,
                config.membership, seed=self.schedule.seed,
                topology=self.topology, leader_board=leader_board,
            )
            # Churn hardening: when the manager evicts a dead peer it
            # prunes the scoreboard itself; the trust EWMAs/windows and
            # the flowctl deadline windows are pruned through these
            # listeners so no plane holds O(everyone-ever-seen) state.
            if self.trust is not None:
                self.membership.add_evict_listener(self.trust.evict_peer)
            if self._estimator is not None:
                self.membership.add_evict_listener(
                    self._estimator.evict_peer
                )
            if self.membership.partial is not None:
                # Bounded partial views (membership.view): the LRU
                # state cap must never silently drop a collapsed-trust
                # verdict, and trust snapshots switch to tracked-map
                # iteration (len(peers) == N no longer holds).
                if self.trust is not None:
                    self.membership.add_cap_protector(
                        self.trust.is_collapsed
                    )
                    self.trust.enable_capped_snapshots()
        # dpwalint: double_buffered(_last_digest_nbytes) -- a single int rebound whole by the publish path; the healthz snapshot reads the old or new value, never a torn write (stale-but-consistent telemetry)
        self._last_digest_nbytes = 0
        if self.trust is not None and self.scoreboard is not None:
            # Collapsed trust feeds the scoreboard as ``untrusted``
            # probes — the quarantine path for a persistently-suspect
            # peer no single rejection condemns.
            self.trust.attach_scoreboard(self.scoreboard)
        # Self-tuning wire (docs/tune.md): the per-link degradation
        # controller that walks the frozen codec ladder — escalating
        # compression on wire-bound links, backing off when the sketch
        # plane shows convergence stalling, and shedding FIDELITY (not
        # rounds) at a DEGRADED partner.  None when tune.enabled is off:
        # every publish then takes the original static-codec branches
        # and the frames stay byte-identical.
        self._tuner = None
        # Per-(link, shard) top-k error-feedback encoders and the last
        # effective rung served per link — a rung change drops the
        # accumulated residual base (ops/quantize.TopkEncoder.retune),
        # because it was measured against what the OLD codec told the
        # ring.  Training-thread-only state, like _topk_encoder.
        self._tune_topk_encoders: Dict[Tuple[int, int], object] = {}
        self._tune_last_rung: Dict[int, int] = {}
        self._tune_plan_cache: Optional[tuple] = None
        if config.tune.enabled:
            from dpwa_tpu.tune import LinkTuner, start_rung_for

            self._tuner = LinkTuner(config.tune, seed=self.schedule.seed)
            # Anchor at the static YAML rung: a link that never shows
            # evidence publishes exactly what the config asked for.
            self._tuner.set_start_rung(start_rung_for(
                "topk" if self._wire_topk else "dense",
                config.protocol.wire_dtype,
                config.protocol.topk_fraction,
            ))
            if self.membership is not None:
                # Same churn-hardening contract as trust/flowctl: an
                # evicted peer's ladder state dies with it; a rejoiner
                # re-enters at the static start rung.
                self.membership.add_evict_listener(self._tuner.evict_peer)
        self.healthz = None
        if config.health.enabled and config.health.healthz_port is not None:
            from dpwa_tpu.health.endpoint import HealthzServer

            extra_routes: dict = {}
            if self.incidents is not None:
                extra_routes["/incidents"] = self.incidents.snapshot
            if self.flight is not None:
                extra_routes["/flightdump"] = self._flight_dump_route
            self.healthz = HealthzServer(
                self.health_snapshot, spec.host, config.health.healthz_port,
                metrics_fn=(
                    self.metrics_registry.render
                    if self.metrics_registry is not None
                    else None
                ),
                extra_routes=extra_routes or None,
            )
        # Bookkeeping for metrics/adapters: last fetch outcome and the
        # last round's partner resolution (schedule vs. health remap).
        # dpwalint: double_buffered(last_fetch) -- rebound as one fresh dict per fetch; readers take the whole ref (stale-but-consistent telemetry)
        self.last_fetch: dict = {}
        self.last_round: dict = {}
        # Recovery bookkeeping: the clock we last published (for the
        # re-admission freshness check) and a pending re-sync advice
        # record the adapter pops when a readmitted peer's clock shows
        # WE are the stale replica.  _last_clock is guarded by
        # _clock_lock: the training thread writes it in _publish while a
        # prefetch/overlap daemon leg may concurrently read it through
        # _link_blocked (chaos partitions are keyed on the publish
        # clock).
        self._clock_lock = threading.Lock()
        self._last_clock = 0.0
        self.resync_advice: Optional[dict] = None
        # Barrier-free async round plane (docs/async.md): when
        # protocol.async_rounds is enabled an AsyncExchangeEngine wraps
        # this transport — publish decoupled from merge, frames queueing
        # per peer and merging staleness-damped whenever ready — and
        # arms _async_guard, the per-peer highest-merged-publish-clock
        # map that _consume_fetch uses to drop duplicate deliveries (a
        # frame both prefetched and queued async must merge exactly
        # once).  Both stay None when the block is off: the lock-step
        # paths then take no async branches and produce byte-identical
        # frames and merges.
        # dpwalint: double_buffered(_async_guard) -- written only by the training thread inside _consume_fetch; async fetch slots read a consistent snapshot at admission time (a miss is re-screened at consume)
        self.async_engine = None
        self._async_guard: Optional[Dict[int, float]] = None
        if config.protocol.async_rounds.enabled:
            # Deferred import: async_loop imports schedules/detector and
            # is wired onto this transport, not the other way around.
            from dpwa_tpu.parallel.async_loop import AsyncExchangeEngine

            AsyncExchangeEngine(self)
        if self._chaos_engine is not None:
            # Compile-once discipline for the control plane: the threefry
            # draws (fallback/relay/heal/...) jit on first call, and left
            # lazy that compile fires at the first FAILURE — stalling only
            # the replicas having the incident.  Under chaos the injected
            # windows are keyed on each process's own publish clock, so a
            # seconds-long stall on half the ring desynchronizes the very
            # faults being injected; warm the draws off the step clock.
            # Without chaos the stall is a one-time latency blip and lazy
            # compile wins: a restarted worker must reach its bootstrap
            # probes before the survivors move on, not sit in jit.
            from dpwa_tpu.parallel.schedules import warm_control_draws

            warm_control_draws(self.schedule.seed, self.me)
        if self.metrics_registry is not None:
            # Last: collectors read plane snapshots, so every plane must
            # exist before its collector registers.
            self._register_metrics(self.metrics_registry)

    @property
    def port(self) -> int:
        return self.server.port

    def set_peer_port(self, index: int, port: int) -> None:
        """Tests use OS-assigned ports (port 0); let the driver rewire."""
        host, _ = self._ports[index]
        self._ports[index] = (host, port)

    def publish(self, vec: np.ndarray, clock: float, loss: float) -> None:
        tr = self.tracer
        if tr is None:
            self._publish(vec, clock, loss)
            return
        t0 = time.monotonic()
        try:
            self._publish(vec, clock, loss)
        finally:
            tr.mark("publish", time.monotonic() - t0)
            tr.set(trace_id=self._trace_id)

    def _make_obs_trailer(self, vec: np.ndarray, clock: float) -> bytes:
        """The DPWT section for this publish: trace id + (optionally)
        the replica sketch.  The norm estimate is the sketch's own L2
        norm — unbiased for the replica norm under Rademacher signs, so
        it costs no extra pass over the parameters."""
        from dpwa_tpu.obs.wire import encode_obs

        seq = int(clock) & 0xFFFFFFFF
        self._trace_id = f"{self.me}:{seq}"
        # One trailer per publish clock: the round protocol republishes
        # the same replica under the same clock (driver publish, then
        # the publish inside ``_round``), and seq granularity is the
        # estimator's contract anyway — so a same-seq republish reuses
        # the encoded section instead of paying a second sketch pass on
        # the exchange hot path.
        cached = self._obs_trailer_cache
        if cached is not None and cached[0] == seq:
            return cached[1]
        sketch = None
        norm = 0.0
        board = self.sketchboard
        if (
            board is not None
            and vec.dtype == np.float32
            and int(clock) % self.config.obs.sketch_every == 0
        ):
            from dpwa_tpu.obs.sketch import replica_sketch

            sketch = replica_sketch(vec, self.schedule.seed, board.k)
            norm = float(np.dot(sketch, sketch)) ** 0.5
            board.note_local(seq, sketch)
        blob = encode_obs(self.me, seq, norm, sketch)
        self._obs_trailer_cache = (seq, blob)
        return blob

    def _publish(self, vec: np.ndarray, clock: float, loss: float) -> None:
        # Compressed wire: only the PUBLISHED (served) copy is compressed
        # — bf16 halves the wire bytes, int8 quarters them; the local
        # replica stays f32 (mirrors the ICI transport, which compresses
        # the shipped copy before the collective).  int8 is quantized
        # with stochastic rounding keyed on (seed, clock, me) and
        # dequantized by the FETCHING side (ops/quantize.py).
        with self._clock_lock:
            self._last_clock = float(clock)
        f32_vec = None  # contiguous-f32 view of vec, stashed below
        if (
            self.trust is not None
            or self._wire_topk
            or self._shard_on
            # The tuner can move any link onto a top-k rung at runtime,
            # and a fetched top-k frame can only densify against the
            # stashed replica — so the self-tuning wire always stashes.
            or self._tuner is not None
            or (
                self.config.recovery.enabled
                and self.config.recovery.min_param_norm_ratio > 0.0
            )
        ) and vec.dtype in (np.float32, np.float64):
            # Stash the f32 replica this round merges against: trust
            # screening and the zero-energy guard both compare the
            # incoming payload to what we just published — and a top-k
            # or shard frame can only densify against it.
            self._local_vec = np.ascontiguousarray(vec, dtype=np.float32)
            f32_vec = self._local_vec
            self._local_norm = float(
                np.linalg.norm(self._local_vec.astype(np.float64))
            )
        # Epidemic piggyback: the current membership digest rides every
        # published frame as the optional trailer (_frame docstring).
        digest = (
            self.membership.encode(int(clock))
            if self.membership is not None
            else None
        )
        if digest is not None:
            # Partial-view observability: the actual digest bytes this
            # frame carries (O(digest_sample) under membership.view).
            self._last_digest_nbytes = len(digest)
        # Observability piggyback: trace id + replica sketch ride AFTER
        # the digest (ordering is the back-compat contract — see _frame).
        # When trust/topk/guard already stashed a contiguous-f32 copy of
        # this vec, sketch THAT — it saves a second full-replica pass
        # (and a device transfer when vec is a jax array).
        obs = (
            self._make_obs_trailer(
                vec if f32_vec is None else f32_vec, clock
            )
            if self._obs_wire
            else None
        )
        tid = self._trace_id if obs is not None else None
        # Self-tuning wire: one controller decision per publish clock
        # for the scheduled partner's link; None when the tuner is off
        # (the static branches below then run untouched).
        tune_sel = (
            self._tune_plan(int(clock))
            if self._tuner is not None and vec.dtype == np.float32
            else None
        )
        if self._shard_on and vec.dtype == np.float32:
            # Sharded wire (code 6): the obs trailer above was built
            # from the FULL replica — the sketch plane's rel_rms stays
            # full-vector so convergence accounting is honest even
            # though the frame below carries one slice.
            self._publish_shard(vec, f32_vec, clock, loss, digest, obs,
                                tid, tune_sel)
            return
        if tune_sel is not None:
            self._publish_tuned(
                vec, f32_vec, clock, loss, digest, obs, tid, tune_sel
            )
            return
        if self._wire_topk and vec.dtype == np.float32:
            payload = self._topk_encoder.encode(
                np.ascontiguousarray(vec, dtype=np.float32).reshape(-1),
                self.schedule.seed, clock, self.me,
            )
            self._note_published(int(payload.size), int(vec.size) * 4)
            self.server.publish(
                payload, clock, loss, code=_TOPK_DELTA, digest=digest,
                obs=obs, trace_id=tid,
            )
            return
        if self._wire_int8 and vec.dtype == np.float32:
            from dpwa_tpu.ops.quantize import encode_int8_payload

            payload = encode_int8_payload(
                vec, self.schedule.seed, clock, self.me
            )
            self._note_published(int(payload.size), int(vec.size) * 4)
            self.server.publish(
                payload, clock, loss, code=_INT8_CHUNKED, digest=digest,
                obs=obs, trace_id=tid,
            )
            return
        if self._wire_bf16 and vec.dtype == np.float32:
            vec = vec.astype(_DTYPES[3])
        self._note_published(int(vec.nbytes), int(vec.size) * 4)
        self.server.publish(vec, clock, loss, digest=digest, obs=obs,
                            trace_id=tid)

    def _tune_plan(self, step: int):
        """One ladder decision per publish clock: resolve the scheduled
        partner (the link this frame is FOR under pairwise gossip),
        overlay the DEGRADED fidelity shed, and return ``(link, rung)``
        — or None when this clock pairs the node with itself.

        Memoized per clock like the obs trailer: the round protocol
        republishes the same replica under the same clock (driver
        publish, then the publish inside ``_round``), and the dwell
        clock must advance once per ROUND, not once per frame."""
        cached = self._tune_plan_cache
        if cached is not None and cached[0] == step:
            return cached[1]
        link = self.schedule.partner(step, self.me)
        sel = None
        if link != self.me:
            sb = self.scoreboard
            degraded = bool(
                sb is not None and sb.is_degraded(link, step)
            )
            rung = self._tuner.plan(link, step, degraded=degraded)
            eff = self._tuner.effective_rung(link)
            last = self._tune_last_rung.get(link)
            if last is not None and last != eff:
                # Rung change: drop the error-feedback base of every
                # top-k encoder serving this link — the accumulated
                # residual was measured against what the OLD codec told
                # the ring, and replaying it through the new one would
                # double-ship (or re-ship stale) coordinates.
                for key, enc in self._tune_topk_encoders.items():
                    if key[0] != link:
                        continue
                    if rung.codec == "topk":
                        enc.retune(rung.topk_fraction)
                    else:
                        enc.reset()
            self._tune_last_rung[link] = eff
            sel = (link, rung)
        self._tune_plan_cache = (step, sel)
        return sel

    def _tune_topk_encoder(self, link: int, fraction: float, shard: int):
        """The (link, shard) error-feedback encoder at ``fraction``,
        created on first use and retuned (fraction swap + base reset)
        when the ladder moved it to a different top-k rung."""
        key = (link, shard)
        enc = self._tune_topk_encoders.get(key)
        if enc is None:
            from dpwa_tpu.ops.quantize import TopkEncoder

            enc = TopkEncoder(
                fraction, self.config.protocol.topk_values
            )
            self._tune_topk_encoders[key] = enc
        elif enc.fraction != fraction:
            enc.retune(fraction)
        return enc

    def _publish_tuned(
        self, vec: np.ndarray, f32_vec: Optional[np.ndarray],
        clock: float, loss: float, digest, obs, tid, sel,
    ) -> None:
        """Publish one frame at the link's current ladder rung.  Frames
        stay self-describing (code byte), so the fetching side decodes
        whatever rung this side chose without negotiation."""
        link, rung = sel
        flat = (
            f32_vec
            if f32_vec is not None
            else np.ascontiguousarray(vec, dtype=np.float32)
        ).reshape(-1)
        if rung.codec == "topk":
            enc = self._tune_topk_encoder(link, rung.topk_fraction, -1)
            payload = enc.encode(
                flat, self.schedule.seed, clock, self.me
            )
            self._note_published(int(payload.size), int(flat.size) * 4)
            self.server.publish(
                payload, clock, loss, code=_TOPK_DELTA, digest=digest,
                obs=obs, trace_id=tid,
            )
            return
        if rung.dtype == "int8":
            from dpwa_tpu.ops.quantize import encode_int8_payload

            payload = encode_int8_payload(
                flat, self.schedule.seed, clock, self.me
            )
            self._note_published(int(payload.size), int(flat.size) * 4)
            self.server.publish(
                payload, clock, loss, code=_INT8_CHUNKED, digest=digest,
                obs=obs, trace_id=tid,
            )
            return
        out = flat.astype(_DTYPES[3]) if rung.dtype == "bf16" else flat
        self._note_published(int(out.nbytes), int(flat.size) * 4)
        self.server.publish(out, clock, loss, digest=digest, obs=obs,
                            trace_id=tid)

    def _observed_wire_rung(self, sp, vec, nbytes: int) -> int:
        """Ladder rung the partner encoded its last frame at, for
        mirroring.  Sparse payloads are explicit about their codec;
        dense frames are classified by the wire-bytes-per-element ratio
        (the code byte is consumed inside fetch_blob_full, and
        f32/bf16/int8 sit well apart at ~4/2/1 bytes per element).
        Shard frames mirror the INNER codec — shard width is never on
        the ladder."""
        from dpwa_tpu.ops.shard import ShardPayload
        from dpwa_tpu.tune import start_rung_for

        if sp is not None:
            if isinstance(sp, ShardPayload):
                inner = sp.inner
                if not isinstance(inner, np.ndarray):
                    lo, hi = sp.bounds
                    frac = float(inner.values.size) / max(1, hi - lo)
                    return start_rung_for("topk", "f32", frac)
                return {0: 0, 3: 1, 4: 2}.get(sp.inner_code, 0)
            frac = float(sp.values.size) / max(1, int(sp.n))
            return start_rung_for("topk", "f32", frac)
        n = max(1, int(getattr(vec, "size", 1)))
        ratio = float(nbytes) / n
        if ratio < 1.5:
            return 2
        if ratio < 3.0:
            return 1
        return 0

    def _shard_index(self, step: int, k: int) -> int:
        """This publish clock's shard under the per-epoch permutation
        (schedules.shard_draw semantics), with the epoch's permutation
        memoized — one threefry draw per k rounds, not per publish."""
        from dpwa_tpu.parallel.schedules import shard_permutation

        epoch, pos = divmod(int(step), k)
        memo = self._shard_perm
        if memo is None or memo[0] != epoch:
            memo = (epoch, shard_permutation(self.schedule.seed, epoch, k))
            self._shard_perm = memo
        return int(memo[1][pos])

    def _publish_shard(
        self, vec: np.ndarray, f32_vec: Optional[np.ndarray],
        clock: float, loss: float, digest, obs, tid,
        tune_sel=None,
    ) -> None:
        """Serve this round's shard: slice -> inner wire_dtype /
        wire_codec encoding -> SHARD_HDR preamble -> code-6 frame.  The
        codecs compose per slice: top-k selects within the shard (one
        error-feedback encoder per shard), the int8 scale tables restart
        at the slice boundary because chunking is per-payload.  Shard k
        itself is never tuned (both ends must agree on the round-robin
        permutation); with the tuner on, the ladder rung selects the
        INNER codec of the slice instead."""
        from dpwa_tpu.ops import shard as _shard_ops

        flat = (
            f32_vec
            if f32_vec is not None
            else np.ascontiguousarray(vec, dtype=np.float32)
        ).reshape(-1)
        k = self._shard_k
        idx = self._shard_index(int(clock), k)
        lo, hi = _shard_ops.shard_bounds(flat.size, k, idx)
        sl = np.ascontiguousarray(flat[lo:hi])
        if tune_sel is not None:
            link, rung = tune_sel
            if rung.codec == "topk":
                enc = self._tune_topk_encoder(
                    link, rung.topk_fraction, idx
                )
                inner = enc.encode(
                    sl, self.schedule.seed, clock, self.me
                )
                inner_code = _TOPK_DELTA
            elif rung.dtype == "int8":
                from dpwa_tpu.ops.quantize import encode_int8_payload

                inner = encode_int8_payload(
                    sl, self.schedule.seed, clock, self.me
                )
                inner_code = _INT8_CHUNKED
            elif rung.dtype == "bf16":
                inner = sl.astype(_DTYPES[3]).view(np.uint8)
                inner_code = _pc.PAYLOAD_BF16
            else:
                arr = (
                    sl if sl.dtype == np.dtype("<f4")
                    else sl.astype("<f4")
                )
                inner = arr.view(np.uint8)
                inner_code = _pc.PAYLOAD_F32
        elif self._wire_topk:
            enc = self._shard_topk_encoders.get(idx)
            if enc is None:
                from dpwa_tpu.ops.quantize import TopkEncoder

                enc = TopkEncoder(
                    self.config.protocol.topk_fraction,
                    self.config.protocol.topk_values,
                )
                self._shard_topk_encoders[idx] = enc
            inner = enc.encode(sl, self.schedule.seed, clock, self.me)
            inner_code = _TOPK_DELTA
        elif self._wire_int8:
            from dpwa_tpu.ops.quantize import encode_int8_payload

            inner = encode_int8_payload(
                sl, self.schedule.seed, clock, self.me
            )
            inner_code = _INT8_CHUNKED
        elif self._wire_bf16:
            # astype is the required downcast; the uint8 reinterpret is
            # a free view (the old frombuffer(tobytes()) round-trip
            # copied the slice twice).
            inner = sl.astype(_DTYPES[3]).view(np.uint8)
            inner_code = _pc.PAYLOAD_BF16
        else:
            arr = sl if sl.dtype == np.dtype("<f4") else sl.astype("<f4")
            inner = arr.view(np.uint8)
            inner_code = _pc.PAYLOAD_F32
        payload = _shard_ops.encode_shard_payload(
            inner, flat.size, k, idx, inner_code
        )
        self._note_published(
            int(payload.size), int(flat.size) * 4, shard=idx
        )
        self.server.publish(
            payload, clock, loss, code=_SHARD, digest=digest, obs=obs,
            trace_id=tid,
        )

    def _note_published(
        self, wire_bytes: int, dense_bytes: int,
        shard: Optional[int] = None,
    ) -> None:
        with self._stats_lock:
            t = self._wire_tally
            t["frames"] += 1
            t["wire_bytes"] += wire_bytes
            t["dense_bytes"] += dense_bytes
            if shard is not None:
                st = self._shard_tally.get(shard)
                if st is None:
                    st = self._shard_tally[shard] = {
                        "frames": 0, "wire_bytes": 0,
                    }
                st["frames"] += 1
                st["wire_bytes"] += wire_bytes

    # dpwalint: thread_root(overlap-fetch)
    def fetch(
        self,
        peer_index: int,
        timeout_ms: Optional[int] = None,
        step: Optional[int] = None,
    ) -> Optional[Tuple[np.ndarray, float, float]]:
        return self._consume_fetch(
            self._wire_fetch(peer_index, timeout_ms, step), step
        )

    def _wire_fetch(
        self,
        peer_index: int,
        timeout_ms: Optional[int] = None,
        step: Optional[int] = None,
    ) -> tuple:
        """The WIRE leg of a fetch — connect, stream, frame-validate —
        with none of the consuming-side semantics (densify, guard, trust,
        scoreboard, estimator).  Split from :meth:`_consume_fetch` so the
        prefetch pipeline can stream round t+1's bytes on a background
        thread while round t is still screening: only byte movement may
        run ahead; every judgement about a payload happens at consume
        time against the replica it would actually merge into.

        Returns the 9-tuple ``(winner_peer, got, outcome, latency_s,
        nbytes, digest, obs, hedged, hedge_winner)``."""
        if timeout_ms is None:
            timeout_ms = self.config.protocol.timeout_ms
        if self._link_blocked(peer_index):
            # Injected partition, fetcher side: the chaos harness blocks
            # this directed link, so no socket is even opened — the
            # round records a refused fetch, exactly what a firewalled
            # link produces.
            return (
                peer_index, None, Outcome.REFUSED, 0.0, 0, None, None,
                False, None,
            )
        if self._estimator is not None:
            # Flowctl path: the estimator's adaptive cumulative deadline
            # (falling back to timeout_ms while cold) plus at most one
            # hedged retry to the schedule's fallback partner once the
            # quantile budget lapses.  The winner slot may come back as
            # the FALLBACK peer — everything recorded by the consume
            # half (trust, guard, scoreboard, estimator) is then charged
            # to the peer whose payload actually merges; the losing leg
            # was already recorded inside _hedged_fetch.
            return self._hedged_fetch(peer_index, step, timeout_ms)
        host, port = self._ports[peer_index]
        got, outcome, latency_s, nbytes, digest, obs = fetch_blob_full(
            host, port, timeout_ms,
            min_bandwidth_bps=(
                self.config.protocol.min_wire_mb_per_s * 1e6
            ),
            want_digest=self.membership is not None,
            want_obs=self._obs_wire,
        )
        return (
            peer_index, got, outcome, latency_s, nbytes, digest, obs,
            False, None,
        )

    def _consume_fetch(
        self, raw: tuple, step: Optional[int]
    ) -> Optional[Tuple[np.ndarray, float, float]]:
        """The CONSUME leg: densify a sparse frame against the CURRENT
        local replica, then guard/trust/scoreboard/estimator — all
        charged to the consuming round's ``step``.  Under the prefetch
        pipeline the wire leg may have run a full round earlier; this is
        the publish-clock guard in structural form — a prefetched payload
        that straddled a local publish is screened against the replica
        that exists NOW, never against the one that existed at launch."""
        (
            peer_index, got, outcome, latency_s, nbytes, digest, obs,
            hedged, hedge_winner,
        ) = raw
        est = self._estimator
        tr = self.tracer
        timing = tr is not None and tr.active
        if timing:
            # The wire span is the leg's own streaming duration — under
            # prefetch it ran on a background slot a round earlier; the
            # blocking cost the caller actually paid is the join_wait
            # span marked by _prefetch_take.
            tr.mark("wire", latency_s)
        if obs is not None and (timing or self.sketchboard is not None):
            from dpwa_tpu.obs.wire import decode_obs

            frame = decode_obs(obs)
            if frame is not None:
                if timing:
                    tr.set(remote_trace_id=frame.trace_id)
                if self.sketchboard is not None and frame.sketch is not None:
                    self.sketchboard.note_remote(
                        frame.origin, frame.seq, frame.sketch, round=step
                    )
        if (
            self._async_guard is not None
            and got is not None
            and float(got[1])
            <= self._async_guard.get(peer_index, float("-inf"))
        ):
            # Async publish-clock dedup: this peer's publish clock (or a
            # newer one) already merged through SOME path — an async
            # queue drain, a prefetch slot, a hedge leg.  Whichever leg
            # re-delivered it, merging twice would double-count the
            # frame, so it is dropped here as the soft ``stale``
            # outcome before any decode/guard/trust work is spent.
            got = None
            outcome = Outcome.STALE
        codec = None
        wire_sp = None        # decoded sparse payload (rung mirroring)
        sparse_guard = None   # (values, local_selected) for the guard
        sparse_trust = None   # (indices, values) for trust screening
        trust_codec = None    # baseline family key (inner codec for shard)
        trust_shard = None    # shard index for per-(codec, shard) windows
        trust_local = None    # slice-local vectors for shard screening
        trust_remote = None
        # Double-buffered shard bounds: None for every dense/top-k frame
        # so the merge substrates fall through to the full-vector lerp;
        # a successfully decoded shard frame below overwrites it with
        # its [lo, hi) before finish() joins the round.
        self._pending_shard = None
        self._pending_topk = None
        if got is not None and not isinstance(got[0], np.ndarray):
            t_stage = time.monotonic() if timing else 0.0
            # Sparse frame: fetch_blob_full returns the decoded payload
            # object (TopkPayload or ShardPayload) in the vector slot;
            # only this side holds the replica it splices into.  No
            # stashed local replica (or a size mismatch after a reshard)
            # means the frame cannot be interpreted — classified
            # corrupt, never merged.
            from dpwa_tpu.ops.shard import ShardPayload

            sp = got[0]
            wire_sp = sp
            lv = self._local_vec
            if isinstance(sp, ShardPayload):
                if lv is None or int(lv.size) != int(sp.d):
                    got = None
                    outcome = Outcome.CORRUPT
                else:
                    lo, hi = sp.bounds
                    local_slice = np.ascontiguousarray(lv[lo:hi])
                    est_slice = sp.slice_estimate(local_slice)
                    inner = sp.inner
                    if not isinstance(inner, np.ndarray):
                        # top-k within the shard: guard/trust judge the
                        # SUPPORT, indices relative to the slice.
                        trust_codec = "topk"
                        local_sel = local_slice[
                            inner.indices.astype(np.intp)
                        ]
                        sparse_guard = (inner.values, local_sel)
                        sparse_trust = (inner.indices, inner.values)
                    else:
                        trust_codec = {
                            0: "f32", 3: "bf16", 4: "int8",
                        }.get(sp.inner_code, "dense")
                        # Zero-energy screening on the slice actually
                        # shipped — the densified remote shares k−1
                        # slices with the local replica, which would
                        # mask a silenced shard.
                        sparse_guard = (est_slice, local_slice)
                    codec = f"shard+{trust_codec}"
                    trust_shard = sp.shard_idx
                    # Trust compares slice against slice: cosine/norm on
                    # the densified FULL vector would sit near +1 by
                    # construction (k−1 shared slices) and dilute the
                    # byzantine signal k-fold.
                    trust_local = local_slice
                    trust_remote = est_slice
                    if self._sparse_consume:
                        # Device merge: ship the m-sized slice estimate
                        # straight to the dynamic-slice kernel — the
                        # full-vector densified copy never exists.
                        got = (est_slice, got[1], got[2])
                    else:
                        remote = lv.astype(np.float32, copy=True)
                        remote[lo:hi] = est_slice
                        got = (remote, got[1], got[2])
                    self._pending_shard = (lo, hi)
            elif lv is None or int(lv.size) != int(sp.n):
                got = None
                outcome = Outcome.CORRUPT
            else:
                codec = "topk"
                local_sel = lv[sp.indices.astype(np.intp)]
                if self._sparse_consume:
                    # Device merge: keep the support sparse for the
                    # scatter-lerp kernel.  Trust still screens the
                    # frame on its SUPPORT via payload_stats_sparse —
                    # the dense remote argument is only a shape check
                    # there, so the local replica stands in for the
                    # densified estimate bit-identically.  The guard
                    # judges the shipped values (sparse_guard) rather
                    # than a densified vector it would have to build.
                    got = (sp.values, got[1], got[2])
                    self._pending_topk = (sp.indices, sp.values)
                    trust_remote = lv
                else:
                    got = (sp.densify(lv), got[1], got[2])
                sparse_guard = (sp.values, local_sel)
                sparse_trust = (sp.indices, sp.values)
            if timing:
                tr.mark("decode", time.monotonic() - t_stage)
        if (
            self._tuner is not None
            and got is not None
            and peer_index != self.me
        ):
            # Rung mirroring: the frame just decoded tells us what rung
            # the partner encoded this link at — floor our own effective
            # rung with it so a one-sided throttle (where only the
            # partner's fetches observe slowness) still slims BOTH
            # directions of the pair.
            self._tuner.note_partner_rung(
                peer_index,
                self._observed_wire_rung(wire_sp, got[0], int(nbytes)),
            )
        reason = None
        if got is not None and self.config.recovery.enabled:
            # Divergence/poison guard: a frame can be perfectly formed
            # and still carry a sick replica (NaNs, exploded norm, an
            # insane advertised loss).  Reject BEFORE the merge and feed
            # the detector — a diverged peer is as unfit a partner as a
            # dead one.
            from dpwa_tpu.recovery.guard import validate_payload

            t_stage = time.monotonic() if timing else 0.0
            reason = validate_payload(
                got[0], got[2], self.config.recovery,
                local_norm=self._local_norm,
                sparse=sparse_guard,
            )
            if timing:
                tr.mark("guard", time.monotonic() - t_stage)
            if reason is not None:
                got = None
                outcome = Outcome.POISONED
        trust_info = None
        self._pending_trust_scale = 1.0
        if (
            got is not None
            and self.trust is not None
            and self._local_vec is not None
        ):
            # Trust screening runs on the DECODED f32 vector (the int8
            # wire path dequantized inside fetch_blob_full, bf16 casts
            # in payload_stats) — the payload is judged on what would
            # actually merge.  A top-k frame is judged on its SUPPORT
            # (payload_stats_sparse) under its own per-codec baselines.
            # A rejection is the ``untrusted`` outcome:
            # recorded below exactly like ``poisoned``, and — also like
            # poisoned — never gated behind indirect probing, since a
            # byzantine peer answers header probes perfectly.
            t_stage = time.monotonic() if timing else 0.0
            verdict, scale, tstats = self.trust.screen(
                peer_index,
                trust_remote if trust_remote is not None else got[0],
                got[1],
                trust_local if trust_local is not None else self._local_vec,
                round=step,
                codec=trust_codec or codec or "dense",
                sparse=sparse_trust,
                shard=trust_shard,
            )
            if timing:
                tr.mark("trust", time.monotonic() - t_stage)
            from dpwa_tpu.trust.manager import REJECTED

            trust_info = dict(
                tstats, verdict=verdict, alpha_scale=round(scale, 4)
            )
            if verdict == REJECTED:
                got = None
                outcome = Outcome.UNTRUSTED
            else:
                self._pending_trust_scale = scale
        self.last_fetch = {
            "peer": peer_index, "outcome": outcome,
            "latency_s": latency_s, "nbytes": nbytes,
        }
        if codec is not None:
            self.last_fetch["codec"] = codec
        if hedged:
            self.last_fetch["hedged"] = True
            self.last_fetch["hedge_winner"] = hedge_winner
        if reason is not None:
            self.last_fetch["poison_reason"] = reason
        if trust_info is not None:
            self.last_fetch["trust"] = trust_info
        if self.membership is not None and digest is not None:
            self.membership.merge(digest, round=step)
        if (
            self.membership is not None
            and self.scoreboard is not None
            and step is not None
            and outcome
            in (
                Outcome.TIMEOUT,
                Outcome.REFUSED,
                Outcome.SHORT_READ,
                Outcome.CORRUPT,
            )
            and self.config.membership.indirect_probes > 0
            and self.scoreboard.would_quarantine(peer_index, outcome)
        ):
            # SWIM indirect probing: this failure WOULD cross the
            # quarantine threshold on our evidence alone — before the
            # record below promotes the peer, ask drawn healthy relays
            # to probe it for us.  A single vouch decays our suspicion
            # (an asymmetric-link false positive); when every relay
            # agrees the peer is gone, nothing is fed and the record
            # promotes on the ordinary single-failure weight.  POISONED
            # is deliberately not gated: a diverged peer answers header
            # probes perfectly and every relay would vouch for it.
            self._indirect_probe(peer_index, step)
        if self.scoreboard is not None:
            self.scoreboard.record(
                peer_index, outcome,
                latency_s=latency_s, nbytes=nbytes, round=step,
            )
        if est is not None:
            # The estimator feeds on the FINAL classified outcome (after
            # guard/trust screening): a poisoned success must not teach
            # the deadline that the peer is healthy-fast.
            est.observe(
                peer_index, outcome, latency_s=latency_s, nbytes=nbytes
            )
        if self._async_guard is not None and got is not None:
            # Latch the merged publish clock AFTER every screen passed:
            # a guarded/untrusted frame never merged, so a later clean
            # re-delivery of the same clock must still be admissible.
            ck = float(got[1])
            if ck > self._async_guard.get(peer_index, float("-inf")):
                self._async_guard[peer_index] = ck
        return got

    def _fetch_leg(
        self, peer: int, deadline_ms: float, box: list, sock_box: list
    ) -> None:
        """One fetch leg of a (possibly hedged) flowctl fetch, run on a
        thread: appends the full 6-tuple to ``box``; ``sock_box`` lets
        the racing side cancel this leg by closing its socket."""
        host, port = self._ports[peer]
        box.append(
            fetch_blob_full(
                host, port, int(deadline_ms),
                min_bandwidth_bps=(
                    self.config.protocol.min_wire_mb_per_s * 1e6
                ),
                want_digest=self.membership is not None,
                sock_box=sock_box,
                want_obs=self._obs_wire,
            )
        )

    def _view_candidates(self) -> Optional[List[int]]:
        """The active partial view when ``membership.view`` is on, else
        None (draws range over all of ``nodes:`` — the legacy path)."""
        if self.membership is None:
            return None
        return self.membership.partner_candidates()

    def _remap_mask(self, candidates: Optional[List[int]], step: int):
        """Fallback-eligibility mask for ``remap_partner``: the full
        O(N) healthy mask on the legacy path, or an O(active) map over
        the view candidates."""
        if candidates is not None:
            return self.scoreboard.healthy_map(candidates, step)
        return self.scoreboard.healthy_mask(step)

    def _hedge_fallback(self, peer: int, step: int) -> Optional[int]:
        """The deterministic hedge target: the schedule's fallback draw
        over currently-healthy peers (the SAME draw a quarantine remap
        would make this round), or None when no distinct healthy
        candidate exists."""
        n = len(self.config.nodes)
        candidates = self._view_candidates()
        if self.scoreboard is not None:
            mask = self._remap_mask(candidates, step)
        else:
            mask = [True] * n
        fallback = self.schedule.remap_partner(
            step, self.me, peer, mask, candidates
        )
        if (
            fallback == self.me
            or fallback == peer
            or self._link_blocked(fallback)
        ):
            return None
        return int(fallback)

    @staticmethod
    def _close_leg(sock_box: list) -> None:
        for s in sock_box:
            try:
                s.close()
            except OSError:
                pass

    @staticmethod
    def _leg_result(box: list, elapsed: float) -> tuple:
        """A leg's 6-tuple result; a leg that died without reporting
        (should not happen — fetch_blob_full classifies every failure)
        degrades to a short_read instead of crashing the round."""
        if box:
            return box[0]
        return None, Outcome.SHORT_READ, elapsed, 0, None, None

    def _record_loser(
        self,
        peer: int,
        result: Optional[tuple],
        cancelled: bool,
        latency_s: float,
        step: Optional[int],
    ) -> None:
        """Feed the LOSING leg of a hedge race to the scoreboard and
        estimator.  A leg we cancelled by closing its socket surfaces a
        short_read/timeout ARTIFACT of our own close — recording that
        hard evidence would walk an honest slow peer into quarantine, so
        a cancelled leg records the low-weight ``slow`` outcome instead.
        A leg that genuinely finished records its real outcome."""
        if cancelled or result is None:
            outcome, lat, nbytes = Outcome.SLOW, latency_s, 0
        else:
            _got, outcome, lat, nbytes, _digest, _obs = result
        if self.scoreboard is not None:
            self.scoreboard.record(
                peer, outcome, latency_s=lat, nbytes=nbytes, round=step
            )
        if self._estimator is not None:
            self._estimator.observe(
                peer, outcome, latency_s=lat, nbytes=nbytes
            )

    def _hedged_fetch(
        self, peer: int, step: Optional[int], timeout_ms: float
    ) -> tuple:
        """Adaptive-deadline fetch with a single hedged retry.

        Runs the primary fetch under the estimator's cumulative deadline
        for ``peer`` (``timeout_ms`` while the estimator is cold); if the
        un-margined quantile budget lapses with the primary still in
        flight and a healthy fallback partner exists, launches ONE hedge
        leg and returns the first success (closing the loser's socket
        promptly).  Returns ``(winner_peer, got, outcome, latency_s,
        nbytes, digest, obs, hedged, hedge_winner)`` — the winner's outcome
        flows through fetch()'s normal screening tail; only the LOSER is
        recorded here."""
        est = self._estimator
        r = int(step) if step is not None else 0
        deadline_ms = (
            est.deadline_ms(peer) if est.warm(peer) else float(timeout_ms)
        )
        t0 = time.monotonic()
        p_box: list = []
        p_sock: list = []
        p_thread = threading.Thread(
            target=self._fetch_leg,
            args=(peer, deadline_ms, p_box, p_sock),
            daemon=True,
        )
        p_thread.start()
        launch_ms = (
            est.hedge_launch_ms(peer) if self.config.flowctl.hedge else None
        )
        fallback = None
        if launch_ms is not None:
            p_thread.join(launch_ms / 1000.0)
            if p_thread.is_alive():
                fallback = self._hedge_fallback(peer, r)
        if fallback is None:
            # No hedge: cold estimator, fast primary, or no healthy
            # fallback.  The leg's own cumulative deadline bounds the
            # join (budget extends only while bytes actually flow).
            p_thread.join()
            got, outcome, latency_s, nbytes, digest, obs = self._leg_result(
                p_box, time.monotonic() - t0
            )
            return (
                peer, got, outcome, latency_s, nbytes, digest, obs,
                False, None,
            )
        est.note_hedge(peer)
        f_box: list = []
        f_sock: list = []
        f_thread = threading.Thread(
            target=self._fetch_leg,
            args=(fallback, est.deadline_ms(fallback), f_box, f_sock),
            daemon=True,
        )
        f_thread.start()
        # Race: first SUCCESS wins; ties (both done) prefer the
        # scheduled primary.  Both legs self-terminate on their own
        # cumulative deadlines, so the poll loop is bounded.
        while True:
            p_done = not p_thread.is_alive()
            f_done = not f_thread.is_alive()
            if p_done and p_box and p_box[0][1] == Outcome.SUCCESS:
                break
            if f_done and f_box and f_box[0][1] == Outcome.SUCCESS:
                break
            if p_done and f_done:
                break
            time.sleep(0.002)
        p_done = not p_thread.is_alive()
        f_done = not f_thread.is_alive()
        p_ok = p_done and p_box and p_box[0][1] == Outcome.SUCCESS
        primary_wins = p_ok or (p_done and f_done and not (
            f_box and f_box[0][1] == Outcome.SUCCESS
        ))
        elapsed = time.monotonic() - t0
        if primary_wins:
            # Cancel the hedge leg.  A leg that never got a fair budget
            # (cancelled mid-flight) is not evidence against the
            # fallback peer — only a genuinely finished leg records.
            self._close_leg(f_sock)
            f_thread.join(0.5)
            if f_done and f_box:
                self._record_loser(
                    fallback, f_box[0], cancelled=False,
                    latency_s=f_box[0][2], step=step,
                )
            got, outcome, latency_s, nbytes, digest, obs = self._leg_result(
                p_box, elapsed
            )
            return (
                peer, got, outcome, latency_s, nbytes, digest, obs,
                True, peer,
            )
        # Fallback wins (or both failed — prefer the fallback's result
        # only on success; otherwise report the primary's real failure).
        if f_done and f_box and f_box[0][1] == Outcome.SUCCESS:
            est.note_hedge_win(peer)
            self._close_leg(p_sock)
            p_thread.join(0.5)
            self._record_loser(
                peer,
                p_box[0] if p_box else None,
                cancelled=not (p_done and p_box),
                latency_s=elapsed,
                step=step,
            )
            got, outcome, latency_s, nbytes, digest, obs = f_box[0]
            return (
                fallback, got, outcome, latency_s, nbytes, digest, obs,
                True, fallback,
            )
        # Both legs finished without a success: record the fallback's
        # genuine failure here, report the primary's through the tail.
        if f_box:
            self._record_loser(
                fallback, f_box[0], cancelled=False,
                latency_s=f_box[0][2], step=step,
            )
        got, outcome, latency_s, nbytes, digest, obs = self._leg_result(
            p_box, elapsed
        )
        return peer, got, outcome, latency_s, nbytes, digest, obs, True, None

    def _link_blocked(self, peer_index: int) -> bool:
        """Fetcher-side view of an injected partition (False without
        chaos).  Keyed on the last PUBLISHED clock — publish always
        precedes fetch in a round, so both endpoints and any relay
        agree on the same round key."""
        if self._chaos_engine is None:
            return False
        with self._clock_lock:
            clock = self._last_clock
        return self._chaos_engine.link_blocked(
            int(clock), self.me, peer_index
        )

    def _indirect_probe(self, suspect: int, step: int) -> None:
        """Ask K deterministically-drawn healthy peers to header-probe
        ``suspect`` on our behalf (the RELAY verb), and feed the
        scoreboard AT MOST one summarized outcome for the suspect.

        The relay set is drawn with :func:`~dpwa_tpu.parallel.schedules.
        relay_draw` — counter-based threefry keyed on (seed, step, me,
        slot), no wall clock — so replays pick identical relays.  Each
        relay's OWN reachability outcome feeds its record too: a relay
        that cannot be reached is itself evidence."""
        from dpwa_tpu.parallel.schedules import relay_draw

        sb = self.scoreboard
        view = self._view_candidates()
        universe = (
            view if view is not None else range(len(self.config.nodes))
        )
        candidates = [
            p
            for p in universe
            if p != self.me
            and p != suspect
            and sb.state(p) == PeerState.HEALTHY
        ]
        if not candidates:
            return
        k = min(int(self.config.membership.indirect_probes), len(candidates))
        s_host, s_port = self._ports[suspect]
        vouched = False
        for slot in range(k):
            idx = int(
                relay_draw(
                    self.schedule.seed, step, self.me, slot, len(candidates)
                )
            )
            relay = candidates.pop(idx)
            if self._link_blocked(relay):
                relay_outcome, probe_outcome = Outcome.REFUSED, None
            else:
                r_host, r_port = self._ports[relay]
                relay_outcome, probe_outcome, _clock = relay_probe(
                    r_host, r_port, suspect, s_host, s_port,
                    self.config.health.probe_timeout_ms,
                    self.config.membership.relay_timeout_ms,
                )
            sb.record_probe(relay, relay_outcome, round=step)
            if probe_outcome == Outcome.SUCCESS:
                vouched = True
        if vouched:
            sb.record_probe(suspect, Outcome.SUCCESS, round=step)

    def _resolve_partner(self, step: int) -> Tuple[int, int, bool]:
        """Health-aware partner resolution: ``(scheduled, actual,
        remapped)`` for this round.

        If the scheduled partner is quarantined and its backoff has
        elapsed, spend a cheap header-only probe first (probes ride the
        pairing rounds that would have fetched from it anyway, so the
        probe budget is self-rationing).  If it is (still) quarantined
        after that, remap to a threefry-drawn healthy fallback
        (:meth:`Schedule.remap_partner`) — replicas sharing the same
        scoreboard view make the identical draw, and with health
        disabled this degrades to the plain schedule partner."""
        sched = self.schedule.partner(step, self.me)
        partner, remapped = sched, False
        sb = self.scoreboard
        if sb is not None and sched != self.me:
            if sb.probe_due(sched, step):
                if self._link_blocked(sched):
                    outcome, remote_clock = Outcome.REFUSED, None
                else:
                    host, port = self._ports[sched]
                    outcome, remote_clock = probe_header_classified(
                        host, port, self.config.health.probe_timeout_ms
                    )
                sb.record_probe(sched, outcome, round=step)
                ok = outcome == Outcome.SUCCESS
                with self._clock_lock:
                    local_clock = self._last_clock
                if (
                    ok
                    and remote_clock is not None
                    and self.config.recovery.enabled
                    and remote_clock - local_clock
                    > self.config.recovery.max_clock_lag
                ):
                    # Re-admission freshness check: the peer came back
                    # with a clock far AHEAD of ours — we are the stale
                    # one (partitioned while the ring kept training).
                    # Interpolation alone digs out slowly; advise the
                    # adapter to re-sync (it bootstraps if auto_resync).
                    self.resync_advice = {
                        "peer": sched,
                        "remote_clock": float(remote_clock),
                        "local_clock": float(local_clock),
                        "step": int(step),
                    }
            if sb.is_quarantined(sched, step):
                view = self._view_candidates()
                partner = self.schedule.remap_partner(
                    step, self.me, sched, self._remap_mask(view, step),
                    view,
                )
                remapped = True
            elif (
                self.config.flowctl.enabled
                and self.config.flowctl.degrade_shed_fraction > 0.0
                # With the self-tuning wire running, a DEGRADED partner
                # sheds FIDELITY at publish (the ladder overlay) instead
                # of rounds — the round-drop remap below is bypassed so
                # the honest-peer round rate never dips under load.
                and self._tuner is None
                and sb.is_degraded(sched, step)
            ):
                # Scoreboard soft-degrade: a DEGRADED partner (load, not
                # death) keeps a deterministic fraction of its scheduled
                # pairings — full shedding would starve it of the very
                # successes that drain its suspicion — and the rest remap
                # to a healthy fallback.  The draw is threefry-keyed on
                # (seed, step, me): bit-identical across reruns.
                from dpwa_tpu.parallel.schedules import degrade_shed_draw

                if (
                    degrade_shed_draw(self.schedule.seed, step, self.me)
                    < self.config.flowctl.degrade_shed_fraction
                ):
                    view = self._view_candidates()
                    partner = self.schedule.remap_partner(
                        step, self.me, sched,
                        self._remap_mask(view, step), view,
                    )
                    remapped = True
        return sched, partner, remapped

    def publish_state(self, blob: bytes) -> None:
        """Expose this worker's serialized train state for peers to
        bootstrap from (zero shared-disk recovery)."""
        self.server.publish_state(blob)

    def fetch_state(
        self, peer_index: int, timeout_ms: Optional[int] = None
    ) -> Tuple[Optional[bytes], str, float, int]:
        """Pull a donor's full serialized state (chunked, CRC-checked,
        resumable — :func:`fetch_state`), sized by the ``recovery:``
        config block."""
        if self._link_blocked(peer_index):
            return None, Outcome.REFUSED, 0.0, 0
        host, port = self._ports[peer_index]
        rec = self.config.recovery
        if timeout_ms is None:
            timeout_ms = rec.bootstrap_timeout_ms
        return fetch_state(
            host, port, timeout_ms,
            chunk_bytes=rec.state_chunk_bytes,
            max_retries=rec.max_resume_retries,
            min_bandwidth_bps=self.config.protocol.min_wire_mb_per_s * 1e6,
        )

    def pop_resync_advice(self) -> Optional[dict]:
        """Consume the pending re-admission freshness advice, if any."""
        advice, self.resync_advice = self.resync_advice, None
        return advice

    # dpwalint: thread_root(healthz)
    def health_snapshot(self) -> dict:
        """JSON-ready per-peer health state (scoreboard + detector
        EWMAs, plus per-peer trust columns and a top-level ``trust``
        view when the trust plane is on); the payload behind metrics'
        ``health`` records and the optional /healthz endpoint."""
        if self.scoreboard is None:
            snap = {"me": self.me, "round": 0, "peers": {}}
        else:
            snap = self.scoreboard.snapshot()
        if self.trust is not None:
            tsnap = self.trust.snapshot()
            for p, info in tsnap["peers"].items():
                snap["peers"].setdefault(p, {}).update(info)
            snap["trust"] = tsnap
        if self._estimator is not None:
            fsnap = self._estimator.snapshot()
            admission = getattr(self.server, "admission", None)
            if admission is not None:
                fsnap["admission"] = admission.snapshot()
            for p, info in fsnap["peers"].items():
                snap["peers"].setdefault(p, {}).update(
                    {
                        "deadline_ms": info["deadline_ms"],
                        "hedges": info["hedges"],
                        "hedge_wins": info["hedge_wins"],
                        "busy": info["busy"],
                        "slow": info["slow"],
                    }
                )
            snap["flowctl"] = fsnap
        reactor_snap = getattr(self.server, "reactor_snapshot", None)
        if reactor_snap is not None:
            # Present exactly when the reactor serves this node, so
            # threaded runs keep their health records byte-identical.
            snap["reactor"] = reactor_snap()
        from dpwa_tpu.device import device_snapshot as _device_snapshot

        if (
            self._wire_topk or self._prefetch_on or self._shard_on
            or _device_snapshot()["device_rounds"] > 0
            or (
                self.membership is not None
                and self.membership.partial is not None
            )
        ):
            # Gated on the new planes being ON (or the device merge
            # engine having served a round): a dense sequential host
            # run keeps its health records byte-identical to PR 5.
            snap["wire"] = self.wire_snapshot()
        if self.tracer is not None or self.sketchboard is not None:
            snap["obs"] = self.obs_snapshot()
        if self.incidents is not None:
            snap["incidents"] = self.incidents.snapshot()
        if self.async_engine is not None:
            # Present exactly when the barrier-free round loop drives
            # this transport (protocol.async_rounds), so lock-step runs
            # keep their health records byte-identical.
            snap["async"] = self.async_engine.snapshot()
        if self._tuner is not None:
            # Present exactly when the self-tuning wire is on, so
            # static-wire runs keep their health records byte-identical.
            snap["tune"] = self._tuner.snapshot()
        return snap

    # dpwalint: thread_root(healthz)
    def obs_snapshot(self) -> dict:
        """JSON-ready observability sub-document (healthz ``obs`` key,
        metrics' ``disagreement_*`` columns): the sketch-based ring
        convergence estimate and the tracer's per-stage summary."""
        out: dict = {}
        if self.sketchboard is not None:
            out["convergence"] = self.sketchboard.snapshot()
        if self.tracer is not None:
            out["trace"] = self.tracer.stage_summary()
        return out

    # dpwalint: thread_root(healthz)
    def wire_snapshot(self) -> dict:
        """JSON-ready wire-plane state: which codec is publishing, the
        actual on-wire vs dense f32 byte tallies behind the
        ``compression_ratio`` column, and — under the prefetch pipeline
        — the overlap accounting (``occupancy`` = fetch in-flight time
        over entry-to-entry round wall; ``hidden_frac`` = the fraction
        of fetch wall-time the caller never waited on)."""
        with self._stats_lock:
            t = dict(self._wire_tally)
            shard_tally = {
                i: dict(st) for i, st in self._shard_tally.items()
            }
        codec = "topk" if self._wire_topk else self.config.protocol.wire_dtype
        if self._shard_on:
            codec = f"shard+{codec}"
        zc = _ingest.rx_stats()
        out = {
            "codec": codec,
            "frames": t["frames"],
            "wire_bytes": t["wire_bytes"],
            "dense_bytes": t["dense_bytes"],
            "compression_ratio": (
                round(t["dense_bytes"] / t["wire_bytes"], 4)
                if t["wire_bytes"]
                else 0.0
            ),
            # Zero-copy hot-path accounting (process-wide: the receive
            # ring and the copy tally are shared across transports, like
            # the frame path itself): payload-sized copies per decoded
            # frame (0.0 = views straight out of the ring) and the
            # fraction of ring bytes currently leased out.
            "copies_per_frame": round(zc["copies_per_frame"], 4),
            "ring_occupancy": round(zc["ring_occupancy"], 4),
        }
        # Device-plane accounting (process-wide, like the receive ring):
        # the merge engine's jit cache and dispatch tallies, plus the
        # zero-copy fraction of host→device crossings.  All zeros until
        # a device exchange runs; never imports a JAX backend.
        from dpwa_tpu.device import device_snapshot

        dv = device_snapshot()
        out["device"] = {
            "device_rounds": dv["device_rounds"],
            "jit_cache_hits": dv["jit_cache_hits"],
            "jit_cache_misses": dv["jit_cache_misses"],
            "device_dispatches_per_round": dv[
                "device_dispatches_per_round"
            ],
            "h2d_zero_copy_frac": round(dv["h2d_zero_copy_frac"], 4),
            "fold_frames": dv["fold_frames"],
        }
        if self._wire_topk:
            out["topk_fraction"] = self.config.protocol.topk_fraction
            out["topk_values"] = self.config.protocol.topk_values
        if self._shard_on:
            k = self._shard_k
            # coverage = distinct shards this node has actually served /
            # k — the round-robin invariant says it reaches 1.0 within
            # the first k publishes and stays there.
            out["shard"] = {
                "k": k,
                "frames_per_shard": [
                    shard_tally.get(i, {}).get("frames", 0)
                    for i in range(k)
                ],
                "wire_bytes_per_shard": [
                    shard_tally.get(i, {}).get("wire_bytes", 0)
                    for i in range(k)
                ],
                "coverage": round(len(shard_tally) / k, 4),
            }
        if self.membership is not None and self.membership.partial is not None:
            # Partial-view accounting (membership.view): view sizes,
            # residency, evictions by cause, and the actual digest bytes
            # the last published frame carried — the O(sample) numbers
            # that stay bounded as the fleet grows.  Schema-frozen as the
            # ``view_*`` group (tools/schema_check.py); present exactly
            # when the view plane is on.
            vs = dict(self.membership.view_snapshot().get("view") or {})
            vs["view_digest_bytes"] = self._last_digest_nbytes
            out["view"] = vs
        if self._prefetch_on:
            with self._stats_lock:
                o = dict(self._overlap)
            out["overlap"] = {
                "rounds": o["rounds"],
                "prefetched": o["prefetched"],
                "straddled": o["straddled"],
                "fetch_s": round(o["fetch_s"], 6),
                "join_wait_s": round(o["join_wait_s"], 6),
                "occupancy": (
                    round(o["inflight_s"] / o["round_s"], 4)
                    if o["round_s"] > 0
                    else 0.0
                ),
                "hidden_frac": (
                    round(max(1.0 - o["join_wait_s"] / o["fetch_s"], 0.0), 4)
                    if o["fetch_s"] > 0
                    else 0.0
                ),
            }
        return out

    def _register_metrics(self, registry) -> None:
        """Wire every enabled plane's collectors into the /metrics
        registry (called once, at the end of __init__).  Collectors read
        the planes' existing snapshots at scrape time — nothing here
        touches the exchange hot path."""
        from dpwa_tpu.obs.prometheus import Family

        registry.gauge_fn(
            "dpwa_me", "This node's ring index.", lambda: self.me
        )
        if self.scoreboard is not None:
            from dpwa_tpu.health.scoreboard import (
                register_metrics as _reg_health,
            )

            _reg_health(registry, self.scoreboard)
        if self.membership is not None:
            from dpwa_tpu.membership.manager import (
                register_metrics as _reg_member,
            )

            _reg_member(registry, self.membership)
        if self.trust is not None:
            from dpwa_tpu.trust.manager import (
                register_metrics as _reg_trust,
            )

            _reg_trust(registry, self.trust)
        if self._estimator is not None:
            from dpwa_tpu.flowctl.estimator import (
                register_metrics as _reg_est,
            )

            _reg_est(registry, self._estimator)
        admission = getattr(self.server, "admission", None)
        if admission is not None:
            from dpwa_tpu.flowctl.admission import (
                register_metrics as _reg_adm,
            )

            _reg_adm(registry, admission)
        if hasattr(self.server, "reactor_snapshot"):
            from dpwa_tpu.parallel.reactor import (
                register_metrics as _reg_reactor,
            )

            _reg_reactor(registry, self.server)

        def _wire():
            snap = self.wire_snapshot()
            fams = [
                Family(
                    "dpwa_wire_bytes_total",
                    "counter",
                    "Payload bytes published to the wire.",
                ).sample(snap["wire_bytes"]),
                Family(
                    "dpwa_wire_frames_total",
                    "counter",
                    "Frames published to the wire.",
                ).sample(snap["frames"]),
                Family(
                    "dpwa_wire_compression_ratio",
                    "gauge",
                    "Dense f32 bytes over on-wire bytes.",
                ).sample(snap["compression_ratio"]),
            ]
            ov = snap.get("overlap")
            if ov is not None:
                fams.append(
                    Family(
                        "dpwa_overlap_occupancy",
                        "gauge",
                        "Fetch in-flight time over round wall time.",
                    ).sample(ov["occupancy"])
                )
                fams.append(
                    Family(
                        "dpwa_overlap_hidden_frac",
                        "gauge",
                        "Fraction of fetch wall-time hidden from the "
                        "caller.",
                    ).sample(ov["hidden_frac"])
                )
            return fams

        registry.register(_wire)
        if self.sketchboard is not None:

            def _sketch():
                snap = self.sketchboard.snapshot()
                return [
                    Family(
                        "dpwa_disagreement_rms",
                        "gauge",
                        "Sketch-estimated RMS replica disagreement "
                        "across peers seen.",
                    ).sample(snap["rms"]),
                    Family(
                        "dpwa_disagreement_rel",
                        "gauge",
                        "RMS disagreement relative to the local "
                        "replica norm estimate.",
                    ).sample(snap["rel_rms"]),
                    Family(
                        "dpwa_sketch_peers",
                        "gauge",
                        "Peers with a current sketch on the board.",
                    ).sample(snap["peers_seen"]),
                ]

            registry.register(_sketch)
        if self.tracer is not None:

            def _trace():
                summary = self.tracer.stage_summary()
                total = Family(
                    "dpwa_trace_stage_seconds_total",
                    "counter",
                    "Cumulative seconds spent per exchange stage.",
                )
                med = Family(
                    "dpwa_trace_stage_median_ms",
                    "gauge",
                    "Median stage duration over the recent window.",
                )
                for stage, info in summary.items():
                    total.sample(info["total_s"], {"stage": stage})
                    med.sample(info["median_ms"], {"stage": stage})
                return [total, med]

            registry.register(_trace)
        if self.incidents is not None:
            from dpwa_tpu.obs.incidents import (
                register_metrics as _reg_inc,
            )

            _reg_inc(registry, self.incidents)
        if self._tuner is not None:
            from dpwa_tpu.tune import register_metrics as _reg_tune

            _reg_tune(registry, self._tuner)

    def _trust_alpha_scale(self) -> float:
        """The CURRENT exchange's trust damping (interpolation hook)."""
        return self._pending_trust_scale

    def _wire_nbytes(self, vec: np.ndarray) -> int:
        """Bytes the published frame's PAYLOAD occupies on the wire —
        what a symmetric partner fetch will actually stream, used to
        size the overlapped-join backstop.  Mirrors :meth:`publish`'s
        encoding choice exactly."""
        n = int(vec.size)
        if self._tuner is not None and vec.dtype == np.float32:
            # Self-tuning wire: the partner's rung can sit anywhere on
            # the ladder by the time it fetches — size the backstop for
            # the f32 floor, the ladder's largest frame (a conservative
            # bound is the contract here).
            if self._shard_on:
                m = -(-n // self._shard_k)
                return _pc.SHARD_HDR.size + 4 * m
            return 4 * n
        if self._shard_on and vec.dtype == np.float32:
            # Sharded frame: SHARD_HDR preamble + the inner encoding
            # over the LONGEST slice (ceil(n/k)) — a conservative upper
            # bound is fine for a join backstop.
            m = -(-n // self._shard_k)
            if self._wire_topk:
                from dpwa_tpu.ops.quantize import topk_k, topk_nbytes

                inner = topk_nbytes(
                    m,
                    topk_k(m, self.config.protocol.topk_fraction),
                    self.config.protocol.topk_values,
                )
            elif self._wire_int8:
                from dpwa_tpu.ops.quantize import _n_chunks

                inner = 8 + 4 * _n_chunks(m) + m
            elif self._wire_bf16:
                inner = 2 * m
            else:
                inner = 4 * m
            return _pc.SHARD_HDR.size + inner
        if self._wire_topk and vec.dtype == np.float32:
            from dpwa_tpu.ops.quantize import topk_k, topk_nbytes

            return topk_nbytes(
                n,
                topk_k(n, self.config.protocol.topk_fraction),
                self.config.protocol.topk_values,
            )
        if self._wire_int8 and vec.dtype == np.float32:
            from dpwa_tpu.ops.quantize import _n_chunks

            return 8 + 4 * _n_chunks(n) + n  # u64 n | f32 scales | int8 q
        if self._wire_bf16 and vec.dtype == np.float32:
            return 2 * n
        return int(vec.nbytes)

    def _weigh_remote(
        self, got: Tuple[np.ndarray, float, float], clock: float, loss: float
    ) -> Tuple[np.ndarray, float]:
        """Fetched blob -> (f32-ready remote vector, interpolation α):
        the metadata weighing + bf16-wire upcast shared by every merge
        substrate (host, device-resident, overlapped)."""
        remote_vec, remote_clock, remote_loss = got
        local = PeerMeta(np.float32(clock), np.float32(loss))
        remote = PeerMeta(np.float32(remote_clock), np.float32(remote_loss))
        alpha = float(self.interp(local, remote))
        if self.membership is not None:
            # Degraded-mode damping: inside a below-quorum component the
            # merge pull is optionally scaled down (1.0 by default — a
            # bit-exact no-op) so a small island doesn't overcommit to
            # its own consensus before the heal.
            alpha *= self.membership.alpha_scale()
        if (
            not self._sparse_consume
            and ml_dtypes is not None
            and remote_vec.dtype == _DTYPES[3]
        ):
            # bf16 off the wire: upcast once, merge in f32 (same math as
            # the ICI transport's bf16-wire merge).  The device engine
            # skips this copy — its bf16 kernel bitcasts and upcasts
            # in-graph, so the raw u16 wire view crosses the seam as-is.
            remote_vec = remote_vec.astype(np.float32)
        return remote_vec, alpha

    def _merge_remote(
        self, vec: np.ndarray, remote_vec: np.ndarray, alpha: float
    ) -> np.ndarray:
        """The merge shared by every host-side substrate: full-vector
        lerp normally; when the consume leg stashed shard bounds, lerp
        ONLY the ``[lo, hi)`` slice and copy the rest bit-exactly.  An
        f32 ``(1-α)·x + α·x`` is NOT exactly ``x``, so lerping the
        densified full vector would silently perturb the k−1 slices the
        frame never shipped."""
        bounds = self._pending_shard
        if bounds is None:
            return _host_merge(vec, remote_vec, alpha)
        lo, hi = bounds
        merged = np.array(vec, dtype=np.float32, copy=True)
        merged[lo:hi] = _host_merge(
            np.ascontiguousarray(merged[lo:hi]),
            np.ascontiguousarray(remote_vec[lo:hi]),
            alpha,
        )
        return merged

    def _round(
        self, vec: np.ndarray, clock: float, loss: float, step: int
    ) -> Tuple[Optional[np.ndarray], float, int]:
        """The round protocol shared by every merge substrate: publish,
        pick partner, participation gate, fetch, interpolation weight,
        bf16-wire upcast.  Returns (remote_f32_vector | None, alpha,
        partner); None means the round was skipped (self-pair, masked, or
        fetch timeout) and the caller keeps its vector untouched."""
        try:
            self.publish(vec, clock, loss)
            tr = self.tracer
            timing = tr is not None and tr.active
            t0 = time.monotonic() if timing else 0.0
            sched, partner, remapped = self._resolve_partner(step)
            if timing:
                tr.mark("partner_resolve", time.monotonic() - t0)
            self.last_round = {
                "step": step, "sched_partner": sched, "partner": partner,
                "remapped": remapped, "outcome": None,
            }
            # Participation stays keyed on the ORIGINAL pairing (identical
            # threefry draw to the ICI path); remap changes only the fetch
            # target.  A remap to self (no healthy candidate) skips.
            if partner == self.me or not self.schedule.participates(
                step, self.me
            ):
                return None, 0.0, partner
            got = self.fetch(partner, step=step)
            self.last_round["outcome"] = self.last_fetch.get("outcome")
            if "codec" in self.last_fetch:
                self.last_round["codec"] = self.last_fetch["codec"]
            if "trust" in self.last_fetch:
                self.last_round["trust"] = self.last_fetch["trust"]
            if self.last_fetch.get("hedged"):
                self.last_round["hedged"] = True
                self.last_round["hedge_winner"] = self.last_fetch.get(
                    "hedge_winner"
                )
            if got is None:
                # dead/slow peer: skip, keep training
                return None, 0.0, partner
            remote_vec, alpha = self._weigh_remote(got, clock, loss)
            return remote_vec, alpha, partner
        finally:
            # Membership round boundary runs on EVERY exit path —
            # component/quorum state must advance even on skipped rounds
            # (a partitioned node skips every round, and that is exactly
            # when it must notice it is partitioned).
            self._membership_end_round(step)

    def _membership_end_round(self, step: int) -> None:
        if self.membership is not None:
            self.membership.end_round(step)
        if self.incidents is not None or self.flight is not None:
            self._obs_round_end(step)
        elif self._tuner is not None:
            # Tuner without the incident plane: feed the controller its
            # round evidence on the same every-exit-path boundary, but
            # WITHOUT draining membership/trust events (that drain is
            # the incident plane's contract — pop_*_events would lose
            # the buffered copies otherwise).
            self._tune_round_end(step)

    def _obs_round_end(self, step: int) -> None:
        """Incident-plane + flight-recorder round boundary — runs right
        after the membership boundary on EVERY exit path of every
        exchange substrate.  Gathers this round's evidence from state
        the round already produced (``last_round``/``last_fetch``, the
        scoreboard, the membership view, the sketch board) — no extra
        wire traffic, no device syncs."""
        now = time.monotonic()
        wall = None
        if self._obs_round_entry_t is not None:
            # Entry-to-entry wall: compute + exchange, the quantity the
            # SLO-burn detector baselines.
            wall = now - self._obs_round_entry_t
        self._obs_round_entry_t = now
        lr = self.last_round
        this_round = lr.get("step") == step
        peer = lr.get("partner") if this_round else None
        outcome = lr.get("outcome") if this_round else None
        lf = self.last_fetch if this_round else {}
        # Drain membership/trust events HERE so detectors see them the
        # round they happen; adapters still receive every event through
        # the pop_*_events buffers (one drain later at worst).
        events: list = []
        if self.membership is not None:
            evs = self.membership.pop_events()
            events.extend(evs)
            self._membership_event_buf.extend(evs)
        if self.trust is not None:
            evs = self.trust.pop_events()
            events.extend(evs)
            self._trust_event_buf.extend(evs)
        board = (
            self.scoreboard.snapshot()
            if self.scoreboard is not None
            else None
        )
        partition_state = component = None
        if self.membership is not None:
            view = self.membership.view_snapshot()
            partition_state = view.get("partition_state")
            component = view.get("component")
        rel = None
        if self.sketchboard is not None:
            _, rel = self.sketchboard.disagreement()
        if self._tuner is not None and peer is not None and peer != self.me:
            self._tuner.observe(
                peer,
                wall_s=wall,
                wire_s=lf.get("latency_s"),
                soft=outcome in _TUNE_SOFT_OUTCOMES,
                rel=rel,
            )
        stale_peers: Sequence[int] = ()
        if self.async_engine is not None:
            # Peers whose frames the bounded-staleness rule dropped this
            # round — the staleness_storm detector's evidence stream.
            stale_peers = self.async_engine.pop_round_stale()
        fired: list = []
        opened = False
        if self.incidents is not None:
            res = self.incidents.observe_round(
                step,
                outcome=outcome,
                peer=peer,
                board=board,
                events=events,
                rel_rms=rel,
                wall_s=wall,
                partition_state=partition_state,
                component=component,
                stale_peers=stale_peers,
            )
            fired = res["alerts"]
            opened = res["opened"]
        if self.flight is not None:
            self.flight.note_round(
                step,
                partner=peer,
                sched_partner=lr.get("sched_partner") if this_round else None,
                remapped=lr.get("remapped") if this_round else None,
                outcome=outcome,
                codec=lr.get("codec") if this_round else None,
                trust=lr.get("trust") if this_round else None,
                latency_s=lf.get("latency_s"),
                nbytes=lf.get("nbytes"),
                rel_rms=rel,
                wall_s=round(wall, 6) if wall is not None else None,
                partition_state=partition_state,
                events=[e.get("event") for e in events] or None,
                alerts=fired or None,
            )
            if opened:
                # Incident open is a dump trigger: preserve the run-up
                # before the ring scrolls past it.
                self.flight.dump("incident", step)

    def _tune_round_end(self, step: int) -> None:
        """Controller-only round boundary (incident plane off): the
        same entry-to-entry wall + last-fetch spans the obs boundary
        gathers, quantized inside LinkTuner.observe before any decision
        can branch on them."""
        now = time.monotonic()
        wall = None
        if self._obs_round_entry_t is not None:
            wall = now - self._obs_round_entry_t
        self._obs_round_entry_t = now
        lr = self.last_round
        this_round = lr.get("step") == step
        peer = lr.get("partner") if this_round else None
        if peer is None or peer == self.me:
            return
        lf = self.last_fetch if this_round else {}
        outcome = lr.get("outcome") if this_round else None
        rel = None
        if self.sketchboard is not None:
            _, rel = self.sketchboard.disagreement()
        self._tuner.observe(
            peer,
            wall_s=wall,
            wire_s=lf.get("latency_s"),
            soft=outcome in _TUNE_SOFT_OUTCOMES,
            rel=rel,
        )

    def pop_tune_decisions(self) -> list:
        """Drain the controller's buffered ladder decisions (the JSONL
        ``tune`` record kind); [] when the tuner is off."""
        if self._tuner is None:
            return []
        return self._tuner.pop_decisions()

    def _flight_dump_route(self) -> dict:
        """``/flightdump`` healthz route: dump the ring on demand."""
        path = (
            self.flight.dump("endpoint")
            if self.flight is not None
            else None
        )
        out: dict = {"dumped": path is not None}
        if path is not None:
            out["path"] = path
        return out

    def pop_membership_events(self) -> list:
        """Drain membership events (refutations, component changes,
        partition entered/healed) for the metrics JSONL."""
        if self.membership is None:
            return []
        if self.incidents is not None or self.flight is not None:
            out = self._membership_event_buf
            self._membership_event_buf = []
            out.extend(self.membership.pop_events())
            return out
        return self.membership.pop_events()

    def pop_heal_advice(self) -> Optional[dict]:
        """Consume the pending heal-reconciliation advice, if any."""
        if self.membership is None:
            return None
        return self.membership.pop_heal_advice()

    def pop_trust_events(self) -> list:
        """Drain trust events (collapse, recovery, clock resets) for the
        metrics JSONL."""
        if self.trust is None:
            return []
        if self.incidents is not None or self.flight is not None:
            out = self._trust_event_buf
            self._trust_event_buf = []
            out.extend(self.trust.pop_events())
            return out
        return self.trust.pop_events()

    def set_trust_leaves(self, sizes) -> None:
        """Adopt the adapter pytree's leaf sizes so the per-leaf max-abs
        screening statistic follows real parameter boundaries instead of
        fixed segments (adapters call this once at construction)."""
        if self.trust is not None:
            self.trust.set_leaf_sizes(sizes)

    def exchange(
        self, vec: np.ndarray, clock: float, loss: float, step: int
    ) -> Tuple[np.ndarray, float, int]:
        """One full gossip round: publish, pick partner, fetch, merge.

        Returns (merged_vector, alpha_applied, partner).  alpha == 0.0 means
        the round was skipped (self-pair, masked, or fetch timeout).

        With ``protocol.overlap_prefetch`` the wire leg of the NEXT
        round's fetch is launched before this round returns, so the
        caller's compute between exchanges hides the partner stream
        (:meth:`_exchange_pipelined`); the sequential path below is the
        bit-identity reference the pipeline is tested against.

        With ``protocol.async_rounds`` the round goes barrier-free
        through the :class:`~dpwa_tpu.parallel.async_loop
        .AsyncExchangeEngine` instead — publish decoupled from merge,
        pending frames draining staleness-damped — and the returned
        alpha is the damped alpha applied to THIS round's schedule
        partner (0.0 when its frame is still in flight)."""
        if self.async_engine is not None:
            return self._exchange_async(vec, clock, loss, step)
        if self._prefetch_on:
            return self._exchange_pipelined(vec, clock, loss, step)
        tr = self.tracer
        rt = tr is not None and tr.begin_round(step)
        try:
            remote_vec, alpha, partner = self._round(vec, clock, loss, step)
            if remote_vec is None:
                return vec, alpha, partner
            t0 = time.monotonic() if rt else 0.0
            merged = self._merge_remote(vec, remote_vec, alpha)
            if rt:
                tr.mark("merge", time.monotonic() - t0)
                tr.set(alpha=float(alpha))
            return merged, alpha, partner
        finally:
            if rt:
                self._trace_finish(tr)

    def _exchange_pipelined(
        self, vec: np.ndarray, clock: float, loss: float, step: int
    ) -> Tuple[np.ndarray, float, int]:
        """One gossip round through the double-buffered prefetch slot.

        Steady state per round ``t``: publish x_t, JOIN the slot that has
        been streaming partner(t)'s frame since round t−1 (the caller's
        compute between exchanges is what the stream hid under), LAUNCH
        round t+1's wire fetch on a fresh background slot, then decode →
        guard → trust-screen → merge round t's payload.  Everything
        judgemental runs at consume time against the replica published
        THIS round — the publish-clock guard: a payload whose fetch
        straddled our publish is screened against the current local
        view, never the one that existed at launch (``straddled`` counts
        those rounds).  Failure semantics (busy, slow, hedge losers,
        chaos partitions) are charged to the consuming round's step, and
        a partition that opened after launch still refuses the payload
        at consume (:meth:`_prefetch_take`)."""
        t_entry = time.monotonic()
        with self._stats_lock:
            o = self._overlap
            if self._pipe_last_entry is not None:
                # Entry-to-entry wall clock — the denominator of the
                # overlap-occupancy column (compute + exchange, everything).
                o["round_s"] += t_entry - self._pipe_last_entry
            self._pipe_last_entry = t_entry
            o["rounds"] += 1
        tr = self.tracer
        rt = tr is not None and tr.begin_round(step)
        try:
            self.publish(vec, clock, loss)
            raw, sched, partner, remapped = self._prefetch_take(step)
            self.last_round = {
                "step": step, "sched_partner": sched, "partner": partner,
                "remapped": remapped, "outcome": None,
            }
            # Launch round t+1's wire leg BEFORE consuming round t: the
            # stream overlaps this round's decode/screen/merge and the
            # caller's next compute interval.
            self._prefetch_launch(step + 1, self._wire_nbytes(vec))
            if raw is None:
                return vec, 0.0, partner
            got = self._consume_fetch(raw, step)
            self.last_round["outcome"] = self.last_fetch.get("outcome")
            if "codec" in self.last_fetch:
                self.last_round["codec"] = self.last_fetch["codec"]
            if "trust" in self.last_fetch:
                self.last_round["trust"] = self.last_fetch["trust"]
            if self.last_fetch.get("hedged"):
                self.last_round["hedged"] = True
                self.last_round["hedge_winner"] = self.last_fetch.get(
                    "hedge_winner"
                )
            if got is None:
                return vec, 0.0, partner
            remote_vec, alpha = self._weigh_remote(got, clock, loss)
            t_m = time.monotonic() if rt else 0.0
            merged = self._merge_remote(vec, remote_vec, alpha)
            if rt:
                tr.mark("merge", time.monotonic() - t_m)
                tr.set(alpha=float(alpha))
            return merged, alpha, partner
        finally:
            self._membership_end_round(step)
            if rt:
                self._trace_finish(tr)

    def _exchange_async(
        self, vec: np.ndarray, clock: float, loss: float, step: int
    ) -> Tuple[np.ndarray, float, int]:
        """Adapt the async engine's ``(vec, merges)`` round to the
        lock-step ``(vec, alpha, partner)`` contract: the reported alpha
        is the staleness-damped alpha of this round's resolved partner
        when its frame merged, else the LAST merge applied (pending
        frames from other peers fold in the same round).  Callers treat
        ``alpha != 0.0`` as "the replica moved", so it must be non-zero
        whenever ANY frame merged — 0.0 only for a genuinely empty
        round, exactly what a skipped lock-step round reports."""
        merged, merges = self.async_engine.exchange(vec, clock, loss, step)
        partner = self.last_round.get("partner", self.me)
        alpha = merges[-1][1] if merges else 0.0
        for peer, damped, _lag in merges:
            if peer == partner:
                alpha = damped
        return merged, alpha, partner

    def _trace_finish(self, tr) -> None:
        """Close the active round trace with the round's resolution
        fields (from ``last_round``/``last_fetch``) plus the current
        sketch-based disagreement estimate when the board is on."""
        lr = self.last_round
        fields = {
            "partner": lr.get("partner"),
            "sched_partner": lr.get("sched_partner"),
            "remapped": lr.get("remapped"),
            "outcome": lr.get("outcome"),
            "codec": lr.get("codec"),
        }
        if lr.get("outcome") is not None:
            fields["nbytes"] = self.last_fetch.get("nbytes")
        if lr.get("hedged"):
            fields["hedged"] = True
        if self.sketchboard is not None:
            rms, rel = self.sketchboard.disagreement()
            fields["disagreement_rms"] = rms
            fields["disagreement_rel"] = rel
        tr.end_round(**fields)

    def _prefetch_launch(self, step: int, expected_nbytes: int) -> None:
        """Arm the slot for round ``step``: resolve its partner NOW (the
        scoreboard view is one round younger than a sequential resolve
        would see — acceptable prefetch skew, the pipeline is config-
        gated) and start the wire leg on a daemon thread.  A slot whose
        round does not participate (self-pair / masked) is armed with no
        thread so the take side still returns its partner resolution."""
        tr = self.tracer
        timing = tr is not None and tr.active
        t0 = time.monotonic() if timing else 0.0
        sched, partner, remapped = self._resolve_partner(step)
        if timing:
            tr.mark("partner_resolve", time.monotonic() - t0)
        slot = {
            "step": step, "sched": sched, "partner": partner,
            "remapped": remapped, "expected_nbytes": int(expected_nbytes),
            "thread": None, "box": [], "t_start": 0.0, "t_end": [0.0],
        }
        if partner != self.me and self.schedule.participates(step, self.me):
            box, t_end = slot["box"], slot["t_end"]

            def _run():
                box.append(self._wire_fetch(partner, step=step))
                t_end[0] = time.monotonic()

            slot["t_start"] = time.monotonic()
            th = threading.Thread(
                target=_run, daemon=True,
                name=f"dpwa-prefetch:{self.port}",
            )
            slot["thread"] = th
            th.start()
        self._prefetch_slot = slot

    def _prefetch_take(self, step: int) -> tuple:
        """Claim the slot for round ``step``: ``(raw_9tuple | None,
        sched, partner, remapped)``.

        A cold pipeline (first round) or a step discontinuity resolves
        and fetches synchronously — correctness never depends on the
        slot being warm.  The join backstop mirrors the overlapped
        exchange's: the wire leg's own cumulative deadline (doubled
        under flowctl for a hedge's two sequential budgets) plus the
        per-byte allowance for the expected frame, so a healthy large
        stream is never abandoned while a hung leg cannot stall the
        round — a lapsed join skips the merge like any failed fetch."""
        slot, self._prefetch_slot = self._prefetch_slot, None
        tr = self.tracer
        timing = tr is not None and tr.active
        if slot is None or slot["step"] != step:
            sched, partner, remapped = self._resolve_partner(step)
            if partner == self.me or not self.schedule.participates(
                step, self.me
            ):
                return None, sched, partner, remapped
            t0 = time.monotonic()
            raw = self._wire_fetch(partner, step=step)
            dt = time.monotonic() - t0
            # A synchronous fill is all join-wait: nothing was hidden.
            with self._stats_lock:
                o = self._overlap
                o["fetch_s"] += dt
                o["join_wait_s"] += dt
                o["inflight_s"] += dt
            if timing:
                tr.mark("join_wait", dt)
                tr.set(prefetched=False)
            return raw, sched, partner, remapped
        sched, partner, remapped = (
            slot["sched"], slot["partner"], slot["remapped"]
        )
        th = slot["thread"]
        if th is None:
            return None, sched, partner, remapped
        with self._stats_lock:
            self._overlap["prefetched"] += 1
        if timing:
            tr.set(prefetched=True, straddled=slot["t_end"][0] == 0.0)
        if slot["t_end"][0] == 0.0:
            # Still streaming as this round's publish landed: the
            # payload straddled a local publish and the consume-time
            # screen (not any launch-time state) is what judges it.
            with self._stats_lock:
                self._overlap["straddled"] += 1
        fc = self.config.flowctl
        base_s = self.config.protocol.timeout_ms / 1000.0
        if fc.enabled:
            base_s = 2.0 * max(base_s, fc.max_ms / 1000.0)
        t_join = time.monotonic()
        th.join(
            1.0
            + base_s
            + slot["expected_nbytes"]
            / (self.config.protocol.min_wire_mb_per_s * 1e6)
        )
        join_dt = time.monotonic() - t_join
        if timing:
            tr.mark("join_wait", join_dt)
        t_end = slot["t_end"][0] or time.monotonic()
        span = max(t_end - slot["t_start"], 0.0)
        with self._stats_lock:
            o = self._overlap
            o["join_wait_s"] += join_dt
            o["fetch_s"] += span
            o["inflight_s"] += span
        if not slot["box"]:
            # Join backstop lapsed: the daemon leg keeps running but
            # this round moves on without a merge.
            return None, sched, partner, remapped
        raw = slot["box"][0]
        if self._link_blocked(partner):
            # A chaos partition keyed on the CURRENT publish clock —
            # the consuming round's — refuses the payload even though
            # the launch-time check (one clock earlier) let the wire
            # leg run: partition semantics charge the consuming round.
            raw = (partner, None, Outcome.REFUSED, 0.0, 0, None, None,
                   False, None)
        return raw, sched, partner, remapped

    def exchange_overlapped_start(
        self, vec: np.ndarray, clock: float, loss: float, step: int
    ) -> "_OverlappedExchange":
        """Begin a gossip round that OVERLAPS the partner fetch with the
        caller's compute — the TCP twin of the SPMD paths'
        ``overlap=True`` (publish the PRE-step replica, never gate the
        exchange wire time on this step's fwd/bwd).

        Publishes ``vec`` (the pre-step replica), resolves
        partner/participation, and starts the fetch on a daemon thread;
        the caller runs its local step, then calls
        :meth:`_OverlappedExchange.finish` with its pre-step vector and
        the step's update to get ``merge(pre, remote) + update`` — the
        exact ``overlap=True`` algebra of
        :func:`dpwa_tpu.train.make_gossip_train_step`."""
        self.publish(vec, clock, loss)
        ex = _OverlappedExchange(
            self, clock, loss, step, expected_nbytes=self._wire_nbytes(vec)
        )
        ex.start()
        return ex

    def exchange_on_device(
        self, vec_dev, clock: float, loss: float, step: int
    ):
        """:meth:`exchange` with a DEVICE-RESIDENT replica (VERDICT r3 #6).

        ``vec_dev`` is a flat f32 JAX array living on an accelerator (or
        the forced-CPU backend standing in for one): the local replica
        never exists as host state — TCP is only the wire.  Per round:
        download once to publish (the wire needs host bytes; on real
        hardware this is the device→NIC staging copy), fetch the
        partner's bytes, upload them, and merge ON DEVICE with a jitted
        lerp.  Returns ``(merged_device_vec, alpha, partner)`` with the
        result still on the device; alpha == 0.0 means the round was
        skipped and ``vec_dev`` is returned untouched (no copies).

        This is the reference's free-running async semantics executed on
        the rebuild's actual data plane — each OS process free-runs its
        own device-resident replica — where the lock-step SPMD paths
        emulate it with masked merges.

        The data plane is the device merge engine (docs/device.md): the
        publish-side readback is LAZY (a skipped round republishes from
        the cached host mirror for free), the consume leg keeps sparse
        frames sparse (``_sparse_consume``), and every codec family
        merges through one fused kernel — scatter-lerp for top-k,
        dynamic-slice lerp for shards (the slice-only invariant is
        structural, no host round-trip), in-kernel bitcast+upcast for
        bf16 wires.

        With ``protocol.async_rounds`` the round goes barrier-free
        through the async engine's device drain instead — same
        ``(merged, alpha, partner)`` adaptation as :meth:`exchange`."""
        if self.async_engine is not None:
            merged, merges = self.async_engine.exchange_on_device(
                vec_dev, clock, loss, step
            )
            partner = self.last_round.get("partner", self.me)
            alpha = merges[-1][1] if merges else 0.0
            for peer, damped, _lag in merges:
                if peer == partner:
                    alpha = damped
            return merged, alpha, partner
        from dpwa_tpu.device import DeviceReplica, default_engine

        eng = default_engine()
        rep = self._dev_replica
        if rep is None or rep.dev is not vec_dev:
            # A replica the engine didn't produce (first round, or the
            # caller trained on a fresh array): adopt it; its mirror is
            # read back once below and cached until the next merge.
            rep = DeviceReplica(vec_dev)
            self._dev_replica = rep
        host_vec = rep.host()
        self._sparse_consume = True
        try:
            remote_vec, alpha, partner = self._round(
                host_vec, clock, loss, step
            )
        finally:
            self._sparse_consume = False
        eng.note_round()
        if remote_vec is None:
            return rep.dev, alpha, partner
        if self._pending_topk is not None:
            idx, vals = self._pending_topk
            merged = eng.merge_topk(rep.dev, idx, vals, alpha)
        elif self._pending_shard is not None:
            # remote_vec IS the m-sized slice estimate (the consume leg
            # never densified); the kernel lerps [lo, lo+m) in-graph and
            # rides the other k−1 slices through bit-identically.
            lo, _hi = self._pending_shard
            merged = eng.merge_shard(rep.dev, lo, remote_vec, alpha)
        elif ml_dtypes is not None and remote_vec.dtype == _DTYPES[3]:
            merged = eng.merge_bf16(rep.dev, remote_vec, alpha)
        else:
            if remote_vec.dtype != np.float32:
                remote_vec = remote_vec.astype(np.float32)
            merged = eng.merge_dense(rep.dev, remote_vec, alpha)
        rep.swap(merged)
        return merged, alpha, partner

    def exchange_on_device_fold(
        self, vec_dev, clock: float, loss: float, step: int,
        peers: Sequence[int],
    ):
        """Fan-in round: fetch a frame from EACH listed peer and fold
        every accepted one into the device replica, batching runs of
        consecutive dense frames into single ``fold`` dispatches.

        Where :meth:`exchange_on_device` is the schedule-driven pairwise
        round (one partner, one frame), this is the explicit fan-in the
        batched-fold kernel exists for: hedged/prefetch legs or an
        experiment harness that drains several ready peers at once.
        Each frame still runs the full consume leg — decode, guard,
        trust screen, scoreboard — exactly as a pairwise round would,
        and the result is bit-identical to applying the accepted frames
        as sequential :meth:`exchange_on_device` merges in arrival
        order (the fold kernel's ``lax.scan`` contract).  Sparse and
        bf16 frames break a dense run and dispatch their own fused
        kernel, preserving arrival order.

        Returns ``(merged_device_vec, merges)`` where ``merges`` is the
        arrival-ordered list of ``(peer, alpha)`` actually applied."""
        from dpwa_tpu.device import DeviceReplica, default_engine

        eng = default_engine()
        rep = self._dev_replica
        if rep is None or rep.dev is not vec_dev:
            rep = DeviceReplica(vec_dev)
            self._dev_replica = rep
        self.publish(rep.host(), clock, loss)
        frames = []  # (kind, payload, peer, alpha) in arrival order
        self._sparse_consume = True
        try:
            for peer in peers:
                if peer == self.me:
                    continue
                got = self.fetch(peer, step=step)
                if got is None:
                    continue
                remote_vec, alpha = self._weigh_remote(got, clock, loss)
                frames.append(
                    self._classify_device_frame(remote_vec, peer, alpha)
                )
        finally:
            self._sparse_consume = False
            self._membership_end_round(step)
        merges = [(peer, alpha) for _, _, peer, alpha in frames]
        merged = self._apply_device_frames(eng, rep.dev, frames)
        eng.note_round()
        if merged is not rep.dev:
            rep.swap(merged)
        return merged, merges

    def _classify_device_frame(
        self, remote_vec, peer: int, alpha: float
    ) -> tuple:
        """Map one sparse-mode consumed frame to its device-merge
        descriptor ``(kind, payload, peer, alpha)``, reading the
        double-buffered pending support ``_consume_fetch`` just set —
        must therefore run before the next consume, like the merge
        substrates themselves."""
        if self._pending_topk is not None:
            return ("topk", self._pending_topk, peer, alpha)
        if self._pending_shard is not None:
            # remote_vec IS the m-sized slice estimate (sparse consume
            # never densified); the kernel lerps [lo, lo+m) in-graph.
            return (
                "shard", (self._pending_shard[0], remote_vec), peer, alpha,
            )
        if ml_dtypes is not None and remote_vec.dtype == _DTYPES[3]:
            return ("bf16", remote_vec, peer, alpha)
        if remote_vec.dtype != np.float32:
            remote_vec = remote_vec.astype(np.float32)
        return ("dense", remote_vec, peer, alpha)

    def _apply_device_frames(
        self, eng, start_dev, frames: Sequence[tuple], fold: bool = True,
    ):
        """Apply device-frame descriptors in order onto ``start_dev``.

        Runs of consecutive dense frames batch into single ``fold``
        dispatches — bit-identical to applying them as sequential
        merges (the fold kernel's ``lax.scan`` contract); sparse and
        bf16 frames break a run and dispatch their own fused kernel,
        preserving order.  ``fold=False`` dispatches one kernel per
        frame (``async_rounds.fold`` off).  Shared by the fan-in fold
        round and the async engine's device drain; returns the merged
        device array (the caller swaps the replica)."""
        merged = start_dev
        run_r: list = []
        run_a: list = []

        def _flush_dense():
            nonlocal merged
            if not run_r:
                return
            if len(run_r) == 1 or not fold:
                for r, a in zip(run_r, run_a):
                    merged = eng.merge_dense(merged, r, a)
            else:
                merged = eng.fold(merged, list(run_r), list(run_a))
            run_r.clear()
            run_a.clear()

        for kind, payload, _peer, alpha in frames:
            if kind == "dense":
                run_r.append(payload)
                run_a.append(alpha)
                continue
            _flush_dense()
            if kind == "topk":
                idx, vals = payload
                merged = eng.merge_topk(merged, idx, vals, alpha)
            elif kind == "shard":
                lo, est_slice = payload
                merged = eng.merge_shard(merged, lo, est_slice, alpha)
            else:
                merged = eng.merge_bf16(merged, payload, alpha)
        _flush_dense()
        return merged

    def close(self) -> None:
        if self.flight is not None:
            # Clean-close dump, then drop the crash hooks — atexit must
            # not overwrite this dump with a shorter post-close ring.
            self.flight.dump("close")
            self.flight.disarm()
        if self.incidents is not None:
            self.incidents.close()
        if self.healthz is not None:
            self.healthz.close()
        if self.tracer is not None:
            self.tracer.close()
        self.server.close()
