"""Reactor Rx server tests (ISSUE 10, docs/transport.md).

Covers the event-loop server's headline claims:

- a 256-simulated-peer in-process ring is served with bounded wall time
  on ONE loop thread (tests/fleet_worker.py drives the fleet);
- the PR 5 malformed-frame corpus — truncations, bit-flipped magics,
  lying length fields, garbage, RST mid-request — always ends in a
  closed connection and a live loop, never a stall;
- a 4-node soak under ``rx_server=reactor`` produces byte-identical
  merge trajectories to the threaded server;
- chaos composes with the reactor: ``rx_server: reactor`` +
  ``chaos.enabled`` selects the event-loop chaos server, which serves
  byte-identical faults to the threaded wrapper (the identity matrix
  lives in tests/test_fleet.py);
- the observability surface: ``reactor`` sub-document in
  ``health_snapshot()`` and ``dpwa_reactor_*`` families on /metrics.

The shed/evict/busy semantics shared with the threaded server are
pinned by the parameterized tests in test_flowctl.py, test_membership.py
and test_tcp_transport.py.
"""

import socket
import struct
import time

import numpy as np

from dpwa_tpu.config import FlowctlConfig, make_local_config
from dpwa_tpu.health import Outcome
from dpwa_tpu.obs.prometheus import MetricsRegistry
from dpwa_tpu.parallel.reactor import ReactorPeerServer, register_metrics
from dpwa_tpu.parallel.tcp import (
    _RELAY_REQ,
    _REQ,
    _STATE_REQ,
    TcpTransport,
    fetch_blob_ex,
    fetch_blob_full,
)

from tests.fleet_worker import (
    close_connections,
    held_open,
    hold_connections,
    run_fleet,
)


def _open_flowctl(**kw):
    """Token pacing opened up: every simulated peer shares 127.0.0.1, so
    the per-host bucket would throttle the harness, not model reality."""
    kw.setdefault("token_rate", 1e9)
    kw.setdefault("token_burst", 1e9)
    return FlowctlConfig(**kw)


def make_ring(n, **cfg_kwargs):
    cfg = make_local_config(n, base_port=0, **cfg_kwargs)
    ts = [TcpTransport(cfg, f"node{i}") for i in range(n)]
    for t in ts:
        for i, other in enumerate(ts):
            t.set_peer_port(i, other.port)
    return ts


def close_all(ts):
    for t in ts:
        t.close()


# ---------------------------------------------------------------------------
# Large-N harness: 256 simulated peers, one loop thread
# ---------------------------------------------------------------------------


def test_reactor_serves_256_fetching_peers_bounded_wall():
    srv = ReactorPeerServer("127.0.0.1", 0, flowctl=_open_flowctl())
    try:
        srv.publish(np.arange(4096, dtype=np.float32), 1.0, 0.1)
        fleet = run_fleet(srv.port, n_peers=256, rounds=2, workers=16)
        assert fleet["outcomes"] == {Outcome.SUCCESS: 512}
        # Bounded per-round wall: 512 fetches of a 16 KiB blob on
        # loopback finish in well under a minute even on a loaded CI
        # box (observed ~1 s); a stalled loop would eat the full fetch
        # timeout per request instead.
        assert fleet["wall_s"] < 60.0
        # The client can see its last payload a beat before the loop
        # thread books the completed write, so give the counters a
        # moment to settle.
        deadline = time.monotonic() + 5.0
        while (
            srv.reactor_snapshot()["frames"] < 512
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        snap = srv.reactor_snapshot()
        assert snap["frames"] == 512
        assert snap["accepted"] >= 512
        assert snap["open"] == 0
    finally:
        srv.close()


def test_reactor_holds_256_idle_peers_and_still_serves():
    srv = ReactorPeerServer("127.0.0.1", 0, flowctl=_open_flowctl())
    try:
        srv.publish(np.arange(64, dtype=np.float32), 1.0, 0.1)
        socks = hold_connections(srv.port, 256)
        try:
            deadline = time.monotonic() + 10.0
            while (
                srv.reactor_snapshot()["open"] < 256
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            assert held_open(socks) == 256
            # A fresh probe is served while all 256 holds stay open —
            # the thread-per-connection server tops out at its 32-thread
            # cap here.
            got, outcome, *_ = fetch_blob_full("127.0.0.1", srv.port, 2000)
            assert outcome == Outcome.SUCCESS
            assert srv.reactor_snapshot()["peak_open"] >= 256
        finally:
            close_connections(socks)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Malformed-frame corpus (PR 5) against the reactor
# ---------------------------------------------------------------------------


def _corpus(rng):
    """Request-side corpus: truncations, bit-flips, lying lengths,
    garbage.  Each case is (label, payload_bytes, rst_close)."""
    cases = [
        ("empty", b"", False),
        ("trunc-1", _REQ[:1], False),
        ("trunc-2", _REQ[:2], False),
        ("trunc-4", _REQ[:4], False),  # prefix of ALL three verbs
        ("garbage-12", bytes(rng.integers(0, 256, 12, dtype=np.uint8)), False),
    ]
    for verb, name in ((_REQ, "blob"), (_STATE_REQ, "state"),
                       (_RELAY_REQ, "relay")):
        flipped = bytearray(verb)
        flipped[4] ^= 0x20  # bit-flip the verb byte
        cases.append((f"bitflip-{name}", bytes(flipped), False))
    # Lying lengths: a relay body promising 64 host bytes but sending 3,
    # and a state body cut mid-struct then RST.
    cases.append(
        (
            "lying-relay-hostlen",
            _RELAY_REQ + struct.pack("<HHIB", 1, 9, 200, 64) + b"127",
            False,
        )
    )
    cases.append(("trunc-state-body", _STATE_REQ + b"\x00\x01\x02", True))
    cases.append(("rst-mid-request", _REQ[:3], True))
    return cases


def test_reactor_fuzz_corpus_closes_clean_and_loop_survives():
    srv = ReactorPeerServer(
        "127.0.0.1", 0,
        flowctl=_open_flowctl(request_timeout_ms=300),
    )
    rng = np.random.default_rng(0xBEEF)
    try:
        srv.publish(np.arange(8, dtype=np.float32), 1.0, 0.5)
        for label, payload, rst in _corpus(rng):
            with socket.create_connection(
                ("127.0.0.1", srv.port), timeout=5
            ) as c:
                if payload:
                    c.sendall(payload)
                if rst:
                    c.setsockopt(
                        socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0),
                    )
                    continue
                # The server must CLOSE the connection — immediately for
                # recognized garbage, at the 300 ms request deadline for
                # a stalled prefix — never hold it open indefinitely.
                c.settimeout(3.0)
                assert c.recv(16) == b"", label
        # The loop survived the barrage: admission slots all drained and
        # a well-formed fetch succeeds.
        deadline = time.monotonic() + 5.0
        while (
            srv.admission.snapshot()["active"] > 0
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        assert srv.admission.snapshot()["active"] == 0
        got, outcome, *_ = fetch_blob_full("127.0.0.1", srv.port, 1000)
        assert outcome == Outcome.SUCCESS
        np.testing.assert_array_equal(got[0], np.arange(8, dtype=np.float32))
    finally:
        srv.close()


def test_fetcher_classifies_reactor_short_frames():
    """The PR 5 fetcher-side taxonomy holds against the reactor: nothing
    published -> clean EOF is a classified failure, not a hang."""
    srv = ReactorPeerServer("127.0.0.1", 0, flowctl=_open_flowctl())
    try:
        t0 = time.monotonic()
        res, outcome, *_ = fetch_blob_full("127.0.0.1", srv.port, 500)
        assert res is None
        assert outcome in (Outcome.SHORT_READ, Outcome.TIMEOUT)
        assert time.monotonic() - t0 < 3.0
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Byte-identity soak: threaded vs reactor merge trajectories
# ---------------------------------------------------------------------------


def _soak(rx, steps=8):
    ts = make_ring(4, schedule="ring", seed=5, factor=0.5, rx_server=rx)
    try:
        vecs = [np.full(256, float(i + 1), np.float32) for i in range(4)]
        traj = []
        for step in range(steps):
            for i, t in enumerate(ts):
                t.publish(vecs[i], float(step + 1), 0.1)
            for i, t in enumerate(ts):
                merged, alpha, _ = t.exchange(
                    vecs[i], float(step + 1), 0.1, step
                )
                if alpha != 0.0:
                    vecs[i] = np.asarray(merged, np.float32)
            traj.append([v.tobytes() for v in vecs])
        return traj
    finally:
        close_all(ts)


def test_reactor_soak_is_byte_identical_to_threaded():
    assert _soak("threaded") == _soak("reactor")


def test_chaos_selects_matching_server_per_rx_backend():
    """Chaos no longer forces the threaded wrapper: under
    ``rx_server: reactor`` the event-loop chaos server is selected, so
    the soak's Rx architecture survives fault injection.  The two
    servers share the pure frame mutators, making the served fault
    bytes identical (tests/test_fleet.py pins the matrix)."""
    from dpwa_tpu.health.chaos import (
        ChaosPeerServer,
        ChaosReactorPeerServer,
    )

    cfg = make_local_config(
        2, base_port=0, rx_server="reactor",
        chaos=dict(enabled=True, seed=1),
    )
    ts = [TcpTransport(cfg, f"node{i}") for i in range(2)]
    try:
        assert all(
            isinstance(t.server, ChaosReactorPeerServer) for t in ts
        )
    finally:
        close_all(ts)
    cfg = make_local_config(
        2, base_port=0, chaos=dict(enabled=True, seed=1),
    )
    ts = [TcpTransport(cfg, f"node{i}") for i in range(2)]
    try:
        assert all(isinstance(t.server, ChaosPeerServer) for t in ts)
    finally:
        close_all(ts)


# ---------------------------------------------------------------------------
# Observability surface
# ---------------------------------------------------------------------------


def test_reactor_subdocument_in_health_snapshot():
    ts = make_ring(2, schedule="ring", rx_server="reactor", timeout_ms=500)
    try:
        for i, t in enumerate(ts):
            t.publish(np.full(8, float(i + 1), np.float32), 1.0, 0.1)
        assert ts[0].fetch(1, step=0) is not None
        # fetch(1) was served by NODE 1's reactor; node 0's sub-document
        # is present but idle.
        served = ts[1].health_snapshot()["reactor"]
        assert served["frames"] >= 1 and served["accepted"] >= 1
        r = ts[0].health_snapshot()["reactor"]
        for key in (
            "open", "peak_open", "evicted", "busy_shed",
            "loop_lag_ms", "ready_depth", "relay_pending",
        ):
            assert key in r
    finally:
        close_all(ts)
    # The threaded server exports no reactor block.
    ts2 = make_ring(2, schedule="ring", timeout_ms=500)
    try:
        assert "reactor" not in ts2[0].health_snapshot()
    finally:
        close_all(ts2)


def test_reactor_prometheus_families():
    srv = ReactorPeerServer("127.0.0.1", 0, flowctl=_open_flowctl())
    try:
        srv.publish(np.arange(8, dtype=np.float32), 1.0, 0.1)
        assert fetch_blob_ex("127.0.0.1", srv.port, 1000)[0] is not None
        # Same settle as the 256-peer test: the client sees its payload
        # a beat before the loop thread books the completed write.
        deadline = time.monotonic() + 5.0
        while (
            srv.reactor_snapshot()["frames"] < 1
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        reg = MetricsRegistry()
        register_metrics(reg, srv)
        text = reg.render()
        for name in (
            "dpwa_reactor_loop_lag_ms",
            "dpwa_reactor_ready_depth",
            "dpwa_reactor_open_connections",
            "dpwa_reactor_peak_connections",
            "dpwa_reactor_accepted_total",
            "dpwa_reactor_evicted_total",
            "dpwa_reactor_busy_shed_total",
            "dpwa_reactor_frames_served_total",
            "dpwa_reactor_relay_pending",
        ):
            assert name in text
        assert "dpwa_reactor_frames_served_total 1" in text
    finally:
        srv.close()
