"""TCP transport tests: framing, Rx thread, timeouts, lock-step exchange."""

import threading

import numpy as np
import pytest

from dpwa_tpu.config import make_local_config
from dpwa_tpu.parallel.tcp import (
    NativePeerServer,
    PeerServer,
    TcpTransport,
    fetch_blob,
    make_peer_server,
)
from dpwa_tpu.parallel.reactor import ReactorPeerServer

# Core transport semantics must hold on both Rx servers — the threaded
# thread-per-connection PeerServer and the event-loop reactor behind the
# ``protocol.rx_server`` switch (docs/transport.md).
_RX_SERVERS = pytest.mark.parametrize(
    "server_cls", [PeerServer, ReactorPeerServer],
    ids=["threaded", "reactor"],
)
_RX_CONFIGS = pytest.mark.parametrize("rx", ["threaded", "reactor"])


def test_native_rx_server_parity_with_python_server():
    """The C++ Rx server must serve byte-identical blobs and metadata to
    the Python thread for every wire dtype, including publish overwrite
    and the no-payload-yet case."""
    try:
        nat = NativePeerServer("127.0.0.1", 0)
    except (RuntimeError, OSError):
        pytest.skip("native toolchain unavailable")
    py = PeerServer("127.0.0.1", 0)
    try:
        # Before any publish: fetch must come back empty (None) from both.
        assert fetch_blob("127.0.0.1", nat.port, 500) is None
        assert fetch_blob("127.0.0.1", py.port, 500) is None
        for dtype in (np.float32, np.float64):
            vec = np.arange(513, dtype=dtype)
            nat.publish(vec, 7.0, 0.125)
            py.publish(vec, 7.0, 0.125)
            got_n = fetch_blob("127.0.0.1", nat.port, 2000)
            got_p = fetch_blob("127.0.0.1", py.port, 2000)
            assert got_n is not None and got_p is not None
            np.testing.assert_array_equal(got_n[0], got_p[0])
            assert got_n[1:] == got_p[1:] == (7.0, 0.125)
        # Overwrite: latest publish wins.
        nat.publish(np.full(8, 9.0, np.float32), 8.0, 0.5)
        vec, clock, loss = fetch_blob("127.0.0.1", nat.port, 2000)
        np.testing.assert_array_equal(vec, np.full(8, 9.0, np.float32))
        assert (clock, loss) == (8.0, 0.5)
    finally:
        nat.close()
        py.close()


def test_native_rx_server_serves_concurrent_fetchers():
    """Several peers fetching at once must all get complete blobs (the
    native loop serves connections sequentially; concurrency shows up as
    queued accepts, never partial or interleaved payloads)."""
    try:
        srv = NativePeerServer("127.0.0.1", 0)
    except (RuntimeError, OSError):
        pytest.skip("native toolchain unavailable")
    try:
        vec = np.arange(200_000, dtype=np.float32)  # ~800 KB blob
        srv.publish(vec, 5.0, 0.75)
        results = [None] * 6

        def fetch(i):
            results[i] = fetch_blob("127.0.0.1", srv.port, 5000)

        threads = [
            threading.Thread(target=fetch, args=(i,))
            for i in range(len(results))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for got in results:
            assert got is not None
            np.testing.assert_array_equal(got[0], vec)
            assert got[1:] == (5.0, 0.75)
    finally:
        srv.close()


def test_make_peer_server_env_fallback(monkeypatch):
    monkeypatch.setenv("DPWA_NATIVE_RX", "0")
    srv = make_peer_server("127.0.0.1", 0)
    try:
        assert isinstance(srv, PeerServer)
    finally:
        srv.close()


def make_ring(n, **cfg_kwargs):
    """n transports on OS-assigned ports, all wired to each other."""
    cfg = make_local_config(n, base_port=0, **cfg_kwargs)
    ts = [TcpTransport(cfg, f"node{i}") for i in range(n)]
    for t in ts:
        for i, other in enumerate(ts):
            t.set_peer_port(i, other.port)
    return ts


def close_all(ts):
    for t in ts:
        t.close()


def test_base_port_zero_leaves_every_port_to_the_os():
    # base_port=0 used to give node i port 0 + i: node 0 was bound by the
    # OS, node 1 bound 127.0.0.1:1, node 2 bound :2, and two test workers
    # building rings at once collided ("Address already in use").
    assert [n.port for n in make_local_config(3, base_port=0).nodes] == [0] * 3
    assert [n.port for n in make_local_config(3, base_port=7).nodes] == [
        7, 8, 9,
    ]
    rings = [make_ring(3), make_ring(3)]  # side by side, both alive
    try:
        ports = [t.port for ring in rings for t in ring]
        assert len(set(ports)) == 6 and all(p > 1023 for p in ports), ports
        for ring in rings:
            assert [t._ports[i][1] for t in ring for i in range(3)] == [
                t.port for t in ring
            ] * 3
    finally:
        for ring in rings:
            close_all(ring)


@_RX_SERVERS
def test_publish_fetch_roundtrip(server_cls):
    server = server_cls("127.0.0.1", 0)
    try:
        vec = np.arange(1000, dtype=np.float32)
        server.publish(vec, clock=7.0, loss=0.25)
        got = fetch_blob("127.0.0.1", server.port, timeout_ms=2000)
        assert got is not None
        out, clock, loss = got
        np.testing.assert_array_equal(out, vec)
        assert clock == 7.0 and loss == 0.25
    finally:
        server.close()


@_RX_SERVERS
def test_fetch_before_publish_returns_none_payload_safely(server_cls):
    server = server_cls("127.0.0.1", 0)
    try:
        # Nothing published yet: the Rx thread sends nothing and the client
        # times out cleanly instead of crashing.
        got = fetch_blob("127.0.0.1", server.port, timeout_ms=200)
        assert got is None
    finally:
        server.close()


def test_fetch_dead_peer_times_out():
    # Nothing listening on this port.
    got = fetch_blob("127.0.0.1", 1, timeout_ms=200)
    assert got is None


@_RX_SERVERS
def test_publish_overwrites(server_cls):
    server = server_cls("127.0.0.1", 0)
    try:
        server.publish(np.zeros(4, np.float32), 0, 0)
        server.publish(np.ones(4, np.float32), 1, 0)
        out, clock, _ = fetch_blob("127.0.0.1", server.port, 2000)
        np.testing.assert_array_equal(out, np.ones(4, np.float32))
        assert clock == 1.0
    finally:
        server.close()


@_RX_SERVERS
def test_float64_and_bf16_roundtrip(server_cls):
    server = server_cls("127.0.0.1", 0)
    try:
        vec = np.linspace(0, 1, 17, dtype=np.float64)
        server.publish(vec, 0, 0)
        out, _, _ = fetch_blob("127.0.0.1", server.port, 2000)
        assert out.dtype == np.float64
        np.testing.assert_array_equal(out, vec)
    finally:
        server.close()


@_RX_CONFIGS
def test_two_peer_lockstep_exchange_is_half_merge(rx):
    ts = make_ring(2, factor=0.5, rx_server=rx)
    try:
        # Nonzero on both sides: an all-zero replica served to a nonzero
        # peer is now rejected as zero-energy (recovery guard).
        v0 = np.full(64, 0.25, np.float32)
        v1 = np.full(64, 0.75, np.float32)
        # Lock-step: both publish before either fetches (barrier), so both
        # merge against pre-merge state — the ICI semantics.
        ts[0].publish(v0, 1, 0.5)
        ts[1].publish(v1, 1, 0.5)
        m0, a0, p0 = ts[0].exchange(v0, 1, 0.5, step=0)
        m1, a1, p1 = ts[1].exchange(v1, 1, 0.5, step=0)
        assert (p0, p1) == (1, 0)
        assert a0 == a1 == 0.5
        np.testing.assert_allclose(m0, np.full(64, 0.5))
        np.testing.assert_allclose(m1, np.full(64, 0.5))
    finally:
        close_all(ts)


def test_exchange_skips_when_masked():
    ts = make_ring(2, fetch_probability=0.0)
    try:
        v = np.ones(8, np.float32)
        merged, alpha, _ = ts[0].exchange(v, 1, 0, step=0)
        assert alpha == 0.0
        np.testing.assert_array_equal(merged, v)
    finally:
        close_all(ts)


def test_exchange_survives_dead_partner():
    ts = make_ring(2)
    try:
        ts[1].close()  # partner dies
        cfg_timeout_vec = np.ones(8, np.float32)
        merged, alpha, partner = ts[0].exchange(cfg_timeout_vec, 1, 0, step=0)
        assert partner == 1 and alpha == 0.0
        np.testing.assert_array_equal(merged, cfg_timeout_vec)
    finally:
        ts[0].close()


@_RX_CONFIGS
def test_four_peer_ring_concurrent_exchange(rx):
    ts = make_ring(4, schedule="ring", rx_server=rx)
    try:
        # 1-based values: an all-zero replica would be rejected as
        # zero-energy by the recovery guard's norm-ratio floor.
        vecs = [np.full(32, float(i + 1), np.float32) for i in range(4)]
        for t, v in zip(ts, vecs):
            t.publish(v, 1, 1)
        results = [None] * 4
        # Free-running threads, like the reference's N processes.
        def run(i):
            results[i] = ts[i].exchange(vecs[i], 1, 1, step=0)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        # Step 0 ring pairing: (0,1) and (2,3); constant alpha = 0.5.
        np.testing.assert_allclose(results[0][0], np.full(32, 1.5))
        np.testing.assert_allclose(results[1][0], np.full(32, 1.5))
        np.testing.assert_allclose(results[2][0], np.full(32, 3.5))
        np.testing.assert_allclose(results[3][0], np.full(32, 3.5))
    finally:
        close_all(ts)


def test_clock_weighted_over_tcp():
    ts = make_ring(2, interpolation="clock", factor=1.0)
    try:
        v0 = np.zeros(8, np.float32)
        v1 = np.ones(8, np.float32)
        ts[0].publish(v0, 0.0, 0)   # fresh
        ts[1].publish(v1, 10.0, 0)  # trained
        m0, a0, _ = ts[0].exchange(v0, 0.0, 0, step=0)
        m1, a1, _ = ts[1].exchange(v1, 10.0, 0, step=0)
        assert a0 == pytest.approx(1.0)
        assert a1 == pytest.approx(0.0)
        np.testing.assert_allclose(m0, v1)
        np.testing.assert_allclose(m1, v1)
    finally:
        close_all(ts)


def test_fetch_abandons_trickling_peer_within_budget():
    """Slow-loris guard: a peer dribbling bytes must not pin the fetcher
    past the cumulative timeout_ms budget.  Per-recv timeouts alone reset
    on every received byte; fetch_blob enforces a monotonic deadline
    across the whole header+payload read."""
    import socket as socket_mod
    import time

    from dpwa_tpu.parallel.tcp import _frame

    srv = socket_mod.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    stop = threading.Event()

    def loris():
        try:
            conn, _ = srv.accept()
        except OSError:
            return
        try:
            conn.recv(64)  # the DPWA? request
            frame = _frame(np.arange(4096, dtype=np.float32), 1.0, 0.5)
            # One byte every 50 ms: finishing would take ~14 min; the
            # old per-recv timeout would happily wait it out.
            for i in range(len(frame)):
                if stop.is_set():
                    break
                conn.sendall(frame[i : i + 1])
                time.sleep(0.05)
        except OSError:
            pass
        finally:
            conn.close()

    th = threading.Thread(target=loris, daemon=True)
    th.start()
    try:
        t0 = time.monotonic()
        got = fetch_blob("127.0.0.1", port, timeout_ms=500)
        elapsed = time.monotonic() - t0
        assert got is None
        # Abandoned inside ~2× timeout_ms (0.5 s slack for scheduling).
        assert elapsed < 1.5, f"fetch pinned for {elapsed:.2f}s"
    finally:
        stop.set()
        srv.close()
        th.join(timeout=2.0)


def test_fetch_tolerates_large_payload_slower_than_base_budget():
    """The deadline must SCALE with the advertised payload: a healthy
    peer streaming a large replica over longer than timeout_ms (but far
    above the _MIN_WIRE_BANDWIDTH floor) is a working exchange, not a
    slow peer — a fixed whole-fetch budget would reject every blob
    larger than bandwidth × timeout_ms forever."""
    import socket as socket_mod
    import time

    from dpwa_tpu.parallel.tcp import _frame

    srv = socket_mod.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    vec = np.arange(4 << 20, dtype=np.float32)  # 16 MB payload

    def server():
        try:
            conn, _ = srv.accept()
        except OSError:
            return
        try:
            conn.recv(64)
            frame = _frame(vec, 3.0, 0.25)
            # ~13 MB/s: total ~1.2 s > timeout_ms, rate > the 10 MB/s floor.
            step = 2 << 20
            for off in range(0, len(frame), step):
                conn.sendall(frame[off : off + step])
                time.sleep(0.15)
        except OSError:
            pass
        finally:
            conn.close()

    th = threading.Thread(target=server, daemon=True)
    th.start()
    try:
        got = fetch_blob("127.0.0.1", port, timeout_ms=500)
        assert got is not None
        fetched, clock, loss = got
        np.testing.assert_array_equal(fetched, vec)
        assert (clock, loss) == (3.0, 0.25)
    finally:
        srv.close()
        th.join(timeout=5.0)


def test_overlapped_join_waits_for_scaled_large_payload():
    """The overlapped path's join backstop must scale with the published
    replica size the way fetch_blob's deadline does — a fixed ~2.5 s
    join would abandon (alpha=0) large-replica fetches the deadline
    deliberately tolerates, silently disabling gossip."""
    import socket as socket_mod
    import time

    from dpwa_tpu.parallel.tcp import _frame

    ts = make_ring(2, timeout_ms=500)
    srv = socket_mod.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    vec = np.arange(8 << 20, dtype=np.float32)  # 32 MiB replica

    def slow_peer():
        try:
            conn, _ = srv.accept()
        except OSError:
            return
        try:
            conn.recv(64)
            frame = _frame(vec, 5.0, 0.5)
            # 16 chunks, last landing at ~2.7 s (> the old fixed 2.5 s
            # join, so a regression to it WOULD fail this test) at
            # ~12 MB/s — above the 10 MB/s floor, inside the scaled
            # budget of 0.5 + 32/10 ≈ 3.7 s.
            step = 2 << 20
            for off in range(0, len(frame), step):
                conn.sendall(frame[off : off + step])
                if off + step < len(frame):
                    time.sleep(0.18)
        except OSError:
            pass
        finally:
            conn.close()

    th = threading.Thread(target=slow_peer, daemon=True)
    th.start()
    try:
        ts[0].set_peer_port(1, srv.getsockname()[1])
        ex = ts[0].exchange_overlapped_start(vec.copy(), 1.0, 0.5, step=0)
        merged, alpha, partner = ex.finish(vec.copy())
        assert partner == 1
        assert alpha == 0.5  # fetch completed — NOT abandoned at 2.5 s
        np.testing.assert_allclose(merged, vec, rtol=1e-6)
    finally:
        srv.close()
        close_all(ts)
        th.join(timeout=5.0)


def test_fetch_bandwidth_floor_is_configurable():
    """protocol.min_wire_mb_per_s sets the slowest rate treated as a live
    peer: the same pacing that the default 10 MB/s floor tolerates is
    abandoned under a 100 MB/s floor.  (Default-floor acceptance is
    covered by the large-payload test above.)"""
    import socket as socket_mod
    import time

    from dpwa_tpu.parallel.tcp import _frame

    srv = socket_mod.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    stop = threading.Event()

    def server():
        try:
            conn, _ = srv.accept()
        except OSError:
            return
        try:
            conn.recv(64)
            frame = _frame(np.arange(2 << 20, dtype=np.float32), 1.0, 0.5)
            step = 1 << 20  # ~10 MB/s pacing: 8 MiB over ~0.8 s
            for off in range(0, len(frame), step):
                if stop.is_set():
                    break
                conn.sendall(frame[off : off + step])
                time.sleep(0.1)
        except OSError:
            pass
        finally:
            conn.close()

    th = threading.Thread(target=server, daemon=True)
    th.start()
    try:
        t0 = time.monotonic()
        got = fetch_blob("127.0.0.1", port, 500, min_bandwidth_bps=100e6)
        elapsed = time.monotonic() - t0
        assert got is None  # 10 MB/s pacing is "dead" under a 100 MB/s floor
        assert elapsed < 1.5
        # The transport plumbs the YAML knob through (validation + wiring).
        cfg = make_local_config(2, min_wire_mb_per_s=0.5)
        assert cfg.protocol.min_wire_mb_per_s == 0.5
        with pytest.raises(ValueError):
            make_local_config(2, min_wire_mb_per_s=0)
    finally:
        stop.set()
        srv.close()
        th.join(timeout=2.0)


def test_negative_loss_alpha_clamped_over_tcp():
    # Same clamp contract as the ICI path: a negative loss riding the
    # wire metadata must never turn the host merge into extrapolation.
    ts = make_ring(2, interpolation="loss")
    try:
        v0 = np.zeros(8, np.float32)
        v1 = np.ones(8, np.float32)
        ts[0].publish(v0, 1, -5.0)
        ts[1].publish(v1, 1, 1.0)
        m0, a0, _ = ts[0].exchange(v0, 1, -5.0, step=0)
        m1, a1, _ = ts[1].exchange(v1, 1, 1.0, step=0)
        for a in (a0, a1):
            assert 0.0 <= a <= 1.0
        for m in (m0, m1):
            assert np.all(m >= 0.0) and np.all(m <= 1.0)
    finally:
        close_all(ts)


def test_exchange_on_device_matches_host_exchange():
    """VERDICT r3 #6: the device-resident exchange keeps the replica a JAX
    array, merges on-device, and produces the same numbers as the host
    (numpy/native-axpy) exchange."""
    import jax
    import jax.numpy as jnp

    ts = make_ring(2, schedule="ring", fetch_probability=1.0)
    try:
        d = 512
        v0 = np.arange(d, dtype=np.float32)
        v1 = np.arange(d, dtype=np.float32)[::-1].copy()
        # Host path on transport 0 (after both publish).
        ts[0].publish(v0, 1.0, 0.5)
        ts[1].publish(v1, 1.0, 0.5)
        host_merged, host_alpha, host_partner = ts[0].exchange(
            v0, 1.0, 0.5, 0
        )
        assert host_alpha != 0.0

        # Device path, same inputs/step: identical partner/alpha/math.
        dev0 = jnp.asarray(v0)
        dev_merged, dev_alpha, dev_partner = ts[0].exchange_on_device(
            dev0, 1.0, 0.5, 0
        )
        assert isinstance(dev_merged, jax.Array)
        assert dev_partner == host_partner
        assert dev_alpha == host_alpha
        np.testing.assert_allclose(
            np.asarray(dev_merged), host_merged, rtol=1e-6, atol=1e-6
        )
    finally:
        close_all(ts)


def test_exchange_on_device_skip_returns_same_array():
    """A skipped round (fetch timeout) must hand back the device array
    untouched — no host round-trip, no copy."""
    import jax.numpy as jnp

    ts = make_ring(2, schedule="ring", fetch_probability=1.0, timeout_ms=200)
    try:
        dev = jnp.ones(64, jnp.float32)
        # Partner never published: fetch returns None -> skip.
        merged, alpha, partner = ts[0].exchange_on_device(dev, 1.0, 0.0, 0)
        assert alpha == 0.0
        assert merged is dev
    finally:
        close_all(ts)


def test_exchange_overlapped_matches_sequential_algebra():
    """The overlapped round must produce exactly
    merge(pre_local, pre_remote) + update — the SPMD overlap=True
    algebra — with the same alpha the blocking exchange would use."""
    ts = make_ring(2, schedule="ring", fetch_probability=1.0)
    try:
        d = 256
        pre0 = np.arange(d, dtype=np.float32)
        pre1 = np.arange(d, dtype=np.float32)[::-1].copy()
        update0 = np.full(d, 0.25, np.float32)
        # Both peers publish their PRE-step replicas (start() publishes
        # for node0; node1 publishes manually).
        ts[1].publish(pre1, 1.0, 0.5)
        ex = ts[0].exchange_overlapped_start(pre0, 1.0, 0.5, 0)
        # ... node0's local step would run here, overlapping the fetch ...
        merged, alpha, partner = ex.finish(pre0, update0)
        assert partner == 1 and alpha != 0.0
        want = (1.0 - alpha) * pre0 + alpha * pre1 + update0
        np.testing.assert_allclose(merged, want, rtol=1e-6, atol=1e-6)
    finally:
        close_all(ts)


def test_exchange_overlapped_skip_keeps_update():
    """A failed fetch (partner never published) degrades to plain local
    SGD: pre + update, alpha 0 — the timeout-skip elasticity."""
    ts = make_ring(2, schedule="ring", fetch_probability=1.0, timeout_ms=200)
    try:
        pre = np.ones(64, np.float32)
        update = np.full(64, -0.5, np.float32)
        ex = ts[0].exchange_overlapped_start(pre, 1.0, 0.0, 0)
        merged, alpha, partner = ex.finish(pre, update)
        assert alpha == 0.0
        np.testing.assert_array_equal(merged, pre + update)
    finally:
        close_all(ts)
