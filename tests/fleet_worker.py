"""In-process simulated-peer fleet for the reactor large-N harness.

A "simulated peer" here is a *client* of one Rx server: either an
active fetcher (one blob request per round, like a ring partner's
exchange leg) or a passive holder (an open connection that sends
nothing — the idle phase of a slow peer).  N is bounded by file
descriptors, not OS threads: fetchers are multiplexed over a small
worker pool and holders are plain sockets, so a single test process
can drive a 256-peer ring against one server (docs/transport.md).

Used by tests/test_reactor.py.
"""

import socket
import threading
import time

from dpwa_tpu.parallel.tcp import fetch_blob_ex


def run_fleet(
    port,
    n_peers,
    rounds,
    workers=16,
    timeout_ms=2000,
    host="127.0.0.1",
):
    """Each of ``n_peers`` performs ``rounds`` sequential blob fetches,
    the fleet multiplexed over ``workers`` threads (peer p runs on
    worker ``p % workers``).  Returns the outcome tally and wall time:
    ``{"outcomes": {outcome: count}, "fetches": int, "wall_s": float}``.
    """
    tallies = [{} for _ in range(workers)]

    def work(w):
        for _peer in range(w, n_peers, workers):
            for _ in range(rounds):
                res = fetch_blob_ex(host, port, timeout_ms)
                tallies[w][res[1]] = tallies[w].get(res[1], 0) + 1

    threads = [
        threading.Thread(target=work, args=(w,)) for w in range(workers)
    ]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    outcomes = {}
    for t in tallies:
        for k, v in t.items():
            outcomes[k] = outcomes.get(k, 0) + v
    return {
        "outcomes": outcomes,
        "fetches": n_peers * rounds,
        "wall_s": wall,
    }


def hold_connections(port, n, host="127.0.0.1"):
    """Open ``n`` connections that send nothing (passive holders)."""
    socks = []
    for _ in range(n):
        socks.append(socket.create_connection((host, port), timeout=5.0))
    return socks


def held_open(socks):
    """Connections the server still holds open: a shed/evicted one has
    a busy frame, EOF, or RST waiting; a held one has nothing readable.
    """
    held = 0
    for s in socks:
        s.setblocking(False)
        try:
            s.recv(16)  # bytes or b"" -> shed/closed
        except (BlockingIOError, InterruptedError):
            held += 1
        except OSError:
            pass  # reset -> shed
    return held


def close_connections(socks):
    for s in socks:
        try:
            s.close()
        except OSError:
            pass
