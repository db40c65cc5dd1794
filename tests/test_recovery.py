"""Recovery subsystem (ISSUE 2): peer-assisted bootstrap over the STATE
wire, the divergence/poisoned-payload guard + rollback ring, and the
restart supervisor.

The chunked-transfer tests bind real localhost sockets with the fast
test timeouts (conftest enforces a per-test wall deadline for this
module); the full supervisor chaos soak — kill one of four worker
PROCESSES mid-run, watch it bootstrap-rejoin over TCP with zero shared
disk — runs under the ``slow`` marker.
"""

from __future__ import annotations

import json
import os
import socket
import struct
import subprocess
import sys
import threading
import zlib

import numpy as np
import pytest

from dpwa_tpu.adapters.tcp_adapter import DpwaTcpAdapter
from dpwa_tpu.config import RecoveryConfig, make_local_config
from dpwa_tpu.health.detector import Outcome
from dpwa_tpu.parallel.tcp import (
    _STATE_HDR,
    _STATE_MAGIC,
    _STATE_REQ,
    _STATE_REQ_BODY,
    PeerServer,
    TcpTransport,
    fetch_state,
    fetch_state_chunk,
    probe_header_ex,
)
from dpwa_tpu.recovery import (
    RollbackRing,
    pack_state,
    unpack_state,
    validate_payload,
)
from dpwa_tpu.recovery.bootstrap import choose_donor

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))

from supervisor import Supervisor, WorkerSpec  # noqa: E402


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
# pack_state / unpack_state
# ---------------------------------------------------------------------------


def test_pack_unpack_roundtrip_bit_identical():
    tree = {
        "w": np.arange(12, dtype=np.float32).reshape(3, 4),
        "b": np.float64(3.25),  # 0-d leaf: shape must survive packing
        "n": np.int32(7),
    }
    import jax

    blob = pack_state(tree, meta={"step": 5, "clock": 2.0})
    like = jax.tree.map(np.zeros_like, tree)
    state, meta = unpack_state(blob, like=like)
    assert meta == {"step": 5, "clock": 2.0}
    for got, want in zip(
        jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(tree)
    ):
        assert got.dtype == np.asarray(want).dtype
        assert got.shape == np.asarray(want).shape
        np.testing.assert_array_equal(got, np.asarray(want))


def test_unpack_rejects_corruption_and_wrong_template():
    tree = [np.ones(8, np.float32)]
    blob = pack_state(tree)
    # Flip a payload byte: CRC must catch it.
    bad = bytearray(blob)
    bad[-1] ^= 0xFF
    with pytest.raises(ValueError, match="CRC"):
        unpack_state(bytes(bad))
    with pytest.raises(ValueError, match="magic"):
        unpack_state(b"XXXX" + blob[4:])
    with pytest.raises(ValueError, match="CRC|truncated|trailing|length"):
        unpack_state(blob[:-3])
    with pytest.raises(ValueError, match="shape"):
        unpack_state(blob, like=[np.zeros((2, 4), np.float32)])
    with pytest.raises(ValueError, match="leaves"):
        unpack_state(blob, like=[np.zeros(8, np.float32)] * 2)


# ---------------------------------------------------------------------------
# STATE wire: chunked, CRC-checked, resumable
# ---------------------------------------------------------------------------


def test_fetch_state_chunked_roundtrip_and_probe_clock():
    srv = PeerServer("127.0.0.1", 0)
    try:
        blob = pack_state(
            [np.arange(9001, dtype=np.float32)], meta={"step": 3}
        )
        srv.publish_state(blob)
        srv.publish(np.zeros(4, np.float32), clock=7.0, loss=0.1)
        # Tiny chunks force many one-shot connections.
        got, outcome, _lat, nrx = fetch_state(
            "127.0.0.1", srv.port, timeout_ms=2000, chunk_bytes=1024
        )
        assert outcome == Outcome.SUCCESS
        assert got == blob and nrx == len(blob)
        state, meta = unpack_state(got)
        assert meta["step"] == 3
        np.testing.assert_array_equal(
            state[0], np.arange(9001, dtype=np.float32)
        )
        ok, clock = probe_header_ex("127.0.0.1", srv.port, timeout_ms=500)
        assert ok and clock == 7.0
    finally:
        srv.close()


def test_fetch_state_resumes_after_short_reads():
    """Chunks that die mid-flight resume at the banked offset; the blob
    still arrives bit-identical within the retry budget."""
    srv = PeerServer("127.0.0.1", 0)
    blob = pack_state([np.arange(4096, dtype=np.float32)])
    srv.publish_state(blob)

    gate = socket.socket()
    gate.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    gate.bind(("127.0.0.1", 0))
    gate.listen(8)
    fails = {"left": 3}

    def flaky():
        # A proxy that hard-closes the first 3 chunk connections after a
        # partial header — fetch_state must classify short_read/timeout
        # and resume, never restart from zero.
        while True:
            try:
                conn, _ = gate.accept()
            except OSError:
                return
            with conn:
                try:
                    req = conn.recv(len(_STATE_REQ) + _STATE_REQ_BODY.size)
                    if fails["left"] > 0:
                        fails["left"] -= 1
                        conn.sendall(_STATE_MAGIC)  # partial header
                        continue
                    up = socket.create_connection(
                        ("127.0.0.1", srv.port), timeout=2.0
                    )
                    with up:
                        up.sendall(req)
                        while True:
                            b = up.recv(65536)
                            if not b:
                                break
                            conn.sendall(b)
                except OSError:
                    pass

    th = threading.Thread(target=flaky, daemon=True)
    th.start()
    try:
        got, outcome, _lat, _nrx = fetch_state(
            "127.0.0.1", gate.getsockname()[1],
            timeout_ms=1000, chunk_bytes=2048, max_retries=8,
        )
        assert outcome == Outcome.SUCCESS
        assert got == blob
        assert fails["left"] == 0  # the faults actually fired
    finally:
        gate.close()
        srv.close()


def test_fetch_state_chunk_crc_mismatch_is_corrupt():
    """A server whose chunk bytes don't match the header CRC is CORRUPT."""
    lsn = socket.socket()
    lsn.bind(("127.0.0.1", 0))
    lsn.listen(1)

    def evil():
        conn, _ = lsn.accept()
        with conn:
            try:
                conn.recv(len(_STATE_REQ) + _STATE_REQ_BODY.size)
                payload = b"\x00" * 64
                hdr = _STATE_HDR.pack(
                    _STATE_MAGIC, 1, 0, 64, 0, 64,
                    zlib.crc32(payload) ^ 0xDEADBEEF,
                )
                conn.sendall(hdr + payload)
            except OSError:
                pass

    th = threading.Thread(target=evil, daemon=True)
    th.start()
    try:
        got, outcome, _lat, _nrx = fetch_state_chunk(
            "127.0.0.1", lsn.getsockname()[1], 0, 1 << 20, timeout_ms=1000
        )
        assert got is None and outcome == Outcome.CORRUPT
    finally:
        lsn.close()


def test_fetch_state_no_published_state_is_empty_success():
    srv = PeerServer("127.0.0.1", 0)
    try:
        got, outcome, _lat, _nrx = fetch_state(
            "127.0.0.1", srv.port, timeout_ms=1000
        )
        assert outcome == Outcome.SUCCESS and got == b""
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Guard + rollback ring
# ---------------------------------------------------------------------------


def test_validate_payload_classifies_each_violation():
    rec = RecoveryConfig(max_param_norm=10.0, max_loss=100.0)
    ok = np.ones(4, np.float32)
    assert validate_payload(ok, 1.0, rec) is None
    assert (
        validate_payload(np.array([1.0, np.nan], np.float32), 1.0, rec)
        == "nonfinite_params"
    )
    assert validate_payload(ok * 1e6, 1.0, rec) == "param_norm"
    assert validate_payload(ok, float("nan"), rec) == "nonfinite_loss"
    assert validate_payload(ok, 1e9, rec) == "loss_bound"
    assert validate_payload(ok, -1e9, rec) == "loss_bound"


def test_rollback_ring_pops_newest_then_digs_deeper():
    ring = RollbackRing(3)
    vecs = [np.full(4, float(i), np.float32) for i in range(5)]
    for i, v in enumerate(vecs):
        ring.push(v, step=i, clock=float(i), loss=0.1 * i)
        v += 100.0  # mutate the caller's buffer: the ring must hold copies
    # Capacity 3: snapshots 2, 3, 4 remain. Consecutive rollbacks dig
    # deeper (4, then 3, then 2), each returning the ORIGINAL bytes.
    for want in (4, 3, 2):
        snap = ring.rollback()
        assert snap.step == want
        np.testing.assert_array_equal(
            snap.vec, np.full(4, float(want), np.float32)
        )
    assert ring.rollback() is None
    assert ring.pushes == 5 and ring.rollbacks == 3


def test_rollback_ring_determinism_across_replays():
    def replay():
        ring = RollbackRing(4)
        out = []
        for i in range(10):
            ring.push(np.arange(3, dtype=np.float32) * i, i, float(i), 0.0)
            if i % 4 == 3:
                snap = ring.rollback()
                out.append((snap.step, snap.vec.tobytes()))
        return out

    assert replay() == replay()


# ---------------------------------------------------------------------------
# Poisoned remote payloads feed the detector; local divergence rolls back
# ---------------------------------------------------------------------------


def test_poisoned_remote_payload_rejected_before_merge():
    ts = make_ring(2, timeout_ms=500, seed=1)
    try:
        good = np.ones(32, np.float32)
        ts[0].publish(good, 1.0, 0.5)
        # Peer 1 publishes a NaN replica; node 0 must never average it.
        ts[1].publish(np.full(32, np.nan, np.float32), 1.0, 0.5)
        merged, alpha, _ = ts[0].exchange(good.copy(), 1.0, 0.5, step=0)
        assert alpha == 0.0
        np.testing.assert_array_equal(merged, good)
        assert ts[0].last_fetch["outcome"] == Outcome.POISONED
        assert ts[0].last_fetch["poison_reason"] == "nonfinite_params"
        # The rejection accrues suspicion like any wire failure.
        assert ts[0].scoreboard.detector.suspicion(1) > 0.0

        # Exploded-norm replica: rejected as param_norm.
        ts[1].publish(np.full(32, 1e20, np.float32), 2.0, 0.5)
        merged, alpha, _ = ts[0].exchange(good.copy(), 2.0, 0.5, step=1)
        assert alpha == 0.0
        assert ts[0].last_fetch["poison_reason"] == "param_norm"
    finally:
        close_all(ts)


def test_local_divergence_rolls_back_and_logs_event(tmp_path):
    paths = [str(tmp_path / f"m{i}.jsonl") for i in range(2)]
    cfg = make_local_config(
        2, base_port=0, timeout_ms=500,
        recovery=dict(snapshot_ring=4, max_loss=1e6),
    )
    ads = [
        DpwaTcpAdapter(
            {"w": np.full(16, float(i), np.float32)},
            f"node{i}", cfg, metrics=paths[i],
        )
        for i in range(2)
    ]
    try:
        for a in ads:
            for i, other in enumerate(ads):
                a.transport.set_peer_port(i, other.transport.port)
        for _ in range(3):
            for a in ads:
                a.update(loss=0.5)
        good_vec = ads[0]._vec.copy()
        good_loss = ads[0]._last_loss
        # Poison node 0's replica locally (a diverged optimizer step).
        bad = ads[0].params
        bad["w"] = np.full(16, np.nan, np.float32)
        ads[0].update(loss=float("nan"), params=bad)
        assert ads[0].last_rollback is not None
        assert ads[0].last_rollback["reason"] == "nonfinite_params"
        assert ads[0].last_rollback["restored"]
        assert np.isfinite(ads[0]._vec).all()
        # The published frame after rollback must carry the snapshot's
        # sane loss, not the caller's NaN.
        assert np.isfinite(ads[0]._last_loss)
        # Finite-but-huge loss also trips the guard (loss_bound).
        ads[0].update(loss=1e30)
        assert ads[0].last_rollback["reason"] == "loss_bound"
    finally:
        for a in ads:
            a.close()
    # Events are visible in the metrics JSONL and in health_report.
    events = [
        json.loads(l)
        for l in open(paths[0])
        if '"record": "event"' in l
    ]
    kinds = [e["event"] for e in events]
    assert kinds.count("rollback") == 2
    del good_vec, good_loss

    import health_report

    summary = health_report.summarize([paths[0]])
    assert summary["recovery"]["rollbacks"] == 2
    assert summary["recovery"]["rollback_reasons"] == {
        "nonfinite_params": 1, "loss_bound": 1,
    }


# ---------------------------------------------------------------------------
# Donor election + in-process bootstrap
# ---------------------------------------------------------------------------


def test_choose_donor_deterministic_healthy_only():
    healthy = [True, True, False, True]
    a = choose_donor(0, 4, step=7, seed=3, healthy=healthy)
    b = choose_donor(0, 4, step=7, seed=3, healthy=healthy)
    assert a == b and a in (1, 3)  # never self (0), never sick (2)
    # Exclusion removes a failed donor from the candidate list.
    c = choose_donor(0, 4, step=7, seed=3, healthy=healthy, exclude=(a,))
    assert c is not None and c != a
    assert (
        choose_donor(0, 4, step=7, seed=3, healthy=[False] * 4) is None
    )


def test_adapter_bootstrap_lands_on_donor_state(tmp_path):
    cfg = make_local_config(2, base_port=0, timeout_ms=500)
    donor = DpwaTcpAdapter(
        {"w": np.arange(24, dtype=np.float32)}, "node0", cfg
    )
    rejoiner = DpwaTcpAdapter(
        {"w": np.zeros(24, np.float32)}, "node1", cfg,
        metrics=str(tmp_path / "m.jsonl"), bootstrap=False,
    )
    try:
        for a in (donor, rejoiner):
            for i, other in enumerate((donor, rejoiner)):
                a.transport.set_peer_port(i, other.transport.port)
        for _ in range(4):
            donor.update(loss=0.25)
        assert rejoiner._bootstrap_from_peer()
        np.testing.assert_array_equal(rejoiner._vec, donor._vec)
        assert rejoiner.step == donor.step == 4
        assert rejoiner._clock == donor._clock
        assert rejoiner.last_bootstrap["donor"] == 0
        assert rejoiner.last_bootstrap["nbytes"] > 24 * 4
    finally:
        donor.close()
        rejoiner.close()


def test_bootstrap_rejects_poisoned_donor(tmp_path):
    """A donor serving NaN state must not seed the rejoiner."""
    cfg = make_local_config(2, base_port=0, timeout_ms=500)
    donor = DpwaTcpAdapter(
        {"w": np.ones(8, np.float32)}, "node0", cfg
    )
    rejoiner = DpwaTcpAdapter(
        {"w": np.zeros(8, np.float32)}, "node1", cfg, bootstrap=False
    )
    try:
        for a in (donor, rejoiner):
            for i, other in enumerate((donor, rejoiner)):
                a.transport.set_peer_port(i, other.transport.port)
        donor.transport.publish_state(
            pack_state(
                [np.full(8, np.nan, np.float32)],
                meta={"clock": 1.0, "step": 1, "loss": 0.5},
            )
        )
        assert not rejoiner._bootstrap_from_peer()
        np.testing.assert_array_equal(
            rejoiner._vec, np.zeros(8, np.float32)
        )
    finally:
        donor.close()
        rejoiner.close()


# ---------------------------------------------------------------------------
# Checkpoint-parity: the peer wire hands over EXACTLY what Orbax restores
# ---------------------------------------------------------------------------


def test_state_transfer_parity_with_orbax_restore(tmp_path):
    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.checkpoint import restore_checkpoint, save_checkpoint
    from dpwa_tpu.parallel.ici import IciTransport
    from dpwa_tpu.parallel.mesh import make_mesh
    from dpwa_tpu.train import (
        init_gossip_state,
        land_peer_state,
        slice_peer_state,
        stack_params,
    )

    n, peer = 8, 3
    cfg = make_local_config(n, schedule="ring")
    transport = IciTransport(cfg, mesh=make_mesh(cfg))
    opt = optax.adam(1e-2)
    params = {"w": jnp.ones((5, 3)), "b": jnp.zeros(3)}
    state = init_gossip_state(stack_params(params, n), opt, transport)

    # A couple of real steps so opt state / clocks are non-trivial.
    def loss_fn(p, batch):
        return ((batch @ p["w"] + p["b"]) ** 2).mean()

    from dpwa_tpu.train import make_gossip_train_step

    step_fn = make_gossip_train_step(loss_fn, opt, transport)
    batch = jnp.ones((n, 4, 5))
    for _ in range(3):
        state, _, _ = step_fn(state, batch)

    # Disk path: Orbax checkpoint round-trip.
    ckpt = str(tmp_path / "ckpt")
    save_checkpoint(ckpt, state)
    restored = restore_checkpoint(ckpt, like=state)

    # Wire path: donor serves its slice; rejoiner lands it into a COLD
    # state built from its own init (template-driven unpack — structure
    # never rides the wire).
    donor_slice = slice_peer_state(state, peer)
    srv = PeerServer("127.0.0.1", 0)
    try:
        srv.publish_state(
            pack_state(donor_slice, meta={"peer": peer})
        )
        blob, outcome, _lat, _nrx = fetch_state(
            "127.0.0.1", srv.port, timeout_ms=2000, chunk_bytes=512
        )
        assert outcome == Outcome.SUCCESS
        cold = init_gossip_state(stack_params(params, n), opt, transport)
        cold_template = slice_peer_state(cold, peer)
        fetched_slice, meta = unpack_state(blob, like=cold_template)
        assert meta["peer"] == peer
        landed = land_peer_state(cold, peer, fetched_slice)
    finally:
        srv.close()

    # The wire-bootstrapped peer row is bit-identical to the Orbax
    # restore of the same peer at the same step.
    want = slice_peer_state(restored, peer)
    got = slice_peer_state(landed, peer)
    for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(landed.step) == int(restored.step) == 3


def test_validate_and_fallback_checkpoint(tmp_path):
    """Corrupt newest checkpoint -> restore_latest_valid warns and falls
    back to the older valid one; validate_checkpoint names the fault."""
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.checkpoint import (
        restore_latest_valid,
        save_checkpoint,
        validate_checkpoint,
    )
    from dpwa_tpu.parallel.ici import IciTransport
    from dpwa_tpu.parallel.mesh import make_mesh
    from dpwa_tpu.train import init_gossip_state, stack_params

    n = 8
    cfg = make_local_config(n, schedule="ring")
    transport = IciTransport(cfg, mesh=make_mesh(cfg))
    state = init_gossip_state(
        stack_params({"w": jnp.ones(6)}, n), optax.sgd(0.1), transport
    )
    old, new = str(tmp_path / "c1"), str(tmp_path / "c2")
    save_checkpoint(old, state)
    save_checkpoint(new, state)
    assert validate_checkpoint(old) is None
    assert validate_checkpoint(str(tmp_path / "nope")) == "not a directory"

    # Vandalize the newest checkpoint the way a mid-write crash would:
    # strip Orbax's files out from under the directory.
    import shutil

    for entry in os.listdir(new):
        p = os.path.join(new, entry)
        shutil.rmtree(p) if os.path.isdir(p) else os.remove(p)
    assert validate_checkpoint(new) is not None

    with pytest.warns(UserWarning, match="falling back"):
        restored = restore_latest_valid([old, new], like=state)
    assert int(restored.step) == int(state.step)

    with pytest.raises(FileNotFoundError):
        restore_latest_valid([new])


# ---------------------------------------------------------------------------
# Supervisor
# ---------------------------------------------------------------------------


def test_supervisor_restarts_with_bootstrap_env_then_gives_up(tmp_path):
    marker = str(tmp_path / "boots.txt")
    script = (
        "import os, sys\n"
        "open(sys.argv[1], 'a').write("
        "os.environ.get('DPWA_BOOTSTRAP', '0') + '\\n')\n"
        "sys.exit(1)\n"
    )
    sup = Supervisor(
        [
            WorkerSpec(
                name="crashy",
                argv=[sys.executable, "-c", script, marker],
            )
        ],
        max_restarts=2,
        backoff_base_s=0.05,
        backoff_max_s=0.2,
        poll_interval_s=0.02,
    )
    sup.start()
    final = sup.run(timeout_s=30.0)
    assert final["gave_up"] == 1
    kinds = [e["event"] for e in sup.events]
    assert kinds.count("crashed") == 3  # initial + 2 restarts
    assert kinds.count("restart_scheduled") == 2
    assert kinds[-1] == "gave_up"
    # First spawn is cold; every restart enters bootstrap-rejoin mode.
    assert open(marker).read().split() == ["0", "1", "1"]


def test_supervisor_clean_exit_is_not_restarted():
    sup = Supervisor(
        [WorkerSpec(name="ok", argv=[sys.executable, "-c", "pass"])],
        backoff_base_s=0.05,
        poll_interval_s=0.02,
    )
    sup.start()
    final = sup.run(timeout_s=15.0)
    assert final == {
        "running": 0, "pending_restart": 0, "gave_up": 0, "done": 1,
        "restarts": {"ok": 0},
    }
    assert [e["event"] for e in sup.events] == ["spawn", "exited"]


def test_supervisor_healthz_strikeout_restarts_worker():
    """A hung-but-alive worker (no /healthz listener) is killed and
    restarted after consecutive probe strikes."""
    sup = Supervisor(
        [
            WorkerSpec(
                name="hung",
                argv=[sys.executable, "-c", "import time; time.sleep(60)"],
                healthz_port=1,  # reserved port: nothing ever listens
            )
        ],
        max_restarts=0,  # first healthz death -> gave_up, ends the test
        healthz_grace_s=0.1,
        healthz_timeout_s=0.2,
        healthz_strikes=2,
        poll_interval_s=0.05,
        backoff_base_s=0.05,
    )
    sup.start()
    final = sup.run(timeout_s=30.0)
    assert final["gave_up"] == 1
    kinds = [e["event"] for e in sup.events]
    assert "unhealthy" in kinds


# ---------------------------------------------------------------------------
# The four-peer chaos acceptance soak (slow tier)
# ---------------------------------------------------------------------------

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "recovery_worker.py")


def _free_base_port(span: int) -> int:
    """A base port with ``span`` consecutive free ports (fixed ports let
    a restarted worker rebind its slot with no coordination service)."""
    for _ in range(50):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        if p + span >= 65536:
            continue
        held = []
        try:
            for k in range(span):
                t = socket.socket()
                t.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                t.bind(("127.0.0.1", p + k))
                held.append(t)
        except OSError:
            continue
        finally:
            for t in held:
                t.close()
        if len(held) == span:
            return p
    raise RuntimeError("no consecutive free port range found")


def _run_soak(tmp_path, tag, *, steps=30, crash_at=8, n=4, victim=2):
    base_port = _free_base_port(n)
    paths = [str(tmp_path / f"{tag}_{i}.jsonl") for i in range(n)]
    workers = []
    for i in range(n):
        argv = [
            sys.executable, _WORKER,
            "--index", str(i), "--n", str(n),
            "--base-port", str(base_port),
            "--steps", str(steps),
            "--metrics", paths[i],
        ]
        if i == victim:
            argv += ["--crash-at-step", str(crash_at)]
        workers.append(WorkerSpec(name=f"node{i}", argv=argv))
    sup = Supervisor(
        workers,
        max_restarts=3,
        backoff_base_s=0.2,
        backoff_max_s=1.0,
        poll_interval_s=0.1,
    )
    sup.start()
    final = sup.run(timeout_s=240.0)
    assert final["gave_up"] == 0, sup.events
    assert final["running"] == 0, "soak timed out"
    assert final["restarts"][f"node{victim}"] == 1, sup.events
    records = []
    for p in paths:
        recs = [json.loads(l) for l in open(p)]
        records.append(recs)
    return records


@pytest.mark.slow
def test_acceptance_supervisor_kill_bootstrap_rejoin(tmp_path):
    """ISSUE 2 acceptance: the supervisor kills one of four worker
    processes mid-run; the restarted worker bootstraps its full state
    over the TCP STATE wire from a deterministically elected donor
    (zero shared disk), lands on the donor's schedule step, and the run
    completes — with the survivors' scheduled pairing sequence
    bit-identical across two full reruns.

    The step count leaves the restart path (python + jax import
    dominate, ~2s) comfortable room to land mid-run: the epidemic
    membership layer rides every exchange now, and its extra per-round
    work (digest piggyback, indirect probes around the victim's death)
    must not turn this soak into a knife-edge race."""
    n, victim, steps, crash_at = 4, 2, 42, 8

    def survivors_schedule(records):
        out = []
        for i in range(n):
            if i == victim:
                continue
            out.append(
                [
                    (r["step"], r.get("sched_partner"))
                    for r in records[i]
                    if "sched_partner" in r
                ]
            )
        return out

    rec_a = _run_soak(
        tmp_path, "a", steps=steps, crash_at=crash_at, victim=victim
    )
    # The victim's restarted incarnation bootstrapped over the wire...
    boots = [
        r for r in rec_a[victim]
        if r.get("record") == "event" and r.get("event") == "bootstrap"
    ]
    assert len(boots) == 1, [
        r for r in rec_a[victim] if r.get("record") == "event"
    ]
    donor_a = boots[0]["donor"]
    assert donor_a != victim
    # ...landing at the donor's (post-crash) schedule position, not 0.
    assert boots[0]["landed_step"] >= crash_at - 1
    # The rejoiner finished the run from there.
    victim_steps = [
        r["step"] for r in rec_a[victim] if "sched_partner" in r
    ]
    assert max(victim_steps) == steps - 1
    # Survivors each completed all steps.
    for i in range(n):
        if i == victim:
            continue
        ex_steps = [r["step"] for r in rec_a[i] if "sched_partner" in r]
        assert ex_steps == list(range(steps))

    # Rerun: same donor election, same survivor pairing sequence.
    rec_b = _run_soak(
        tmp_path, "b", steps=steps, crash_at=crash_at, victim=victim
    )
    boots_b = [
        r for r in rec_b[victim]
        if r.get("record") == "event" and r.get("event") == "bootstrap"
    ]
    assert len(boots_b) == 1 and boots_b[0]["donor"] == donor_a
    assert survivors_schedule(rec_a) == survivors_schedule(rec_b)

    # health_report folds the whole story from the victim's JSONL.
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import health_report

    summary = health_report.summarize(
        [str(tmp_path / f"a_{victim}.jsonl")]
    )
    assert summary["recovery"]["bootstraps"] == 1
    assert summary["recovery"]["bootstrap_donors"] == {str(donor_a): 1}
