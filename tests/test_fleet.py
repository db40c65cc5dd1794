"""Elastic churn survival tests (ISSUE 11, docs/fleet.md).

Covers the PR's headline claims:

- churn schedules are pure threefry functions of (seed, round, peer):
  an 8-peer mini-churn episode (join + leave + rolling restart + one
  MIXED chaos window) replays bit-identically;
- rolling restarts rejoin through the donor/bootstrap path under
  active churn, and cohort arrivals are admitted by the observer's
  membership view;
- the churn-hardened planes stay O(live): evicted peers vanish from
  the scoreboard/trust/flowctl per-peer maps and the membership
  digest, across a 1k-round churn grind;
- each injected fault class yields exactly one correctly-labeled
  incident cluster from the PR 8 correlator (the chaos-to-incident
  matrix at the cluster level);
- the reactor Rx server serves BYTE-IDENTICAL chaos to the threaded
  wrapper for every content fault (and the same RST behavior for
  drop/down), so `rx_server: reactor` + `chaos.enabled` is the same
  experiment;
- slow: a 256-peer churn soak holds convergence, sub-linear membership
  convergence, bounded digests, and detected fault windows.
"""

import json
import os
import socket
import sys

import numpy as np
import pytest

from dpwa_tpu.config import (
    ChaosConfig,
    HealthConfig,
    MembershipConfig,
    ObsConfig,
    ViewConfig,
)
from dpwa_tpu.flowctl.estimator import DeadlineEstimator
from dpwa_tpu.fleet import (
    ChaosWindow,
    ChurnSchedule,
    ChurnSpec,
    FleetOrchestrator,
)
from dpwa_tpu.health.chaos import (
    ChaosEngine,
    ChaosPeerServer,
    ChaosReactorPeerServer,
)
from dpwa_tpu.health.detector import Outcome
from dpwa_tpu.obs.incidents import ALERT_KINDS, IncidentPlane
from dpwa_tpu.parallel.tcp import _REQ
from dpwa_tpu.trust.manager import TrustManager

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from tools import fleet_report, incident_report, schema_check  # noqa: E402

# Fast plane configs: suspicion trips in 2 bad rounds, quarantine
# backoff is short, a dead claim gossips briefly then evicts — so a
# full leave -> DEAD -> evicted -> probe -> readmit lifecycle fits in
# a tier-1-sized episode.
FAST_HEALTH = dict(
    quarantine_base_rounds=2,
    quarantine_max_rounds=8,
    jitter_rounds=0,
)
FAST_MEMBER = dict(
    dead_after_quarantines=2,
    dead_gossip_rounds=4,
)


def _fast_orch(n, spec, **kw):
    kw.setdefault("health", HealthConfig(**FAST_HEALTH))
    kw.setdefault("membership", MembershipConfig(**FAST_MEMBER))
    kw.setdefault("dim", 8)
    return FleetOrchestrator(n, spec, **kw)


MINI_SPEC = ChurnSpec(
    seed=11,
    leave_probability=0.12,
    join_probability=0.3,
    cohort_every=8,
    cohort_max=2,
    restart_every=6,
    min_live=3,
    chaos_windows=(
        ChaosWindow(
            10, 16, ("partition", "byzantine", "straggler"),
            group=(0, 1, 2),
        ),
    ),
)


# ---------------------------------------------------------------------------
# Churn schedule: pure, deterministic, floored
# ---------------------------------------------------------------------------


def test_schedule_events_replay_bit_identically():
    a = ChurnSchedule(MINI_SPEC, 8)
    b = ChurnSchedule(MINI_SPEC, 8)
    live, departed = [0, 1, 2, 4, 6], [3, 5, 7]
    for r in range(64):
        assert a.events(r, live, departed) == b.events(r, live, departed)


def test_schedule_respects_min_live_floor_and_protected():
    spec = ChurnSpec(seed=3, leave_probability=1.0, min_live=3,
                     protected=(0,))
    sched = ChurnSchedule(spec, 8)
    ev = sched.events(5, list(range(8)), [])
    # Everybody wants to leave; the floor caps it at live - min_live
    # and the protected observer never departs.
    assert len(ev.leaves) == 8 - 3
    assert 0 not in ev.leaves


def test_schedule_joins_only_from_departed_and_cohort_cadence():
    spec = ChurnSpec(seed=3, join_probability=1.0, cohort_every=4,
                     cohort_max=2)
    sched = ChurnSchedule(spec, 8)
    ev = sched.events(1, [0, 1, 2, 3], [4, 5, 6, 7])
    assert ev.joins == (4, 5, 6, 7)
    assert ev.cohort == ()  # round 1 is off-cadence
    ev4 = sched.events(4, [0, 1, 2, 3], [4, 5, 6, 7])
    # On-cadence cohort admits only peers the join draws left behind.
    assert set(ev4.cohort).isdisjoint(ev4.joins)
    assert len(ev4.cohort) <= 2


def test_schedule_restart_excludes_protected_and_leavers():
    spec = ChurnSpec(seed=9, leave_probability=0.5, restart_every=2,
                     min_live=2, protected=(0,))
    sched = ChurnSchedule(spec, 8)
    seen = 0
    for r in range(2, 40, 2):
        ev = sched.events(r, list(range(8)), [])
        if ev.restart:
            seen += 1
            assert ev.restart[0] != 0
            assert ev.restart[0] not in ev.leaves
    assert seen > 0


def test_spec_validation_rejects_bad_knobs():
    with pytest.raises(ValueError):
        ChurnSpec(leave_probability=1.5)
    with pytest.raises(ValueError):
        ChurnSpec(min_live=0)
    with pytest.raises(ValueError):
        ChurnSpec(chaos_windows=(ChaosWindow(0, 4, ("gremlins",)),))
    with pytest.raises(ValueError):
        # A partition window must name its minority side.
        ChurnSpec(chaos_windows=(ChaosWindow(0, 4, ("partition",)),))


# ---------------------------------------------------------------------------
# Mini-churn acceptance: 8 peers, join+leave+restart+mixed chaos,
# bit-identical replay (tier-1's fast stand-in for the 256 soak)
# ---------------------------------------------------------------------------


def _mini_run(tmp_path=None, name="a"):
    path = str(tmp_path / f"fleet_{name}.jsonl") if tmp_path else None
    orch = _fast_orch(8, MINI_SPEC, path=path)
    return orch.run(24)


def test_mini_churn_is_bit_identical_across_reruns(tmp_path):
    r1 = _mini_run(tmp_path, "a")
    r2 = _mini_run(tmp_path, "b")
    # The deterministic stream (churn records) replays exactly; round
    # records carry wall time and are compared on their deterministic
    # fields only.
    assert r1.churn_records == r2.churn_records
    det = lambda r: {  # noqa: E731
        k: v for k, v in r.items() if k not in ("wall_s", "rel_rms")
    }
    rounds1 = [det(r) for r in r1.records if r.get("kind") == "round"]
    rounds2 = [det(r) for r in r2.records if r.get("kind") == "round"]
    assert rounds1 == rounds2


def test_mini_churn_episode_exercises_every_churn_family(tmp_path):
    res = _mini_run(tmp_path, "c")
    churn = res.churn_records
    assert any(r["leaves"] for r in churn)
    assert any(r["joins"] or r["cohort"] for r in churn)
    assert any(r["restart"] for r in churn)
    mixed = [r for r in churn if len(r["chaos"]) == 3]
    assert mixed, "the mixed chaos window never activated"
    # The episode ends convergent and with no STUCK membership events:
    # a join is allowed to still be pending only if it happened too
    # close to episode end to clear quarantine backoff.
    ep = res.episode
    assert ep["final_rel_rms"] < 1e-3
    last_join = {}
    for r in churn:
        for p in list(r["joins"]) + list(r["cohort"]) + list(r["restart"]):
            last_join[p] = r["round"]
    for p in ep["unresolved_joins"]:
        assert last_join.get(p, 0) > 24 - 12, (p, last_join.get(p))
    # The stream passes the frozen schema.
    for rec in res.records:
        assert schema_check.check_record(rec) == [], rec


def test_mini_churn_jsonl_feeds_fleet_report(tmp_path):
    path = tmp_path / "fleet_rep.jsonl"
    orch = _fast_orch(8, MINI_SPEC, path=str(path))
    orch.run(24)
    records = fleet_report.load_records([str(path)])
    rep = fleet_report.build_report(records)
    assert rep["episode"]["rounds"] == 24
    assert rep["wall"]["rounds"] == 24
    assert len(rep["faults"]) == 1
    w = rep["faults"][0]
    assert (w["start"], w["stop"]) == (10, 16)
    assert w["kinds"] == ["byzantine", "partition", "straggler"]


def test_different_seed_yields_different_episode():
    spec = ChurnSpec(
        seed=12, leave_probability=0.12, join_probability=0.3,
        cohort_every=8, cohort_max=2, restart_every=6, min_live=3,
        chaos_windows=MINI_SPEC.chaos_windows,
    )
    base = _fast_orch(8, MINI_SPEC).run(24).churn_records
    other = _fast_orch(8, spec).run(24).churn_records
    strip = lambda recs: [  # noqa: E731
        {k: v for k, v in r.items() if k != "chaos"} for r in recs
    ]
    assert strip(base) != strip(other)


# ---------------------------------------------------------------------------
# Rolling restarts + cohort arrivals (satellite 4 units)
# ---------------------------------------------------------------------------


def test_rolling_restart_rejoins_under_active_churn():
    spec = ChurnSpec(
        seed=5, leave_probability=0.1, join_probability=0.4,
        restart_every=4, min_live=4, protected=(0,),
    )
    orch = _fast_orch(8, spec)
    res = orch.run(20)
    restarted = sorted(
        {p for r in res.churn_records for p in r["restart"]}
    )
    assert restarted, "no rolling restart fired"
    for p in restarted:
        node = orch.nodes[p]
        assert node.boots >= 2
        # The rejoiner came back under a bumped incarnation (the stale
        # DEAD-claim refutation key, docs/membership.md).
        assert node.next_incarnation >= 2
    # Restarts resolved: nothing restarted is still waiting on the
    # observer's mask at episode end.
    assert set(res.episode["unresolved_joins"]).isdisjoint(restarted)


def test_restart_restores_replica_from_live_donor():
    spec = ChurnSpec(seed=5, restart_every=3, min_live=2)
    orch = _fast_orch(6, spec)
    res = orch.run(30)
    restarted = [p for r in res.churn_records for p in r["restart"]]
    assert restarted
    # A restarted node rejoined with a replica interpolated back into
    # the ring: it converges with everyone else.
    assert res.episode["final_rel_rms"] < 1e-2
    assert res.episode["final_live"] == 6


def test_cohort_arrival_is_admitted_by_observer_membership():
    spec = ChurnSpec(seed=2, cohort_every=4, cohort_max=3, min_live=2)
    orch = _fast_orch(8, spec, initial_live=5)
    res = orch.run(28)
    cohorts = [r["cohort"] for r in res.churn_records if r["cohort"]]
    assert cohorts, "no cohort arrival fired"
    arrived = sorted({p for c in cohorts for p in c})
    assert set(arrived) <= {5, 6, 7}  # only departed peers arrive
    ep = res.episode
    assert ep["unresolved_joins"] == []
    assert ep["final_live"] == 5 + len(arrived)
    # Every arrival the observer admitted converged in bounded rounds
    # (quarantine backoff for the initially-departed peers caps it).
    assert all(c <= 16 for c in ep["join_convergence_rounds"])


def test_cohort_draw_respects_cohort_max():
    spec = ChurnSpec(seed=2, cohort_every=2, cohort_max=2)
    sched = ChurnSchedule(spec, 16)
    for r in range(2, 40, 2):
        ev = sched.events(r, [0, 1], list(range(2, 16)))
        assert len(ev.cohort) <= 2


# ---------------------------------------------------------------------------
# Churn-hardened planes: bounded per-peer state (satellite 3)
# ---------------------------------------------------------------------------

_BOARD_MAPS = (
    "_state", "_release_round", "_quarantine_streak", "_quarantines",
    "_quarantined_rounds", "_quarantined_at", "_degrades",
    "_degraded_rounds", "_degraded_at", "_probe_attempts",
    "_probe_successes", "_last_contact",
)


def test_thousand_round_churn_grind_keeps_per_peer_state_bounded():
    spec = ChurnSpec(
        seed=42, leave_probability=0.06, join_probability=0.1,
        cohort_every=50, cohort_max=3, restart_every=40, min_live=3,
    )
    orch = _fast_orch(8, spec, dim=4)
    res = orch.run(1000)
    ep = res.episode
    # Churn actually ground through the lifecycle: departures were
    # disseminated dead and EVICTED from the observer's planes.
    assert ep["leave_convergence_rounds"], "no leave ever converged"
    obs = orch.nodes[0]
    evicted = set(obs.board.evicted_peers())
    for name in _BOARD_MAPS:
        d = getattr(obs.board, name)
        assert not (set(d) & evicted), (name, sorted(d), sorted(evicted))
        assert len(d) <= 8
    # The detector's EWMA records are pruned with the peer.
    for p in evicted:
        assert p not in obs.board.detector._peers
    # The membership digest omits evicted peers: its size tracks the
    # non-evicted universe, not all-time membership.
    digest = obs.membership.encode(1000)
    assert len(digest) <= ep["max_digest_bytes"]
    view = obs.membership.view_snapshot()
    assert set(view.get("evicted", ())) == evicted


def test_thousand_round_churn_grind_bounds_capped_view_state():
    """ISSUE 18 extension of the grind: under ``membership.view`` the
    per-node PEAK map sizes must stay O(state_cap), not O(N), across
    the scoreboard / membership / trust / flowctl planes — a cap that
    only holds at the final round would hide mid-stream leaks."""
    # Sized so the bounds BITE: cap + slack (= digest_sample + 2) must
    # stay below N-1, else a full-universe map would pass the cap check.
    view = ViewConfig(
        enabled=True, active_size=3, passive_size=5, digest_sample=3,
        state_cap=5, shuffle_every=8,
    )
    spec = ChurnSpec(
        seed=42, leave_probability=0.06, join_probability=0.1,
        cohort_every=50, cohort_max=3, restart_every=40, min_live=4,
    )
    orch = _fast_orch(
        12, spec, dim=4,
        membership=MembershipConfig(view=view, **FAST_MEMBER),
    )
    # Trust/flowctl ride the observer's evict-listener + cap-protector
    # path exactly as the transport wires them; the spy screens every
    # newly tracked peer on merge (what tcp does on receive), so their
    # maps grow with the tracked horizon and must shrink with the cap.
    obs = orch.nodes[0]
    trust = TrustManager(12, 0)
    est = DeadlineEstimator(timeout_ms=100.0)
    trust.enable_capped_snapshots()
    obs.membership.add_evict_listener(trust.evict_peer)
    obs.membership.add_evict_listener(est.evict_peer)
    obs.membership.add_cap_protector(trust.is_collapsed)
    local = np.zeros(8, np.float32)
    peaks = {"trust": 0, "est": 0}
    real_merge = obs.membership.merge

    def merge_spy(blob, round=None):
        real_merge(blob, round)
        for p in obs.membership._tracked_candidates():
            if p not in trust._trust:
                trust.screen(
                    p, np.ones(8, np.float32), 1.0, local,
                    round=int(round or 0),
                )
                est.observe(p, Outcome.SUCCESS, latency_s=0.01)
        peaks["trust"] = max(peaks["trust"], len(trust.tracked_peers()))
        peaks["est"] = max(peaks["est"], len(est.tracked_peers()))

    obs.membership.merge = merge_spy
    res = orch.run(1000)
    assert res.episode["leave_convergence_rounds"]
    cap = view.state_cap
    # Between end_rounds a merge can admit at most one frame's worth of
    # new peers before the cap re-runs — that is the only lawful
    # overshoot.
    slack = view.digest_sample + 2
    assert obs.membership._evictions_by_cause["cap"] > 0
    assert peaks["trust"] <= cap + slack, peaks
    assert peaks["est"] <= cap + slack, peaks
    # Trust/flowctl hold no peer the observer no longer tracks.
    tracked_now = set(obs.membership._tracked_candidates())
    assert set(trust.tracked_peers()) <= tracked_now
    assert set(est.tracked_peers()) <= tracked_now
    for f in range(12):
        node = orch.nodes[f]
        if node.board is None:
            continue
        tomb = len(node.board._evicted)
        # The cap yields to the QUARANTINED carve-out (a verdict is
        # never silently dropped), so residency may lawfully overshoot
        # by the protected count — deterministic at 2 under this seed.
        assert node.membership._peak_resident <= cap + 2
        assert node.membership._peak_sb_tracked <= cap + slack
        for name in _BOARD_MAPS:
            assert len(getattr(node.board, name)) <= cap + slack + tomb, (
                f, name
            )
        assert len(node.membership._view) <= cap + slack
        part = node.membership.partial
        assert len(part._last_touch) <= cap + slack
        assert len(part.active) <= view.active_size
        assert len(part.passive) <= view.passive_size


def test_trust_and_flowctl_evict_drop_per_peer_maps():
    trust = TrustManager(8, 0)
    est = DeadlineEstimator(timeout_ms=100.0)
    local = np.zeros(64, np.float32)
    for peer in (3, 5):
        vec = np.ones(64, np.float32)
        trust.screen(peer, vec, 1.0, local, round=1)
        est.observe(peer, Outcome.SUCCESS, latency_s=0.01, nbytes=256)
    assert 3 in trust._trust and 3 in est._window
    trust.evict_peer(3)
    est.evict_peer(3)
    for d in (trust._trust, trust._counts, trust._last_seen,
              trust._last_clock):
        assert 3 not in d
    assert 3 not in est._window and 3 not in est._counts
    # The untouched peer keeps its records: eviction is per-peer.
    assert 5 in trust._trust and 5 in est._window


def test_partner_draws_skip_evicted_ghosts():
    """A ring where half the membership is gone must keep pairing live
    peers: quarantined/evicted partners are remapped, never fetched."""
    spec = ChurnSpec(seed=8, leave_probability=0.5, min_live=4,
                     protected=(0,))
    orch = _fast_orch(8, spec, dim=4)
    res = orch.run(60)
    rounds = [r for r in res.records if r.get("kind") == "round"]
    settled = rounds[20:]
    # After the detectors settle, dead partners are remapped away:
    # exchanges keep happening every round even at 50% churn.
    assert all(r["exchanges"] > 0 for r in settled)
    timeouts = sum(
        r["outcomes"].get(Outcome.TIMEOUT, 0) for r in settled
    )
    exchanges = sum(r["exchanges"] for r in settled)
    assert exchanges > timeouts, (exchanges, timeouts)


# ---------------------------------------------------------------------------
# Mixed-chaos incident-classification matrix (satellite 4)
# ---------------------------------------------------------------------------

_MATRIX = [
    (
        "partition",
        lambda p: [
            p.observe_round(
                s,
                events=[
                    {"event": "partition_entered", "component": [0, 1]}
                ],
                partition_state="degraded",
            )
            for s in range(2)
        ],
    ),
    (
        "byzantine",
        lambda p: [
            p.observe_round(s, outcome=Outcome.POISONED, peer=2)
            for s in range(3)
        ],
    ),
    (
        "peer_down",
        lambda p: [
            p.observe_round(s, outcome=Outcome.TIMEOUT, peer=3)
            for s in range(3)
        ],
    ),
    (
        "straggler",
        lambda p: [
            p.observe_round(s, outcome=Outcome.SLOW, peer=1)
            for s in range(3)
        ],
    ),
]


@pytest.mark.parametrize("kind,drive", _MATRIX, ids=[m[0] for m in _MATRIX])
def test_each_fault_class_yields_one_correct_cluster(kind, drive):
    plane = IncidentPlane(0, 4, ObsConfig())
    drive(plane)
    recs = plane.pop_records()
    buckets = {"alert": [], "incident": [], "flight": []}
    for r in recs:
        if r["record"] in buckets:
            buckets[r["record"]].append(r)
    rep = incident_report.build_report(buckets)
    assert len(rep["clusters"]) == 1, rep
    assert rep["clusters"][0]["kind"] == kind


def test_mixed_window_folds_to_highest_priority_cluster():
    """All three classes of the mixed window at once: the correlator
    keeps ONE incident, classified by the root-cause priority order
    (partition explains the rest)."""
    plane = IncidentPlane(0, 4, ObsConfig())
    plane.observe_round(0, outcome=Outcome.TIMEOUT, peer=3)
    plane.observe_round(1, outcome=Outcome.TIMEOUT, peer=3)
    plane.observe_round(2, outcome=Outcome.POISONED, peer=2)
    plane.observe_round(3, outcome=Outcome.POISONED, peer=2)
    plane.observe_round(
        4,
        events=[{"event": "partition_entered", "component": [0, 1]}],
        partition_state="degraded",
    )
    recs = plane.pop_records()
    buckets = {"alert": [], "incident": [], "flight": []}
    for r in recs:
        if r["record"] in buckets:
            buckets[r["record"]].append(r)
    rep = incident_report.build_report(buckets)
    assert len(rep["clusters"]) == 1
    assert rep["clusters"][0]["kind"] == "partition"


def test_report_tool_fault_expectations_match_alert_kinds():
    # tools/fleet_report.py duplicates the alert -> classification map
    # to stay stdlib-only; pin it against the live plane's table.
    for alert, (_, cls, _) in ALERT_KINDS.items():
        assert fleet_report.ALERT_CLASS[alert] == cls
    for kinds in fleet_report.FAULT_EXPECTATIONS.values():
        for k in kinds:
            assert k in incident_report.KIND_PRIORITY


# ---------------------------------------------------------------------------
# Reactor chaos byte-identity (satellite 1)
# ---------------------------------------------------------------------------


def _raw_fetch(port: int, timeout: float = 3.0) -> bytes:
    """One raw BLOB fetch; RST/timeout become markers so abnormal
    closes compare as first-class outcomes."""
    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    s.settimeout(timeout)
    chunks = []
    try:
        s.sendall(_REQ)
        while True:
            try:
                b = s.recv(65536)
            except socket.timeout:
                return b"<TIMEOUT>" + b"".join(chunks)
            except (ConnectionResetError, OSError):
                return b"<RST>" + b"".join(chunks)
            if not b:
                return b"".join(chunks)
            chunks.append(b)
    finally:
        s.close()


_CHAOS_CASES = {
    "none": {},
    "corrupt": {"corrupt_probability": 1.0},
    "truncate": {"truncate_probability": 1.0},
    "drop": {"drop_probability": 1.0},
    "down": {"down_windows": ((1, 0, 10),)},
    "byz_sign": {"byzantine_sign_probability": 1.0},
    "byz_scale": {"byzantine_scale_probability": 1.0},
    "byz_zero": {"byzantine_zero_probability": 1.0},
    "byz_replay": {
        "byzantine_replay_probability": 1.0, "byzantine_replay_age": 1,
    },
}


@pytest.mark.parametrize("case", sorted(_CHAOS_CASES))
def test_reactor_chaos_serves_byte_identical_faults(case):
    cfg = ChaosConfig(enabled=True, seed=77, **_CHAOS_CASES[case])
    vec0 = np.arange(64, dtype=np.float32)
    vec1 = vec0 * 2.0
    servers = [
        ChaosPeerServer("127.0.0.1", 0, ChaosEngine(cfg, peer=1)),
        ChaosReactorPeerServer("127.0.0.1", 0, ChaosEngine(cfg, peer=1)),
    ]
    try:
        for srv in servers:
            # Two publishes so the replay attack has real history (the
            # round-1 fetch replays the round-0 frame).
            srv.publish(vec0, 0, 0.5)
            srv.publish(vec1, 1, 0.25)
        got = [_raw_fetch(srv.port) for srv in servers]
        if case in ("drop", "down"):
            # Both paths abort the connection with nothing served; RST
            # vs bare FIN is a kernel race (whether the request bytes
            # landed before the close), and the detector classifies
            # both as the same hard failure.
            for g in got:
                assert g in (b"", b"<RST>"), (case, g)
        else:
            assert got[0] == got[1], case
            assert len(got[0]) > 0
    finally:
        for srv in servers:
            srv.close()


def test_reactor_chaos_partition_blocks_relay_guard():
    cfg = ChaosConfig(
        enabled=True, seed=7,
        partition_windows=(((0, 1), 0, 10),),
    )
    srv = ChaosReactorPeerServer(
        "127.0.0.1", 0, ChaosEngine(cfg, peer=1)
    )
    try:
        srv.publish(np.ones(8, np.float32), 1, 0.0)
        # Relay probes honor the injected split: target 2 is across the
        # cut from peer 1, target 0 is inside the component.
        assert srv.relay_guard(2)
        assert not srv.relay_guard(0)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# 256-peer churn soak (slow; the PR's tentpole acceptance)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_256_peer_churn_soak(tmp_path):
    n = 256
    path = tmp_path / "fleet_256.jsonl"
    spec = ChurnSpec(
        seed=1,
        leave_probability=0.01,
        join_probability=0.15,
        cohort_every=20,
        cohort_max=8,
        restart_every=15,
        min_live=128,
        chaos_windows=(
            # The observer sits INSIDE the minority side, and the group
            # is INTERLEAVED with the ring so every in-group pull is
            # cross-cut: suspicion actually accrues ring-wide (a
            # contiguous cut only fails at its two edges), the
            # observer's component drops below quorum -> degraded ->
            # partition evidence (docs/incidents.md).
            ChaosWindow(
                30, 60, ("partition",), group=tuple(range(0, 240, 2))
            ),
            ChaosWindow(70, 90, ("byzantine", "straggler")),
        ),
    )
    # Eviction horizon slower than the partition's suspicion spread:
    # evicting the far side as it dies would shrink the quorum
    # denominator in lockstep with the component and mask the split.
    orch = _fast_orch(
        n, spec, dim=16, path=str(path),
        membership=MembershipConfig(
            dead_after_quarantines=2, dead_gossip_rounds=24
        ),
    )
    res = orch.run(120)
    ep = res.episode

    # Convergence within tolerance of a static (no churn) run.
    static = _fast_orch(n, ChurnSpec(seed=1), dim=16).run(120)
    assert ep["final_rel_rms"] < max(
        1e-4, 100.0 * static.episode["final_rel_rms"]
    )

    # Membership convergence is sub-linear in N: joins are admitted in
    # a handful of rounds, nowhere near O(256).
    joins = ep["join_convergence_rounds"]
    assert joins and float(np.median(joins)) <= 8
    assert max(joins) < n // 4

    # Bounded per-round wall: the orchestration loop never wedges.
    rounds = [r for r in res.records if r.get("kind") == "round"]
    walls = sorted(r["wall_s"] for r in rounds)
    p50 = walls[len(walls) // 2]
    assert walls[-1] < max(5.0, 50.0 * p50)

    # Bounded memory: evicted peers are gone from the observer's maps
    # and the digest is far below the 256-peer full-map worst case.
    obs = orch.nodes[0]
    evicted = set(obs.board.evicted_peers())
    for name in _BOARD_MAPS:
        assert not (set(getattr(obs.board, name)) & evicted)

    # Fault windows were observed with the right classifications.
    rep = fleet_report.build_report(
        fleet_report.load_records([str(path)])
    )
    verdicts = {
        (f["start"], f["stop"]): f for f in rep["faults"]
    }
    part = verdicts[(30, 60)]
    assert "partition" in part["observed_classes"]
    byz = verdicts[(70, 90)]
    assert "byzantine" in byz["observed_classes"]
    assert ep["incidents_opened"] >= 1


@pytest.mark.slow
def test_256_peer_soak_schema_clean(tmp_path):
    path = tmp_path / "fleet_small.jsonl"
    spec = ChurnSpec(seed=4, leave_probability=0.05,
                     join_probability=0.2, min_live=64)
    _fast_orch(256, spec, dim=8, path=str(path)).run(40)
    bad = 0
    with open(path) as f:
        for ln in f:
            bad += bool(schema_check.check_record(json.loads(ln)))
    assert bad == 0


# ---------------------------------------------------------------------------
# Bounded partial views at fleet scale (ISSUE 18, docs/membership.md)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_256_peer_full_horizon_view_is_byte_identical_to_global():
    """ISSUE 18 acceptance: with ``digest_sample >= N``, ``state_cap >=
    N`` and ``active_size >= N-1`` the ENTIRE deterministic record
    stream — every churn record and every decision field of every round
    record — is byte-identical to the global-view path at 256 peers
    under real churn."""
    n = 256
    spec = ChurnSpec(
        seed=5, leave_probability=0.02, join_probability=0.2,
        cohort_every=16, cohort_max=4, restart_every=12, min_live=128,
        chaos_windows=(
            ChaosWindow(20, 34, ("partition",),
                        group=tuple(range(0, 240, 2))),
        ),
    )

    def run(view):
        orch = _fast_orch(
            n, spec, dim=8,
            membership=MembershipConfig(view=view, **FAST_MEMBER),
        )
        res = orch.run(80)
        churn = [r for r in res.records if r.get("kind") == "churn"]
        rounds = [
            {k: v for k, v in r.items() if k not in ("wall_s", "rel_rms")}
            for r in res.records if r.get("kind") == "round"
        ]
        ep = {
            k: v for k, v in res.episode.items()
            if not k.startswith("view_")
            and k not in ("max_wall_s", "final_rel_rms")
        }
        return churn, rounds, ep

    full = ViewConfig(
        enabled=True, active_size=n - 1, passive_size=0,
        digest_sample=n, state_cap=n, shuffle_every=0,
    )
    churn_g, rounds_g, ep_g = run(ViewConfig())
    churn_v, rounds_v, ep_v = run(full)
    assert churn_v == churn_g, "churn stream diverged under full horizon"
    assert rounds_v == rounds_g, "round decisions diverged"
    assert ep_v == ep_g, "episode summary diverged"


@pytest.mark.slow
def test_4096_peer_partial_view_soak_converges_with_bounded_state():
    """The tentpole soak: 4096 peers, joins + leaves + cohort arrivals
    + a partition window, every node seeing the ring through an
    O(sample) partial view.  Membership must still converge (SWIM
    incarnation/refutation through sampled digests), per-node state
    must stay O(state_cap), frames O(digest_sample), and the whole
    episode must replay bit-identically for a seed."""
    n = 4096
    view = ViewConfig(
        enabled=True, active_size=8, passive_size=32, digest_sample=16,
        state_cap=64, shuffle_every=8,
    )
    spec = ChurnSpec(
        seed=9, leave_probability=0.001, join_probability=0.2,
        cohort_every=12, cohort_max=8, restart_every=16, min_live=3584,
        chaos_windows=(
            ChaosWindow(14, 24, ("partition",),
                        group=tuple(range(0, 2048))),
        ),
    )

    def run():
        orch = _fast_orch(
            n, spec, dim=8,
            membership=MembershipConfig(view=view, **FAST_MEMBER),
        )
        res = orch.run(44)
        churn = [r for r in res.records if r.get("kind") == "churn"]
        return orch, res, churn

    orch, res, churn = run()
    ep = res.episode

    # Membership converges through churn: arrivals are admitted in a
    # handful of rounds — nowhere near O(4096) — and the only
    # unresolved joins are the freshest arrivals still inside the
    # admission horizon at cutoff.
    joins = ep["join_convergence_rounds"]
    assert joins and float(np.median(joins)) <= 8
    assert max(joins) < 64
    assert len(ep["unresolved_joins"]) <= len(joins)

    # O(sample) frames and O(state_cap) residency, fleet-wide peaks.
    from dpwa_tpu.membership import digest as _digest
    assert ep["view_max_digest_entries"] <= view.digest_sample + 1
    assert ep["max_digest_bytes"] <= (
        _digest._DIGEST_HDR.size
        + _digest.entries_size(view.digest_sample + 1)
    )
    assert ep["view_max_tracked"] <= view.state_cap
    live = [p for p in range(n) if orch.nodes[p].alive]
    for p in live[:: max(1, len(live) // 64)]:
        node = orch.nodes[p]
        assert node.membership._peak_resident <= view.state_cap
        snap = orch.residency_snapshot(p)
        assert snap["board_tracked"] <= view.state_cap + view.digest_sample
        assert snap["view_active"] <= view.active_size
        assert snap["view_passive"] <= view.passive_size

    # The partition window was actually felt (the observer sits in the
    # majority; the minority's absence shows up as suspicion traffic),
    # and the fleet kept exchanging throughout.
    rounds = [r for r in res.records if r.get("kind") == "round"]
    assert all(r["exchanges"] > 0 for r in rounds)

    # Bit-identical replay: the deterministic churn stream is
    # byte-for-byte stable across reruns of the seed.
    _, res2, churn2 = run()
    assert churn2 == churn
    assert res2.episode["view_max_tracked"] == ep["view_max_tracked"]
    assert res2.episode["view_max_digest_entries"] == (
        ep["view_max_digest_entries"]
    )
