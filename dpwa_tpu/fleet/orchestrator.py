"""Plane-level elastic-fleet simulator: churn the CONTROL planes at 256.

``tests/fleet_worker.py`` proved one Rx server can feed a 256-peer ring;
this module proves the *decision planes* survive 256 peers CHURNING.  It
deliberately simulates the wire (an exchange is a numpy average + an
Outcome string) while running the REAL control-plane objects per node —
:class:`~dpwa_tpu.health.scoreboard.Scoreboard`,
:class:`~dpwa_tpu.membership.manager.MembershipManager` (real digests
through ``encode``/``merge``), and the observer's
:class:`~dpwa_tpu.obs.incidents.IncidentPlane` — because those are where
the O(N)-forever assumptions lived (ROADMAP "Elastic fleet churn").  256
full TCP transports would measure socket limits; this measures the
eviction/readmission/digest machinery that PR 11 hardens.

Single-threaded by construction: one loop drives every node in sorted
peer order, every control decision is a threefry draw keyed on round
counters (:mod:`dpwa_tpu.fleet.schedule`), and wall time is only ever
*reported* (``wall_s``) — never consulted — so the churn record stream
is bit-identical across reruns of a seed.

Emits the frozen-schema ``fleet`` JSONL stream (tools/schema_check.py):

- ``kind: churn`` — one per non-quiet round; deterministic fields only
  (the bit-identity anchor tests replay);
- ``kind: round`` — one per round; adds measured fields (``wall_s``,
  ``rel_rms``) that vary run to run;
- ``kind: episode`` — one per run; convergence + incident summary
  (``tools/fleet_report.py`` joins it with trace/incident streams).
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from dpwa_tpu.config import (
    ChaosConfig,
    HealthConfig,
    ObsConfig,
    MembershipConfig,
)
from dpwa_tpu.fleet.schedule import ChurnSchedule, ChurnSpec
from dpwa_tpu.health.chaos import ChaosEngine
from dpwa_tpu.hier.leader import LeaderBoard
from dpwa_tpu.hier.topology import Topology
from dpwa_tpu.health.detector import Outcome
from dpwa_tpu.health.scoreboard import Scoreboard
from dpwa_tpu.membership.manager import MembershipManager
from dpwa_tpu.obs.incidents import IncidentPlane
from dpwa_tpu.parallel.schedules import Schedule, _ring_pull
from dpwa_tpu.recovery.bootstrap import choose_donor


class SimNode:
    """One fleet member: a numpy replica plus its real control planes.

    ``boot`` builds FRESH Scoreboard/MembershipManager instances — a
    rejoiner has no memory of its past life except the monotonically
    bumped incarnation (which is what lets it refute stale DEAD claims,
    docs/membership.md)."""

    def __init__(
        self,
        peer: int,
        n_peers: int,
        seed: int,
        topology: Optional[Topology] = None,
    ):
        self.peer = int(peer)
        self.n_peers = int(n_peers)
        self.seed = int(seed)
        self.topology = topology
        self.alive = False
        self.boots = 0
        self.next_incarnation = 0
        self.vec: Optional[np.ndarray] = None
        self.board: Optional[Scoreboard] = None
        self.membership: Optional[MembershipManager] = None

    def boot(
        self,
        vec: np.ndarray,
        health: HealthConfig,
        member: MembershipConfig,
    ) -> None:
        self.board = Scoreboard(
            self.n_peers, self.peer, config=health, seed=self.seed
        )
        # With a topology the node's manager owns a per-node LeaderBoard
        # (built inside MembershipManager) and speaks the v2 digest:
        # every node converges on leadership through gossip, the way the
        # live transport does — the orchestrator's own board is just the
        # ground-truth copy the schedule resolves restarts against.
        self.membership = MembershipManager(
            self.n_peers,
            self.peer,
            self.board,
            config=member,
            seed=self.seed,
            topology=self.topology,
        )
        self.membership.incarnation = self.next_incarnation
        self.next_incarnation += 1
        self.vec = np.array(vec, dtype=np.float64, copy=True)
        self.alive = True
        self.boots += 1

    def stop(self) -> None:
        """Departure: the process is gone.  The replica is kept frozen
        (a restarting supervisor may resurrect the box) but the control
        planes are dropped — a rejoiner gets fresh ones."""
        self.alive = False
        self.board = None
        self.membership = None


@dataclasses.dataclass
class EpisodeResult:
    """What :meth:`FleetOrchestrator.run` hands back (and logs)."""

    records: List[dict]
    episode: dict

    @property
    def churn_records(self) -> List[dict]:
        return [r for r in self.records if r.get("kind") == "churn"]


class FleetOrchestrator:
    """Drive one elastic-churn episode over ``n_peers`` simulated nodes.

    The observer (``spec.protected[0]``, default peer 0) is never
    churned; its scoreboard/membership/incident planes are the ones the
    episode summary reads — one stable vantage point, the way a soak's
    operator watches one node's /healthz."""

    def __init__(
        self,
        n_peers: int,
        spec: ChurnSpec,
        dim: int = 32,
        health: Optional[HealthConfig] = None,
        membership: Optional[MembershipConfig] = None,
        chaos: Optional[ChaosConfig] = None,
        incidents: Optional[ObsConfig] = None,
        path: Optional[str] = None,
        initial_live: Optional[int] = None,
        topology: Optional[Topology] = None,
    ):
        self.n_peers = int(n_peers)
        self.spec = spec
        if topology is not None and topology.n_peers != self.n_peers:
            raise ValueError(
                f"topology covers {topology.n_peers} peers, fleet has"
                f" {self.n_peers}"
            )
        self.topology = topology
        self.seed = int(spec.seed)
        self.dim = int(dim)
        self.health = health if health is not None else HealthConfig()
        self.membership_cfg = (
            membership if membership is not None else MembershipConfig()
        )
        # Fault DRAW probabilities for chaos windows; the window's kind
        # list gates which draws take effect (schedule.py).
        self.chaos_cfg = (
            chaos
            if chaos is not None
            else ChaosConfig(
                enabled=True,
                seed=self.seed,
                delay_probability=0.5,
                throttle_probability=0.25,
                byzantine_sign_probability=0.3,
                byzantine_scale_probability=0.2,
                byzantine_zero_probability=0.1,
            )
        )
        self.schedule = ChurnSchedule(spec, self.n_peers, topology=topology)
        # Ground-truth leadership view the orchestrator itself maintains
        # (resolves leader restarts, stamps island records); per-node
        # boards live inside each SimNode's MembershipManager and
        # converge on this through v2 digests.
        self.leader_board = (
            LeaderBoard(topology, seed=self.seed)
            if topology is not None
            else None
        )
        self._board_events: List[dict] = (
            list(self.leader_board.initial_events())
            if self.leader_board is not None
            else []
        )
        self.observer = spec.protected[0] if spec.protected else 0
        self._path = path
        self._file = (
            open(path, "a", encoding="utf-8") if path else None
        )
        self.records: List[dict] = []
        # One engine per SERVING peer: fault draws are (seed, round,
        # server)-keyed, exactly like the wire chaos harness.
        self._engines = [
            ChaosEngine(self.chaos_cfg, peer=p)
            for p in range(self.n_peers)
        ]
        # Gossip pairing: the one-sided pull ring the TCP transport uses
        # (remap_partner gives the health-aware fallback).
        self._sched = Schedule(
            pool=np.stack(
                [_ring_pull(self.n_peers, 0), _ring_pull(self.n_peers, 1)]
            ),
            n_peers=self.n_peers,
            fetch_probability=1.0,
            seed=self.seed,
            name="ring",
            mode="pull",
        )
        self.nodes = [
            SimNode(p, self.n_peers, self.seed, topology=topology)
            for p in range(self.n_peers)
        ]
        n_live = (
            self.n_peers if initial_live is None else int(initial_live)
        )
        for p in range(n_live):
            self.nodes[p].boot(
                self._init_vec(p), self.health, self.membership_cfg
            )
        inc_cfg = incidents
        if inc_cfg is None:
            inc_cfg = ObsConfig()
        self.incidents = IncidentPlane(
            self.observer, self.n_peers, inc_cfg, path=None,
            topology=topology,
        )
        # Convergence bookkeeping: (event round, peer) -> resolved round.
        self._leave_pending: Dict[int, int] = {}  # peer -> left round
        self._join_pending: Dict[int, int] = {}  # peer -> joined round
        self._leave_convergence: List[int] = []
        self._join_convergence: List[int] = []

    # ------------------------------------------------------------------
    # Node lifecycle
    # ------------------------------------------------------------------

    def _init_vec(self, peer: int) -> np.ndarray:
        """Deterministic per-peer initial replica (seeded, no wall
        clock): distinct vectors so rel_rms measures real convergence."""
        rng = np.random.default_rng([self.seed, peer])
        return rng.standard_normal(self.dim)

    def _live(self) -> List[int]:
        return [n.peer for n in self.nodes if n.alive]

    def _departed(self) -> List[int]:
        return [n.peer for n in self.nodes if not n.alive]

    def _donor_vec(self, joiner: int, round_: int) -> np.ndarray:
        """Bootstrap the joiner's replica from a deterministically
        elected live donor (the PR 2 donor draw), falling back to the
        joiner's frozen/initial replica when nobody can serve."""
        healthy = [n.alive for n in self.nodes]
        donor = choose_donor(
            joiner, self.n_peers, round_, self.seed, healthy
        )
        if donor is not None and self.nodes[donor].vec is not None:
            return self.nodes[donor].vec
        node = self.nodes[joiner]
        if node.vec is not None:
            return node.vec
        return self._init_vec(joiner)

    def _boot_peer(self, peer: int, round_: int) -> None:
        self.nodes[peer].boot(
            self._donor_vec(peer, round_),
            self.health,
            self.membership_cfg,
        )
        # A rejoin before ring-wide eviction cancels the pending leave:
        # there is no ghost left to evict, so the departure is no longer
        # a convergence event (it would otherwise sit "unresolved"
        # forever and poison the episode summary).
        self._leave_pending.pop(peer, None)
        self._join_pending.setdefault(peer, int(round_))
        if self.leader_board is not None:
            self._board_events.extend(self.leader_board.note_alive(peer))

    def _stop_peer(self, peer: int, round_: int) -> None:
        self.nodes[peer].stop()
        self._leave_pending.setdefault(peer, int(round_))
        self._join_pending.pop(peer, None)
        if self.leader_board is not None:
            # Leader deaths bump the island's term and draw a successor
            # — the ground-truth copy of what each node's board does
            # once its scoreboard notices (docs/hierarchy.md).
            self._board_events.extend(self.leader_board.note_dead(peer))

    # ------------------------------------------------------------------
    # One gossip exchange (plane-level wire)
    # ------------------------------------------------------------------

    def _blocked(
        self, src: int, dst: int, group: Tuple[int, ...]
    ) -> bool:
        """Whether the active partition window cuts the src<->dst link
        (links inside either side stay up)."""
        if not group:
            return False
        return (src in group) != (dst in group)

    def _fetch_outcome(
        self,
        fetcher: SimNode,
        target: int,
        round_: int,
        chaos_kinds: Tuple[str, ...],
        group: Tuple[int, ...],
    ) -> str:
        """Classify one fetch the way the transport's wire path would."""
        if self._blocked(fetcher.peer, target, group):
            return Outcome.TIMEOUT
        node = self.nodes[target]
        if not node.alive:
            return Outcome.TIMEOUT
        if chaos_kinds:
            plan = self._engines[target].plan(round_)
            if "byzantine" in chaos_kinds and plan.byzantine != "none":
                # The trust plane screens the lying frame: classified
                # poisoned, payload discarded (docs/trust.md).
                return Outcome.POISONED
            if "straggler" in chaos_kinds and (
                plan.kind in ("delay", "throttle") or plan.stall_s > 0.0
            ):
                return Outcome.SLOW
        return Outcome.SUCCESS

    # ------------------------------------------------------------------
    # The round loop
    # ------------------------------------------------------------------

    def run(self, rounds: int) -> EpisodeResult:
        outcome_totals: Dict[str, int] = {}
        max_digest = 0
        max_wall = 0.0
        alerts_total: Dict[str, int] = {}
        incidents_opened = 0
        for r in range(int(rounds)):
            t0 = time.perf_counter()
            ev = self.schedule.events(r, self._live(), self._departed())
            group = self.schedule.partition_group(r)
            # -- churn application ------------------------------------
            for p in ev.leaves:
                self._stop_peer(p, r)
            for p in ev.joins:
                self._boot_peer(p, r)
            for p in ev.cohort:
                self._boot_peer(p, r)
            for p in ev.restart:
                # Rolling restart: down and back within the round, state
                # restored through the donor path (the supervisor's
                # crash->bootstrap cycle compressed to one round).
                self._stop_peer(p, r)
                self._boot_peer(p, r)
            # Island-granular families (hier fleets only; empty tuples
            # on flat fleets keep this a no-op).
            for p in ev.island_leaves:
                self._stop_peer(p, r)
            for p in ev.island_joins:
                self._boot_peer(p, r)
            leader_restarts: List[int] = []
            for g in ev.leader_restart_islands:
                # The schedule names the ISLAND; the orchestrator's
                # ground-truth board resolves who its leader is NOW.
                leader = self.leader_board.leader_of(g)
                if (
                    leader is None
                    or leader in self.spec.protected
                    or not self.nodes[leader].alive
                ):
                    continue
                self._stop_peer(leader, r)
                self._boot_peer(leader, r)
                leader_restarts.append(leader)
            live = self._live()
            # -- gossip exchanges -------------------------------------
            digests: Dict[int, bytes] = {}
            exchanges = 0
            failures = 0
            obs_outcome: Optional[str] = None
            obs_partner: Optional[int] = None
            round_outcomes: Dict[str, int] = {}
            use_view = self.membership_cfg.view.enabled
            for f in sorted(live):
                node = self.nodes[f]
                partner = self._sched.partner(r, f)
                if partner != f and node.board.is_quarantined(
                    partner, r
                ):
                    if use_view:
                        # Bounded remap (membership.view): the fallback
                        # draw ranges over the node's active view and an
                        # O(active) healthy map — never an O(N) mask.
                        cands = node.membership.partner_candidates()
                        partner = self._sched.remap_partner(
                            r, f, partner,
                            node.board.healthy_map(cands, r), cands,
                        )
                    else:
                        partner = self._sched.remap_partner(
                            r, f, partner, node.board.healthy_mask(r)
                        )
                if partner == f:
                    continue
                outcome = self._fetch_outcome(
                    node, partner, r, ev.chaos, group
                )
                latency = 0.05 if outcome == Outcome.SLOW else 0.005
                node.board.record(
                    partner, outcome, latency_s=latency, round=r
                )
                round_outcomes[outcome] = (
                    round_outcomes.get(outcome, 0) + 1
                )
                if outcome in (Outcome.SUCCESS, Outcome.SLOW):
                    exchanges += 1
                    node.vec = 0.5 * (
                        node.vec + self.nodes[partner].vec
                    )
                    blob = digests.get(partner)
                    if blob is None:
                        blob = digests[partner] = self.nodes[
                            partner
                        ].membership.encode(r)
                        max_digest = max(max_digest, len(blob))
                    node.membership.merge(blob, r)
                else:
                    failures += 1
                if f == self.observer:
                    obs_outcome = outcome
                    obs_partner = partner
            # -- probes (readmission + evicted-ghost reprobe) ---------
            for f in sorted(live):
                node = self.nodes[f]
                # O(quarantined + tombstones) walk: probe_candidates()
                # returns exactly the peers probe_due() would flag, so
                # this stays byte-identical to the full range(N) scan
                # while making 4096-peer rounds affordable.
                for q in node.board.probe_candidates(r):
                    if q == f:
                        continue
                    ok = self.nodes[q].alive and not self._blocked(
                        f, q, group
                    )
                    node.board.record_probe(q, ok, round=r)
            # -- membership round end ---------------------------------
            for f in sorted(live):
                self.nodes[f].membership.end_round(r)
            # -- observer planes --------------------------------------
            obs = self.nodes[self.observer]
            obs_events: List[dict] = []
            for f in sorted(live):
                events = self.nodes[f].membership.pop_events()
                if f == self.observer:
                    obs_events = events
            if self._board_events:
                # Leadership events from this round's churn (elections,
                # failover successions) reach the observer alongside its
                # own membership events — the incident plane classifies
                # leader_failover as a root cause (docs/incidents.md).
                obs_events = obs_events + self._board_events
                self._board_events = []
            rel_rms = self._rel_rms(live)
            wall = time.perf_counter() - t0
            max_wall = max(max_wall, wall)
            view = obs.membership.view_snapshot()
            inc = self.incidents.observe_round(
                r,
                outcome=obs_outcome,
                peer=obs_partner,
                board=obs.board.snapshot(r),
                events=obs_events,
                rel_rms=rel_rms,
                wall_s=wall,
                partition_state=view.get("partition_state"),
                component=view.get("component"),
            )
            for kind in inc["alerts"]:
                alerts_total[kind] = alerts_total.get(kind, 0) + 1
            if inc["opened"]:
                incidents_opened += 1
            for k, v in sorted(round_outcomes.items()):
                outcome_totals[k] = outcome_totals.get(k, 0) + v
            self._settle_convergence(r)
            # -- records ----------------------------------------------
            evicted = obs.board.evicted_peers()
            if not ev.quiet:
                churn_rec = {
                    "record": "fleet",
                    "kind": "churn",
                    "round": r,
                    "leaves": list(ev.leaves),
                    "joins": list(ev.joins),
                    "cohort": list(ev.cohort),
                    "restart": list(ev.restart),
                    "chaos": list(ev.chaos),
                    "live": len(live),
                    "evicted": evicted,
                }
                if self.topology is not None:
                    # Hier-only optional fields — a flat fleet's churn
                    # stream stays byte-identical to pre-hierarchy runs.
                    churn_rec["island_leaves"] = list(ev.island_leaves)
                    churn_rec["island_joins"] = list(ev.island_joins)
                    churn_rec["churned_islands"] = list(
                        ev.churned_islands
                    )
                    churn_rec["leader_restarts"] = leader_restarts
                self._emit(churn_rec)
            if self.topology is not None:
                for g in range(self.topology.n_islands):
                    members = self.topology.members_of(g)
                    live_m = [p for p in members if self.nodes[p].alive]
                    island_rec = {
                        "record": "island",
                        "round": r,
                        "island": self.topology.island_name(g),
                        "term": self.leader_board.term_of(g),
                        "live": len(live_m),
                        "rel_rms": round(self._rel_rms(live_m), 9),
                    }
                    leader = self.leader_board.leader_of(g)
                    if leader is not None:
                        island_rec["leader"] = int(leader)
                    self._emit(island_rec)
            self._emit(
                {
                    "record": "fleet",
                    "kind": "round",
                    "round": r,
                    "live": len(live),
                    "exchanges": exchanges,
                    "failures": failures,
                    "outcomes": dict(sorted(round_outcomes.items())),
                    "rel_rms": round(rel_rms, 9),
                    "wall_s": round(wall, 6),
                    "digest_bytes": max_digest,
                    "evicted": len(evicted),
                    "alerts": inc["alerts"],
                }
            )
        episode = self._finish(int(rounds), outcome_totals, max_digest,
                               max_wall, alerts_total, incidents_opened)
        return EpisodeResult(records=self.records, episode=episode)

    # ------------------------------------------------------------------
    # Summaries
    # ------------------------------------------------------------------

    def _rel_rms(self, live: Sequence[int]) -> float:
        """Relative RMS disagreement of live replicas (the sketch
        board's convergence figure, computed exactly here)."""
        if len(live) < 2:
            return 0.0
        vecs = np.stack([self.nodes[p].vec for p in sorted(live)])
        mean = vecs.mean(axis=0)
        num = float(np.sqrt(np.mean((vecs - mean) ** 2)))
        den = float(np.sqrt(np.mean(mean**2))) + 1e-12
        return num / den

    def residency_snapshot(self, peer: int) -> dict:
        """Resident per-peer control-plane state for one live node.

        Returns entry counts and an approximate resident byte figure
        (``sys.getsizeof`` sums over the per-peer containers) for the
        scoreboard and membership planes — the quantity the slow soaks in
        ``tests/test_fleet.py`` read per node to prove the ``membership.view``
        ``state_cap`` bound holds at 4096 (docs/membership.md).  The
        byte figure is an approximation, but a consistent one across N,
        which is all an O(sample)-vs-O(N) verdict needs.
        """
        node = self.nodes[peer]
        board, member = node.board, node.membership
        if board is None or member is None:
            return {"peer": peer, "alive": False}
        board_maps = [
            board._state, board._quarantine_streak, board._quarantines,
            board._degrades, board._probe_attempts, board._last_contact,
            board._evicted, board.detector._peers,
        ]
        member_maps: list = [member._view, member._evicted, member._capped]
        part = member.partial
        if part is not None:
            member_maps.extend([part.active, part.passive, part._last_touch])
        nbytes = 0
        for m in board_maps + member_maps:
            nbytes += sys.getsizeof(m)
            if isinstance(m, dict):
                for v in m.values():
                    nbytes += sys.getsizeof(v)
        snap = {
            "peer": peer,
            "alive": True,
            "board_tracked": len(board.tracked_peers()),
            "board_tombstones": len(board._evicted),
            "member_tracked": len(member._view),
            "member_capped": len(member._capped),
            "digest_entries": member._digest_entries_last,
            "resident_bytes": nbytes,
        }
        if part is not None:
            snap["view_active"] = len(part.active)
            snap["view_passive"] = len(part.passive)
        return snap

    def _settle_convergence(self, r: int) -> None:
        """Resolve pending leave/join events against the OBSERVER's
        view: a leave converges when the observer evicts the ghost, a
        join when the observer's mask admits the rejoiner."""
        obs = self.nodes[self.observer]
        if obs.board is None:
            return
        evicted = set(obs.board.evicted_peers())
        mask = obs.board.healthy_mask(r)
        for p in sorted(self._leave_pending):
            if p in evicted:
                self._leave_convergence.append(r - self._leave_pending[p])
                del self._leave_pending[p]
        for p in sorted(self._join_pending):
            if self.nodes[p].alive and p < len(mask) and mask[p]:
                self._join_convergence.append(r - self._join_pending[p])
                del self._join_pending[p]

    def _finish(
        self,
        rounds: int,
        outcome_totals: Dict[str, int],
        max_digest: int,
        max_wall: float,
        alerts_total: Dict[str, int],
        incidents_opened: int,
    ) -> dict:
        live = self._live()
        obs = self.nodes[self.observer]
        episode = {
            "record": "fleet",
            "kind": "episode",
            "rounds": rounds,
            "n_peers": self.n_peers,
            "seed": self.seed,
            "final_live": len(live),
            "final_rel_rms": round(self._rel_rms(live), 9),
            "outcomes": dict(sorted(outcome_totals.items())),
            "max_digest_bytes": max_digest,
            "max_wall_s": round(max_wall, 6),
            "evicted": obs.board.evicted_peers(),
            "leave_convergence_rounds": sorted(self._leave_convergence),
            "join_convergence_rounds": sorted(self._join_convergence),
            "unresolved_leaves": sorted(self._leave_pending),
            "unresolved_joins": sorted(self._join_pending),
            "alerts": dict(sorted(alerts_total.items())),
            "incidents_opened": incidents_opened,
        }
        if self.membership_cfg.view.enabled:
            # View-only optional fields (legacy episodes byte-identical):
            # worst-case residency across live nodes — the O(state_cap)
            # figures the soaks bound (docs/membership.md).
            res = [self.residency_snapshot(p) for p in live]
            episode["view_max_resident_bytes"] = max(
                (s["resident_bytes"] for s in res), default=0
            )
            episode["view_max_tracked"] = max(
                (max(s["board_tracked"], s["member_tracked"]) for s in res),
                default=0,
            )
            episode["view_max_digest_entries"] = max(
                (s["digest_entries"] for s in res), default=0
            )
        if self.topology is not None:
            # Hier-only optional fields (flat episodes byte-identical).
            episode["islands"] = self.topology.n_islands
            episode["leader_terms"] = {
                self.topology.island_name(g): self.leader_board.term_of(g)
                for g in range(self.topology.n_islands)
            }
        self._emit(episode)
        if self._file is not None:
            self._file.close()
            self._file = None
        return episode

    def _emit(self, rec: dict) -> None:
        self.records.append(rec)
        if self._file is not None:
            self._file.write(json.dumps(rec, sort_keys=True) + "\n")
            self._file.flush()
