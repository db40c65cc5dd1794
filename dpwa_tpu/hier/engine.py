"""In-process hierarchical gossip simulator (CPU stand-in for the rig).

The TPU probe is dead, so the two-level data path is proven the same way
the fleet plane was (``fleet/orchestrator.py``): numpy replicas driven
by the REAL control objects — :class:`LeaderBoard` elections/succession,
the :class:`IncidentPlane` observer — with the wire reduced to array
averages.  Per round (mirroring ``hier/schedule.py``'s cycle):

1. **intra-island all-reduce**: each island's live members collapse to
   their exact mean — the semantics of the ``parallel/ici.py`` ppermute
   hypercube (one pass over the log2(k) XOR slots at α = 0.5 IS the
   mean); frame accounting charges ``k·ceil(log2 k)`` ICI frames;
2. **wide-area leg**: ONLY island leaders exchange, paired by the same
   round-robin island tournament the TCP pool compiles in; 2 wide-area
   frames per realized pair — this is the ~island_size× frame reduction
   ``tests/test_hier.py`` counts;
3. **fan-back**: the leader's merged replica is re-broadcast in-island
   (ICI frames again), so every member re-enters the next round equal.

``topology=None`` runs the flat even/odd ring instead — every frame
wide-area — which is the baseline the acceptance comparison is against.
No wall clock is read anywhere, so a rerun at the same seed is
bit-identical (the churn soak's determinism story, docs/fleet.md).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

from dpwa_tpu.config import ObsConfig
from dpwa_tpu.hier.leader import LeaderBoard
from dpwa_tpu.hier.topology import Topology
from dpwa_tpu.obs.incidents import IncidentPlane
from dpwa_tpu.parallel.schedules import (
    _group_round_robin,
    _ring_even,
    _ring_odd,
)


class HierGossipEngine:
    """Drive one two-level (or flat) gossip episode over numpy replicas."""

    def __init__(
        self,
        n_peers: int,
        dim: int = 32,
        seed: int = 0,
        topology: Optional[Topology] = None,
        incidents: Optional[ObsConfig] = None,
        observer: int = 0,
    ):
        if topology is not None and topology.n_peers != n_peers:
            raise ValueError(
                f"topology covers {topology.n_peers} peers, engine has"
                f" {n_peers}"
            )
        self.n_peers = int(n_peers)
        self.dim = int(dim)
        self.seed = int(seed)
        self.topology = topology
        self.observer = int(observer)
        self.alive = [True] * self.n_peers
        rng = np.random.default_rng(self.seed)
        self.replicas = rng.standard_normal((self.n_peers, self.dim))
        self.board = (
            LeaderBoard(topology, seed=self.seed)
            if topology is not None
            else None
        )
        self.incidents = (
            IncidentPlane(
                self.observer, self.n_peers, incidents,
                path=None, topology=topology,
            )
            if incidents is not None
            else None
        )
        self.wide_frames = 0
        self.intra_frames = 0
        self.records: List[dict] = []
        self.events_seen: List[dict] = []
        self.incidents_opened = 0
        self.alerts_total: Dict[str, int] = {}
        # Deaths fold into the leader board immediately (the scoreboard
        # marks a peer dead synchronously too); the succession events
        # they cause are charged to the NEXT round's evidence, like a
        # digest arriving one exchange later.
        self._pending_events: List[dict] = []
        if self.board is not None:
            self._pending_events.extend(self.board.initial_events())

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------

    def kill(self, peer: int) -> None:
        """Mark ``peer`` dead (crash; no goodbye).  Leader deaths queue
        a deterministic succession (:meth:`LeaderBoard.note_dead`)."""
        if not self.alive[peer]:
            return
        self.alive[peer] = False
        if self.board is not None:
            self._pending_events.extend(self.board.note_dead(peer))

    def revive(self, peer: int) -> None:
        """Bring ``peer`` back into its island's candidate set."""
        if self.alive[peer]:
            return
        self.alive[peer] = True
        if self.board is not None:
            self._pending_events.extend(self.board.note_alive(peer))

    # ------------------------------------------------------------------
    # Convergence figure (exact; the sketch board estimates this)
    # ------------------------------------------------------------------

    def rel_rms(self) -> float:
        live = [p for p in range(self.n_peers) if self.alive[p]]
        if len(live) < 2:
            return 0.0
        vecs = self.replicas[live]
        mean = vecs.mean(axis=0)
        num = float(np.sqrt(np.mean((vecs - mean) ** 2)))
        den = float(np.sqrt(np.mean(mean**2))) + 1e-12
        return num / den

    # ------------------------------------------------------------------
    # One round
    # ------------------------------------------------------------------

    def _step_flat(self, r: int) -> None:
        """Flat baseline: one even/odd ring phase, every frame wide."""
        phase = _ring_even(self.n_peers) if r % 2 == 0 else _ring_odd(
            self.n_peers
        )
        for i in range(self.n_peers):
            j = int(phase[i])
            if j <= i or not (self.alive[i] and self.alive[j]):
                continue
            merged = 0.5 * (self.replicas[i] + self.replicas[j])
            self.replicas[i] = merged
            self.replicas[j] = merged
            self.wide_frames += 2  # both sides fetch (pairwise mode)

    def _intra_allreduce(self, members: List[int]) -> None:
        """Exact island mean — the hypercube ppermute pass, charged at
        its recursive-doubling frame cost."""
        k = len(members)
        if k < 2:
            return
        mean = self.replicas[members].mean(axis=0)
        self.replicas[members] = mean
        self.intra_frames += k * int(math.ceil(math.log2(k)))

    def _step_hier(self, r: int) -> None:
        topo = self.topology
        board = self.board
        assert topo is not None and board is not None
        live_members = [
            [p for p in topo.members_of(g) if self.alive[p]]
            for g in range(topo.n_islands)
        ]
        # 1. intra-island all-reduce (ICI leg).
        for members in live_members:
            self._intra_allreduce(members)
        # 2. wide-area leg: leaders only, on the island tournament.
        if topo.n_islands > 1:
            rounds = _group_round_robin(topo.n_islands)
            gperm = rounds[r % len(rounds)]
            for g in range(topo.n_islands):
                pg = int(gperm[g])
                if pg <= g:
                    continue
                a, b = board.leader_of(g), board.leader_of(pg)
                if (
                    a is None or b is None
                    or not (self.alive[a] and self.alive[b])
                ):
                    continue
                merged = 0.5 * (self.replicas[a] + self.replicas[b])
                self.replicas[a] = merged
                self.replicas[b] = merged
                self.wide_frames += 2
        # 3. fan-back: members adopt their leader's merged replica.
        for g, members in enumerate(live_members):
            leader = board.leader_of(g)
            if leader is None or not self.alive[leader]:
                continue
            followers = [p for p in members if p != leader]
            if followers:
                self.replicas[followers] = self.replicas[leader]
                self.intra_frames += len(followers)

    def step(self, r: int) -> dict:
        """One gossip round; returns the round's observer result."""
        events, self._pending_events = self._pending_events, []
        self.events_seen.extend(events)
        if self.topology is None:
            self._step_flat(r)
        else:
            self._step_hier(r)
        rel = self.rel_rms()
        inc = {"alerts": [], "opened": False}
        if self.incidents is not None:
            inc = self.incidents.observe_round(
                r, events=events, rel_rms=rel
            )
            for kind in inc["alerts"]:
                self.alerts_total[kind] = (
                    self.alerts_total.get(kind, 0) + 1
                )
            if inc["opened"]:
                self.incidents_opened += 1
        if self.topology is not None:
            for g in range(self.topology.n_islands):
                members = self.topology.members_of(g)
                live = [p for p in members if self.alive[p]]
                vecs = self.replicas[live] if live else None
                intra_rel = 0.0
                if vecs is not None and len(live) >= 2:
                    mean = vecs.mean(axis=0)
                    num = float(np.sqrt(np.mean((vecs - mean) ** 2)))
                    den = float(np.sqrt(np.mean(mean**2))) + 1e-12
                    intra_rel = num / den
                rec = {
                    "record": "island",
                    "round": int(r),
                    "island": self.topology.island_name(g),
                    "term": self.board.term_of(g),
                    "live": len(live),
                    "rel_rms": round(intra_rel, 9),
                    "wide_frames": self.wide_frames,
                }
                leader = self.board.leader_of(g)
                if leader is not None:
                    rec["leader"] = int(leader)
                self.records.append(rec)
        return {"round": r, "rel_rms": rel, **inc}

    def run(
        self, rounds: int, target_rel: Optional[float] = None
    ) -> dict:
        """Drive ``rounds`` rounds; returns the episode summary
        (``rounds_to_target`` is None when the target was never hit)."""
        history: List[float] = []
        rounds_to_target: Optional[int] = None
        for r in range(int(rounds)):
            out = self.step(r)
            history.append(out["rel_rms"])
            if (
                target_rel is not None
                and rounds_to_target is None
                and out["rel_rms"] <= target_rel
            ):
                rounds_to_target = r + 1
        return {
            "rounds": int(rounds),
            "final_rel_rms": history[-1] if history else 0.0,
            "history": history,
            "rounds_to_target": rounds_to_target,
            "wide_frames": self.wide_frames,
            "intra_frames": self.intra_frames,
            "incidents_opened": self.incidents_opened,
            "alerts": dict(sorted(self.alerts_total.items())),
        }
