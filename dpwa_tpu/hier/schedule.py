"""Two-level (island × wide-area) pairing schedule for the TCP ring.

Builds a standard :class:`~dpwa_tpu.parallel.schedules.Schedule` — same
frozen dataclass, same host/jit pairing API — whose pool realizes the
hierarchical cycle (docs/hierarchy.md):

- **intra slots**: every island runs its own ring pairing phases among
  its members (the CPU-simulated stand-in for the ``parallel/ici.py``
  ppermute path — on hardware these exchanges ride ICI, not the wide
  area), ``topology.intra_rounds`` sweeps per block;
- **inter slots**: ONLY the threefry-elected island leaders pair, on a
  round-robin tournament over islands (reusing the flat hierarchical
  schedule's :func:`_group_round_robin` connectivity guarantee); every
  non-leader self-pairs, and a self-pair never fetches
  (``Schedule.participates`` is False), which is exactly where the
  ~island_size× wide-area frame reduction comes from.

Leaders are the term-0 election (:class:`LeaderBoard`); the pool is
static like every other schedule.  Live failover on the TCP path rides
the existing health machinery: a dead leader is quarantined by the
scoreboard and ``Schedule.remap_partner`` re-draws the fetch — while the
membership/fleet planes converge on the successor through the
:class:`LeaderBoard` succession draw.
"""

from __future__ import annotations

import numpy as np

from dpwa_tpu.config import DpwaConfig
from dpwa_tpu.hier.leader import LeaderBoard
from dpwa_tpu.hier.topology import Topology
from dpwa_tpu.parallel.schedules import (
    Schedule,
    _group_round_robin,
    _ring_even,
    _ring_odd,
    is_involution,
)


def _intra_perm(topo: Topology, phase: int) -> np.ndarray:
    """One intra-island slot: each island's members ring-paired among
    themselves (phase 0 = even pairs, 1 = odd pairs), islands of size 1
    self-paired."""
    perm = np.arange(topo.n_peers)
    ring = _ring_even if phase % 2 == 0 else _ring_odd
    for g in range(topo.n_islands):
        members = np.asarray(topo.members_of(g))
        if len(members) < 2:
            continue
        local = ring(len(members))
        perm[members] = members[local]
    return perm


def _inter_perm(
    topo: Topology, board: LeaderBoard, gperm: np.ndarray
) -> np.ndarray:
    """One wide-area slot: the tournament round's island pairing applied
    to island LEADERS; everyone else self-pairs."""
    perm = np.arange(topo.n_peers)
    for g in range(topo.n_islands):
        pg = int(gperm[g])
        if pg == g:
            continue
        a, b = board.leader_of(g), board.leader_of(pg)
        if a is None or b is None:
            continue
        perm[a], perm[b] = b, a
    return perm


def build_hier_schedule(config: DpwaConfig) -> Schedule:
    """Materialize the hierarchical pool for ``config.topology``."""
    topo = Topology.from_config(config)
    board = LeaderBoard(topo, seed=config.topology.leader_seed)
    proto = config.protocol
    intra = [_intra_perm(topo, 0), _intra_perm(topo, 1)]
    pool = list(intra)
    cycle: list = []
    intra_cycle = [0, 1] * config.topology.intra_rounds
    if topo.n_islands > 1:
        for gperm in _group_round_robin(topo.n_islands):
            cycle.extend(intra_cycle)
            pool.append(_inter_perm(topo, board, gperm))
            cycle.append(len(pool) - 1)
    else:
        cycle.extend(intra_cycle)
    arr = np.stack(pool).astype(np.int32)
    for row in arr:
        assert is_involution(row), "hier slot is not an involution"
    return Schedule(
        pool=arr,
        n_peers=config.n_peers,
        fetch_probability=proto.fetch_probability,
        seed=proto.seed,
        name="hier",
        drop_probability=proto.drop_probability,
        mode="pairwise",
        wire_dtype=proto.wire_dtype,
        branch_map=np.asarray(cycle, dtype=np.int32),
    )


def wide_slot_indices(schedule: Schedule, topo: Topology) -> tuple:
    """Pool-row indices whose pairings cross islands (the wide-area
    slots) — the accounting hook ``tests/test_hier.py`` counts with."""
    wide = []
    for k, row in enumerate(schedule.pool):
        if any(
            topo.island_of(i) != topo.island_of(int(row[i]))
            for i in range(len(row))
        ):
            wide.append(k)
    return tuple(wide)
