"""Island topology: the resolved two-level view of the ``nodes:`` list.

``config.topology`` declares islands by NODE NAME (the YAML contract);
everything downstream — schedules, membership digests, the leader board,
the fleet orchestrator — works in PEER IDS (positions in ``nodes:``).
:class:`Topology` is that resolution, computed once and frozen: a
partition of ``range(n_peers)`` into named islands, with O(1) lookup in
both directions.  A flat config (no ``topology:`` block) has no
Topology; callers gate on ``config.topology.enabled`` so the flat path
never constructs one (bit-identical back-compat, docs/hierarchy.md).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

from dpwa_tpu.config import DpwaConfig


@dataclasses.dataclass(frozen=True)
class Topology:
    """A validated partition of peer ids into islands.

    Attributes:
      names: island display names, in declaration order.
      members: per island, the member peer ids sorted ascending.
      n_peers: total ring size (sum of island sizes — the partition is
        total by config validation).
    """

    names: Tuple[str, ...]
    members: Tuple[Tuple[int, ...], ...]
    n_peers: int

    @classmethod
    def from_config(cls, config: DpwaConfig) -> "Topology":
        """Resolve ``config.topology`` against ``config.nodes``.

        The config layer already validated the partition (unknown /
        duplicated / uncovered nodes all raise there, naming the
        offender), so this is pure index resolution."""
        if not config.topology.enabled:
            raise ValueError(
                "Topology.from_config on a flat config — gate on"
                " config.topology.enabled first"
            )
        index = {name: i for i, name in enumerate(config.node_names)}
        return cls(
            names=tuple(isl.name for isl in config.topology.islands),
            members=tuple(
                tuple(sorted(index[n] for n in isl.nodes))
                for isl in config.topology.islands
            ),
            n_peers=config.n_peers,
        )

    @classmethod
    def uniform(cls, n_islands: int, island_size: int) -> "Topology":
        """Synthetic even partition (tests): island ``g``
        owns peers ``[g*island_size, (g+1)*island_size)``."""
        if n_islands < 1 or island_size < 1:
            raise ValueError(
                f"need n_islands >= 1 and island_size >= 1, got"
                f" {n_islands} x {island_size}"
            )
        return cls(
            names=tuple(f"island{g}" for g in range(n_islands)),
            members=tuple(
                tuple(range(g * island_size, (g + 1) * island_size))
                for g in range(n_islands)
            ),
            n_peers=n_islands * island_size,
        )

    def __post_init__(self) -> None:
        seen: set = set()
        for ms in self.members:
            for p in ms:
                if p in seen:
                    raise ValueError(f"peer {p} in two islands")
                seen.add(p)
        if seen != set(range(self.n_peers)):
            raise ValueError(
                f"islands cover {sorted(seen)}, expected all of"
                f" range({self.n_peers})"
            )
        # O(1) peer -> island lookup; object.__setattr__ because frozen.
        island_of = [0] * self.n_peers
        for g, ms in enumerate(self.members):
            for p in ms:
                island_of[p] = g
        object.__setattr__(self, "_island_of", tuple(island_of))

    @property
    def n_islands(self) -> int:
        return len(self.members)

    def island_of(self, peer: int) -> int:
        """Island index owning ``peer``."""
        return self._island_of[peer]  # type: ignore[attr-defined]

    def members_of(self, island: int) -> Tuple[int, ...]:
        """Sorted member peer ids of ``island``."""
        return self.members[island]

    def island_name(self, island: int) -> str:
        return self.names[island]
