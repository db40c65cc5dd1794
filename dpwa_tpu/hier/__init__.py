"""Hierarchical gossip: ICI islands × wide-area ring (docs/hierarchy.md).

The ``topology:`` config block partitions the ``nodes:`` list into
islands; each island averages internally over the fast fabric and
delegates its wide-area voice to one threefry-elected leader.  This
package holds the resolved topology view, the leader board
(election + failover succession), the two-level TCP pairing schedule,
and the in-process CPU simulator the tests drive.
"""

from dpwa_tpu.hier.engine import HierGossipEngine
from dpwa_tpu.hier.leader import LeaderBoard
from dpwa_tpu.hier.schedule import build_hier_schedule, wide_slot_indices
from dpwa_tpu.hier.topology import Topology

__all__ = [
    "HierGossipEngine",
    "LeaderBoard",
    "Topology",
    "build_hier_schedule",
    "wide_slot_indices",
]
