"""The least memory traffic of KSP2_ED_ECMP's second-family re-solves,
counted from the window's events and the configuration, not from how the
program batches or pads them.

Every topology event clears the k-path memo, so each KSP2 destination
needs one re-solve of the root's distances with its first path family's
links masked out.  Its least traffic is one read of the directed edge
arrays (source, destination and metric, 4 bytes each) and of the mask
row (1 byte an edge), and one write of a V-entry distance table (4
bytes each).  It is a floor: a Bellman-Ford solve reads the edges once
per round.
"""

from __future__ import annotations

EDGE_BYTES = 3 * 4  # src, dst, metric
MASK_BYTES = 1
DIST_BYTES = 4

#: the event kinds that change the topology
TOPOLOGY_EVENTS = ("down", "up", "metric")


def resolve_bytes(directed_edges: int, nodes: int) -> int:
    """Least bytes of one masked re-solve."""
    return directed_edges * (EDGE_BYTES + MASK_BYTES) + nodes * DIST_BYTES


def ksp2_bytes(events, ksp2_dests: int, directed_edges: int,
               nodes: int) -> int:
    """Least bytes of the re-solves the window's ``events`` need: one
    per KSP2 destination per topology event."""
    solves = sum(e["kind"] in TOPOLOGY_EVENTS for e in events) * ksp2_dests
    return solves * resolve_bytes(directed_edges, nodes)
