"""Operation and byte counts of the graph kernels, from the graph's
sizes alone, so that no implementation can move them."""

from __future__ import annotations


def pagerank_iteration_bytes(n: int, arcs: int) -> int:
    """Least bytes any exact float32 PageRank step moves: one 4-byte
    neighbour id per arc, and per vertex its 4-byte rank read and its
    4-byte rank written."""
    return 4 * arcs + 8 * n
