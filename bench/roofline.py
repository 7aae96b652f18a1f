"""Bytes that one PageRank sweep has to move, counted from the graph.

A pull sweep reads every edge's source index once (4 bytes an edge) and,
for every vertex, reads its rank and its out-degree and writes its new rank
(8 + 4 + 8 bytes in float64). The gathered contributions may hit on-chip
memory and are not counted. Both counts come from the graph and not from
any layout, so padding, masks or a second orientation can only make the
achieved share lower, never push it past 100%.
"""
from __future__ import annotations

__all__ = ["bytes_per_sweep"]


def bytes_per_sweep(n: int, m: int) -> int:
    """HBM bytes of one sweep over ``n`` vertices and ``m`` edges
    (self-loops included)."""
    return 4 * m + 20 * n
