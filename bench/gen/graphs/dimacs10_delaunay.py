"""DIMACS10 Delaunay graphs (the 10th DIMACS Implementation Challenge,
family ``delaunay_n10`` ... ``delaunay_n24``): the Delaunay triangulation of
``2**k`` points drawn uniformly at random in the unit square, in numpy and
``scipy.spatial.Delaunay``.

Each published member is one random instance of that definition; this
draws another from the generator's seed. The graph is planar and
undirected: every unique edge of a triangle is returned in both directions.
Self-loops are added and duplicates dropped when the graph is built, as for
every generator.

A configuration names it as ``"generator": "dimacs10_delaunay"`` with
``log2_points``.
"""
from __future__ import annotations

import numpy as np

__all__ = ["edges", "delaunay_edges"]


def delaunay_edges(log2_points: int, rng: np.random.Generator):
    """(src, dst) int32 arrays: each unique edge of the triangulation of
    ``2**log2_points`` uniform points, once in each direction."""
    from scipy.spatial import Delaunay

    n = 1 << log2_points
    tri = Delaunay(rng.random((n, 2))).simplices.astype(np.int64)
    pairs = np.concatenate([tri[:, [0, 1]], tri[:, [1, 2]], tri[:, [2, 0]]])
    pairs.sort(axis=1)
    keys = np.unique(pairs[:, 0] * n + pairs[:, 1])
    lo, hi = (keys // n).astype(np.int32), (keys % n).astype(np.int32)
    return np.concatenate([lo, hi]), np.concatenate([hi, lo])


def edges(graph: dict, rng: np.random.Generator):
    """(n, src, dst) of the configuration's edge stream."""
    src, dst = delaunay_edges(graph["log2_points"], rng)
    return 1 << graph["log2_points"], src, dst
