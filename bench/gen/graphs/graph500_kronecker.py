"""Graph500 Kronecker (R-MAT) edge generator, Kernel 1 of the Graph500
specification (graph500.org), in numpy.

It follows the specification's reference code: ``edge_factor * 2**scale``
draws, each built bit by bit from the initiator probabilities (A, B, C,
D = 1 - A - B - C), then the vertex labels permuted at random and the order
of the draws permuted at random. Every draw is kept as one directed edge
(src -> dst); duplicates and self-loops are left in the stream and removed
when the graph is built.

A configuration names it as ``"generator": "graph500_kronecker"`` with
``scale``, ``edge_factor``, ``A``, ``B`` and ``C``.
"""
from __future__ import annotations

import numpy as np

__all__ = ["edges", "rmat_edges"]


def rmat_edges(scale: int, edge_factor: int, a: float, b: float, c: float,
               rng: np.random.Generator):
    """Return (src, dst) int32 arrays of ``edge_factor * 2**scale`` draws."""
    n = 1 << scale
    m = edge_factor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        src_bit = rng.random(m) > ab
        dst_bit = rng.random(m) > np.where(src_bit, c_norm, a_norm)
        src += src_bit.astype(np.int64) << bit
        dst += dst_bit.astype(np.int64) << bit
    label = rng.permutation(n)
    order = rng.permutation(m)
    return (label[src[order]].astype(np.int32),
            label[dst[order]].astype(np.int32))


def edges(graph: dict, rng: np.random.Generator):
    """(n, src, dst) of the configuration's edge stream."""
    src, dst = rmat_edges(graph["scale"], graph["edge_factor"], graph["A"],
                          graph["B"], graph["C"], rng)
    return 1 << graph["scale"], src, dst
