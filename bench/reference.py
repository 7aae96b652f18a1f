"""The plain reference: static and DF-P PageRank by power iteration in numpy.

The static solve is a copy of the program's float64 oracle (Eq. 1 of the
source paper, pull-based, every vertex carrying a self-loop so that none
dangles). The DF-P update follows the paper's Alg. 2, 3 and 5 as the
configuration states them: the updated sources flag their out-neighbours and
every deletion target is affected; each sweep recomputes the affected
vertices by the closed form (Eq. 2), prunes those whose relative change is at
most tau_p and flags the out-neighbours of those whose change exceeds tau_f,
until no rank moves by more than tau. Each solve sweeps the whole graph with
dense numpy arrays: slow, and easy to read.

It imports nothing of the program and takes nothing the program made: its
graph is kept from the generator's own edge lists.
"""
from __future__ import annotations

import numpy as np

__all__ = ["RefGraph", "ref_graph", "pagerank", "canonical", "dfp_update",
           "ReferenceStream", "l1_gap"]


class RefGraph:
    """A directed graph as sorted unique int64 keys ``src * n + dst``,
    self-loops included."""

    def __init__(self, n: int, keys: np.ndarray):
        self.n = n
        self.keys = keys
        self.src = keys // n
        self.dst = keys % n
        self.out_deg = np.bincount(self.src, minlength=n).astype(np.float64)

    @property
    def m(self) -> int:
        return int(self.keys.size)

    def out_neighbours(self, flags: np.ndarray) -> np.ndarray:
        """Bool mask of the vertices that some flagged vertex points to."""
        out = np.zeros(self.n, bool)
        out[self.dst[flags[self.src]]] = True
        return out


def ref_graph(n: int, src: np.ndarray, dst: np.ndarray) -> RefGraph:
    """Build from an edge list: duplicates dropped, self-loops added."""
    loops = np.arange(n, dtype=np.int64)
    keys = np.concatenate([np.asarray(src, np.int64) * n
                           + np.asarray(dst, np.int64), loops * n + loops])
    return RefGraph(n, np.unique(keys))


def _pull(g: RefGraph, r: np.ndarray, dtype) -> np.ndarray:
    c = r / g.out_deg.astype(dtype)
    return np.bincount(g.dst, weights=c[g.src], minlength=g.n).astype(dtype)


def pagerank(g: RefGraph, alpha: float = 0.85, tol: float = 1e-10,
             norm: str = "linf", max_iter: int = 500, r0=None,
             dtype=np.float64):
    """Static PageRank: synchronous power iteration (Eq. 1) from ``r0``
    (uniform by default) until the ``norm`` ("linf" or "l1") of one
    sweep's change is at most ``tol``. Returns (ranks, sweeps)."""
    n = g.n
    r = (np.full(n, 1.0 / n, dtype) if r0 is None
         else np.asarray(r0, dtype).copy())
    c0 = dtype((1.0 - alpha) / n)
    a = dtype(alpha)
    it = 0
    for it in range(1, max_iter + 1):
        r_new = c0 + a * _pull(g, r, dtype)
        d = np.abs(r_new - r)
        step = float(d.max() if norm == "linf" else d.sum())
        r = r_new
        if step <= tol:
            break
    return r, it


def canonical(n: int, del_src, del_dst, ins_src, ins_dst):
    """A batch as the session applies it: unique deletion and insertion
    keys, self-loops never deleted, and a pair in both lists kept as an
    insertion (deletions apply first). Returns (del_keys, ins_keys)."""
    dk = np.unique(np.asarray(del_src, np.int64) * n
                   + np.asarray(del_dst, np.int64))
    dk = dk[dk // n != dk % n]
    ik = np.unique(np.asarray(ins_src, np.int64) * n
                   + np.asarray(ins_dst, np.int64))
    return np.setdiff1d(dk, ik, assume_unique=True), ik


def dfp_update(g: RefGraph, r_prev: np.ndarray, del_keys: np.ndarray,
               ins_keys: np.ndarray, alpha: float, tau: float, tau_f: float,
               tau_p: float, max_iter: int, dtype=np.float64):
    """One DF-P solve on the updated graph ``g`` from the previous ranks.
    Returns (ranks, sweeps)."""
    n = g.n
    dn = np.zeros(n, bool)
    dn[del_keys // n] = True
    dn[ins_keys // n] = True
    dv = np.zeros(n, bool)
    dv[del_keys % n] = True
    dv |= g.out_neighbours(dn)
    dn = np.zeros(n, bool)
    r = np.asarray(r_prev, dtype)
    d = g.out_deg.astype(dtype)
    c0 = dtype((1.0 - alpha) / n)
    a = dtype(alpha)
    delta, it = np.inf, 0
    while delta > tau and it < max_iter:
        if it > 0:
            dv |= g.out_neighbours(dn)
        s = _pull(g, r, dtype)
        rv = (c0 + a * (s - r / d)) / (1 - a / d)
        r_new = np.where(dv, rv, r)
        dr = np.abs(r_new - r)
        rel = dr / np.maximum(r_new, r)
        dv &= ~(rel <= tau_p)
        dn = rel > tau_f
        delta = float(dr.max())
        r = r_new
        it += 1
    return r, it


class ReferenceStream:
    """The session's semantics from the generator's edge lists: a static
    warm start on the base graph, then one DF-P solve per batch, each from
    the previous ranks, in ``dtype``."""

    def __init__(self, n: int, base_src, base_dst, guarantees: dict,
                 dtype=np.float64):
        self.g = ref_graph(n, base_src, base_dst)
        self.p = guarantees
        self.dtype = dtype
        #: sweeps of the static warm start
        self.ranks, self.static_sweeps = pagerank(
            self.g, alpha=guarantees["alpha"], tol=guarantees["tau"],
            max_iter=guarantees["max_iter"], dtype=dtype)

    def apply(self, del_src, del_dst, ins_src, ins_dst) -> np.ndarray:
        n, keys = self.g.n, self.g.keys
        dk, ik = canonical(n, del_src, del_dst, ins_src, ins_dst)
        at = np.searchsorted(keys, dk)
        hit = at < keys.size
        hit[hit] = keys[at[hit]] == dk[hit]
        keys = np.delete(keys, at[hit])
        at = np.minimum(np.searchsorted(keys, ik), keys.size - 1)
        new = ik[keys[at] != ik]
        keys = np.insert(keys, np.searchsorted(keys, new), new)
        self.g = RefGraph(n, keys)
        p = self.p
        self.ranks = dfp_update(self.g, self.ranks, dk, ik, p["alpha"],
                                p["tau"], p["tau_f"], p["tau_p"],
                                p["max_iter"], self.dtype)[0]
        return self.ranks


def l1_gap(r: np.ndarray, ref: np.ndarray) -> float:
    """L1 distance of a rank vector from the reference (paper §5.1.5)."""
    return float(np.sum(np.abs(np.asarray(r, np.float64)
                               - np.asarray(ref, np.float64))))
