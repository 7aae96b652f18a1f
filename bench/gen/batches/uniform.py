"""The source paper's random batches (section 5.1.3): insertions between
uniformly drawn vertex pairs, deletions of edges of the current graph drawn
uniformly (self-loops included, then dropped), as the program's
``random_batch`` draws them (copied here). The whole edge stream is loaded
as the base graph. A traffic mix names it as ``"kind": "uniform"`` with
``size_frac`` (a batch's edges, as a share of the base graph's unique
edges, self-loops included), ``insert_frac`` and ``count``, the batches
drawn."""
from __future__ import annotations

from typing import List

import numpy as np

from bench.gen.traffic import Batch

__all__ = ["cut", "random_batches"]


class _EdgeSet:
    """The current edge set (self-loops included), for sampling deletions
    uniformly: an array of keys with swap-remove, and each key's place."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray):
        loops = np.arange(n, dtype=np.int64)
        keys = np.unique(np.concatenate([
            src.astype(np.int64) * n + dst, loops * n + loops]))
        self.n = n
        self.keys = keys.tolist()
        self.pos = {k: i for i, k in enumerate(self.keys)}

    def __len__(self) -> int:
        return len(self.keys)

    def remove(self, key: int) -> None:
        i = self.pos.pop(key, None)
        if i is None:
            return
        last = self.keys.pop()
        if i < len(self.keys):
            self.keys[i] = last
            self.pos[last] = i

    def add(self, key: int) -> None:
        if key not in self.pos:
            self.pos[key] = len(self.keys)
            self.keys.append(key)


def random_batches(n: int, base_src, base_dst, count: int, size: int,
                   insert_frac: float, rng: np.random.Generator
                   ) -> List[Batch]:
    """``count`` batches of ``size`` edges, each drawn on the graph that
    the batches before it left."""
    edges = _EdgeSet(n, base_src, base_dst)
    ni = int(round(size * insert_frac))
    nd = size - ni
    out = []
    for _ in range(count):
        ins_src = rng.integers(0, n, size=ni).astype(np.int32)
        ins_dst = rng.integers(0, n, size=ni).astype(np.int32)
        if nd > 0:
            pick = rng.integers(0, len(edges), size=nd)
            dk = np.array([edges.keys[i] for i in pick], np.int64)
            del_src, del_dst = (dk // n).astype(np.int32), (dk % n).astype(
                np.int32)
            keep = del_src != del_dst
            del_src, del_dst = del_src[keep], del_dst[keep]
        else:
            del_src = del_dst = np.zeros(0, np.int32)
        # the batch's effect on the graph: deletions first, then insertions
        for k in (del_src.astype(np.int64) * n + del_dst).tolist():
            edges.remove(k)
        for k in (ins_src.astype(np.int64) * n + ins_dst).tolist():
            edges.add(k)
        out.append(Batch(del_src, del_dst, ins_src, ins_dst))
    return out


def cut(n: int, src: np.ndarray, dst: np.ndarray, spec: dict,
        rng: np.random.Generator):
    """(base_src, base_dst, batches) of the mix."""
    loops = np.arange(n, dtype=np.int64)
    m = np.unique(np.concatenate([src.astype(np.int64) * n + dst,
                                  loops * n + loops])).size
    size = max(1, int(round(spec["size_frac"] * m)))
    return src, dst, random_batches(n, src, dst, spec["count"], size,
                                    spec["insert_frac"], rng)
