"""The one traffic generator: a configuration's edge stream, cut into a base
graph and a fixed sequence of update batches by a traffic mix's parameters.

A configuration names its edge generator and sizes (``bench/configs``); a
traffic mix is a data file of parameters (``bench/traffic``):

- ``loop``: ``stream`` (batches through ``StreamSession.apply``) or
  ``static`` (repeated from-scratch solves, no batches);
- ``batch``: for a ``stream``, ``kind``, the batch kind, and whatever that
  kind reads;
- ``warmup_clean`` and ``warmup_cap``, read by the harness: warm up until
  that many calls in a row lower no new program, or the cap.

A configuration's ``graph.generator`` names a module under
``bench/gen/graphs``, whose ``edges`` draws the edge stream, and a mix's
``batch.kind`` one under ``bench/gen/batches``, whose ``cut`` splits that
stream into a base graph and batches (``bench/named.py``): a new generator
or batch kind is a new file. A static mix loads the whole stream.

The graph and its batches are one fixed instance, drawn from
``INSTANCE_SEED``; ``--seed`` renames its vertices by a random permutation.
Every seed then serves the same graph and the same batches up to the names
of the vertices: each batch has the same size, frontier and sweeps, laid out
in another order in memory. Drawing a new graph and new batches for each
seed made runs of different seeds differ by far more than two runs of one
seed (6-10% in the median batch time on a TPU v5e), which is the seed
changing the work, not noise.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

from bench.named import load

__all__ = ["INSTANCE_SEED", "Batch", "Traffic", "edge_stream",
           "make_traffic"]

#: the one instance every seed renames
INSTANCE_SEED = 1


@dataclasses.dataclass(frozen=True)
class Batch:
    del_src: np.ndarray
    del_dst: np.ndarray
    ins_src: np.ndarray
    ins_dst: np.ndarray

    @property
    def size(self) -> int:
        return int(self.del_src.size + self.ins_src.size)


@dataclasses.dataclass(frozen=True)
class Traffic:
    n: int
    base_src: np.ndarray
    base_dst: np.ndarray
    batches: List[Batch]


def edge_stream(cfg: dict, rng: np.random.Generator
                ) -> Tuple[int, np.ndarray, np.ndarray]:
    """(n, src, dst) of the configuration's edge stream, in stream order."""
    g = cfg["graph"]
    return load("gen/graphs", g["generator"]).edges(g, rng)


def make_traffic(cfg: dict, mix: dict, seed: int) -> Traffic:
    """Base graph and batches of one cell: the configuration's instance,
    its vertices renamed by a permutation drawn from ``seed``."""
    graph_rng, batch_rng = (np.random.default_rng(s) for s in
                            np.random.SeedSequence(INSTANCE_SEED).spawn(2))
    t = _instance(cfg, mix, graph_rng, batch_rng)
    name = np.random.default_rng(seed).permutation(t.n).astype(np.int32)
    return Traffic(t.n, name[t.base_src], name[t.base_dst],
                   [Batch(name[b.del_src], name[b.del_dst],
                          name[b.ins_src], name[b.ins_dst])
                    for b in t.batches])


def _instance(cfg: dict, mix: dict, graph_rng, batch_rng) -> Traffic:
    n, src, dst = edge_stream(cfg, graph_rng)
    if mix["loop"] == "static":
        return Traffic(n, src, dst, [])
    b = mix["batch"]
    base_src, base_dst, batches = load("gen/batches", b["kind"]).cut(
        n, src, dst, b, batch_rng)
    return Traffic(n, base_src, base_dst, batches)
