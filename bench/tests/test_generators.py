"""The generators against the sizes their configurations state."""
import json
import os

import numpy as np

import pytest

from bench.gen.graphs.graph500_kronecker import rmat_edges
from bench.gen.traffic import edge_stream, make_traffic
from bench.named import load

from repro.core import build_graph, random_batch

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


random_batches = load("gen/batches", "uniform").random_batches


def _unique(n, src, dst):
    return np.unique(src.astype(np.int64) * n + dst)


def test_rmat_unique_edges_match_config():
    cfg = _cfg("graph500-rmat")
    g, want = cfg["graph"], cfg["expected"]
    for seed in (0, 2**31 + 11):
        src, dst = rmat_edges(g["scale"], g["edge_factor"], g["A"], g["B"],
                              g["C"], np.random.default_rng(seed))
        assert src.size == g["edge_factor"] << g["scale"]
        n = 1 << g["scale"]
        k = _unique(n, src, dst)
        nonloop = int((k // n != k % n).sum())
        assert abs(nonloop / want["unique_nonloop_edges"] - 1) \
            <= want["tolerance"]


def test_rmat_skews_out_degree_too():
    src, dst = rmat_edges(12, 16, 0.57, 0.19, 0.19,
                          np.random.default_rng(1))
    out = np.bincount(src, minlength=1 << 12)
    assert out.max() > 20 * out.mean()


def test_first_random_batch_is_the_programs_random_batch():
    n = 300
    rng = np.random.default_rng(3)
    src = rng.integers(0, n, 3000).astype(np.int32)
    dst = rng.integers(0, n, 3000).astype(np.int32)
    g = build_graph(n, src, dst)
    ours = random_batches(n, src, dst, 1, 20, 0.8,
                           np.random.default_rng(42))[0]
    theirs = random_batch(g, 20 / g.m, 0.8, seed=42)
    for a, b in ((ours.ins_src, theirs.ins_src), (ours.ins_dst,
                                                  theirs.ins_dst),
                 (ours.del_src, theirs.del_src), (ours.del_dst,
                                                  theirs.del_dst)):
        assert np.array_equal(a, b)


def test_random_deletions_pick_current_edges():
    n = 200
    rng = np.random.default_rng(5)
    src = rng.integers(0, n, 1500).astype(np.int32)
    dst = rng.integers(0, n, 1500).astype(np.int32)
    keys = set(_unique(n, src, dst).tolist())
    for b in random_batches(n, src, dst, 50, 10, 0.5,
                             np.random.default_rng(6)):
        dk = (b.del_src.astype(np.int64) * n + b.del_dst).tolist()
        assert all(k in keys for k in dk)
        keys.difference_update(dk)
        keys.update((b.ins_src.astype(np.int64) * n + b.ins_dst).tolist())


def test_traffic_is_fixed_by_the_seed():
    cfg = _cfg("graph500-rmat")
    cfg["graph"].update(cfg["rehearsal"]["graph"])
    mix = {"loop": "stream",
           "batch": {"kind": "uniform", "size_frac": 1e-3,
                     "insert_frac": 0.8, "count": 5}}
    a = make_traffic(cfg, mix, 2**33 + 1)
    b = make_traffic(cfg, mix, 2**33 + 1)
    c = make_traffic(cfg, mix, 2**33 + 2)
    assert np.array_equal(a.base_src, b.base_src)
    assert all(np.array_equal(x.ins_dst, y.ins_dst)
               and np.array_equal(x.del_src, y.del_src)
               for x, y in zip(a.batches, b.batches))
    assert not np.array_equal(a.base_src, c.base_src)
    assert len(a.batches) == 5
    assert all(x.ins_src.size == 10 for x in a.batches)


def test_generator_and_batch_kind_are_found_by_name():
    cfg = _cfg("graph500-rmat")
    cfg["graph"].update(cfg["rehearsal"]["graph"])
    n, src, dst = edge_stream(cfg, np.random.default_rng(0))
    assert n == 1 << 10 and src.size == 16 << 10
    cfg["graph"]["generator"] = "no_such_generator"
    with pytest.raises(ValueError, match="no_such_generator"):
        edge_stream(cfg, np.random.default_rng(0))
    mix = {"loop": "stream", "batch": {"kind": "no_such_kind"}}
    cfg["graph"]["generator"] = "graph500_kronecker"
    with pytest.raises(ValueError, match="no_such_kind"):
        make_traffic(cfg, mix, 1)


def test_seeds_rename_one_instance():
    cfg = _cfg("graph500-rmat")
    cfg["graph"].update(cfg["rehearsal"]["graph"])
    mix = {"loop": "stream",
           "batch": {"kind": "uniform", "size_frac": 1e-3,
                     "insert_frac": 0.8, "count": 4}}
    a, c = make_traffic(cfg, mix, 3), make_traffic(cfg, mix, 2**40 + 3)
    # the same out-degree sequence and batch sizes under other names
    assert np.array_equal(np.sort(np.bincount(a.base_src)),
                          np.sort(np.bincount(c.base_src)))
    assert [b.size for b in a.batches] == [b.size for b in c.batches]
    assert not np.array_equal(a.base_src, c.base_src)
