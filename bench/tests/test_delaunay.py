"""The DIMACS10 Delaunay generator against the size its configuration
states."""
import numpy as np

import pytest

from bench.gen.traffic import edge_stream, make_traffic
from bench.tests.test_generators import _cfg, _unique


def _delaunay_rehearsal():
    cfg = _cfg("dimacs10-delaunay")
    cfg["graph"].update(cfg["rehearsal"]["graph"])
    return cfg


def test_delaunay_is_found_by_name_and_keeps_both_directions():
    n, src, dst = edge_stream(_delaunay_rehearsal(),
                              np.random.default_rng(0))
    assert n == 1 << 10 and src.dtype == dst.dtype == np.int32
    assert not np.any(src == dst)
    k = _unique(n, src, dst)
    assert k.size == src.size
    assert np.array_equal(k, _unique(n, dst, src))


@pytest.mark.parametrize("log2_points,seed", [(10, 0), (10, 2**31 + 11),
                                              (12, 7)])
def test_delaunay_edge_count_is_eulers(log2_points, seed):
    """A triangulation of n points with h on the hull has 3n - 3 - h
    edges."""
    from scipy.spatial import Delaunay
    from bench.gen.graphs.dimacs10_delaunay import delaunay_edges
    n = 1 << log2_points
    src, dst = delaunay_edges(log2_points, np.random.default_rng(seed))
    tri = Delaunay(np.random.default_rng(seed).random((n, 2)))
    h = np.unique(tri.convex_hull).size
    assert src.size == 2 * (3 * n - 3 - h)
    deg = np.bincount(src, minlength=n)
    assert deg.min() >= 2 and 5.5 < deg.mean() < 6


def test_delaunay_unique_edges_match_config():
    """The instance the benchmark draws (INSTANCE_SEED, as make_traffic
    spawns it) at the configured size has the stated edge count."""
    from bench.gen.traffic import INSTANCE_SEED
    cfg = _cfg("dimacs10-delaunay")
    want = cfg["expected"]
    graph_rng = np.random.default_rng(
        np.random.SeedSequence(INSTANCE_SEED).spawn(2)[0])
    n, src, dst = edge_stream(cfg, graph_rng)
    assert n == 1 << cfg["graph"]["log2_points"]
    assert abs(src.size // 2 / want["unique_undirected_edges"] - 1) \
        <= want["tolerance"]


def test_delaunay_config_states_eulers_count():
    """The stated count is 3n - 3 - h for a hull of 3 to 100 vertices
    (about (8/3) ln n for uniform points)."""
    cfg = _cfg("dimacs10-delaunay")
    n = 1 << cfg["graph"]["log2_points"]
    h = 3 * n - 3 - cfg["expected"]["unique_undirected_edges"]
    assert 3 <= h <= 100


def test_delaunay_seeds_rename_one_instance():
    cfg = _delaunay_rehearsal()
    mix = {"loop": "stream",
           "batch": {"kind": "uniform", "size_frac": 1e-3,
                     "insert_frac": 0.8, "count": 4}}
    seeds = (3, 2**40 + 3)
    a, c = (make_traffic(cfg, mix, s) for s in seeds)
    assert not np.array_equal(a.base_src, c.base_src)
    # the vertex that seed 3 names pa[v], the other seed names pc[v]
    pa, pc = (np.random.default_rng(s).permutation(a.n) for s in seeds)
    rename = np.empty(a.n, np.int64)
    rename[pa] = pc
    assert np.array_equal(
        _unique(a.n, rename[a.base_src], rename[a.base_dst]),
        _unique(c.n, c.base_src, c.base_dst))
    for x, y in zip(a.batches, c.batches):
        assert np.array_equal(rename[x.ins_src], y.ins_src)
        assert np.array_equal(rename[x.del_dst], y.del_dst)
