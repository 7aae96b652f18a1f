"""The float64 reference against the program's own oracle and engines."""
import numpy as np

from bench.reference import (ReferenceStream, canonical, l1_gap, pagerank,
                             ref_graph)

from repro.core import (BatchUpdate, PRParams, numpy_pagerank,
                        powerlaw_graph, random_batch)
from repro.stream import StreamSession
from repro.stream.delta import ingest

GUAR = dict(alpha=0.85, tau=1e-10, tau_f=1e-9, tau_p=1e-9, max_iter=500)


def _edges(n=500, m=4000, seed=0):
    g = powerlaw_graph(n, m, seed=seed)
    return g, *g.edges()


def test_static_matches_numpy_pagerank():
    g, src, dst = _edges()
    ours, it = pagerank(ref_graph(g.n, src, dst))
    theirs, it2 = numpy_pagerank(g)
    assert it == it2
    assert l1_gap(ours, theirs) < 1e-14


def test_graph_has_self_loops_and_no_duplicates():
    rg = ref_graph(4, np.array([0, 0, 1]), np.array([1, 1, 2]))
    assert rg.m == 2 + 4
    assert np.all(rg.out_deg >= 1)


def test_canonical_matches_ingest():
    rng = np.random.default_rng(1)
    n = 50
    b = BatchUpdate(*(rng.integers(0, n, 30).astype(np.int32)
                      for _ in range(4)))
    b = BatchUpdate(np.concatenate([b.del_src, b.ins_src[:5], [3]]),
                    np.concatenate([b.del_dst, b.ins_dst[:5], [3]]),
                    b.ins_src, b.ins_dst)
    dk, ik = canonical(n, b.del_src, b.del_dst, b.ins_src, b.ins_dst)
    d = ingest(b, n)
    assert np.array_equal(dk, np.sort(d.del_src.astype(np.int64) * n
                                      + d.del_dst))
    assert np.array_equal(ik, np.sort(d.ins_src.astype(np.int64) * n
                                      + d.ins_dst))


def test_dfp_stream_matches_session_on_cpu():
    g, src, dst = _edges(seed=2)
    sess = StreamSession(g, params=PRParams(**GUAR))
    ref = ReferenceStream(g.n, src, dst, GUAR)
    assert l1_gap(np.asarray(sess.ranks), ref.ranks) < 1e-13
    cur = g
    for k in range(6):
        b = random_batch(cur, 2e-3, seed=k)
        r = np.asarray(sess.apply(b))
        want = ref.apply(b.del_src, b.del_dst, b.ins_src, b.ins_dst)
        assert l1_gap(r, want) < 1e-13
        cur = sess.snap.graph()


def test_dfp_reference_tracks_the_true_pagerank():
    g, src, dst = _edges(seed=3)
    ref = ReferenceStream(g.n, src, dst, GUAR)
    b = random_batch(g, 5e-3, seed=9)
    got = ref.apply(b.del_src, b.del_dst, b.ins_src, b.ins_dst)
    exact = pagerank(ref.g, tol=1e-15, norm="l1", max_iter=2000)[0]
    assert l1_gap(got, exact) < 1e-7


def test_float32_reference_is_far_from_float64():
    g, src, dst = _edges(n=2000, m=20000, seed=4)
    r64 = ReferenceStream(g.n, src, dst, GUAR).ranks
    r32 = ReferenceStream(g.n, src, dst, GUAR, np.float32).ranks
    assert l1_gap(r32, r64) > 1e-8
