"""Frontier-compacted DF/DF-P: equivalence with the dense engine, capacity
overflow fallback, and the work-reduction property."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (apply_batch, batch_to_device, device_graph,
                        df_pagerank, df_pagerank_compact, dfp_pagerank,
                        dfp_pagerank_compact, forward_device_graph,
                        init_ranks, l1_error, powerlaw_graph, random_batch,
                        random_graph, reference_pagerank, static_pagerank)
from repro.core.compact import _compact_loop, _scatter_expand
from repro.core.frontier import expand_affected, initial_affected
from repro.core.pagerank import PRParams

CAPS = dict(d_p=16, tile=64)


def _setup(n=2000, m=20000, frac=1e-3, seed=3):
    g = powerlaw_graph(n, m, seed=seed)
    dg = device_graph(g, **CAPS)
    fwd = forward_device_graph(g, **CAPS)
    r_prev, _ = static_pagerank(dg, init_ranks(g.n))
    b = random_batch(g, frac, seed=seed + 2)
    g2 = apply_batch(g, b)
    dg2 = device_graph(g2, **CAPS)
    fwd2 = forward_device_graph(g2, **CAPS)
    db = batch_to_device(b, g.n)
    return g2, dg2, fwd2, r_prev, db


def test_scatter_expand_matches_dense_pull():
    g2, dg2, fwd2, r_prev, db = _setup()
    n = dg2.n
    dv, dn = initial_affected(n, db.del_src, db.del_dst, db.ins_src)
    dense = expand_affected(dg2, dv, dn)
    compact = dv | _scatter_expand(fwd2, dn, n)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(compact))


@pytest.mark.parametrize("prune", [True, False])
def test_compact_loop_matches_dense_at_full_capacity(prune):
    from repro.core.dynamic import _loop
    g2, dg2, fwd2, r_prev, db = _setup()
    n = dg2.n
    dv, dn = initial_affected(n, db.del_src, db.del_dst, db.ins_src)
    dv = expand_affected(dg2, dv, dn)
    off = jnp.zeros(n, bool)
    p = PRParams(max_iter=6)
    r_d, _ = jax.jit(lambda: _loop(dg2, r_prev, dv, off, p, expand=True,
                                   prune=prune, closed_form=prune))()
    r_c, *_ = _compact_loop(dg2, fwd2, r_prev, dv, off, p, n,
                            dg2.hi_tiles.shape[0], n, prune)
    np.testing.assert_allclose(np.asarray(r_d), np.asarray(r_c), atol=1e-15)


@pytest.mark.parametrize("frac", [1e-4, 1e-3, 1e-2])
def test_compact_dfp_correct_across_batch_sizes(frac):
    g2, dg2, fwd2, r_prev, db = _setup(frac=frac)
    ref = reference_pagerank(g2)
    r, iters = dfp_pagerank_compact(dg2, fwd2, r_prev, db)
    assert l1_error(np.asarray(r), ref) < 1e-3
    assert int(iters) > 0


def test_compact_df_correct():
    g2, dg2, fwd2, r_prev, db = _setup()
    ref = reference_pagerank(g2)
    r, _ = df_pagerank_compact(dg2, fwd2, r_prev, db)
    assert l1_error(np.asarray(r), ref) < 1e-5


def test_overflow_falls_back_to_dense():
    """A huge batch overflows any reasonable capacity; results must still be
    correct because the dense engine finishes the job."""
    g2, dg2, fwd2, r_prev, db = _setup(frac=0.2)
    ref = reference_pagerank(g2)
    r, iters = dfp_pagerank_compact(dg2, fwd2, r_prev, db)
    assert l1_error(np.asarray(r), ref) < 1e-2


# -- a planar mesh: the compact loop sweeps before it hands off ----------------

def _delaunay_graph(log2_points, seed):
    """Delaunay triangulation of uniform points in the unit square, each
    edge both ways (the DIMACS10 delaunay_n* family, small)."""
    from scipy.spatial import Delaunay
    from repro.core import build_graph
    n = 1 << log2_points
    tri = Delaunay(np.random.default_rng(seed).random((n, 2))).simplices
    e = np.concatenate([tri[:, [0, 1]], tri[:, [1, 2]], tri[:, [2, 0]]])
    src = np.concatenate([e[:, 0], e[:, 1]]).astype(np.int32)
    dst = np.concatenate([e[:, 1], e[:, 0]]).astype(np.int32)
    return build_graph(n, src, dst, self_loops=True)


@pytest.fixture(scope="module")
def planar_stream():
    """Six 4-edge batches through a compact-engine session and a dense one
    on a 2^11-point mesh; per batch, the compact counters, the updated
    graph, and the ranks of both sessions."""
    from repro.core.compact import _dense_finish
    from repro.obs.spans import get_registry, reset_registry
    from repro.stream import StreamSession
    g = _delaunay_graph(11, seed=11)
    compact = StreamSession(g, d_p=16, tile=64, engine="compact")
    dense = StreamSession(g, d_p=16, tile=64, engine="dense")
    programs = _dense_finish._cache_size()
    out = []
    for i in range(6):
        b = random_batch(g, 4 / g.m, 0.8, seed=100 + i)
        g = apply_batch(g, b)
        reset_registry()
        compact.apply(b)
        reg = get_registry()
        counters = {k: reg.counter("compact." + k) for k in (
            "overflows", "sweeps", "finish_sweeps", "capacity",
            "exit_rows")}
        dense.apply(b)
        out.append(dict(counters, iters=compact.history[-1].iters,
                        engine=compact.history[-1].engine, graph=g,
                        r=np.asarray(compact.ranks),
                        r_dense=np.asarray(dense.ranks)))
    reset_registry()
    return out, _dense_finish._cache_size() - programs


def test_compact_loop_sweeps_on_a_planar_mesh(planar_stream):
    """The frontier grows for several sweeps on a mesh: the loop commits at
    least two before it overflows (its count includes the overflowing
    sweep, which commits nothing), and the ranks agree with the dense
    engine's and with a static solve of the updated graph."""
    from repro.core.reference import numpy_pagerank
    batches, _ = planar_stream
    overflowed = [b for b in batches if b["overflows"]]
    assert len(overflowed) >= 4
    for b in batches:
        assert b["engine"] == "compact"
        if b["overflows"]:
            assert b["sweeps"] - 1 >= 2
            assert b["exit_rows"] > b["capacity"]
        assert l1_error(b["r"], b["r_dense"]) < 1e-12
        assert l1_error(b["r"], numpy_pagerank(b["graph"])[0]) < 1e-5


def test_dense_finish_is_one_program_for_any_overflow_sweep(planar_stream):
    batches, lowered = planar_stream
    at = {b["sweeps"] for b in batches if b["overflows"]}
    assert len(at) >= 2, at
    assert lowered == 1


def test_finish_and_loop_sweeps_add_up_to_the_batch(planar_stream):
    batches, _ = planar_stream
    for b in batches:
        assert b["sweeps"] + b["finish_sweeps"] == b["iters"]
        assert (b["finish_sweeps"] > 0) == bool(b["overflows"])


@pytest.mark.parametrize("headroom,enters", [(2, False), (16, True)])
def test_compact_loop_enters_only_where_sweep_one_fits(headroom, enters):
    """The loop runs its first sweep only where the initial frontier's
    out-edges fit K (sweep 1 then cannot overflow and waste it); else it
    hands off before a sweep, and the dense finish solves the batch from
    the initial state exactly as the dense engine does."""
    from repro.core.compact import _df_like_compact
    from repro.core.frontier import plan_capacity
    from repro.obs.spans import get_registry, reset_registry
    g2, dg2, fwd2, r_prev, db = _setup()
    n = dg2.n
    dv, dn = initial_affected(n, db.del_src, db.del_dst, db.ins_src)
    dv = dv | _scatter_expand(fwd2, dn, n)
    k = plan_capacity(int(jnp.sum(dv)) + 1, n, headroom=headroom)
    reach = int(jnp.sum(jnp.where(dv, dg2.out_deg, 0)))
    assert (reach <= k) == enters
    reset_registry()
    reg = get_registry()
    r, iters = _df_like_compact(dg2, fwd2, r_prev, db, PRParams(),
                                prune=True, headroom=headroom)
    assert (reg.counter("compact.sweeps") > 0) == enters
    assert reg.counter("compact.overflows") == int(not enters)
    reset_registry()
    r_d, iters_d = dfp_pagerank(dg2, r_prev, db)
    if not enters:
        assert int(iters) == int(iters_d)
        np.testing.assert_array_equal(np.asarray(r), np.asarray(r_d))
    assert l1_error(np.asarray(r), np.asarray(r_d)) < 1e-12
