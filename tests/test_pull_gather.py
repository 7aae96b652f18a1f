"""The pull reads each vertex's sum by one gather from the parts vector.

`pull_sum` must equal, bit for bit, the scatter-add formulation it
replaced: per-bucket row sums and hi-slot sums scattered once into a zero
vector through the row maps. Each vertex has exactly one place in the
layout (a bucket row or a hi slot), so each receives one add of 0.0 + x.
Covered layouts: a power-law graph with tiles and four buckets, a grid
mesh with no tiles, `d_p=0` (every vertex on the hi side), and a
`DeviceSnapshot` at its device extents after batches that promote, demote
and free rows. The engines give the same ranks and sweep counts with
either pull.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BatchUpdate, PRParams, apply_batch, batch_to_device,
                        build_graph, caps_for, device_graph, dfp_pagerank,
                        init_ranks, powerlaw_graph, pull_sum, random_batch,
                        static_pagerank)
from repro.core.graph import build_hybrid
from repro.core.pagerank import to_device
from repro.stream import DeviceSnapshot, ingest

PARAMS = PRParams(tau_f=1e-9, tau_p=1e-9)


def scatter_pull_sum(dg, c):
    """The scatter-add pull: each part's sums added into zeros through the
    bucket row maps and the hi-slot ids (sentinels dropped)."""
    dt = c.dtype
    out = jnp.zeros(c.shape, dt)
    for blk in dg.buckets:
        sums = jnp.sum(jnp.take(c, blk.idx, axis=0) * blk.mask.astype(dt),
                       axis=1)
        out = out.at[blk.rows].add(sums, mode="drop")
    tile_sums = jnp.sum(jnp.take(c, dg.hi_tiles, axis=0)
                        * dg.hi_tmask.astype(dt), axis=1)
    hi = jax.ops.segment_sum(tile_sums, dg.hi_rowmap,
                             num_segments=dg.n_hi_cap)
    return out.at[dg.hi_ids].add(hi, mode="drop")


def _grid(side):
    """A side x side grid, each edge both ways: degree 2-4, plus the loop."""
    v = np.arange(side * side, dtype=np.int32).reshape(side, side)
    a = np.concatenate([v[:, :-1].ravel(), v[:-1, :].ravel()])
    b = np.concatenate([v[:, 1:].ravel(), v[1:, :].ravel()])
    return build_graph(side * side, np.concatenate([a, b]),
                       np.concatenate([b, a]))


# name -> (graph, layout keywords)
LAYOUTS = {
    "powerlaw_tiles": (lambda: powerlaw_graph(1500, 15000, seed=40),
                       dict(d_p=16, tile=8, widths=(2, 4, 8, 16))),
    "grid_no_tiles": (lambda: _grid(24), dict(d_p=8, tile=16)),
    "all_hi": (lambda: powerlaw_graph(500, 4000, seed=41),
               dict(d_p=0, tile=16)),
}


def _layout(name):
    make, kw = LAYOUTS[name]
    g = make()
    lay = build_hybrid(g, **kw)
    if name == "powerlaw_tiles":
        assert len(lay.buckets) == 4 and lay.hi_tmask.any()
    elif name == "grid_no_tiles":
        assert not lay.hi_tmask.any() and (lay.hi_ids == g.n).all()
    else:
        assert not lay.buckets and not lay.is_low.any()
    return g, to_device(lay), kw


def _assert_bits_equal(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype == np.float64
    np.testing.assert_array_equal(got.view(np.uint64), want.view(np.uint64))


def _contribs(dg, seed):
    r = np.random.default_rng(seed).random(dg.n)
    return jnp.asarray(r / np.asarray(dg.out_deg))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_pull_sum_bit_identical_to_scatter(layout):
    _, dg, _ = _layout(layout)
    c = _contribs(dg, 42)
    _assert_bits_equal(pull_sum(dg, c), scatter_pull_sum(dg, c))
    _assert_bits_equal(jax.jit(pull_sum)(dg, c),
                       jax.jit(scatter_pull_sum)(dg, c))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_static_pagerank_unchanged_by_gather(layout):
    g, dg, _ = _layout(layout)
    r0 = init_ranks(g.n)
    r, iters = static_pagerank(dg, r0, PARAMS)
    r_ref, iters_ref = static_pagerank(dg, r0, PARAMS,
                                       pull_sum_fn=scatter_pull_sum)
    assert int(iters) == int(iters_ref)
    _assert_bits_equal(r, r_ref)


@pytest.mark.parametrize("compact", [False, True])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_dfp_pagerank_unchanged_by_gather(layout, compact):
    """Dense DF-P, and the compacted loop with caps small enough that its
    overflow branch (the dense pull) runs."""
    g, dg, lay_kw = _layout(layout)
    r_prev, _ = static_pagerank(dg, init_ranks(g.n), PARAMS)
    b = random_batch(g, 0.01, seed=43)
    dg2 = device_graph(apply_batch(g, b), **lay_kw)
    batch = batch_to_device(b, g.n)
    kw = dict(frontier_caps=caps_for(dg2, est=4, headroom=1)) if compact \
        else {}
    r, iters = dfp_pagerank(dg2, r_prev, batch, PARAMS, **kw)
    r_ref, iters_ref = dfp_pagerank(dg2, r_prev, batch, PARAMS,
                                    pull_sum_fn=scatter_pull_sum, **kw)
    assert int(iters) == int(iters_ref) > 1
    _assert_bits_equal(r, r_ref)


def _fan_in(dst, srcs, delete=False):
    srcs = np.asarray(srcs, np.int32)
    dsts = np.full(srcs.size, dst, np.int32)
    none = np.zeros(0, np.int32)
    if delete:
        return BatchUpdate(del_src=srcs, del_dst=dsts, ins_src=none,
                           ins_dst=none)
    return BatchUpdate(del_src=none, del_dst=none, ins_src=srcs,
                       ins_dst=dsts)


def test_snapshot_pull_bit_identical_across_migrations():
    """A vertex fans in past d_p (promoted through the buckets to the hi
    side), then loses those edges (demoted, its tiles and hi slot freed),
    then random churn: after each batch both halves' pulls, at the device
    extents, equal the scatter-add pull bit for bit."""
    g = powerlaw_graph(600, 6000, seed=44)
    snap = DeviceSnapshot(g, d_p=8, tile=32)
    pull = snap._pull
    assert [b.rows.shape[0] for b in snap.dg.buckets] != [
        r.shape[0] for r in pull.bk_rows]      # device extents, not caps
    indeg = g.in_degree()
    v = int(np.flatnonzero(indeg == indeg.min())[0])
    srcs = [u for u in range(g.n) if u != v and not g.has_edge(u, v)][:20]
    seen = set()

    def check(seed):
        c = _contribs(snap.dg, seed)
        for dg in (snap.dg, snap.fwd_dg):
            _assert_bits_equal(pull_sum(dg, c), scatter_pull_sum(dg, c))

    check(45)
    for t, b in enumerate([_fan_in(v, srcs), _fan_in(v, srcs, delete=True),
                           random_batch(g, 0.02, seed=46)]):
        st = snap.apply(ingest(b, g.n))
        assert not st.rebuilt and st.migrations > 0
        seen.add(bool(pull.is_low[v]))
        check(47 + t)
    assert seen == {False, True}      # v left the low side and came back
