"""Multi-device / multi-pod PageRank via shard_map.

1-D vertex partition over all mesh axes (flattened): every shard owns
``n_loc = n_pad / nd`` vertices — their ELL rows, tile-padded CSR slices,
ranks and affected flags. The pull model makes the per-iteration communication
exactly one collective: ``all_gather`` of the contribution vector
``c = R / outdeg`` (V·4 B), plus a replicated scalar max for convergence — this is
the paper's "one write per vertex" discipline lifted to the cluster level
(each device writes only its own rank slice; no cross-device scatter exists).

For DF-P, the frontier flags δ_N ride the same all-gather (packed as f32
alongside c, one fused collective — see DESIGN.md §5).

Layout sharing: each shard's block is laid out by the *same* vectorized
`build_hybrid_rows` primitive that builds the single-device hybrid
(DESIGN.md §5) — stored column ids are global, row ids are shard-local —
and the per-iteration math is the *same* `core.rank_step.rank_step` the
dense engine uses; this loop only adds the all-gather plumbing around it.

Elasticity: `build_sharded` is a pure host function of (graph, nd); on device
failure / resize, rebuild with the new nd and re-enter at the checkpointed
(R, δ_V) — see train/elastic.py for the generic machinery. Capacities follow
the pow2/never-shrink discipline of DeviceSnapshot (`sharded_caps`), so
re-sharded snapshots of a dynamic graph keep jit-stable shapes (§7).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .dynamic import solve_health
from .frontier import (FS_ACTIVE_ROWS, FS_ACTIVE_TILES, FS_COMPACT, FS_ITERS,
                       FS_NB, FS_OVERFLOW, active_frontier, active_pull_sum,
                       caps_for_parts, fstats_init, initial_affected,
                       publish_fstats)
from .graph import (Graph, bucket_band_counts, build_hybrid_rows,
                    choose_bucket_widths, next_pow2)
from .pagerank import EllBlock, PRParams
from .rank_step import rank_step
from ..obs.spans import get_registry as _obs
from ..obs.trace import trace_init, trace_record


def stacked_sharding(mesh: Mesh) -> NamedSharding:
    """Placement of every stacked [nd, ...] array: the leading shard axis
    split over all mesh axes (flattened), so shard s lives on device s —
    the layout `shard_map` consumes with in_spec ``P(axis)``."""
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))


__all__ = ["ShardedGraph", "build_sharded", "sharded_caps", "sharded_need",
           "shard_bounds", "shard_block_rows",
           "initial_affected_sharded", "shard_vector", "unshard_vector",
           "distributed_static_pagerank", "distributed_dfp_pagerank",
           "sharded_frontier_caps", "pagerank_step_specs",
           "stacked_sharding"]


class ShardedGraph(NamedTuple):
    """Stacked per-shard hybrid layouts. Leading axis = shard.

    Each ELL degree bucket is one `EllBlock` with stacked arrays: rows
    [nd, cap_b] holds LOCAL row ids (sentinel n_loc), idx/mask
    [nd, cap_b, w_b] hold GLOBAL column ids / validity. Bucket widths and
    caps are shared across shards so stacking gives static shapes.
    """
    buckets: Tuple[EllBlock, ...]
    hi_pos: jnp.ndarray     # [nd, hi_cap] int32, LOCAL row ids (sentinel n_loc)
    hi_tiles: jnp.ndarray   # [nd, t_cap, tile] int32, GLOBAL column ids
    hi_tmask: jnp.ndarray   # [nd, t_cap, tile] f32
    hi_rowmap: jnp.ndarray  # [nd, t_cap] int32
    out_deg: jnp.ndarray    # [nd, n_loc] int32 (>=1)
    valid: jnp.ndarray      # [nd, n_loc] bool (False on padding vertices)
    n_true: int             # real |V| (for the (1-α)/|V| constant)

    @property
    def nd(self) -> int:
        return self.out_deg.shape[0]

    @property
    def n_loc(self) -> int:
        return self.out_deg.shape[1]


def shard_bounds(s: int, n_loc: int, n: int) -> Tuple[int, int]:
    """[lo, hi) of shard s's real vertices, clamped: a trailing shard may be
    entirely padding (lo == hi == n) when n_loc · nd overshoots |V|."""
    return min(s * n_loc, n), min((s + 1) * n_loc, n)


def shard_block_rows(g: Graph, s: int, n_loc: int):
    """(offsets, data) ragged-rows slice of shard s's contiguous vertex
    block in the transpose CSR — the input `build_hybrid_rows` consumes.
    Shared by `build_sharded` and the streaming `ShardedSnapshot` so the
    static and incremental layouts cannot drift."""
    lo, hi = shard_bounds(s, n_loc, g.n)
    off = g.t_offsets[lo:hi + 1] - g.t_offsets[lo]
    dat = g.t_sources[g.t_offsets[lo]:g.t_offsets[hi]]
    return off, dat


def sharded_need(indeg: np.ndarray, nd: int, n_loc: int, d_p: int, tile: int,
                 widths: Tuple[int, ...] = (),
                 band: bool = False) -> Tuple[int, int, Tuple[int, ...]]:
    """Worst-shard (high-slot, tile, per-bucket-slot) needs across the
    contiguous blocks — the raw sizes the pow2 capacity ladder is applied
    to. Bucket needs include each shard's padding rows (degree 0, parked in
    bucket 0 like `build_hybrid_rows` does). `band=True` counts each
    bucket's streaming hysteresis band (`bucket_band_counts`) instead of
    the initial placement census — what incremental snapshots must plan
    capacity against."""
    n = int(indeg.shape[0])
    need_hi = need_t = 1
    need_b = [1] * len(widths)
    for s in range(nd):
        lo, hi = shard_bounds(s, n_loc, n)
        blk = indeg[lo:hi]
        deg_hi = blk[blk > d_p]
        need_hi = max(need_hi, int(deg_hi.size))
        need_t = max(need_t, int(((deg_hi + tile - 1) // tile).sum()))
        if widths:
            if band:
                cnt = list(bucket_band_counts(blk, widths, d_p))
            else:
                low = blk[blk <= d_p]
                grp = np.searchsorted(widths, np.maximum(low, 1), side="left")
                cnt = np.bincount(grp, minlength=len(widths))
            cnt[0] += n_loc - (hi - lo)       # padding rows -> bucket 0
            need_b = [max(a, int(b)) for a, b in zip(need_b, cnt)]
    return need_hi, need_t, tuple(need_b)


def build_sharded(g: Graph, nd: int, d_p: int = 64, tile: int = 1024,
                  hi_cap: Optional[int] = None, t_cap: Optional[int] = None,
                  widths: Optional[Tuple[int, ...]] = None,
                  bucket_caps: Optional[Tuple[int, ...]] = None
                  ) -> ShardedGraph:
    """Host-side partitioner: contiguous vertex blocks, one hybrid per shard.

    Pads |V| to a multiple of nd with isolated vertices (masked out of
    updates and results). Each shard's block is laid out by the shared
    `build_hybrid_rows` primitive — the same vectorized ragged-fill passes
    as the single-device `build_hybrid`, no per-vertex Python loops. Bucket
    widths come from the *global* degree histogram so every shard shares
    one bucket structure; per-shard bucket/high/tile capacities are shared
    across shards so stacking gives static shapes, and default to pow2 of
    the max per-shard need (never pass smaller values than a previous build
    when re-sharding a growing graph — `sharded_caps` extracts the current
    signature).
    """
    n = g.n
    n_pad = ((n + nd - 1) // nd) * nd
    n_loc = n_pad // nd
    indeg = g.in_degree()
    out_deg = g.out_degree()
    if widths is None:
        widths = choose_bucket_widths(indeg, d_p)
    widths = tuple(int(w) for w in widths)

    # capacity discipline (DeviceSnapshot's pow2/never-shrink ladder): size
    # for the worst shard so the stacked shapes are jit-stable across shards
    # and, when the caller threads caps through batches, across snapshots.
    need_hi, need_t, need_b = sharded_need(indeg, nd, n_loc, d_p, tile,
                                           widths)
    if hi_cap is None:
        hi_cap = next_pow2(need_hi, 8)
    if t_cap is None:
        t_cap = next_pow2(need_t, 8)
    if bucket_caps is None:
        bucket_caps = tuple(next_pow2(nb, 8) for nb in need_b)
    assert need_hi <= hi_cap and need_t <= t_cap, \
        "sharded caps too small for this snapshot"
    assert all(nb <= c for nb, c in zip(need_b, bucket_caps)), \
        "sharded bucket caps too small for this snapshot"

    pieces = []
    for s in range(nd):
        off, dat = shard_block_rows(g, s, n_loc)
        pieces.append(build_hybrid_rows(off, dat, d_p=d_p, tile=tile,
                                        n_rows=n_loc, n_hi_cap=hi_cap,
                                        t_cap=t_cap, widths=widths,
                                        bucket_caps=bucket_caps))

    deg = np.ones((nd, n_loc), np.int32)
    valid = np.zeros((nd, n_loc), bool)
    for s in range(nd):
        lo, hi = shard_bounds(s, n_loc, n)
        deg[s, :hi - lo] = out_deg[lo:hi]
        valid[s, :hi - lo] = True

    buckets = tuple(
        EllBlock(
            rows=jnp.asarray(np.stack([p.buckets[b].rows for p in pieces])),
            idx=jnp.asarray(np.stack([p.buckets[b].idx for p in pieces])),
            mask=jnp.asarray(np.stack([p.buckets[b].mask for p in pieces])))
        for b in range(len(widths)))
    return ShardedGraph(
        buckets=buckets,
        hi_pos=jnp.asarray(np.stack([p.hi_ids for p in pieces])),
        hi_tiles=jnp.asarray(np.stack([p.hi_tiles for p in pieces])),
        hi_tmask=jnp.asarray(np.stack([p.hi_tmask for p in pieces])),
        hi_rowmap=jnp.asarray(np.stack([p.hi_rowmap for p in pieces])),
        out_deg=jnp.asarray(deg), valid=jnp.asarray(valid), n_true=n)


def sharded_caps(sg: ShardedGraph) -> dict:
    """Capacity signature — pass as **caps to `build_sharded` to rebuild a
    later snapshot of the same graph with identical device shapes."""
    widths = tuple(int(b.idx.shape[2]) for b in sg.buckets)
    return dict(d_p=widths[-1] if widths else 0,
                tile=int(sg.hi_tiles.shape[2]),
                hi_cap=int(sg.hi_pos.shape[1]), t_cap=int(sg.hi_tiles.shape[1]),
                widths=widths,
                bucket_caps=tuple(int(b.rows.shape[1]) for b in sg.buckets))


# ---------------------------------------------------------------------------
# Host <-> shard staging helpers
# ---------------------------------------------------------------------------

def shard_vector(x: np.ndarray, nd: int, fill=0) -> jnp.ndarray:
    """Stack a dense [n] host vector into [nd, n_loc] (pad with `fill`)."""
    x = np.asarray(x)
    n = x.shape[0]
    n_pad = ((n + nd - 1) // nd) * nd
    if n_pad != n:
        x = np.concatenate([x, np.full(n_pad - n, fill, x.dtype)])
    return jnp.asarray(x.reshape(nd, -1))


def unshard_vector(x, n: int) -> np.ndarray:
    """Inverse of `shard_vector`: [nd, n_loc] -> dense host [n]."""
    return np.asarray(x).reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("nd", "n_loc"))
def _initial_affected_stacked(nd, n_loc, del_src, del_dst, ins_src):
    dv, dn = initial_affected(nd * n_loc, del_src, del_dst, ins_src)
    return dv.reshape(nd, n_loc), dn.reshape(nd, n_loc)


def initial_affected_sharded(nd: int, n_loc: int, batch
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Paper Alg. 5 initialAffected on the stacked shard layout.

    `batch` is a DeviceBatch (ids may be padded with the id-n sentinel; a
    sentinel landing on a padding vertex is harmless — padding vertices have
    `valid=False` and no edges, so neither flag propagates). Returns stacked
    (δ_V [nd, n_loc], δ_N [nd, n_loc]) ready for `distributed_dfp_pagerank`,
    which performs the initial frontier expansion device-side at iteration 0.
    """
    return _initial_affected_stacked(nd, n_loc, batch.del_src, batch.del_dst,
                                     batch.ins_src)


# ---------------------------------------------------------------------------
# Local (per-shard) pull + update, consuming the gathered contribution vector
# ---------------------------------------------------------------------------

def _local_pull(sg_loc, c_full: jnp.ndarray) -> jnp.ndarray:
    dt = c_full.dtype
    n_loc = sg_loc["out_deg"].shape[0]
    low = jnp.zeros((n_loc,), dt)
    for blk in sg_loc["buckets"]:
        sums = jnp.sum(jnp.take(c_full, blk.idx, axis=0)
                       * blk.mask.astype(dt), axis=1)
        low = low.at[blk.rows].add(sums, mode="drop")
    tile_sums = jnp.sum(jnp.take(c_full, sg_loc["hi_tiles"], axis=0)
                        * sg_loc["hi_tmask"].astype(dt), axis=1)
    hi_cap = sg_loc["hi_pos"].shape[0]
    per_slot = jax.ops.segment_sum(tile_sums, sg_loc["hi_rowmap"],
                                   num_segments=hi_cap)
    return low.at[sg_loc["hi_pos"]].add(per_slot, mode="drop")


def _local_pull_max(sg_loc, x_full: jnp.ndarray) -> jnp.ndarray:
    dt = x_full.dtype
    n_loc = sg_loc["out_deg"].shape[0]
    low = jnp.zeros((n_loc,), dt)
    for blk in sg_loc["buckets"]:
        rmax = jnp.max(jnp.take(x_full, blk.idx, axis=0)
                       * blk.mask.astype(dt), axis=1, initial=0)
        low = low.at[blk.rows].max(rmax, mode="drop")
    tmax = jnp.max(jnp.take(x_full, sg_loc["hi_tiles"], axis=0)
                   * sg_loc["hi_tmask"].astype(dt), axis=1, initial=0)
    hi_cap = sg_loc["hi_pos"].shape[0]
    per_slot = jnp.maximum(
        jax.ops.segment_max(tmax, sg_loc["hi_rowmap"], num_segments=hi_cap), 0)
    return low.at[sg_loc["hi_pos"]].max(per_slot, mode="drop")


_FIELDS = ("buckets", "hi_pos", "hi_tiles", "hi_tmask",
           "hi_rowmap", "out_deg", "valid")


def _as_dict(sg: ShardedGraph) -> dict:
    return {k: getattr(sg, k) for k in _FIELDS}


def _squeeze_shard(sgd: dict) -> dict:
    """Inside shard_map each array has leading dim 1 — drop it."""
    return jax.tree.map(lambda v: v[0], sgd)


def _all_max(x: jnp.ndarray, axis) -> jnp.ndarray:
    """Max of a per-shard scalar over the mesh, replicated. A one-hot psum
    stands in for pmax: the TPU lowers only sum all-reduces in float64, and
    adding zeros to the one non-zero lane is exact (NaN still propagates)."""
    lanes = jnp.zeros((jax.lax.axis_size(axis),), x.dtype)
    return jnp.max(jax.lax.psum(lanes.at[jax.lax.axis_index(axis)].set(x),
                                axis))


def _make_loop(axis, params: PRParams, n_true: int, *, dfp: bool,
               compact_frontier: bool = False, delta_every: int = 1,
               trace: bool = False, frontier_caps=None,
               health: bool = False):
    """Build the per-shard while-loop body. `axis` is the (tuple of) mesh
    axis name(s) the vertex dimension is sharded over.

    The per-iteration math is `core.rank_step.rank_step` on this shard's
    slice — the same single implementation the dense engine uses — wrapped
    in the two collectives the 1-D partition needs: the contribution
    all-gather and the convergence max. Frontier expansion (dfp) pulls the
    gathered δ_N through the same local layout, *including at iteration 0*,
    which is the paper's initial expansion (line 9) performed device-side:
    callers seed δ_N with the updated sources (`initial_affected_sharded`)
    instead of pre-expanding on the host.

    `compact_frontier` gathers δ_N as uint8 instead of the rank dtype
    (DESIGN.md §5: the frontier all-gather shrinks 4-8x; the pull-max
    upcasts locally). `delta_every=k` evaluates the global L-inf all-reduce
    every k iterations only — the straggler/latency mitigation of DESIGN.md
    §8: up to k-1 surplus (cheap, local) iterations traded for k-fold fewer
    global syncs.

    `trace` carries an obs.trace.TraceBuffer through the loop; its channels
    come out of psum/pmax collectives so the buffer is replicated across
    shards (out_spec P()). Tracing adds two small per-iteration collectives
    and never feeds back into the rank math; with delta_every>1 the traced
    L∞ is exact every iteration even though the loop predicate still only
    sees it every k-th.

    `frontier_caps` (core.frontier.FrontierCaps over the PER-SHARD layout
    shapes — `caps_for_parts`) switches the rank pull to the compacted
    active lists: each shard compacts its own δ_V slice against its own
    layout and pulls only the active rows/tiles from the gathered
    contribution vector; a shard whose lists overflow runs its dense local
    pull for that iteration (per-shard lax.cond — sound because neither
    branch holds a collective, so shards may diverge freely). The loop then
    also carries a frontier-stats vector, psum-reduced on exit."""

    def loop(sgd: dict, r0, dv0, dn0):
        sgl = _squeeze_shard(sgd)
        r0, dv0, dn0 = r0[0], dv0[0], dn0[0]
        dt = r0.dtype
        d = sgl["out_deg"].astype(dt)
        valid = sgl["valid"]
        n_loc = valid.shape[0]

        def body(state):
            r, dv, dn, _, i, tb, fs = state
            if dfp:
                gdt = jnp.uint8 if compact_frontier else dt
                dn_full = jax.lax.all_gather(dn.astype(gdt), axis, tiled=True)
                grow = _local_pull_max(sgl, dn_full.astype(dt)) > 0
                dv = (dv | grow) & valid
            c_full = jax.lax.all_gather(r / d, axis, tiled=True)
            dv_in = dv & valid
            if frontier_caps is not None:
                af = active_frontier(sgl["buckets"], sgl["hi_pos"],
                                     sgl["hi_rowmap"], dv_in, frontier_caps)
                s = jax.lax.cond(
                    af.overflow,
                    lambda: _local_pull(sgl, c_full),
                    lambda: active_pull_sum(
                        sgl["buckets"], sgl["hi_pos"], sgl["hi_tiles"],
                        sgl["hi_tmask"], sgl["hi_rowmap"], af, c_full,
                        n_loc))
                ok = (~af.overflow).astype(jnp.int32)
                fs = fs.at[FS_ITERS].add(1).at[FS_COMPACT].add(ok) \
                       .at[FS_OVERFLOW].add(1 - ok) \
                       .at[FS_ACTIVE_ROWS].add(af.n_rows * ok) \
                       .at[FS_ACTIVE_TILES].add(af.n_tiles * ok)
                if len(sgl["buckets"]):
                    fs = fs.at[FS_NB:].add(af.bucket_counts * ok)
            else:
                s = _local_pull(sgl, c_full)
            r_new, dv, dn_new, local = rank_step(
                s, r, dv_in, sgl["out_deg"], alpha=params.alpha,
                n_norm=n_true, tau_f=params.tau_f, tau_p=params.tau_p,
                prune=dfp, closed_form=dfp, track_frontier=dfp)
            if not dfp:
                dn_new = dn
            gmax = _all_max(local, axis)
            if delta_every > 1:
                check = (i + 1) % delta_every == 0
                delta = jnp.where(check, gmax, jnp.asarray(jnp.inf, dt))
            else:
                delta = gmax
            if trace:
                counts = jnp.stack([
                    jnp.sum(dv_in), jnp.sum(dn_new),
                    jnp.sum(dv_in) - jnp.sum(dv & valid)]).astype(jnp.int32)
                counts = jax.lax.psum(counts, axis)
                tb = trace_record(tb, i, linf=gmax, frontier=counts[0],
                                  delta_n=counts[1] if dfp else 0,
                                  pruned=counts[2] if dfp else 0)
            return r_new, dv, dn_new, delta, i + 1, tb, fs

        def cond(state):
            delta, i = state[3], state[4]
            return (delta > params.tau) & (i < params.max_iter)

        tb0 = trace_init(params.max_iter, dt,
                         "dfp_1d" if dfp else "static_1d") if trace \
            else jnp.asarray(0, jnp.int32)
        # the frontier stats accumulate per-shard counts (psum'd on exit),
        # so the carry is varying over the mesh axes from its first value
        fs0 = jax.lax.pcast(fstats_init(len(sgl["buckets"])), axis,
                            to="varying")
        init = (r0, dv0, dn0, jnp.asarray(jnp.inf, dt),
                jnp.asarray(0, jnp.int32), tb0, fs0)
        r, dv, dn, delta, iters, tb, fs = jax.lax.while_loop(cond, body, init)
        out = [r[None], iters]
        if trace:
            out.append(tb)
        if health:
            # guard.health word, replicated: delta came through
            # `_all_max`, the mass is one extra psum over the valid slice.
            # A delta left at the inf skip-sentinel (delta_every>1
            # exhausting the budget between checks) clamps to H_MAX_ITER
            # inside solve_health.
            mass = jax.lax.psum(jnp.sum(jnp.where(valid, r, 0)), axis)
            out.append(solve_health(delta, iters, mass, params))
        if frontier_caps is not None:
            out.append(jax.lax.psum(fs, axis))
        return tuple(out)

    return loop


def _specs(mesh: Mesh):
    axis = tuple(mesh.axis_names)
    shard = P(axis)
    return axis, shard


def pagerank_step_specs(mesh: Mesh):
    """(in_specs, out_specs) used by the dry-run lowering for this workload."""
    axis, shard = _specs(mesh)
    return shard, axis


@functools.lru_cache(maxsize=None)
def _solver(mesh: Mesh, params: PRParams, n_true: int, dfp: bool,
            delta_every: int, trace: bool, frontier_caps, health: bool):
    """The jitted shard_map'd loop for one (mesh, params, flags) signature.

    Cached so every batch of a stream re-enters the same jitted callable:
    building ``jax.jit`` around a fresh closure per solve would retrace
    (and recompile) the loop on every call."""
    axis, shard = _specs(mesh)
    loop = _make_loop(axis, params, n_true, dfp=dfp,
                      delta_every=delta_every, trace=trace,
                      frontier_caps=frontier_caps, health=health)
    out_specs = [shard, P()]
    if trace:
        out_specs.append(P())
    if health:
        out_specs.append(P())
    if frontier_caps is not None:
        out_specs.append(P())
    return jax.jit(jax.shard_map(
        loop, mesh=mesh,
        in_specs=({k: shard for k in _FIELDS}, shard, shard, shard),
        out_specs=tuple(out_specs)))


def distributed_static_pagerank(mesh: Mesh, sg: ShardedGraph, r0: jnp.ndarray,
                                params: PRParams = PRParams(),
                                delta_every: int = 1, trace: bool = False,
                                health: bool = False):
    """r0: [nd, n_loc] stacked ranks. Returns (ranks [nd, n_loc], iters),
    plus a replicated obs.trace.TraceBuffer when ``trace=True`` and a
    replicated guard.health word (last) when ``health=True``."""
    nd, n_loc = sg.out_deg.shape
    where = stacked_sharding(mesh)
    on = jnp.ones((nd, n_loc), jnp.bool_, device=where)
    off = jnp.zeros((nd, n_loc), jnp.bool_, device=where)
    fn = _solver(mesh, params, sg.n_true, False, delta_every, trace, None,
                 health)
    with _obs().span("solve.static_1d", annotate=True):
        return fn(_as_dict(sg), r0, on, off)


def sharded_frontier_caps(sg: ShardedGraph, est: int,
                          headroom: int = 16):
    """FrontierCaps over the PER-SHARD layout shapes for `frontier_caps` of
    `distributed_dfp_pagerank`. `est` is the expected initial frontier size
    of the worst shard (a global estimate works too — caps only affect
    speed, never correctness)."""
    return caps_for_parts(
        tuple(int(b.rows.shape[1]) for b in sg.buckets),
        int(sg.hi_pos.shape[1]), int(sg.hi_tiles.shape[1]),
        sg.n_loc, est, headroom)


def distributed_dfp_pagerank(mesh: Mesh, sg: ShardedGraph, r_prev: jnp.ndarray,
                             dv0: jnp.ndarray, dn0: jnp.ndarray,
                             params: PRParams = PRParams(),
                             delta_every: int = 1, trace: bool = False,
                             frontier_caps=None, health: bool = False):
    """DF-P on the cluster: dv0/dn0 are the initial affected / to-expand
    flags ([nd, n_loc], from `initial_affected_sharded`). Iteration 0 pulls
    dn0 through the layout — the paper's initial frontier expansion — so
    callers seed raw flags; pre-expanded dv0 (with dn0 zeroed) also works.
    ``trace=True`` appends a replicated obs.trace.TraceBuffer;
    ``health=True`` appends a replicated guard.health word (before the
    frontier stats, which stay last).
    ``frontier_caps`` (`sharded_frontier_caps`) compacts each shard's rank
    pull to its active rows/tiles — identical results, frontier.* obs
    counters published host-side."""
    fn = _solver(mesh, params, sg.n_true, True, delta_every, trace,
                 frontier_caps, health)
    with _obs().span("solve.dfp_1d", annotate=True):
        out = fn(_as_dict(sg), r_prev, dv0, dn0)
    if frontier_caps is not None:
        *out, fs = out
        publish_fstats(fs)
        out = tuple(out)
    return out
