"""2-D edge-partitioned PageRank (beyond-paper; DESIGN.md §6).

The paper's pull model on a 1-D vertex partition all-gathers the FULL
contribution vector c (V·4 B per device per iteration) — collective-bound at
scale. Classic 2-D SpMV blocking fixes this: on an (r × c) mesh, device
(i, j) owns the edge block with sources in row-range(i) and destinations in
row-range(j); per iteration it

  1. all-gathers c along 'model'  -> c_row [V/r]      (V/r bytes, not V)
  2. pulls its edge block         -> y_partial [V/c]
  3. psum_scatters y along 'data' -> its V/(r·c) piece of destination range j
  4. collective-permutes (i,j)->(j,i) to return the piece to its owner
     (ownership is row-major block b = i·c + j).

Per-device collective bytes drop from ~2·V·4 to ~2·(V/r)·4 (+V/(r·c) for the
transpose) — 16x on the 16×16 pod. Frontier expansion (δ_N OR-pull) rides the
same schedule with sum-as-OR (flags are 0/1, so Σ>0 ⇔ ∨). Everything stays
scatter-free and one-write-per-owned-vertex: the paper's discipline, blocked.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .frontier import (FS_ACTIVE_ROWS, FS_COMPACT, FS_ITERS, FS_OVERFLOW,
                       fstats_init, publish_fstats, stream_compact)
from .graph import Graph
from .pagerank import PRParams
from .rank_step import rank_step
from ..obs.spans import get_registry as _obs
from ..obs.trace import trace_init, trace_record

__all__ = ["Sharded2D", "build_sharded_2d", "pagerank_2d", "dfp_2d"]


class Sharded2D(NamedTuple):
    """Per-device edge blocks, leading axis = r·c (row-major (i, j))."""
    ell_idx: jnp.ndarray    # [rc, V/c, d_p] int32 — LOCAL col ids into c_row
    ell_mask: jnp.ndarray   # [rc, V/c, d_p] f32
    out_deg: jnp.ndarray    # [rc, V/rc] int32 (owned vertices, b = i*c + j)
    valid: jnp.ndarray      # [rc, V/rc] bool
    n_true: int
    r: int
    c: int


def build_sharded_2d(g: Graph, r: int, c: int, d_p: int = 8) -> Sharded2D:
    """Host partitioner. Edge (u -> v) lands on device (u // (V/r) ...
    truncated to r rows, v-range analog for columns). Per-destination degree
    within one block is ~deg/r, so the block layout is pure ELL with a small
    d_p (overflow edges spill to extra ELL columns by raising d_p)."""
    assert r == c, "2-D scheme assumes a square (data, model) sub-mesh"
    n = g.n
    rc = r * c
    n_pad = ((n + rc - 1) // rc) * rc
    v_r = n_pad // r          # row/column range size
    blk = n_pad // rc

    # per-device ELL over destinations in range(j), sources in range(i)
    src, dst = g.edges()
    i_of = np.minimum(src // v_r, r - 1)
    j_of = np.minimum(dst // v_r, c - 1)
    dev = i_of * c + j_of
    order = np.argsort(dev, kind="stable")
    src, dst, dev = src[order], dst[order], dev[order]
    starts = np.searchsorted(dev, np.arange(rc))
    ends = np.searchsorted(dev, np.arange(rc) + 1)

    # find required d_p: max per-(device, destination) multiplicity
    need = 1
    for b in range(rc):
        s, e = starts[b], ends[b]
        if e > s:
            cnt = np.bincount(dst[s:e] - (dev[s:e] % c) * v_r,
                              minlength=v_r)
            need = max(need, int(cnt.max()))
    d_p = max(d_p, need)

    ell_idx = np.zeros((rc, v_r, d_p), np.int32)
    ell_mask = np.zeros((rc, v_r, d_p), np.float32)
    for b in range(rc):
        s, e = starts[b], ends[b]
        if e <= s:
            continue
        i, j = b // c, b % c
        ld = dst[s:e] - j * v_r          # local destination row
        ls = src[s:e] - i * v_r          # local source (col into c_row)
        o = np.argsort(ld, kind="stable")
        lds, lss = ld[o], ls[o]
        pos = np.arange(lds.size) - np.searchsorted(lds, lds, side="left")
        ell_idx[b, lds, pos] = lss
        ell_mask[b, lds, pos] = 1.0

    deg = np.ones((rc, blk), np.int32)
    valid = np.zeros((rc, blk), bool)
    od = g.out_degree()
    for b in range(rc):
        lo = b * blk
        hi = min((b + 1) * blk, n)
        if hi > lo:
            deg[b, :hi - lo] = od[lo:hi]
            valid[b, :hi - lo] = True
    return Sharded2D(ell_idx=jnp.asarray(ell_idx),
                     ell_mask=jnp.asarray(ell_mask),
                     out_deg=jnp.asarray(deg), valid=jnp.asarray(valid),
                     n_true=n, r=r, c=c)


def _loop_2d(params: PRParams, n_true: int, r: int, c: int, *, dfp: bool,
             row_axis="data", col_axis="model", trace: bool = False,
             row_cap: int | None = None):
    """Per-device while loop. Mesh axes: row_axis size r, col_axis size c.

    The per-iteration math is the shared `core.rank_step.rank_step` on the
    owned vertex block; this loop supplies only the blocked pull schedule
    (all-gather along the column axis, psum-scatter along the row axis,
    ppermute back to the owner — DESIGN.md §6). Frontier expansion runs at
    iteration 0 too, so δ_N may be seeded raw (paper's initial expansion,
    device-side) exactly as in the 1-D engine. ``trace`` carries an
    obs.trace.TraceBuffer; channels are psum'd over both mesh axes so the
    buffer is replicated (out_spec P()).

    ``row_cap`` (static) compacts the rank pull's destination loop: the
    mesh-row's δ_V slice is assembled by the same transpose-permute +
    row-axis all-gather the owned pieces use, stream-compacted into a
    [row_cap] active-destination list, and the edge-block gather-reduce runs
    over those rows only — per-device edge work O(row_cap · d_p) instead of
    O(V/r · d_p). Overflow falls back to the full block for that iteration
    (the cond's branches hold no collectives — the all-gather/psum-scatter/
    ppermute schedule stays outside, so divergence across devices is fine).
    The expansion pull stays full-width: its output IS the new frontier,
    which is exactly what is not yet known."""

    def loop(sgd, r0, dv0, dn0):
        ell_idx = sgd["ell_idx"][0]
        ell_mask = sgd["ell_mask"][0]
        out_deg = sgd["out_deg"][0]
        deg = out_deg.astype(r0.dtype)
        valid = sgd["valid"][0]
        rank0, dv0, dn0 = r0[0], dv0[0], dn0[0]
        dt = rank0.dtype
        v_r = ell_idx.shape[0]
        perm = [(a * c + b, b * c + a) for a in range(r) for b in range(c)]

        def pull(vec_own, sel=None, ovf=None):
            """vec_own [blk] -> per-destination sums [v_r] -> own piece."""
            # 1. gather this mesh-row's owned pieces = contiguous row range i
            v_row = jax.lax.all_gather(vec_own, col_axis, tiled=True)

            # 2. local masked gather-reduce over the edge block — all
            # destinations, or only the compacted active list
            def full_part():
                return jnp.sum(jnp.take(v_row, ell_idx, axis=0)
                               * ell_mask.astype(vec_own.dtype), axis=1)

            if sel is None:
                part = full_part()
            else:
                def active_part():
                    idx_s = jnp.take(ell_idx, sel, axis=0, mode="fill",
                                     fill_value=0)
                    msk_s = jnp.take(ell_mask, sel, axis=0, mode="fill",
                                     fill_value=0.0)
                    sums = jnp.sum(jnp.take(v_row, idx_s, axis=0)
                                   * msk_s.astype(vec_own.dtype), axis=1)
                    return jnp.zeros((v_r,), vec_own.dtype) \
                        .at[sel].add(sums, mode="drop")
                part = jax.lax.cond(ovf, full_part, active_part)
            # 3. reduce partials over mesh rows; keep piece i of range j
            piece = jax.lax.psum_scatter(part, row_axis, scatter_dimension=0,
                                         tiled=True)
            # 4. piece belongs to block (j, i) -> transpose devices
            return jax.lax.ppermute(piece, (row_axis, col_axis), perm)

        def dv_row_of(dv_own):
            """Owned δ_V pieces -> this mesh-row's destination-range slice:
            the transpose permute parks block j·c+i on device (i, j), so the
            row-axis gather concatenates blocks j·c+0 .. j·c+(r-1) — range j
            in vertex order (r == c)."""
            dvp = jax.lax.ppermute(dv_own.astype(jnp.uint8),
                                   (row_axis, col_axis), perm)
            return jax.lax.all_gather(dvp, row_axis, tiled=True) > 0

        def body(state):
            rank, dv, dn, _, it, tb, fs = state
            if dfp:
                grow = pull(dn.astype(dt)) > 0          # Σ>0 ⇔ OR
                dv = (dv | grow) & valid
            dv_in = dv & valid
            if row_cap is not None:
                sel, cnt = stream_compact(dv_row_of(dv_in), row_cap, v_r)
                ovf = cnt > row_cap
                s = pull(rank / deg, sel, ovf)
                ok = (~ovf).astype(jnp.int32)
                fs = fs.at[FS_ITERS].add(1).at[FS_COMPACT].add(ok) \
                       .at[FS_OVERFLOW].add(1 - ok) \
                       .at[FS_ACTIVE_ROWS].add(cnt * ok)
            else:
                s = pull(rank / deg)
            r_new, dv_new, dn_new, local = rank_step(
                s, rank, dv_in, out_deg, alpha=params.alpha,
                n_norm=n_true, tau_f=params.tau_f, tau_p=params.tau_p,
                prune=dfp, closed_form=dfp, track_frontier=dfp)
            if dfp:
                dv, dn = dv_new, dn_new
            delta = jax.lax.pmax(local, (row_axis, col_axis))
            if trace:
                counts = jnp.stack([
                    jnp.sum(dv_in), jnp.sum(dn_new),
                    jnp.sum(dv_in) - jnp.sum(dv_new & valid)]
                ).astype(jnp.int32)
                counts = jax.lax.psum(counts, (row_axis, col_axis))
                tb = trace_record(tb, it, linf=delta, frontier=counts[0],
                                  delta_n=counts[1] if dfp else 0,
                                  pruned=counts[2] if dfp else 0)
            return r_new, dv, dn, delta, it + 1, tb, fs

        def cond(state):
            delta, it = state[3], state[4]
            return (delta > params.tau) & (it < params.max_iter)

        tb0 = trace_init(params.max_iter, dt,
                         "dfp_2d" if dfp else "static_2d") if trace \
            else jnp.asarray(0, jnp.int32)
        init = (rank0, dv0, dn0, jnp.asarray(jnp.inf, dt),
                jnp.asarray(0, jnp.int32), tb0, fstats_init(0))
        rank, dv, dn, _, iters, tb, fs = jax.lax.while_loop(cond, body, init)
        out = [rank[None], iters]
        if trace:
            out.append(tb)
        if row_cap is not None:
            out.append(jax.lax.psum(fs, (row_axis, col_axis)))
        return tuple(out)

    return loop


def _run(mesh: Mesh, sg: Sharded2D, r0, dv0, dn0, params, dfp: bool,
         trace: bool = False, row_cap: int | None = None):
    axes = mesh.axis_names
    row_axis, col_axis = axes[-2], axes[-1]
    shard = P((row_axis, col_axis))
    sgd = {"ell_idx": sg.ell_idx, "ell_mask": sg.ell_mask,
           "out_deg": sg.out_deg, "valid": sg.valid}
    loop = _loop_2d(params, sg.n_true, sg.r, sg.c, dfp=dfp,
                    row_axis=row_axis, col_axis=col_axis, trace=trace,
                    row_cap=row_cap)
    out_specs = [shard, P()]
    if trace:
        out_specs.append(P())
    if row_cap is not None:
        out_specs.append(P())
    fn = jax.shard_map(loop, mesh=mesh,
                       in_specs=({k: shard for k in sgd}, shard, shard, shard),
                       out_specs=tuple(out_specs))
    out = jax.jit(fn)(sgd, r0, dv0, dn0)
    if row_cap is not None:
        *out, fs = out
        publish_fstats(fs)
        out = tuple(out)
    return out


def pagerank_2d(mesh: Mesh, sg: Sharded2D, r0, params: PRParams = PRParams(),
                trace: bool = False):
    rc, blk = sg.out_deg.shape
    on = jnp.ones((rc, blk), jnp.bool_)
    off = jnp.zeros((rc, blk), jnp.bool_)
    with _obs().span("solve.static_2d", annotate=True):
        return _run(mesh, sg, r0, on, off, params, dfp=False, trace=trace)


def dfp_2d(mesh: Mesh, sg: Sharded2D, r_prev, dv0, dn0,
           params: PRParams = PRParams(), trace: bool = False,
           row_cap: int | None = None):
    """2-D DF-P. ``row_cap`` (static pow2) compacts each device's
    destination loop to its mesh-row's active δ_V rows — identical ranks,
    O(row_cap·d_p) local edge work, full-block fallback on overflow."""
    with _obs().span("solve.dfp_2d", annotate=True):
        return _run(mesh, sg, r_prev, dv0, dn0, params, dfp=True, trace=trace,
                    row_cap=row_cap)
