"""Frontier-compacted DF / DF-P — the TPU translation of "skip unaffected
vertices".

The paper's update kernels do `if not δ_V[v]: continue`; a GPU thread that
skips costs nothing. Dense XLA arrays don't skip — a masked update still
pays the full |V|·d_p gather — which erases the paper's headline speedup.
This module restores it with static-shape *compaction*:

  * affected vertex ids are extracted with jnp.nonzero(size=K) (K is a
    static capacity, chosen per batch from the initial frontier size);
  * the rank pull gathers ONLY those K rows of the in-neighbor ELL (+ the
    affected high-in-degree tile subset), so per-iteration edge work is
    O(frontier · degree) like the paper's, not O(|E|);
  * frontier expansion mirrors the paper exactly: it walks the OUT-edges of
    flagged vertices (out-degree-partitioned forward layout) and scatters
    flags — work ∝ Σ out-degree(frontier), the same bound as Alg. 5;
  * if the frontier ever outgrows K, the loop exits and the dense engine
    (core/dynamic.py) finishes from the current state — correctness never
    depends on the capacity guess.

One write per affected vertex per iteration is preserved throughout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .dynamic import DeviceBatch, _loop, solve_health
from .frontier import (FrontierCaps, active_frontier, initial_affected,
                       plan_capacity, push_expand, update_ranks_active)
from .graph import Graph, build_hybrid
from .pagerank import DeviceGraph, PRParams, as_device_graph, to_device
from ..obs.spans import get_registry as _obs
from ..obs.trace import trace_init, trace_record

__all__ = ["forward_device_graph", "dfp_pagerank_compact",
           "df_pagerank_compact"]


def forward_device_graph(g: Graph, d_p: int = 64, tile: int = 1024,
                         **caps) -> DeviceGraph:
    """Out-edge hybrid layout (the paper's 'Partition G' by out-degree):
    rows of the ELL are each vertex's OUT-neighbors."""
    return to_device(build_hybrid(g.transpose(), d_p=d_p, tile=tile, **caps))


def _scatter_expand(fwd: DeviceGraph, dn_flags: jnp.ndarray, kn: int
                    ) -> jnp.ndarray:
    """Paper Alg. 5 expandAffected, compacted (core.frontier.push_expand):
    out-neighbors of flagged vertices get marked. Returns a dense bool [n]
    of newly-marked vertices (complete only while Σδ_N ≤ kn)."""
    with jax.named_scope("pr.expand"):
        return push_expand(fwd, dn_flags, kn)[0]


@functools.partial(jax.jit,
                   static_argnames=("params", "k", "kt", "kn", "prune",
                                    "trace"))
def _compact_loop(dg: DeviceGraph, fwd: DeviceGraph, r0, dv0, dn0,
                  params: PRParams, k: int, kt: int, kn: int, prune: bool,
                  trace: bool = False):
    n = dg.n
    dt = r0.dtype
    # the engine's (K, K_t, K_n) sizing expressed on the shared capacity
    # plan: per-bucket lists are K clamped to each bucket's slot count, the
    # total-rows budget K is enforced separately below (this engine *exits*
    # to the dense driver on overflow rather than paying full sweeps, so an
    # oversized total frontier must still trip it even when every
    # per-bucket list individually fits)
    caps = FrontierCaps(
        bucket=tuple(min(k, int(b.rows.shape[0])) for b in dg.buckets),
        hi=min(k, dg.n_hi_cap), tiles=kt, dn=kn, fwd_tiles=0)

    def body(state):
        r, dv, dn, _, i, _, tb = state
        marks, push_ovf = push_expand(fwd, dn, kn)
        dv = jnp.where(i > 0, dv | marks, dv)
        dv_in = dv   # post-expansion frontier entering this sweep (trace)
        af = active_frontier(dg.buckets, dg.hi_ids, dg.hi_rowmap, dv, caps)
        overflow = af.overflow | push_ovf | (af.n_rows > k)
        r_new, dv_new, dn_new, dmax = update_ranks_active(
            dg, r, dv, af, alpha=params.alpha, tau_f=params.tau_f,
            tau_p=params.tau_p, prune=prune, closed_form=prune,
            track_frontier=True)
        # an overflowing iteration must not commit a truncated update: keep
        # the pre-iteration state and exit with delta=inf (dense fallback)
        r_new = jnp.where(overflow, r, r_new)
        dv = jnp.where(overflow, dv_in, dv_new)
        dn_new = jnp.where(overflow, dn, dn_new)
        delta = jnp.where(overflow, jnp.asarray(jnp.inf, dt), dmax)
        if trace:
            # the overflow iteration records linf=inf — the visible marker
            # of the dense handoff. Frontier-size reductions live only on
            # this traced path; the untraced loop computes none.
            frontier = jnp.sum(dv_in)
            tb = trace_record(
                tb, i, linf=delta, frontier=frontier,
                delta_n=jnp.sum(dn_new),
                pruned=frontier - jnp.sum(dv) if prune else 0)
        return r_new, dv, dn_new, delta, i + 1, af.n_rows, tb

    # Entry: sweep 0 runs where the frontier of sweep 1 (at most the
    # initial frontier's out-edges, self-loops included) fits K; else the
    # loop hands off before its first sweep, whose work the overflow of
    # sweep 1 would waste (a hub's out-edges on a power-law graph). The
    # initial delta is the overflow signal, read only where the loop does
    # not enter, so no float64 sentinel has to survive the device (a TPU
    # v5e reads float64 finfo.max as inf).
    enter = jnp.sum(jnp.where(dv0, dg.out_deg, 0)) <= k

    def cond(state):
        delta, i = state[3], state[4]
        go = jnp.where(i == 0, enter,
                       (delta > params.tau) & ~jnp.isinf(delta))
        return go & (i < params.max_iter)
    # NOTE: body sets delta=inf on ANY capacity overflow (row, tile or
    # worklist), and an overflowing body commits nothing — so the inf check
    # alone routes every overflow to the dense fallback; the old per-cond
    # Σδ_V / Σδ_N reductions were dead work and are gone.

    tb0 = trace_init(params.max_iter, dt,
                     "dfp_compact" if prune else "df_compact") if trace \
        else jnp.asarray(0, jnp.int32)
    # rows: the active rows of the last sweep tried (the overflowing one
    # where the loop overflowed)
    init = (r0, dv0, dn0, jnp.asarray(jnp.inf, dt),
            jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32), tb0)
    r, dv, dn, delta, iters, rows, tb = jax.lax.while_loop(cond, body, init)
    return r, dv, dn, delta, iters, rows, (tb if trace else None)


def _df_like_compact(dg, fwd, r_prev, batch: DeviceBatch,
                     params: PRParams, *, prune: bool, headroom: int = 16,
                     trace: bool = False, health: bool = False):
    """The compact loop, then the dense driver if the frontier outgrew its
    capacity. Host steps run under annotated ``compact.*`` spans; the
    ``compact.*`` counters (batches, overflows, the loop's own sweeps, the
    planned capacity, the active rows at the loop's exit, the finish's
    sweeps) take only values the host reads at the syncs the solve has."""
    obs = _obs()
    n = dg.n
    with obs.span("compact.plan", annotate=True):
        dv, dn = initial_affected(n, batch.del_src, batch.del_dst,
                                  batch.ins_src)
        # initial marking via the compacted out-edge walk (paper Alg. 5),
        # not a dense O(|E|) pull — the batch is tiny relative to the graph
        kn_init = plan_capacity(int(jnp.sum(dn)) + 1, n, headroom=2)
        dv = dv | _scatter_expand(fwd, dn, kn_init)
        n_init = int(jnp.sum(dv)) + 1
        k = plan_capacity(n_init, n, headroom=headroom)
        kn = k
        # No tile compaction: affected hubs legitimately need their full
        # tile lists, and the high side is a small fraction of total edge
        # slots — the ELL (low-degree majority) is where compaction pays
        # (tile truncation forced immediate dense fallback on power-law
        # graphs, refuting the tile-compaction hypothesis — DESIGN.md §4).
        kt = dg.hi_tiles.shape[0]
        dn0 = jnp.zeros((n,), jnp.bool_)
    r, dv, dn, delta, iters, rows, tb = _compact_loop(
        dg, fwd, r_prev, dv, dn0, params, k, kt, kn, prune, trace)
    with obs.span("compact.check", annotate=True):
        # one transfer for the three scalars; where the loop converged, the
        # caller's later int(iters) takes the host copy JAX keeps on it
        delta_h, sweeps, rows_h = jax.device_get((delta, iters, rows))
        delta_h, sweeps, rows_h = float(delta_h), int(sweeps), int(rows_h)
    overflow = delta_h > params.tau and sweeps < params.max_iter
    obs.inc("compact.batches")
    obs.inc("compact.overflows", int(overflow))
    obs.inc("compact.sweeps", sweeps)
    obs.inc("compact.capacity", k)
    obs.inc("compact.exit_rows", rows_h)
    hw = None
    if overflow:
        # frontier outgrew the capacity: dense engine finishes the job,
        # appending to the same trace buffer at offset `iters`. Its health
        # word (budget = the REMAINING iterations) is the solve's health
        # word: exhausting the rest is exactly exhausting the total budget.
        with obs.span("compact.finish", annotate=True):
            out = list(_dense_finish(dg, r, dv, dn, params, prune, tb,
                                     jnp.asarray(sweeps, jnp.int32), health))
        if health:
            hw = out.pop()
        r, it2 = out[0], out[1]
        tb = out[2] if trace else None
        # the solve's one read of the finish: its caller reads the sweeps
        # next, and gets a host int
        it2 = int(it2)
        obs.inc("compact.finish_sweeps", it2)
        iters = sweeps + it2
    elif health:
        hw = solve_health(delta, iters, jnp.sum(r), params)
    res = [r, iters]
    if trace:
        res.append(tb)
    if health:
        res.append(hw)
    return tuple(res) if trace or health else (r, iters)


@functools.partial(jax.jit, static_argnames=("params", "prune", "health"))
def _dense_finish(dg, r, dv, dn, params, prune, tb=None, i_off=0,
                  health: bool = False):
    """The dense DF-P loop from the compact loop's state after ``i_off``
    (an int32 operand) sweeps, for the rest of the budget: one program
    for every sweep the loop overflows at."""
    return _loop(dg, r, dv, dn, params, expand=True, prune=prune,
                 closed_form=prune, tb=tb, i_off=i_off, health=health)


def _stage_pair(dg, fwd):
    """Resolve (pull, forward) device graphs; a pre-staged snapshot exposing
    `.dg`/`.fwd_dg` (repro.stream.DeviceSnapshot) may be passed as `dg` with
    fwd=None and supplies both orientations."""
    if fwd is None:
        fwd = getattr(dg, "fwd_dg", None)
        if fwd is None:
            raise TypeError("fwd is required unless dg is a snapshot "
                            "exposing .fwd_dg")
    return as_device_graph(dg), as_device_graph(fwd)


def dfp_pagerank_compact(dg, fwd=None, r_prev=None,
                         batch: DeviceBatch = None,
                         params: PRParams = PRParams(),
                         trace: bool = False, health: bool = False):
    with _obs().span("solve.dfp_compact", annotate=True):
        dg, fwd = _stage_pair(dg, fwd)
        return _df_like_compact(dg, fwd, r_prev, batch, params, prune=True,
                                trace=trace, health=health)


def df_pagerank_compact(dg, fwd=None, r_prev=None,
                        batch: DeviceBatch = None,
                        params: PRParams = PRParams(),
                        trace: bool = False, health: bool = False):
    with _obs().span("solve.df_compact", annotate=True):
        dg, fwd = _stage_pair(dg, fwd)
        return _df_like_compact(dg, fwd, r_prev, batch, params, prune=False,
                                trace=trace, health=health)
