"""Static PageRank (paper Alg. 1) — synchronous, pull-based, scatter-free.

The device graph is the hybrid ELL + tiled-CSR layout of the *transpose* graph
(see core/graph.py). Rank computation is one gather-reduce per iteration with a
single masked write per vertex — the TPU translation of the paper's
atomics-free pull kernels. Low in-degree vertices ride the ELL (lane-per-vertex)
path; high in-degree vertices ride the tiled-CSR (tile-loop-per-vertex) path,
combined with a segment-sum that plays the role of the block reduction.

`update_ranks` is shared verbatim between Static / ND / DT / DF / DF-P (the
paper re-uses `updateRanks()` the same way, toggling the affected flags).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Graph, HybridLayout, build_hybrid
from .rank_step import rank_step
from ..obs.spans import get_registry as _obs
from ..obs.trace import trace_init, trace_record

__all__ = [
    "EllBlock", "DeviceGraph", "to_device", "as_device_graph", "pull_sum",
    "pull_max", "update_ranks", "static_pagerank", "PRParams", "init_ranks",
]

ALPHA = 0.85
TAU = 1e-10
TAU_F = 1e-6
TAU_P = 1e-6
MAX_ITER = 500


class EllBlock(NamedTuple):
    """One degree bucket of the low side, staged on device."""
    rows: jnp.ndarray       # [cap_b] int32 (sentinel = n)
    idx: jnp.ndarray        # [cap_b, w_b] int32
    mask: jnp.ndarray       # [cap_b, w_b] f32

    @property
    def width(self) -> int:
        return self.idx.shape[1]


class DeviceGraph(NamedTuple):
    """Hybrid bucketed pull layout staged on device (all jnp arrays,
    static shapes; the bucket tuple is static pytree structure)."""
    buckets: Tuple[EllBlock, ...]   # degree buckets, ascending width
    bucket_of: jnp.ndarray  # [n] int32 (len(buckets) = CSR side)
    slot_of: jnp.ndarray    # [n] int32 (slot within bucket / hi side)
    hi_ids: jnp.ndarray     # [n_hi_cap] int32 (sentinel = n)
    hi_tiles: jnp.ndarray   # [t_cap, tile] int32
    hi_tmask: jnp.ndarray   # [t_cap, tile] f32
    hi_rowmap: jnp.ndarray  # [t_cap] int32
    is_low: jnp.ndarray     # [n] bool
    out_deg: jnp.ndarray    # [n] int32 (>=1: self-loops guaranteed)

    @property
    def n(self) -> int:
        return self.is_low.shape[0]

    @property
    def n_hi_cap(self) -> int:
        return self.hi_ids.shape[0]


class PRParams(NamedTuple):
    alpha: float = ALPHA
    tau: float = TAU
    tau_f: float = TAU_F
    tau_p: float = TAU_P
    max_iter: int = MAX_ITER


def to_device(layout: HybridLayout) -> DeviceGraph:
    return DeviceGraph(
        buckets=tuple(EllBlock(rows=jnp.asarray(b.rows),
                               idx=jnp.asarray(b.idx),
                               mask=jnp.asarray(b.mask))
                      for b in layout.buckets),
        bucket_of=jnp.asarray(layout.bucket_of),
        slot_of=jnp.asarray(layout.slot_of),
        hi_ids=jnp.asarray(layout.hi_ids),
        hi_tiles=jnp.asarray(layout.hi_tiles),
        hi_tmask=jnp.asarray(layout.hi_tmask),
        hi_rowmap=jnp.asarray(layout.hi_rowmap),
        is_low=jnp.asarray(layout.is_low),
        out_deg=jnp.asarray(layout.out_deg),
    )


def device_graph(g: Graph, d_p: int = 64, tile: int = 1024, **caps) -> DeviceGraph:
    return to_device(build_hybrid(g, d_p=d_p, tile=tile, **caps))


def as_device_graph(obj) -> DeviceGraph:
    """Coerce to a pull-side DeviceGraph.

    Accepts a DeviceGraph (identity), any pre-staged snapshot exposing `.dg`
    (e.g. `repro.stream.DeviceSnapshot`), a host HybridLayout, or a Graph.
    Drivers call this outside their jitted impls so snapshots can be passed
    directly without retracing on the wrapper object.
    """
    if isinstance(obj, DeviceGraph):
        return obj
    staged = getattr(obj, "dg", None)
    if staged is not None:
        return staged
    if isinstance(obj, HybridLayout):
        return to_device(obj)
    if isinstance(obj, Graph):
        return device_graph(obj)
    raise TypeError(f"cannot stage {type(obj).__name__} as a DeviceGraph")


def init_ranks(n: int, dtype=jnp.float64) -> jnp.ndarray:
    dtype = jnp.zeros(0, dtype).dtype  # canonicalize under x64-disabled
    return jnp.full((n,), 1.0 / n, dtype=dtype)


# ---------------------------------------------------------------------------
# Pull primitives (single gather-reduce; one write per vertex)
# ---------------------------------------------------------------------------

def pull_sum(dg: DeviceGraph, c: jnp.ndarray) -> jnp.ndarray:
    """sum_{u in G'.row(v)} c[u] for every v — the paper's two rank kernels.

    ELL side: per degree bucket, [cap_b, w_b] masked gather + row-sum
    (lane-per-vertex at the bucket's width), scattered once through the
    bucket's row map. CSR side: [t_cap, tile] masked gather + tile-sum +
    segment-sum over the tile->row map (tile-loop-per-vertex with an
    on-chip accumulator on TPU), scattered once into the dense result
    (drop-mode handles pad sentinels on both sides). Its device ops carry
    the stage name ``pr.pull`` (a ``jax.named_scope``: op metadata only).
    """
    with jax.named_scope("pr.pull"):
        dt = c.dtype
        out = jnp.zeros(c.shape, dt)
        for blk in dg.buckets:
            sums = jnp.sum(jnp.take(c, blk.idx, axis=0)
                           * blk.mask.astype(dt), axis=1)
            out = out.at[blk.rows].add(sums, mode="drop")
        tile_sums = jnp.sum(jnp.take(c, dg.hi_tiles, axis=0)
                            * dg.hi_tmask.astype(dt), axis=1)
        hi_per_slot = jax.ops.segment_sum(tile_sums, dg.hi_rowmap,
                                          num_segments=dg.n_hi_cap)
        return out.at[dg.hi_ids].add(hi_per_slot, mode="drop")


def pull_max(dg: DeviceGraph, x: jnp.ndarray) -> jnp.ndarray:
    """max_{u in G'.row(v)} x[u] — pull-based frontier expansion primitive.

    Replaces the paper's scatter-based `expandAffected` kernel pair (TPU has no
    cheap scatter); same fixpoint, same schedule, scatter-free. Stage name
    ``pr.expand``.
    """
    with jax.named_scope("pr.expand"):
        dt = x.dtype
        out = jnp.zeros(x.shape, dt)
        for blk in dg.buckets:
            rmax = jnp.max(jnp.take(x, blk.idx, axis=0)
                           * blk.mask.astype(dt), axis=1, initial=0)
            out = out.at[blk.rows].max(rmax, mode="drop")
        tile_max = jnp.max(jnp.take(x, dg.hi_tiles, axis=0)
                           * dg.hi_tmask.astype(dt), axis=1, initial=0)
        hi_per_slot = jax.ops.segment_max(tile_max, dg.hi_rowmap,
                                          num_segments=dg.n_hi_cap)
        # empty segments -> -inf guard
        hi_per_slot = jnp.maximum(hi_per_slot, 0)
        return out.at[dg.hi_ids].max(hi_per_slot, mode="drop")


# ---------------------------------------------------------------------------
# updateRanks (paper Alg. 3) — shared across all five approaches
# ---------------------------------------------------------------------------

def update_ranks(dg: DeviceGraph, r: jnp.ndarray, affected: jnp.ndarray,
                 *, alpha: float, tau_f: float, tau_p: float,
                 prune: bool, closed_form: bool, track_frontier: bool,
                 pull_sum_fn=None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One synchronous rank sweep.

    Returns (r_new, affected', delta_N, linf_delta). With `affected` all-True,
    `prune=False`, `closed_form=False`, `track_frontier=False` this *is* the
    static kernel (paper: "disable the affected flags to utilize the same
    function for Static PageRank").

    This is the dense-engine binding of `core.rank_step.rank_step` — the
    repo-wide single implementation of the Eq. 1/Eq. 2 math — to the hybrid
    pull primitive above.
    """
    psum = pull_sum_fn or pull_sum
    s = psum(dg, r / dg.out_deg.astype(r.dtype))
    return rank_step(s, r, affected, dg.out_deg, alpha=alpha, n_norm=dg.n,
                     tau_f=tau_f, tau_p=tau_p, prune=prune,
                     closed_form=closed_form, track_frontier=track_frontier)


# ---------------------------------------------------------------------------
# Static PageRank driver (paper Alg. 1)
# ---------------------------------------------------------------------------

def static_pagerank(dg, r0: jnp.ndarray, params: PRParams = PRParams(),
                    pull_sum_fn=None, trace: bool = False,
                    health: bool = False):
    """Power iteration to L-inf tolerance. Returns (ranks, n_iters) — or
    (ranks, n_iters, TraceBuffer) with ``trace=True``, which carries the
    per-iteration L∞ series through the loop as aux state (obs.trace;
    identical ranks either way, no host callbacks). ``health=True`` appends
    the solve's guard.health word (int32 bitmask) last.

    `dg` may be a DeviceGraph or any pre-staged snapshot (see as_device_graph).
    """
    # every engine entry point dispatches under an annotated solve.* span,
    # so kernels land on the device timeline whenever a profiler trace is
    # live (ISSUE 10; the span itself times host dispatch only)
    with _obs().span("solve.static", annotate=True):
        return _static_pagerank(as_device_graph(dg), jnp.asarray(r0), params,
                                pull_sum_fn, trace, health)


@functools.partial(jax.jit, static_argnames=("params", "pull_sum_fn",
                                             "trace", "health"))
def _static_pagerank(dg: DeviceGraph, r0: jnp.ndarray,
                     params: PRParams = PRParams(),
                     pull_sum_fn=None, trace: bool = False,
                     health: bool = False):
    n = dg.n
    all_on = jnp.ones((n,), dtype=jnp.bool_)
    zero = jnp.asarray(0, jnp.int32)

    def body(state):
        r, _, i, tb = state
        r_new, _, _, delta = update_ranks(
            dg, r, all_on, alpha=params.alpha, tau_f=params.tau_f,
            tau_p=params.tau_p, prune=False, closed_form=False,
            track_frontier=False, pull_sum_fn=pull_sum_fn)
        if trace:
            tb = trace_record(tb, i, linf=delta, frontier=n, delta_n=0,
                              pruned=0)
        return r_new, delta, i + 1, tb

    def cond(state):
        _, delta, i, _ = state
        return (delta > params.tau) & (i < params.max_iter)

    tb0 = trace_init(params.max_iter, r0.dtype, "static") if trace else zero
    init = (r0, jnp.asarray(jnp.inf, r0.dtype), zero, tb0)
    r, delta, iters, tb = jax.lax.while_loop(cond, body, init)
    out = [r, iters]
    if trace:
        out.append(tb)
    if health:
        from ..guard.health import health_word, rank_mass  # lazy: no cycle
        dt = jnp.asarray(delta).dtype
        delta = jnp.where(jnp.isposinf(delta), jnp.finfo(dt).max, delta)
        out.append(health_word(delta, iters, rank_mass(r), tau=params.tau,
                               max_iter=params.max_iter))
    return tuple(out)
