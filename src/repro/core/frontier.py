"""Paper Alg. 5 affected-set machinery + device-side frontier compaction.

Marking (unchanged since PR 1): `initial_affected` scatters O(|Δ|) flags,
`expand_affected` is the dense pull-based expansion (every vertex pulls the
OR of δ_N over its in-neighbors in G^t), `reach_affected` the DT fixpoint.

Compaction (PR 8, the O(frontier·degree) layer): dense masks make every
sweep O(|E|) regardless of how small δ_V is — the mask only gates the
*write*. This module turns δ_V into *active gather lists* over the hybrid
layout instead, with static shapes so jitted loops never recompile:

  * `stream_compact` — cumsum-based compaction of a flag vector into a
    fixed-capacity index list (the GPU stream-compaction primitive, in XLA);
  * `FrontierCaps` — the static pow2 capacity plan (hashable, a jit static
    arg). Capacities never shrink (`merge_caps`), so a streamed session
    re-uses one compiled loop across batches;
  * `active_frontier` — per-bucket active-slot lists + active hi-slot and
    CSR-tile lists from δ_V, with an `overflow` flag when any list is
    truncated (callers fall back to the full sweep for that iteration —
    capacity guesses affect speed, never correctness);
  * `active_pull_sum` / `update_ranks_active` — the rank pull (and the
    full Alg. 3 sweep) restricted to the active lists: per-iteration edge
    work is O(Σ_b k_b·w_b + k_t·tile), the paper's frontier·degree bound;
  * `push_expand` / `expand_frontier` — the paper's out-edge expansion
    driven by the compacted δ_N worklist (low buckets: one ELL row per
    worklist entry; high out-degree: compacted tile walk — Alg. 5's
    out-degree partitioning), with the dense pull as the overflow branch.

Both the single-device `DeviceGraph` and the per-shard layouts (which lack
`bucket_of`/`slot_of`) are served: compaction is *slot-based* — a bucket's
active rows are found by gathering δ_V at the bucket's row ids, never by
indexing vertex ids into bucket membership tables.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .graph import next_pow2
from .pagerank import DeviceGraph, pull_max
from .rank_step import rank_step

__all__ = [
    "initial_affected", "expand_affected", "reach_affected",
    "stream_compact", "FrontierCaps", "ActiveFrontier", "caps_for",
    "caps_for_parts", "merge_caps", "plan_capacity", "active_frontier",
    "active_pull_sum", "update_ranks_active", "push_expand",
    "expand_frontier", "fstats_init", "publish_fstats",
    "FS_ITERS", "FS_COMPACT", "FS_OVERFLOW", "FS_ACTIVE_ROWS",
    "FS_ACTIVE_TILES", "FS_PUSH", "FS_PULL", "FS_EXPAND_WORK", "FS_NB",
]


def initial_affected(n: int, del_src: jnp.ndarray, del_dst: jnp.ndarray,
                     ins_src: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Alg. 5 initialAffected: δ_N[u]=1 for every updated source u; δ_V[v]=1
    for every deletion target v. Inputs may be padded with id == n (dropped)."""
    dv = jnp.zeros((n,), jnp.bool_)
    dn = jnp.zeros((n,), jnp.bool_)
    dn = dn.at[del_src].set(True, mode="drop")
    dn = dn.at[ins_src].set(True, mode="drop")
    dv = dv.at[del_dst].set(True, mode="drop")
    return dv, dn


def expand_affected(dg: DeviceGraph, dv: jnp.ndarray, dn: jnp.ndarray
                    ) -> jnp.ndarray:
    """δ_V'[v] = δ_V[v] OR (∃ u ∈ G^t.in(v): δ_N[u]) — dense O(|E|) pull.

    NOTE: `dg` here must be the hybrid layout of the *current graph's
    transpose* — i.e. rows are in-neighbors in G^t, which is exactly the rank
    pull structure, so expansion re-uses it (DESIGN.md §2). The compacted
    engines use this only as the worklist-overflow fallback; see
    `expand_frontier`.
    """
    with jax.named_scope("pr.expand"):
        pulled = pull_max(dg, dn.astype(jnp.float32))
        return dv | (pulled > 0.5)


def reach_affected(dg: DeviceGraph, seeds: jnp.ndarray,
                   max_steps: int | None = None) -> jnp.ndarray:
    """Dynamic Traversal marking: all vertices reachable (along out-edges)
    from seed vertices, via pull-based BFS fixpoint on the transpose layout.
    Used by the DT baseline. `seeds` is a dense bool [n] mask."""
    n = dg.n
    max_steps = n if max_steps is None else max_steps

    def body(state):
        vis, _, i = state
        nxt = vis | (pull_max(dg, vis.astype(jnp.float32)) > 0.5)
        changed = jnp.any(nxt != vis)
        return nxt, changed, i + 1

    def cond(state):
        _, changed, i = state
        return changed & (i < max_steps)

    vis, _, _ = jax.lax.while_loop(
        cond, body, (seeds, jnp.asarray(True), jnp.asarray(0, jnp.int32)))
    return vis


# ---------------------------------------------------------------------------
# Stream compaction + capacity plans
# ---------------------------------------------------------------------------

def stream_compact(flags: jnp.ndarray, k: int, fill: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Indices of set flags, order-preserving, into a static [k] list.

    Stream compaction spelled scatter-free: keying each lane with its own
    index (dead lanes key past the end) and sorting brings the set flags to
    the front in order — XLA lowers the sort to a vectorized bitonic/merge
    network, whereas the textbook cumsum + scatter form serializes on the
    scatter (~2x slower on CPU, worse on TPU where arbitrary-index scatter
    is the weakest op). Destinations beyond k are dropped (callers must
    treat count > k as overflow — the list is then truncated). Dead lanes
    hold `fill`. Returns (idx [k] int32, count)."""
    with jax.named_scope("pr.compact"):
        ln = flags.shape[0]
        keys = jnp.where(flags, jnp.arange(ln, dtype=jnp.int32), ln)
        if k > ln:                      # caps may overshoot short flag vectors
            keys = jnp.pad(keys, (0, k - ln), constant_values=ln)
        idx = jax.lax.sort(keys, is_stable=False)[:k]
        idx = jnp.where(idx >= ln, fill, idx)
        return idx, jnp.sum(flags, dtype=jnp.int32)


class FrontierCaps(NamedTuple):
    """Static compaction capacities (a hashable jit static argument).

    All fields are ints on the pow2 ladder (never-shrink across a session —
    `merge_caps` — so capacity growth, not frontier churn, is the only
    recompile trigger). `bucket[b]` bounds bucket b's active-slot list,
    `hi`/`tiles` the active high-slot / CSR-tile lists of the pull layout,
    `dn` the push-expansion vertex worklist, `fwd_tiles` the forward
    layout's tile worklist (0 = uncompacted full tile list: affected hubs
    legitimately need all their tiles and truncating them thrashes the
    fallback — DESIGN.md §4's refuted-tile-compaction lesson)."""
    bucket: Tuple[int, ...]
    hi: int
    tiles: int
    dn: int
    fwd_tiles: int = 0


def plan_capacity(est: int, n: int, headroom: int = 16) -> int:
    """One shared sizing rule: pow2(est·headroom), clamped to n, floor 16."""
    return min(next_pow2(max(int(est), 1) * headroom), max(next_pow2(n), 16))


def caps_for_parts(bucket_caps: Tuple[int, ...], n_hi_cap: int, t_cap: int,
                   n: int, est: int, headroom: int = 16) -> FrontierCaps:
    """Capacity plan from layout shapes + an expected initial frontier size.

    Each list is bounded by both the plan size and its layout capacity (a
    bucket can never hold more active rows than it has slots, so clamped
    lists cannot overflow on that side)."""
    k = plan_capacity(est, n, headroom)
    return FrontierCaps(
        bucket=tuple(min(k, int(c)) for c in bucket_caps),
        hi=min(k, int(n_hi_cap)),
        tiles=min(next_pow2(k), int(t_cap)),
        dn=k,
        fwd_tiles=0)


def caps_for(dg: DeviceGraph, est: int, headroom: int = 16) -> FrontierCaps:
    """`caps_for_parts` reading the shapes off a staged DeviceGraph."""
    return caps_for_parts(
        tuple(int(b.rows.shape[0]) for b in dg.buckets),
        dg.n_hi_cap, int(dg.hi_tiles.shape[0]), dg.n, est, headroom)


def merge_caps(a: Optional[FrontierCaps], b: FrontierCaps) -> FrontierCaps:
    """Elementwise max — the never-shrink discipline across a session."""
    if a is None:
        return b
    return FrontierCaps(
        bucket=tuple(max(x, y) for x, y in zip(a.bucket, b.bucket)),
        hi=max(a.hi, b.hi), tiles=max(a.tiles, b.tiles),
        dn=max(a.dn, b.dn), fwd_tiles=max(a.fwd_tiles, b.fwd_tiles))


# ---------------------------------------------------------------------------
# Active gather lists over the hybrid layout
# ---------------------------------------------------------------------------

class ActiveFrontier(NamedTuple):
    """δ_V compacted against one hybrid layout (static shapes from caps).

    Sentinels: bucket_sel[b] dead lanes = cap_b, hi_sel = n_hi_cap,
    tile_sel = t_cap. `overflow` is the single validity bit: when True some
    list was truncated and NONE of the lists may be used for an update —
    callers run the dense full sweep for that iteration instead."""
    bucket_sel: Tuple[jnp.ndarray, ...]   # per bucket [k_b] slot ids
    hi_sel: jnp.ndarray                   # [k_h] hi slot ids
    tile_sel: jnp.ndarray                 # [k_t] CSR tile ids
    bucket_counts: jnp.ndarray            # [nb] int32 active rows per bucket
    n_rows: jnp.ndarray                   # scalar int32 (buckets + hi)
    n_tiles: jnp.ndarray                  # scalar int32
    overflow: jnp.ndarray                 # scalar bool


def active_frontier(buckets, hi_ids: jnp.ndarray, hi_rowmap: jnp.ndarray,
                    dv: jnp.ndarray, caps: FrontierCaps) -> ActiveFrontier:
    """Compact δ_V into active gather lists, slot-based.

    Works on a DeviceGraph's parts or one shard's squeezed layout (pass
    `hi_pos` as `hi_ids` there): a bucket's active slots are found by
    gathering δ_V at the bucket's row ids (sentinel rows read False), the
    active tile list by gathering the hi-slot activity through the
    tile→slot map — no vertex-id→slot tables needed."""
    with jax.named_scope("pr.compact"):
        assert len(caps.bucket) == len(buckets), \
            "FrontierCaps bucket arity != layout bucket arity"
        sels, counts = [], []
        overflow = jnp.asarray(False)
        for blk, kb in zip(buckets, caps.bucket):
            on = jnp.take(dv, blk.rows, mode="fill", fill_value=False)
            sel, cnt = stream_compact(on, kb, blk.rows.shape[0])
            sels.append(sel)
            counts.append(cnt)
            overflow = overflow | (cnt > kb)
        on_hi = jnp.take(dv, hi_ids, mode="fill", fill_value=False)
        hi_sel, hi_cnt = stream_compact(on_hi, caps.hi, hi_ids.shape[0])
        tile_on = jnp.take(on_hi, hi_rowmap)
        tile_sel, t_cnt = stream_compact(tile_on, caps.tiles,
                                         hi_rowmap.shape[0])
        overflow = overflow | (hi_cnt > caps.hi) | (t_cnt > caps.tiles)
        bucket_counts = (jnp.stack(counts) if counts
                         else jnp.zeros((0,), jnp.int32))
        n_rows = (jnp.sum(bucket_counts, dtype=jnp.int32) if counts
                  else jnp.asarray(0, jnp.int32)) + hi_cnt
        return ActiveFrontier(tuple(sels), hi_sel, tile_sel, bucket_counts,
                              n_rows, t_cnt, overflow)


def active_pull_sum(buckets, hi_ids, hi_tiles, hi_tmask, hi_rowmap,
                    af: ActiveFrontier, c: jnp.ndarray, n_out: int
                    ) -> jnp.ndarray:
    """`pull_sum` restricted to the active lists: dense [n_out] sums that are
    exact for every active row and zero elsewhere (callers mask by δ_V, so
    inactive lanes never feed the rank math). Edge work is
    O(Σ_b k_b·w_b + k_t·tile) — the frontier·degree bound. `c` may be longer
    than n_out (sharded shards gather global columns into local rows).

    Only valid when `af.overflow` is False (truncated lists would silently
    drop in-edges of hubs)."""
    with jax.named_scope("pr.pull"):
        dt = c.dtype
        out = jnp.zeros((n_out,), dt)
        for blk, sel in zip(buckets, af.bucket_sel):
            rows = jnp.take(blk.rows, sel, mode="fill", fill_value=n_out)
            idx = jnp.take(blk.idx, sel, axis=0, mode="fill", fill_value=0)
            msk = jnp.take(blk.mask, sel, axis=0, mode="fill", fill_value=0.0)
            sums = jnp.sum(jnp.take(c, idx, axis=0) * msk.astype(dt), axis=1)
            out = out.at[rows].add(sums, mode="drop")
        tiles = jnp.take(hi_tiles, af.tile_sel, axis=0, mode="fill",
                         fill_value=0)
        tmask = jnp.take(hi_tmask, af.tile_sel, axis=0, mode="fill",
                         fill_value=0.0)
        tsums = jnp.sum(jnp.take(c, tiles, axis=0) * tmask.astype(dt), axis=1)
        slot = jnp.take(hi_rowmap, af.tile_sel, mode="fill", fill_value=0)
        owner = jnp.take(hi_ids, slot)        # dead lanes add 0.0 — inert
        return out.at[owner].add(tsums, mode="drop")


def update_ranks_active(dg: DeviceGraph, r: jnp.ndarray, dv: jnp.ndarray,
                        af: ActiveFrontier, *, alpha: float, tau_f: float,
                        tau_p: float, prune: bool, closed_form: bool,
                        track_frontier: bool):
    """One Alg. 3 sweep whose pull touches only the active lists.

    Same contract (and bit-identical outputs, lane for lane: each row's
    in-edge sum is reduced in the same order as the dense pull) as
    `core.pagerank.update_ranks` whenever `af` covers δ_V — i.e. whenever
    `af.overflow` is False, which callers must guarantee (lax.cond on it)."""
    s = active_pull_sum(dg.buckets, dg.hi_ids, dg.hi_tiles, dg.hi_tmask,
                        dg.hi_rowmap, af, r / dg.out_deg.astype(r.dtype),
                        dg.n)
    return rank_step(s, r, dv, dg.out_deg, alpha=alpha, n_norm=dg.n,
                     tau_f=tau_f, tau_p=tau_p, prune=prune,
                     closed_form=closed_form, track_frontier=track_frontier)


# ---------------------------------------------------------------------------
# Push-style expansion (paper Alg. 5 expandAffected, worklist-driven)
# ---------------------------------------------------------------------------

def push_expand(fwd: DeviceGraph, dn: jnp.ndarray, kn: int,
                kt: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Out-neighbors of the compacted δ_N worklist, marked.

    The paper's out-degree-partitioned kernel pair on the forward hybrid
    layout: low out-degree sources walk their own ELL row (one worklist
    entry = one [w_b] row gather); high out-degree sources walk their tile
    lists through a *compacted* tile worklist (kt = 0 keeps the dense tile
    walk gated by the activity mask — never overflows). Work is
    Σ out-degree(worklist), Alg. 5's bound. Returns (marks [n] bool,
    overflow) — marks are only complete when overflow is False."""
    with jax.named_scope("pr.expand"):
        n = fwd.n
        src, n_src = stream_compact(dn, kn, n)
        overflow = n_src > kn
        nb = len(fwd.buckets)
        b_of = jnp.take(fwd.bucket_of, src, mode="fill", fill_value=nb)
        s_of = jnp.take(fwd.slot_of, src, mode="fill", fill_value=0)
        out = jnp.zeros((n + 1,), jnp.bool_)
        for bi, blk in enumerate(fwd.buckets):
            slot = jnp.where(b_of == bi, s_of, blk.rows.shape[0])
            nbr = jnp.take(blk.idx, slot, axis=0, mode="fill", fill_value=0)
            msk = jnp.take(blk.mask, slot, axis=0, mode="fill", fill_value=0.0)
            tgt = jnp.where(msk > 0, nbr, n)
            out = out.at[tgt.reshape(-1)].set(True, mode="drop")
        # high-out-degree worklist entries: their tile lists
        hi_aff = jnp.take(dn, fwd.hi_ids, mode="fill", fill_value=False)
        tile_on = jnp.take(hi_aff, fwd.hi_rowmap)
        if kt:
            tsel, n_t = stream_compact(tile_on, kt, fwd.hi_tiles.shape[0])
            overflow = overflow | (n_t > kt)
            tiles = jnp.take(fwd.hi_tiles, tsel, axis=0, mode="fill",
                             fill_value=0)
            tmask = jnp.take(fwd.hi_tmask, tsel, axis=0, mode="fill",
                             fill_value=0.0)
            tgt2 = jnp.where(tmask > 0, tiles, n)
        else:
            tgt2 = jnp.where((fwd.hi_tmask > 0) & tile_on[:, None],
                             fwd.hi_tiles, n)
        out = out.at[tgt2.reshape(-1)].set(True, mode="drop")
        return out[:n], overflow


def expand_frontier(dg: DeviceGraph, fwd: DeviceGraph, dv: jnp.ndarray,
                    dn: jnp.ndarray, caps: FrontierCaps
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """δ_V ∪ out-neighbors(δ_N): push-style when the worklist fits its caps,
    dense pull (`expand_affected`) otherwise — chosen per iteration inside
    the jitted loop, so a one-off frontier spike costs one full sweep, not a
    recompile. Returns (δ_V', stats [work, pushed, pulled] int32)."""
    with jax.named_scope("pr.expand"):
        n_dn = jnp.sum(dn, dtype=jnp.int32)
        hi_aff = jnp.take(dn, fwd.hi_ids, mode="fill", fill_value=False)
        n_t = jnp.sum(jnp.take(hi_aff, fwd.hi_rowmap), dtype=jnp.int32)
        ovf = n_dn > caps.dn
        if caps.fwd_tiles:
            ovf = ovf | (n_t > caps.fwd_tiles)

        def pull_branch():
            return expand_affected(dg, dv, dn)

        def push_branch():
            marks, _ = push_expand(fwd, dn, caps.dn, caps.fwd_tiles)
            return dv | marks

        dv_new = jax.lax.cond(ovf, pull_branch, push_branch)
        one = jnp.asarray(1, jnp.int32)
        zero = jnp.asarray(0, jnp.int32)
        stats = jnp.stack([n_dn,
                           jnp.where(ovf, zero, one),
                           jnp.where(ovf, one, zero)])
        return dv_new, stats


# ---------------------------------------------------------------------------
# frontier.* observability (device-accumulated, host-published)
# ---------------------------------------------------------------------------

# fstats vector layout: fixed slots, then one active-row counter per bucket.
FS_ITERS = 0          # loop iterations run
FS_COMPACT = 1        # iterations that used the active lists
FS_OVERFLOW = 2       # iterations that fell back to the full sweep
FS_ACTIVE_ROWS = 3    # Σ active rows over compacted iterations
FS_ACTIVE_TILES = 4   # Σ active CSR tiles over compacted iterations
FS_PUSH = 5           # push-style expansions
FS_PULL = 6           # dense pull expansions (worklist overflow)
FS_EXPAND_WORK = 7    # Σ δ_N worklist sizes fed to expansion
FS_NB = 8             # per-bucket active-row counters start here

_FS_NAMES = ("iters", "compact_iters", "compaction_overflows",
             "active_rows", "active_tiles", "push_expands", "pull_expands",
             "expansion_work")


def fstats_init(n_buckets: int) -> jnp.ndarray:
    """Zeroed frontier-stats accumulator carried through a jitted loop."""
    return jnp.zeros((FS_NB + n_buckets,), jnp.int32)


def publish_fstats(fs, registry=None) -> None:
    """Fold a loop's fstats vector into the host registry (frontier.*)."""
    import numpy as np
    from ..obs.spans import get_registry
    reg = registry if registry is not None else get_registry()
    vals = [int(v) for v in np.asarray(fs)]
    for name, v in zip(_FS_NAMES, vals):
        reg.inc(f"frontier.{name}", v)
    for b, v in enumerate(vals[FS_NB:]):
        reg.inc(f"frontier.active_rows.b{b}", v)
