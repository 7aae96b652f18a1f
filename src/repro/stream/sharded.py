"""Incrementally maintained sharded snapshots — multi-device streaming.

``ShardedSnapshot`` is the multi-device sibling of ``DeviceSnapshot``: it
owns the stacked per-shard hybrid layout of the current graph G^t (the
``ShardedGraph`` consumed by ``core.distributed``) and applies a canonical
``Delta`` *in place* — O(|Δ| · d_p) host bookkeeping on per-shard
``_HalfLayout`` mirrors plus O(touched rows) scatters into the stacked
device arrays — instead of the O(|E|) re-partition + full restage
(`apply_batch` + `build_sharded`) the static sharded pipeline pays per
batch (DESIGN.md §7).

Reuse, not reimplementation: each shard's host mirror IS the single-device
`_HalfLayout` machinery (bucketed-ELL fill-cursor edits, per-bucket and
tile free lists, degree-crossing migration with hysteresis — between
buckets and across the d_p boundary) instantiated on that shard's
`build_hybrid_rows` block — row ids local, stored column ids global. Only
the device residency differs: arrays are stacked [nd, ...] so shard_map can
consume them, and the refresh scatters land at [shard, rows].

Only the pull orientation is maintained. The 1-D distributed DF-P engine
expands its frontier by pulling the all-gathered δ_N through the same pull
layout (no forward orientation exists at this scale), so half the
maintenance work of the single-device snapshot simply disappears.

Capacity discipline matches DeviceSnapshot: per-shard hi/tile caps are pow2
with headroom, shared across shards (stacking needs equal shapes), and
never shrink on rebuild — only genuine pow2 growth changes device shapes /
retriggers jit. Rebuild fallback (capacity exhaustion, fragmentation over
budget, batch above the cost crossover) routes through
`graph_from_sorted_keys` + per-shard `build_hybrid_rows` at fixed caps.
"""
from __future__ import annotations

import functools
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.distributed import (ShardedGraph, shard_block_rows, shard_bounds,
                                sharded_need, stacked_sharding)
from ..core.graph import (Graph, build_hybrid_rows, choose_bucket_widths,
                          edge_keys, graph_from_sorted_keys, next_pow2)
from ..core.pagerank import EllBlock
from ..obs.flight import get_flight
from ..obs.spans import get_registry as _obs
from .delta import Delta
from .snapshot import (CapacityError, SnapshotStats, _HalfLayout, _pad_rows,
                       apply_net_delta, rebuild_reason)

__all__ = ["ShardedSnapshot"]


@functools.lru_cache(maxsize=None)
def _stacked_scatter(mesh: Mesh):
    """jit: arr [nd, R, ...] <- vals at flat row ids ``s * R + row``.

    Runs under shard_map: each shard writes only the ids that fall in its
    own block (the rest drop), so the result keeps arr's placement — every
    shard stays on its own device and nothing is gathered. Ids may repeat
    when the repeats carry identical values (the `_pad_rows` convention)."""
    axis = tuple(mesh.axis_names)

    def scatter_shard(arr, at, vals):
        rows = arr.shape[1]
        r = at - jax.lax.axis_index(axis) * rows
        r = jnp.where((r >= 0) & (r < rows), r, rows)
        with jax.named_scope("snapshot.scatter"):
            return arr.at[0, r].set(vals, mode="drop")

    return jax.jit(jax.shard_map(scatter_shard, mesh=mesh,
                                 in_specs=(P(axis), P(), P()),
                                 out_specs=P(axis)))


class ShardedSnapshot:
    """Stacked per-shard hybrid layouts of G^t, maintained incrementally.

    Exposes `.sg` — the `ShardedGraph` the distributed engines accept — and
    the same `apply(delta) -> SnapshotStats` lifecycle as `DeviceSnapshot`.
    Vertex v lives on shard `v // n_loc` at local row `v % n_loc`
    (contiguous blocks, identical to `build_sharded`). Every stacked array
    is placed with `stacked_sharding(mesh)` — shard s on device s — when it
    is built, and the row scatters keep that placement.
    """

    def __init__(self, g: Graph, mesh: Mesh, d_p: int = 64, tile: int = 256,
                 hi_headroom: float = 2.0, tile_headroom: float = 2.0,
                 rebuild_threshold: float = 0.05, frag_budget: float = 0.6,
                 low_water: Optional[int] = None):
        self.n = g.n
        self.nd = nd = int(mesh.devices.size)
        self.sharding = stacked_sharding(mesh)
        self._scatter = _stacked_scatter(mesh)
        self.n_pad = ((g.n + nd - 1) // nd) * nd
        self.n_loc = self.n_pad // nd
        self.d_p, self.tile = d_p, tile
        self.rebuild_threshold = rebuild_threshold
        self.frag_budget = frag_budget
        self._low_water = low_water
        self._hi_headroom, self._tile_headroom = hi_headroom, tile_headroom
        src, dst = g.edges()
        self._keys = np.sort(edge_keys(g.n, src, dst))
        self._indeg = g.in_degree().astype(np.int64)
        self._outdeg = g.out_degree().astype(np.int64)
        # valid is static: the vertex set never changes across the stream
        valid = np.zeros(self.n_pad, bool)
        valid[:self.n] = True
        self._dev_valid = self._put(valid.reshape(nd, self.n_loc))
        self._adopt(g)
        self._last_rebuild_reason = ""

    # -- construction / rebuild ---------------------------------------------

    def _caps_for(self, indeg: np.ndarray,
                  widths: Optional[tuple] = None) -> dict:
        """Worst-shard bucket/high/tile needs, pow2 with headroom (caps are
        shared across shards — stacking needs equal shapes). Widths are
        chosen once from the global in-degree histogram and then frozen
        across rebuilds (passed back in); only caps may grow."""
        if widths is None:
            widths = choose_bucket_widths(indeg, self.d_p)
        # band=True: caps must cover the hysteresis band each bucket can
        # accumulate under streaming, not just the placement census
        need_hi, need_t, need_b = sharded_need(indeg, self.nd, self.n_loc,
                                               self.d_p, self.tile, widths,
                                               band=True)
        return dict(
            hi_cap=next_pow2(int(need_hi * self._hi_headroom), 8),
            t_cap=next_pow2(int(need_t * self._tile_headroom), 8),
            widths=tuple(widths),
            bucket_caps=tuple(next_pow2(int(nb * self._hi_headroom), 8)
                              for nb in need_b))

    def _adopt(self, g: Graph, caps: Optional[dict] = None) -> None:
        """(Re)build every shard's half from a host Graph at fixed caps."""
        caps = caps or self._caps_for(self._indeg)
        self._caps = caps
        self._halves: List[_HalfLayout] = []
        for s in range(self.nd):
            off, dat = shard_block_rows(g, s, self.n_loc)
            hr = build_hybrid_rows(off, dat, d_p=self.d_p, tile=self.tile,
                                   n_rows=self.n_loc,
                                   n_hi_cap=caps["hi_cap"],
                                   t_cap=caps["t_cap"],
                                   widths=caps["widths"],
                                   bucket_caps=caps["bucket_caps"])
            lo, hi = shard_bounds(s, self.n_loc, self.n)
            row_deg = np.zeros(self.n_loc, np.int64)
            row_deg[:hi - lo] = self._indeg[lo:hi]
            half = _HalfLayout(hr, row_deg, stage_device=False)
            if self._low_water is not None:
                half.low_water = self._low_water
            self._halves.append(half)
        self._restack()

    def _put(self, a: np.ndarray) -> jnp.ndarray:
        """Stage a stacked [nd, ...] host array, shard s on device s."""
        return jax.device_put(a, self.sharding)

    def _stack(self, field: str, bi: Optional[int] = None) -> jnp.ndarray:
        # np.stack copies: the mirrors mutate in place across batches
        return self._put(np.stack([
            getattr(h, field) if bi is None else getattr(h, field)[bi]
            for h in self._halves]))

    def _restack(self) -> None:
        # stacked device residency — adopt/rebuild and checkpoint-restore
        # both end here
        self.dev_buckets: List[EllBlock] = [
            EllBlock(rows=self._stack("bk_rows", bi),
                     idx=self._stack("bk_idx", bi),
                     mask=self._stack("bk_mask", bi))
            for bi in range(len(self._caps["widths"]))]
        self.dev_hi_tiles = self._stack("hi_tiles")
        self.dev_hi_tmask = self._stack("hi_tmask")
        self.dev_hi_rowmap = self._stack("hi_rowmap")
        self.dev_hi_pos = self._stack("hi_ids")
        outdeg = np.ones(self.n_pad, np.int32)
        outdeg[:self.n] = self._outdeg
        self._dev_outdeg = self._put(outdeg.reshape(self.nd, self.n_loc))

    def _rebuild(self, reason: str) -> None:
        caps = self._caps_for(self._indeg, widths=self._caps["widths"])
        # never shrink: keep stacked shapes stable unless we *must* grow
        # (widths stay frozen; bucket_caps grow elementwise)
        caps = dict(
            hi_cap=max(caps["hi_cap"], self._caps["hi_cap"]),
            t_cap=max(caps["t_cap"], self._caps["t_cap"]),
            widths=self._caps["widths"],
            bucket_caps=tuple(max(a, b) for a, b in
                              zip(caps["bucket_caps"],
                                  self._caps["bucket_caps"])),
        )
        self._adopt(self.graph(), caps)
        self._last_rebuild_reason = reason

    def _set_shard(self, arr: jnp.ndarray, s: int,
                   vals: np.ndarray) -> jnp.ndarray:
        """Replace shard s's whole [R] slice of a stacked [nd, R] table."""
        rows = arr.shape[1]
        return self._scatter(arr, jnp.asarray(np.arange(
            s * rows, (s + 1) * rows, dtype=np.int32)), jnp.asarray(vals.copy()))

    # -- queries -------------------------------------------------------------

    @property
    def m(self) -> int:
        return int(self._keys.size)

    @property
    def sg(self) -> ShardedGraph:
        return ShardedGraph(
            buckets=tuple(self.dev_buckets),
            hi_pos=self.dev_hi_pos, hi_tiles=self.dev_hi_tiles,
            hi_tmask=self.dev_hi_tmask, hi_rowmap=self.dev_hi_rowmap,
            out_deg=self._dev_outdeg, valid=self._dev_valid, n_true=self.n)

    def graph(self) -> Graph:
        """Materialize the host CSR Graph (verification / rebuild path)."""
        return graph_from_sorted_keys(self.n, self._keys)

    def fragmentation(self) -> float:
        return max(h.tile_waste() for h in self._halves)

    # -- checkpoint state (guard.journal) ------------------------------------

    def state_dict(self) -> tuple:
        """(arrays, extra): complete stacked-snapshot state — edge keys,
        degrees, and every shard's half mirrors + free-list orders under an
        ``s{shard}.`` prefix (see `DeviceSnapshot.state_dict`)."""
        arrays = dict(keys=self._keys, indeg=self._indeg,
                      outdeg=self._outdeg)
        for s, half in enumerate(self._halves):
            arrays.update(half.state_dict(f"s{s}."))
        extra = {"caps": {k: list(v) if isinstance(v, tuple) else int(v)
                          for k, v in self._caps.items()}}
        return arrays, extra

    def load_state(self, arrays: dict, extra: dict) -> None:
        """Restore from ``state_dict`` output: re-adopt at the checkpointed
        capacities, overwrite every shard's mirrors, restack."""
        self._keys = np.ascontiguousarray(arrays["keys"])
        self._indeg = np.ascontiguousarray(arrays["indeg"])
        self._outdeg = np.ascontiguousarray(arrays["outdeg"])
        caps = {k: tuple(v) if isinstance(v, list) else int(v)
                for k, v in extra["caps"].items()}
        self._adopt(self.graph(), caps)
        for s, half in enumerate(self._halves):
            half.load_state(arrays, f"s{s}.")
        self._restack()

    # -- the batch-update lifecycle ------------------------------------------

    def apply(self, delta: Delta) -> SnapshotStats:
        """Apply a canonical Δ^t in place; returns per-apply stats.

        Feeds the same obs span/counter names as `DeviceSnapshot.apply`
        (prefix ``snapshot.``) so dashboards see one stream regardless of
        session mode, plus ``snapshot.shard_scatters`` for the stacked-row
        scatter count."""
        obs = _obs()
        t0 = time.perf_counter()
        stats = SnapshotStats()
        with obs.span("snapshot.apply_net_delta", annotate=True):
            self._keys, (d_s, d_d), (i_s, i_d) = apply_net_delta(
                self._keys, self.n, delta, self._indeg, self._outdeg)
        stats.net_del, stats.net_ins = int(d_s.size), int(i_s.size)

        reason = rebuild_reason(delta.size, self.m, self.fragmentation(),
                                self.rebuild_threshold, self.frag_budget)
        if reason is not None:
            with obs.span("snapshot.rebuild"):
                self._rebuild(reason)
            obs.inc("snapshot.rebuilds")
            obs.inc(f"snapshot.rebuild.{reason.split(':')[0]}")
            get_flight().emit("snapshot.rebuild", reason=reason,
                              sharded=True)
            stats.rebuilt, stats.rebuild_reason = True, reason
            stats.host_s = time.perf_counter() - t0
            return stats

        n_loc = self.n_loc
        mig0 = sum(h.migrations for h in self._halves)
        try:
            # pull orientation: row = destination vertex, entry = source
            with obs.span("snapshot.host_edit", annotate=True):
                for u, v in zip(d_s.tolist(), d_d.tolist()):
                    self._halves[v // n_loc].delete(v % n_loc, u)
                for u, v in zip(i_s.tolist(), i_d.tolist()):
                    self._halves[v // n_loc].insert(v % n_loc, u)
        except CapacityError as e:
            # mirrors are mid-edit but the key set is complete: rebuild
            with obs.span("snapshot.rebuild"):
                self._rebuild(f"capacity:{e}")
            obs.inc("snapshot.rebuilds")
            obs.inc("snapshot.rebuild.capacity")
            get_flight().emit("snapshot.rebuild", reason=f"capacity:{e}",
                              sharded=True)
            stats.rebuilt, stats.rebuild_reason = True, f"capacity:{e}"
            stats.host_s = time.perf_counter() - t0
            return stats

        stats.migrations = sum(h.migrations for h in self._halves) - mig0
        stats.host_s = time.perf_counter() - t0
        # enqueues the scatters; their device time is the trace's
        # ``snapshot.scatter`` stage
        with obs.span("snapshot.device_refresh", annotate=True):
            for s, half in enumerate(self._halves):
                dirty = half.drain_dirty()
                tiles = dirty["tiles"]
                for bi, slots in enumerate(dirty["bucket_slots"]):
                    blk = self.dev_buckets[bi]
                    if slots.size:
                        at = _pad_rows(slots, next_pow2(slots.size))
                        flat = jnp.asarray(s * blk.idx.shape[1] + at)
                        blk = blk._replace(
                            idx=self._scatter(blk.idx, flat, jnp.asarray(
                                half.bk_idx[bi][at])),
                            mask=self._scatter(blk.mask, flat, jnp.asarray(
                                half.bk_mask[bi][at])))
                        obs.inc("snapshot.shard_scatters")
                        stats.rows_touched += int(slots.size)
                    # bucket row-id maps, restaged per shard only on
                    # migration (they are small: [cap_b])
                    if dirty["bucket_maps"][bi]:
                        blk = blk._replace(rows=self._set_shard(
                            blk.rows, s, half.bk_rows[bi]))
                    self.dev_buckets[bi] = blk
                if tiles.size:
                    at = _pad_rows(tiles, next_pow2(tiles.size))
                    flat = jnp.asarray(s * self.dev_hi_tiles.shape[1] + at)
                    self.dev_hi_tiles = self._scatter(
                        self.dev_hi_tiles, flat,
                        jnp.asarray(half.hi_tiles[at]))
                    self.dev_hi_tmask = self._scatter(
                        self.dev_hi_tmask, flat,
                        jnp.asarray(half.hi_tmask[at]))
                    obs.inc("snapshot.shard_scatters")
                # small per-shard 1-D side tables, restaged only when touched
                if dirty["rowmap_dirty"]:
                    self.dev_hi_rowmap = self._set_shard(
                        self.dev_hi_rowmap, s, half.hi_rowmap)
                if dirty["side_dirty"]:
                    self.dev_hi_pos = self._set_shard(
                        self.dev_hi_pos, s, half.hi_ids)
                stats.tiles_touched += int(tiles.size)
            touched = np.unique(np.concatenate([d_s, i_s]))
            if touched.size:
                # global vertex id == flat row id of the stacked [nd, n_loc]
                at = _pad_rows(touched.astype(np.int32),
                               next_pow2(touched.size))
                self._dev_outdeg = self._scatter(
                    self._dev_outdeg, jnp.asarray(at),
                    jnp.asarray(self._outdeg[at].astype(np.int32)))
        obs.inc("snapshot.inplace_batches")
        obs.inc("snapshot.rows_touched", stats.rows_touched)
        obs.inc("snapshot.tiles_touched", stats.tiles_touched)
        obs.inc("snapshot.migrations", stats.migrations)
        return stats
