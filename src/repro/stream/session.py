"""StreamSession — chained DF-P PageRank over a continuous update stream.

The session keeps everything resident across batches: ranks, the hybrid
graph layouts (via the incremental ``DeviceSnapshot`` — or the stacked
``ShardedSnapshot`` when a ``mesh`` is given), and the jit caches of the
DF-P engines. ``apply(batch)`` is the full per-batch lifecycle:

  ingest Δ^t  ->  in-place snapshot update  ->  DF-P from previous ranks

choosing between the **compact** engine (frontier-gathered work, right when
the initial frontier is a small fraction of |V|) and the **dense** engine
(full-width masked sweeps, right when the batch is large — and the internal
fallback of the compact engine anyway). The engine handoff mirrors
DESIGN.md §4: capacity guesses never affect correctness, only speed.

Multi-device mode (``mesh=``): ranks live sharded [nd, n_loc], snapshot
maintenance scatters only touched rows of the stacked layout, and every
batch routes through ``distributed_dfp_pagerank`` with the initial frontier
seeded device-side (`initial_affected_sharded`; the engine performs the
paper's initial expansion at iteration 0) — chained multi-device DF-P over
a continuous stream, same lifecycle, same accounting (DESIGN.md §7).

Fault tolerance (``guard=GuardConfig(...)`` — DESIGN.md §13): every raw
batch is validated (raise or quarantine out-of-range pairs), every solve
returns a device-side health word, and an unhealthy solve walks the
escalation ladder — full-budget dense (or sharded) DF-P retry from the
pre-solve ranks, then a static recompute — with ``guard.*`` counters at
each rung. ``journal_dir=`` adds a write-ahead delta journal and (with
``checkpoint_every=K``) periodic full-state checkpoints;
``StreamSession.restore(dir)`` rebuilds the session bit-identically from
the newest checkpoint plus a journal replay.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.compact import df_pagerank_compact, dfp_pagerank_compact
from ..core.distributed import (distributed_dfp_pagerank,
                                distributed_static_pagerank,
                                initial_affected_sharded,
                                sharded_frontier_caps)
from ..core.dynamic import df_pagerank, dfp_pagerank
from ..core.frontier import FrontierCaps, caps_for, merge_caps
from ..core.graph import BatchUpdate, Graph, graph_from_sorted_keys
from ..core.pagerank import PRParams, init_ranks, static_pagerank
from ..guard import GuardConfig
from ..guard.health import (HEALTH_OK, H_MASS_DRIFT, MASS_TOL, health_flags)
from ..guard.journal import (DeltaJournal, JournalRecord, journal_path,
                             load_session_checkpoint,
                             save_session_checkpoint)
from ..guard.validate import validate_batch
from ..obs.flight import get_flight
from ..obs.hist import Histogram, SLOConfig, start_profiler, stop_profiler
from ..obs.postmortem import write_bundle
from ..obs.spans import get_registry as _obs
from ..obs.trace import maybe_summary
from .delta import Delta, ingest
from .sharded import ShardedSnapshot
from .snapshot import DeviceSnapshot, SnapshotStats

__all__ = ["StreamSession", "BatchStats", "choose_engine",
           "frontier_estimate"]


def frontier_estimate(delta: Delta, outdeg: np.ndarray) -> int:
    """Initial-frontier size estimate of Δ^t (paper Alg. 5: the first
    expansion marks the out-neighbors of every updated source, plus every
    deletion target) — the one number engine choice and frontier capacity
    planning both key off."""
    srcs = np.unique(np.concatenate([delta.del_src, delta.ins_src]))
    return int(srcs.size) + int(outdeg[srcs].sum()) + int(delta.del_dst.size)


def choose_engine(delta: Delta, outdeg: np.ndarray, n: int,
                  threshold: float) -> str:
    """Dense vs compact, from the *initial frontier estimate*
    (`frontier_estimate`).

    The compact engine sizes its capacity K ≈ 16 · initial frontier and its
    per-iteration cost scales with K; once K approaches |V| it is strictly a
    slower dense sweep (same gathers + nonzero-compactions on top). So
    compaction is only worth entering when the estimated frontier is a small
    fraction of |V| — the oversized case would fall back to dense *inside*
    the compact driver anyway, this skips the detour.
    """
    est = frontier_estimate(delta, outdeg)
    return "compact" if est <= threshold * n else "dense"


@dataclasses.dataclass
class BatchStats:
    """End-to-end accounting for one applied batch."""
    batch_size: int
    engine: str
    iters: int
    ingest_s: float
    snapshot: SnapshotStats
    solve_s: float
    #: per-iteration trace summary (`obs.trace.trace_summary` dict) when the
    #: session was built with ``trace=True``; None otherwise.
    trace: Optional[dict] = None
    #: guard.health word of the FIRST solve attempt (0 = healthy; only
    #: populated on guarded sessions)
    health: int = 0
    #: escalation-ladder rungs walked for this batch (0 = none needed)
    escalations: int = 0
    #: out-of-range pairs dropped by the quarantine policy at ingest
    quarantined: int = 0

    @property
    def total_s(self) -> float:
        return self.ingest_s + self.snapshot.host_s + self.solve_s


def _caps_to_json(caps: Optional[FrontierCaps]):
    if caps is None:
        return None
    return {k: list(v) if isinstance(v, tuple) else int(v)
            for k, v in caps._asdict().items()}


def _caps_from_json(d) -> Optional[FrontierCaps]:
    if d is None:
        return None
    return FrontierCaps(**{k: tuple(v) if isinstance(v, list) else int(v)
                           for k, v in d.items()})


class StreamSession:
    """Incrementally expanding DF-P PageRank over a stream of batches.

    >>> sess = StreamSession(base_graph)
    >>> for batch in batches:
    ...     ranks = sess.apply(batch)
    >>> ids, vals = sess.topk(10)

    Multi-device: pass ``mesh=jax.make_mesh(...)`` — the session shards the
    snapshot over all mesh devices and chains the 1-D distributed DF-P
    engine instead (``engine``/``prune``/``compact_threshold`` apply only to
    the single-device path; sharded DF-P always prunes).

    Fault tolerance: ``guard=GuardConfig(...)`` switches on ingest
    validation, the per-solve health watchdog + escalation ladder and the
    periodic drift audit; ``journal_dir=``/``checkpoint_every=`` add crash
    recovery via ``StreamSession.restore(journal_dir)``.
    """

    def __init__(self, g: Graph, params: Optional[PRParams] = None,
                 d_p: int = 64, tile: int = 256, engine: str = "auto",
                 prune: bool = True, compact_threshold: float = 0.015,
                 snapshot=None, mesh=None, trace: bool = False,
                 guard: Optional[GuardConfig] = None,
                 slo: Optional[SLOConfig] = None,
                 journal_dir: Optional[str] = None,
                 checkpoint_every: int = 0, **snap_kw):
        if engine not in ("auto", "dense", "compact"):
            raise ValueError(f"unknown engine: {engine!r}")
        #: when True every solve threads an iteration TraceBuffer through the
        #: engine and each BatchStats carries its `trace_summary` dict.
        #: `trace` is a jit static arg, so on/off paths compile separately
        #: and the off path is byte-identical to an untraced session.
        self.trace = trace
        # Session default: frontier thresholds at 1e-9 (vs the one-shot
        # default 1e-6). Chained DF-P re-uses its own output as the next
        # prior, so per-batch frontier truncation error would otherwise
        # accumulate across the stream; 1e-9 keeps every batch within
        # L1 1e-8 of a from-scratch static solve while the frontier still
        # collapses (thresholds are relative changes, not absolutes).
        self.params = params if params is not None else PRParams(
            tau_f=1e-9, tau_p=1e-9)
        self.engine = engine
        self.prune = prune
        self.compact_threshold = compact_threshold
        self.mesh = mesh
        self.guard = guard
        self.slo = slo
        self.journal_dir = journal_dir
        self.checkpoint_every = checkpoint_every
        self._snap_kw = dict(snap_kw)
        self._d_p, self._tile = d_p, tile
        if mesh is not None:
            self.snap = snapshot if snapshot is not None else ShardedSnapshot(
                g, mesh, d_p=d_p, tile=tile, **snap_kw)
        else:
            self.snap = snapshot if snapshot is not None else DeviceSnapshot(
                g, d_p=d_p, tile=tile, **snap_kw)
        self.ranks, self._init_iters = self._static_solve()
        self.history: List[BatchStats] = []
        #: never-shrink FrontierCaps across the stream (None until the first
        #: compacted batch). Growing a capacity re-traces the engine once;
        #: keeping the running elementwise max means a burst batch can only
        #: ever grow it, so the jit cache stays warm for the rest of the
        #: stream (zero recompiles after the high-water mark).
        self._caps = None
        #: sequence number of the last journaled batch (noops don't count:
        #: they change no state and are never journaled, so restore() replay
        #: and the live stream stay aligned)
        self._batch_idx = 0
        self._replaying = False
        self._journal = (DeltaJournal(journal_path(journal_dir))
                         if journal_dir is not None else None)
        #: per-session solve-latency histogram (the SLO judges THIS stream's
        #: p99, not the process-wide registry shared across sessions)
        self._solve_hist = Histogram()
        #: profiler-capture state machine: ``_capture_remaining`` batches
        #: still to run under an armed/active trace, ``_capture_active``
        #: while jax.profiler is recording. One automatic arm per session
        #: (``_slo_captured``); re-arm explicitly via `arm_capture`.
        self._capture_remaining = 0
        self._capture_active = False
        self._capture_dir: Optional[str] = None
        self._slo_captured = False
        #: quarantine summary of the most recent non-clean ingest (bundles
        #: embed it: the poisoned batch is usually the story)
        self._last_quarantine: Optional[dict] = None

    @property
    def n(self) -> int:
        return self.snap.n

    @property
    def m(self) -> int:
        return self.snap.m

    # -- the streaming API ---------------------------------------------------

    def apply(self, batch: BatchUpdate | Delta) -> jnp.ndarray:
        """Apply Δ^t and return the new rank vector (device-resident;
        stacked [nd, n_loc] in mesh mode — see `flat_ranks`). Every
        annotated span of the batch carries its sequence number ``seq``."""
        with _obs().tagged(seq=self._batch_idx + 1):
            return self._apply(batch)

    def _apply(self, batch: BatchUpdate | Delta) -> jnp.ndarray:
        obs = _obs()
        flight = get_flight()
        t0 = time.perf_counter()
        with obs.span("session.ingest", annotate=True):
            quarantined = 0
            if isinstance(batch, Delta):
                delta = batch
            else:
                policy = (self.guard.policy if self.guard is not None
                          else "raise")
                batch, report = validate_batch(batch, self.n, policy=policy)
                quarantined = report.size
                if quarantined:
                    self._last_quarantine = {
                        "size": int(report.size),
                        "deletions": int(report.del_src.size),
                        "insertions": int(report.ins_src.size)}
                    flight.emit("guard.quarantine", seq=self._batch_idx + 1,
                                dropped=int(report.size))
                delta = ingest(batch, self.n)
            db = delta.to_device() if delta.size else None
        ingest_s = time.perf_counter() - t0

        if delta.size == 0:
            # an empty (or fully-quarantined) Δ changes nothing: skip the
            # snapshot pass, the solve and the journal entirely — the
            # zero-cost no-op every upstream coalescer is entitled to
            obs.inc("session.engine.noop")
            self.history.append(BatchStats(
                batch_size=0, engine="noop", iters=0, ingest_s=ingest_s,
                snapshot=SnapshotStats(), solve_s=0.0,
                quarantined=quarantined))
            return self.ranks

        # write-ahead: the journal record lands BEFORE the delta touches the
        # snapshot, so a crash anywhere past this line replays the batch
        seq = self._batch_idx + 1
        self._journal_append(seq, delta)

        snap_stats = self.snap.apply(delta)

        t1 = time.perf_counter()
        with obs.span("session.plan", annotate=True):
            engine = self._choose_engine(delta)
            obs.inc(f"session.engine.{engine}")
            flight.emit("session.engine", seq=seq, engine=engine,
                        size=delta.size)
            caps = self._frontier_caps(frontier_estimate(delta,
                                                         self.snap._outdeg))
        guarded = self.guard is not None
        r_pre = self.ranks
        self._maybe_capture_start()
        with obs.span("session.solve", annotate=True):
            if engine == "sharded":
                dv0, dn0 = initial_affected_sharded(
                    self.snap.nd, self.snap.n_loc, db)
                out = distributed_dfp_pagerank(
                    self.mesh, self.snap.sg, self.ranks, dv0, dn0,
                    self.params, trace=self.trace, frontier_caps=caps,
                    health=guarded)
            elif engine == "compact":
                fn = (dfp_pagerank_compact if self.prune
                      else df_pagerank_compact)
                out = fn(self.snap, None, self.ranks, db, self.params,
                         trace=self.trace, health=guarded)
            else:
                fn = dfp_pagerank if self.prune else df_pagerank
                out = fn(self.snap, self.ranks, db, self.params,
                         trace=self.trace, frontier_caps=caps,
                         health=guarded)
            hw = 0
            if guarded:
                *rest, hw_dev = out
                out = tuple(rest)
                hw = self._apply_mass_tol(int(hw_dev), rest[0])
            (r, iters), summary = maybe_summary(out, self.trace)
            iters = int(iters)
            escalations = 0
            if guarded and hw != HEALTH_OK:
                r, iters, escalations = self._escalate(r_pre, db, hw,
                                                       r, iters,
                                                       summary=summary,
                                                       seq=seq)
            r = jax.block_until_ready(r)
        solve_s = time.perf_counter() - t1
        self._maybe_capture_stop()

        self.ranks = r
        self._batch_idx = seq
        self.history.append(BatchStats(
            batch_size=delta.size, engine=engine, iters=iters,
            ingest_s=ingest_s, snapshot=snap_stats, solve_s=solve_s,
            trace=summary, health=hw, escalations=escalations,
            quarantined=quarantined))
        self._solve_hist.add(solve_s)
        flight.emit("session.batch", seq=seq, engine=engine,
                    size=delta.size, iters=iters,
                    solve_us=round(solve_s * 1e6, 1), health=hw,
                    escalations=escalations)
        self._check_slo()
        if (self.guard is not None and self.guard.audit_every
                and self._batch_idx % self.guard.audit_every == 0):
            self._audit()
        if (self._journal is not None and self.checkpoint_every
                and not self._replaying
                and self._batch_idx % self.checkpoint_every == 0):
            self.checkpoint()
        return self.ranks

    # -- SLO + on-demand profiler capture (DESIGN.md §14) --------------------

    def solve_percentiles(self) -> dict:
        """Percentile snapshot of this session's per-batch solve latency
        (seconds): ``{count, p50_s, p95_s, p99_s, max_s}``."""
        return self._solve_hist.as_dict()

    def arm_capture(self, batches: int, log_dir: Optional[str] = None
                    ) -> None:
        """Arm ``jax.profiler`` trace capture around the next ``batches``
        applies (manual re-arm of the SLO auto-capture)."""
        self._capture_remaining = max(int(batches), 0)
        if log_dir is not None:
            self._capture_dir = log_dir

    def _capture_log_dir(self) -> str:
        if self._capture_dir is not None:
            return self._capture_dir
        if self.slo is not None and self.slo.capture_dir is not None:
            return self.slo.capture_dir
        base = self.journal_dir if self.journal_dir is not None else "."
        return os.path.join(base, "profile")

    def _maybe_capture_start(self) -> None:
        if self._capture_remaining <= 0 or self._capture_active:
            return
        log_dir = self._capture_log_dir()
        if start_profiler(log_dir):
            self._capture_active = True
            _obs().inc("slo.capture.start")
            get_flight().emit("slo.capture.start", dir=log_dir,
                              batches=self._capture_remaining)
        else:
            # profiler unavailable on this backend: disarm rather than
            # retrying (and failing) on every subsequent batch
            self._capture_remaining = 0
            _obs().inc("slo.capture.unavailable")

    def _maybe_capture_stop(self) -> None:
        if not self._capture_active:
            return
        self._capture_remaining -= 1
        if self._capture_remaining > 0:
            return
        self._capture_active = False
        stop_profiler()
        _obs().inc("slo.capture.stop")
        get_flight().emit("slo.capture.stop")

    def _check_slo(self) -> None:
        """Judge the running solve p99 against the session's SLOConfig;
        on breach bump counters, emit a flight event, and (once per
        session) auto-arm profiler capture for the next batches."""
        s = self.slo
        if s is None or self._solve_hist.count < max(int(s.min_samples), 1):
            return
        p99 = self._solve_hist.percentile(99)
        if p99 is None or p99 * 1e6 <= s.solve_p99_us:
            return
        _obs().inc("slo.breach.solve_p99")
        get_flight().emit("slo.breach", metric="solve_p99",
                          p99_us=round(p99 * 1e6, 1),
                          budget_us=s.solve_p99_us)
        if s.capture_batches > 0 and not self._slo_captured:
            self._slo_captured = True
            self.arm_capture(s.capture_batches)

    # -- guard: escalation ladder + drift audit ------------------------------

    def _apply_mass_tol(self, hw: int, r) -> int:
        """Re-judge the H_MASS_DRIFT bit under the guard's ``mass_tol``.

        The engines bake the library default (``health.MASS_TOL``) into
        their jitted health epilogue; a session-level override re-derives
        the bit from the candidate ranks host-side — one O(n) reduction,
        negligible next to the solve. A non-finite mass clears the bit
        (H_NONFINITE already covers that failure)."""
        g = self.guard
        if g is None or g.mass_tol == MASS_TOL:
            return hw
        drift = abs(float(jnp.sum(self._flatten(jnp.asarray(r)))) - 1.0)
        if np.isfinite(drift) and drift > g.mass_tol:
            return hw | H_MASS_DRIFT
        return hw & ~H_MASS_DRIFT

    def _recovery_params(self) -> PRParams:
        if self.guard.recovery_params is not None:
            return self.guard.recovery_params
        # the session's params with the full default iteration budget
        # restored: a chaos-starved max_iter=1 session must still recover
        # with a real solve
        return self.params._replace(max_iter=PRParams().max_iter)

    def _escalate(self, r_pre, db, hw: int, r, iters: int,
                  summary: Optional[dict] = None,
                  seq: Optional[int] = None):
        """Walk the recovery ladder after an unhealthy solve.

        Rung 1 retries the batch with the *recovery* params (full iteration
        budget) from the pre-solve ranks — dense DF-P on single-device
        sessions (the compact engine's own superset), the sharded engine in
        mesh mode. Rung 2 resolves from scratch: a static solve from
        ``init_ranks``, which ignores every piece of possibly-poisoned rank
        state. Each rung's result is accepted only if ITS health word is
        clean; ``retry_budget`` bounds the rungs walked. Returns
        ``(ranks, iters, rungs_walked)`` — on an exhausted budget, the last
        attempt's result (counted in ``guard.escalate.exhausted``) plus a
        post-mortem bundle under `_postmortem_dir` (DESIGN.md §14)."""
        obs = _obs()
        flight = get_flight()
        obs.inc("guard.unhealthy")
        for name in health_flags(hw):
            obs.inc(f"guard.health.{name}")
        rp = self._recovery_params()
        rungs = (["sharded"] if self.mesh is not None else ["dense"])
        rungs.append("recompute")
        walked = 0
        hw2 = hw
        for rung in rungs[:max(int(self.guard.retry_budget), 0)]:
            walked += 1
            obs.inc(f"guard.escalate.{rung}")
            flight.emit("guard.escalate", rung=rung, seq=seq, health=hw)
            if rung == "dense":
                fn = dfp_pagerank if self.prune else df_pagerank
                r, it, hw2 = fn(self.snap, r_pre, db, rp, health=True)
            elif rung == "sharded":
                dv0, dn0 = initial_affected_sharded(
                    self.snap.nd, self.snap.n_loc, db)
                r, it, hw2 = distributed_dfp_pagerank(
                    self.mesh, self.snap.sg, r_pre, dv0, dn0, rp,
                    health=True)
            else:
                r, it, hw2 = self._static_solve(params=rp, health=True)
            iters, hw2 = int(it), self._apply_mass_tol(int(hw2), r)
            if hw2 == HEALTH_OK:
                obs.inc("guard.escalate.success")
                return r, iters, walked
        obs.inc("guard.escalate.exhausted")
        flight.emit("guard.escalate.exhausted", seq=seq, health=int(hw2))
        pdir = self._postmortem_dir()
        if pdir is not None:
            write_bundle(pdir, reason="escalation_exhausted",
                         health=int(hw2), trace=summary,
                         quarantine=self._last_quarantine,
                         journal_seq=seq,
                         extra={"first_health": int(hw),
                                "rungs_walked": walked,
                                "slo": self._solve_hist.as_dict()})
        return r, iters, walked

    def _postmortem_dir(self) -> Optional[str]:
        """Where failure bundles land: ``GuardConfig.postmortem_dir``, else
        the journal directory, else ``$REPRO_POSTMORTEM_DIR``; None disables
        bundle writing (no sensible destination)."""
        if self.guard is not None and self.guard.postmortem_dir is not None:
            return self.guard.postmortem_dir
        if self.journal_dir is not None:
            return self.journal_dir
        return os.environ.get("REPRO_POSTMORTEM_DIR") or None

    def _audit(self) -> None:
        """Every-K-batches drift audit: chained ranks vs a from-scratch
        static solve on the current snapshot. Breaching ``audit_tol`` (L1)
        adopts the static solve — the bounded-staleness backstop chained
        approximation error cannot creep past. The reference runs with the
        *recovery* params: the audit exists to catch degraded session state,
        so its anchor must not inherit a degraded iteration budget."""
        obs = _obs()
        obs.inc("guard.audit.runs")
        r_ref = self._static_solve(params=self._recovery_params())[0]
        l1 = float(jnp.sum(jnp.abs(self.flat_ranks()
                                   - self._flatten(r_ref))))
        resync = l1 > self.guard.audit_tol
        get_flight().emit("guard.audit", seq=self._batch_idx, l1=l1,
                          resync=resync)
        if resync:
            obs.inc("guard.audit.resync")
            self.ranks = r_ref

    # -- guard: journal + checkpoint / restore -------------------------------

    def _journal_append(self, seq: int, delta: Delta) -> None:
        if self._journal is None or self._replaying:
            return
        self._journal.append(JournalRecord(
            seq=seq, n=delta.n,
            del_src=np.asarray(delta.del_src, np.int32),
            del_dst=np.asarray(delta.del_dst, np.int32),
            ins_src=np.asarray(delta.ins_src, np.int32),
            ins_dst=np.asarray(delta.ins_dst, np.int32)))

    def _session_config(self) -> dict:
        g = self.guard
        gd = None
        if g is not None:
            gd = dataclasses.asdict(g)
            gd["recovery_params"] = (list(g.recovery_params)
                                     if g.recovery_params is not None
                                     else None)
        slo = (dataclasses.asdict(self.slo) if self.slo is not None
               else None)
        if slo is not None and slo["solve_p99_us"] == float("inf"):
            slo["solve_p99_us"] = None  # JSON has no inf
        return dict(n=self.n, params=list(self.params),
                    d_p=self._d_p, tile=self._tile, engine=self.engine,
                    prune=self.prune,
                    compact_threshold=self.compact_threshold,
                    trace=self.trace, mesh=self.mesh is not None,
                    checkpoint_every=self.checkpoint_every,
                    guard=gd, slo=slo, snap_kw=dict(self._snap_kw))

    def checkpoint(self) -> str:
        """Write a full-state checkpoint (ranks + snapshot mirrors + config)
        under ``journal_dir``, valid after batch ``_batch_idx``. Atomic via
        train/checkpoint.py's manifest rename."""
        if self.journal_dir is None:
            raise ValueError("session has no journal_dir")
        arrays, snap_extra = self.snap.state_dict()
        arrays = dict(arrays)
        arrays["ranks"] = np.asarray(self.ranks)
        extra = {"snap": snap_extra, "session": self._session_config(),
                 "frontier_caps": _caps_to_json(self._caps)}
        path = save_session_checkpoint(self.journal_dir, self._batch_idx,
                                       arrays, extra)
        get_flight().emit("guard.checkpoint", seq=self._batch_idx,
                          path=path)
        return path

    @classmethod
    def restore(cls, directory: str, mesh=None) -> "StreamSession":
        """Rebuild a session from ``directory``: newest checkpoint + replay
        of every journaled delta with a later sequence number.

        Bit-identical to the uninterrupted session: the checkpoint restores
        the snapshot mirrors exactly (free-list order included — it steers
        slot placement and therefore floating-point summation order), the
        frontier-caps high-water mark (overflow→dense fallback changes
        summation order too), and the rank vector; the replay then re-runs
        the deterministic per-batch lifecycle. A torn journal tail (crash
        mid-append) is detected by ``DeltaJournal.scan`` and dropped — at
        most the batch being written when the process died.

        ``mesh`` must be re-supplied for sharded sessions (meshes don't
        serialize)."""
        try:
            return cls._restore_impl(directory, mesh)
        except Exception as e:
            # a failed recovery is the post-mortem case par excellence:
            # bundle the flight tail + registry before re-raising (the
            # write is best-effort and never masks the original error)
            write_bundle(directory, reason="restore_failed",
                         extra={"error": repr(e)})
            raise

    @classmethod
    def _restore_impl(cls, directory: str, mesh) -> "StreamSession":
        arrays, extra, step = load_session_checkpoint(directory)
        cfg = extra["session"]
        if cfg["mesh"] and mesh is None:
            raise ValueError("checkpoint is from a mesh session: pass mesh=")
        if not cfg["mesh"] and mesh is not None:
            raise ValueError("checkpoint is single-device: mesh= given")
        params = PRParams(*cfg["params"])
        guard = None
        if cfg.get("guard") is not None:
            gd = dict(cfg["guard"])
            if gd.get("recovery_params") is not None:
                gd["recovery_params"] = PRParams(*gd["recovery_params"])
            guard = GuardConfig(**gd)
        slo = None
        if cfg.get("slo") is not None:
            sd = dict(cfg["slo"])
            if sd.get("solve_p99_us") is None:
                sd["solve_p99_us"] = float("inf")
            slo = SLOConfig(**sd)
        g = graph_from_sorted_keys(
            int(cfg["n"]), np.ascontiguousarray(arrays["keys"]))
        sess = cls(g, params=params, d_p=cfg["d_p"], tile=cfg["tile"],
                   engine=cfg["engine"], prune=cfg["prune"],
                   compact_threshold=cfg["compact_threshold"], mesh=mesh,
                   trace=cfg["trace"], guard=guard, slo=slo,
                   journal_dir=directory,
                   checkpoint_every=cfg["checkpoint_every"],
                   **cfg.get("snap_kw", {}))
        sess.snap.load_state(arrays, extra["snap"])
        sess.ranks = jnp.asarray(arrays["ranks"])
        sess._batch_idx = step
        sess._caps = _caps_from_json(extra.get("frontier_caps"))
        records, _ = DeltaJournal.scan(journal_path(directory))
        sess._replaying = True
        replayed = 0
        try:
            for rec in records:
                if rec.seq <= step:
                    continue
                sess.apply(Delta(
                    n=rec.n, del_src=rec.del_src.astype(np.int64),
                    del_dst=rec.del_dst.astype(np.int64),
                    ins_src=rec.ins_src.astype(np.int64),
                    ins_dst=rec.ins_dst.astype(np.int64)))
                sess._batch_idx = rec.seq
                replayed += 1
        finally:
            sess._replaying = False
        _obs().inc("guard.restores")
        get_flight().emit("guard.restore", step=step, replayed=replayed)
        return sess

    def close(self) -> None:
        """Close the journal file handle (restore() reopens on demand)."""
        if self._journal is not None:
            self._journal.close()
            self._journal = None

    # -- engine/caps plumbing ------------------------------------------------

    def _frontier_caps(self, est: int):
        """Frontier capacity plan for this batch — the running elementwise
        max over the stream (never-shrink), so capacities only grow at a
        new high-water mark and the engine's jit cache survives every batch
        below it. `frontier.caps_growth` counts the (re-tracing) growth
        events."""
        new = (sharded_frontier_caps(self.snap.sg, est)
               if self.mesh is not None else caps_for(self.snap.dg, est))
        merged = merge_caps(self._caps, new)
        if self._caps is not None and merged != self._caps:
            _obs().inc("frontier.caps_growth")
        self._caps = merged
        return merged

    def _choose_engine(self, delta: Delta) -> str:
        if self.mesh is not None:
            return "sharded"
        if self.engine != "auto":
            return self.engine
        return choose_engine(delta, self.snap._outdeg, self.n,
                             self.compact_threshold)

    def _static_solve(self, params: Optional[PRParams] = None,
                      health: bool = False):
        """From-scratch static solve on the current snapshot, in the
        session's native rank layout (dense [n], or stacked [nd, n_loc] in
        mesh mode). The single place the recipe lives: init vector, engine
        choice and params stay in lock-step across __init__ /
        static_reference / recompute / the ladder's recompute rung."""
        params = params if params is not None else self.params
        if self.mesh is None:
            return static_pagerank(self.snap.dg, init_ranks(self.n),
                                   params, health=health)
        r0 = jnp.full((self.snap.nd, self.snap.n_loc), 1.0 / self.n,
                      init_ranks(1).dtype, device=self.snap.sharding)
        return distributed_static_pagerank(self.mesh, self.snap.sg, r0,
                                           params, health=health)

    def _flatten(self, r: jnp.ndarray) -> jnp.ndarray:
        if self.mesh is None:
            return r
        # replicate first: slicing off the padding of a vector that is
        # still split over the mesh is ambiguous under explicit sharding
        r = jax.device_put(r, NamedSharding(self.mesh, P()))
        return jnp.reshape(r, (-1,))[:self.n]

    def flat_ranks(self) -> jnp.ndarray:
        """Current ranks as a dense [n] vector regardless of session mode."""
        return self._flatten(self.ranks)

    def static_reference(self) -> jnp.ndarray:
        """From-scratch static solve on the *current* snapshot, dense [n] —
        the verification anchor for the chained DF-P ranks. Does not touch
        session state."""
        return self._flatten(self._static_solve()[0])

    def topk(self, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k vertices by rank: (ids [k], ranks [k]), descending."""
        vals, ids = jax.lax.top_k(self.flat_ranks(), k)
        return np.asarray(ids), np.asarray(vals)

    def recompute(self) -> jnp.ndarray:
        """Full static recomputation on the current snapshot (re-sync /
        verification anchor); resets the session's rank state. Appends an
        ``engine="recompute"`` record to ``history`` and bumps the
        ``session.recompute`` counter, so resyncs are visible in the same
        accounting stream as regular batches. Its ``solve_s`` ends when the
        ranks are ready, inside the annotated ``session.recompute`` span."""
        obs = _obs()
        with obs.span("session.recompute", annotate=True):
            t0 = time.perf_counter()
            self.ranks, iters = self._static_solve()
            self.ranks = jax.block_until_ready(self.ranks)
            solve_s = time.perf_counter() - t0
        obs.inc("session.recompute")
        self.history.append(BatchStats(
            batch_size=0, engine="recompute", iters=int(iters),
            ingest_s=0.0, snapshot=SnapshotStats(), solve_s=solve_s))
        return self.ranks
