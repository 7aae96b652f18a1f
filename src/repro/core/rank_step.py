"""The single shared ``updateRanks`` math (paper Alg. 3, Eq. 1 / Eq. 2).

The paper is explicit that one ``updateRanks()`` serves Static, ND, DT, DF
and DF-P alike ("disable the affected flags to utilize the same function for
Static PageRank"); this module is that single source of truth for the repo.
Every engine — dense (`core/pagerank.py` / `core/dynamic.py`), compact
(`core/compact.py`), 1-D sharded (`core/distributed.py`), 2-D sharded
(`core/distributed2d.py`) and the fused Pallas kernel
(`kernels/pr_update.py`) — imports the formulas from here and supplies only
its own *pull* (how the in-neighbor sums `s` are produced) and its own
plumbing (all-gather / psum-scatter / frontier compaction) around them.

The math itself, per vertex v with pulled contribution s = Σ R[u]/|out(u)|:

  Eq. 1 (plain):        R'[v] = (1-α)/N + α·s
  Eq. 2 (closed form):  R'[v] = ((1-α)/N + α·(s - R[v]/d_v)) / (1 - α/d_v)
                        — absorbs the guaranteed self-loop analytically.
  prune:   affected'[v] = affected[v] ∧ ¬(Δr/max(R,R') ≤ τ_p)
  δ_N:     rel > τ_f   (rel is 0 for unaffected vertices: R' == R there)
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["teleport", "rank_value", "relative_change", "rank_step"]


def teleport(alpha: float, n_norm: int, dtype) -> jnp.ndarray:
    """The (1-α)/N teleport constant, canonicalized to the rank dtype.

    `n_norm` is the number of *real* vertices — sharded layouts pad |V| and
    must normalize by the true count, not the padded one.
    """
    return jnp.asarray((1.0 - alpha) / n_norm, dtype)


def rank_value(s: jnp.ndarray, r: jnp.ndarray, d: jnp.ndarray, *,
               alpha: float, c0: jnp.ndarray,
               closed_form: bool) -> jnp.ndarray:
    """Candidate new rank from the pulled in-neighbor sum `s`.

    `d` is the out-degree (≥ 1: self-loops are guaranteed), already in the
    rank dtype. `closed_form` selects Eq. 2 over Eq. 1. Shapes are whatever
    the caller gathered — dense [n], a compacted [K], or a per-shard slice.
    """
    if closed_form:
        return (c0 + alpha * (s - r / d)) / (1.0 - alpha / d)
    return c0 + alpha * s


def relative_change(r_new: jnp.ndarray, r_old: jnp.ndarray,
                    floor: Optional[float] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(|Δr|, |Δr| / max(r_new, r_old)) — the paper's pruning/frontier metric.

    `floor` guards the denominator for callers whose gathered lanes may hold
    zeros (the compact engine's dead slots); dense ranks are strictly
    positive so the default skips the extra op.
    """
    dr = jnp.abs(r_new - r_old)
    den = jnp.maximum(r_new, r_old)
    if floor is not None:
        den = jnp.maximum(den, floor)
    return dr, dr / den


def rank_step(s: jnp.ndarray, r: jnp.ndarray, affected: jnp.ndarray,
              out_deg: jnp.ndarray, *, alpha: float, n_norm: int,
              tau_f: float, tau_p: float, prune: bool, closed_form: bool,
              track_frontier: bool
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One dense-shaped synchronous rank sweep given the pulled sums `s`.

    Returns (r_new, affected', delta_N, linf_delta). Works unchanged on a
    full [n] vector or on one shard's [n_loc] slice (pass the shard's
    affected mask already AND-ed with its validity mask, and the global
    vertex count as `n_norm`); `linf_delta` is then the *local* norm and the
    caller owns the cross-device `pmax`. Its device ops carry the stage
    names ``pr.update`` (the epilogue) and ``pr.converge`` (the L∞).
    """
    with jax.named_scope("pr.update"):
        dt = r.dtype
        d = out_deg.astype(dt)
        rv = rank_value(s, r, d, alpha=alpha,
                        c0=teleport(alpha, n_norm, dt),
                        closed_form=closed_form)
        r_new = jnp.where(affected, rv, r)
        dr, rel = relative_change(r_new, r)
        if prune:
            affected = affected & ~(rel <= tau_p)
        if track_frontier:
            delta_n = rel > tau_f
        else:
            delta_n = jnp.zeros(r.shape, dtype=jnp.bool_)
    with jax.named_scope("pr.converge"):
        linf = jnp.max(dr)
    return r_new, affected, delta_n, linf
