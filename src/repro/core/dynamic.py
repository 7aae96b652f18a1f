"""Dynamic PageRank drivers: ND, DT, DF, DF-P (paper Alg. 2).

All five approaches share `update_ranks` (paper Alg. 3) and the convergence
loop shape of Alg. 1; they differ only in (a) rank initialization, (b) the
affected mask, and (c) frontier expansion/pruning — exactly the paper's
decomposition. Every driver is a single jitted `lax.while_loop`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .frontier import (FS_ACTIVE_ROWS, FS_ACTIVE_TILES, FS_COMPACT,
                       FS_EXPAND_WORK, FS_ITERS, FS_NB, FS_OVERFLOW, FS_PULL,
                       FS_PUSH, active_frontier, expand_affected,
                       expand_frontier, fstats_init, initial_affected,
                       publish_fstats, reach_affected, update_ranks_active)
from .pagerank import DeviceGraph, PRParams, as_device_graph, update_ranks
from ..guard.health import MASS_TOL, health_word, rank_mass
from ..obs.spans import get_registry
from ..obs.trace import trace_init, trace_record

__all__ = ["DeviceBatch", "batch_to_device", "nd_pagerank", "dt_pagerank",
           "df_pagerank", "dfp_pagerank"]


class DeviceBatch(NamedTuple):
    """Batch update staged on device, padded with id == n ("drop" scatters)."""
    del_src: jnp.ndarray
    del_dst: jnp.ndarray
    ins_src: jnp.ndarray
    ins_dst: jnp.ndarray


def batch_to_device(batch, n: int, pad_to: int | None = None) -> DeviceBatch:
    def pad(a, cap):
        a = np.asarray(a, np.int32)
        if cap is None or a.shape[0] == cap:
            return jnp.asarray(a)
        out = np.full(cap, n, np.int32)
        out[:a.shape[0]] = a
        return jnp.asarray(out)
    return DeviceBatch(pad(batch.del_src, pad_to), pad(batch.del_dst, pad_to),
                       pad(batch.ins_src, pad_to), pad(batch.ins_dst, pad_to))


def solve_health(delta, iters, mass, params: PRParams,
                 mass_tol: float = MASS_TOL, max_iter=None):
    """Health word of a finished solve loop (guard.health), from the final
    L∞ delta / iteration count / rank mass. A +inf delta is a *signal*
    (compact-engine overflow, distributed delta_every skip), not a number —
    clamp it finite so it reads as H_MAX_ITER, not H_NONFINITE; NaN (real
    poisoning) passes through untouched. ``max_iter`` (a device scalar may
    be passed) replaces ``params.max_iter`` as the loop's budget."""
    with jax.named_scope("pr.converge"):
        dt = jnp.asarray(delta).dtype
        delta = jnp.where(jnp.isposinf(delta), jnp.finfo(dt).max, delta)
        return health_word(delta, iters, mass, tau=params.tau,
                           max_iter=(params.max_iter if max_iter is None
                                     else max_iter), mass_tol=mass_tol)


def _loop(dg: DeviceGraph, r0: jnp.ndarray, dv0: jnp.ndarray,
          dn0: jnp.ndarray, params: PRParams, *, expand: bool, prune: bool,
          closed_form: bool, pull_sum_fn=None, tb=None, i_off=0,
          fwd=None, caps=None, fs0=None, health: bool = False,
          mass_tol: float = MASS_TOL):
    """Shared Alg. 2 loop. When `expand` is False the affected set is frozen
    (ND/DT); δ_N is then never produced (track_frontier=False).

    `caps` (core.frontier.FrontierCaps, static) switches on the compacted
    execution path: each iteration compacts δ_V into active gather lists and
    runs `update_ranks_active` (edge work O(frontier·degree)); a truncated
    list falls back to the dense full sweep *for that iteration only*
    (lax.cond — no exit, no recompile). With `fwd` (the forward hybrid
    layout) expansion goes push-style through the compacted δ_N worklist
    instead of the dense pull, same per-iteration fallback. Frontier-size
    reductions feed only the device-side `fs` accumulator (returned last)
    and the optional trace buffer — the untraced, uncompacted hot loop
    computes no dense reductions beyond the L∞ it converges on.

    `tb` (obs.trace.TraceBuffer) switches on iteration telemetry: per-sweep
    L∞, frontier size, δ_N and pruned counts recorded at `i_off + i` — the
    offset lets the compact engine's dense fallback append to the buffer its
    compact phase started. The rank math never reads the trace. The loop's
    budget is the rest of `params.max_iter` after those `i_off` sweeps; an
    `i_off` passed as a device scalar keeps one program for every offset."""
    budget = params.max_iter - i_off

    def body(state):
        r, dv, dn, _, i, tb_, fs = state
        if expand:
            # paper line 16: expansion of the *previous* iteration's frontier,
            # performed only because convergence was not reached (cond passed).
            if caps is not None and fwd is not None:
                dv, est = jax.lax.cond(
                    i > 0,
                    lambda: expand_frontier(dg, fwd, dv, dn, caps),
                    lambda: (dv, jnp.zeros((3,), jnp.int32)))
                fs = fs.at[FS_EXPAND_WORK].add(est[0]) \
                       .at[FS_PUSH].add(est[1]).at[FS_PULL].add(est[2])
            else:
                dv = jax.lax.cond(i > 0,
                                  lambda: expand_affected(dg, dv, dn),
                                  lambda: dv)
        if caps is not None:
            af = active_frontier(dg.buckets, dg.hi_ids, dg.hi_rowmap, dv,
                                 caps)
            kw = dict(alpha=params.alpha, tau_f=params.tau_f,
                      tau_p=params.tau_p, prune=prune,
                      closed_form=closed_form, track_frontier=expand)
            r_new, dv_new, dn_new, delta = jax.lax.cond(
                af.overflow,
                lambda: update_ranks(dg, r, dv, pull_sum_fn=pull_sum_fn,
                                     **kw),
                lambda: update_ranks_active(dg, r, dv, af, **kw))
            ok = (~af.overflow).astype(jnp.int32)
            fs = fs.at[FS_ITERS].add(1).at[FS_COMPACT].add(ok) \
                   .at[FS_OVERFLOW].add(1 - ok) \
                   .at[FS_ACTIVE_ROWS].add(af.n_rows * ok) \
                   .at[FS_ACTIVE_TILES].add(af.n_tiles * ok)
            if len(dg.buckets):
                fs = fs.at[FS_NB:].add(af.bucket_counts * ok)
        else:
            r_new, dv_new, dn_new, delta = update_ranks(
                dg, r, dv, alpha=params.alpha, tau_f=params.tau_f,
                tau_p=params.tau_p, prune=prune, closed_form=closed_form,
                track_frontier=expand, pull_sum_fn=pull_sum_fn)
        if tb is not None:
            frontier = jnp.sum(dv)
            pruned = frontier - jnp.sum(dv_new) if prune else 0
            tb_ = trace_record(tb_, i_off + i, linf=delta, frontier=frontier,
                               delta_n=jnp.sum(dn_new) if expand else 0,
                               pruned=pruned)
        return r_new, dv_new, dn_new, delta, i + 1, tb_, fs

    def cond(state):
        delta, i = state[3], state[4]
        return (delta > params.tau) & (i < budget)

    fs_init = fs0 if fs0 is not None else fstats_init(len(dg.buckets))
    init = (r0, dv0, dn0, jnp.asarray(jnp.inf, r0.dtype),
            jnp.asarray(0, jnp.int32),
            jnp.asarray(0, jnp.int32) if tb is None else tb, fs_init)
    r, _, _, delta, iters, tb_out, fs = jax.lax.while_loop(cond, body, init)
    # output shape contract: (r, iters)[, tb][, health][, fs-last] — fs
    # stays last so `_publish` can pop it blind; health (guard.health word,
    # one fused Σ R reduction over the final ranks) rides just before it.
    out = [r, iters]
    if tb is not None:
        out.append(tb_out)
    if health:
        # iters vs the loop's budget, NOT i_off+iters: a dense finish runs
        # with the *remaining* budget, so its own exhaustion is exactly the
        # total budget's exhaustion
        out.append(solve_health(delta, iters, rank_mass(r), params,
                                mass_tol, max_iter=budget))
    if caps is not None:
        out.append(fs)
    return tuple(out)


def nd_pagerank(dg, r_prev: jnp.ndarray, params: PRParams = PRParams(),
                pull_sum_fn=None, trace: bool = False, health: bool = False):
    """Naive-dynamic: previous ranks as the initial guess, all vertices on.

    All four dynamic drivers accept a DeviceGraph or a pre-staged snapshot
    (anything with a `.dg` attribute, e.g. repro.stream.DeviceSnapshot),
    and a ``trace=True`` flag returning (r, iters, obs.trace.TraceBuffer)
    with identical ranks/iters to the untraced call. ``health=True``
    additionally appends the solve's guard.health word (int32 bitmask,
    device-side) after the trace buffer.

    Every driver dispatches under an annotated ``solve.<engine>`` span, so
    its kernels land on the device timeline whenever a profiler trace is
    live (ISSUE 10; the span times host dispatch only).
    """
    with get_registry().span("solve.nd", annotate=True):
        return _nd_pagerank(as_device_graph(dg), r_prev, params, pull_sum_fn,
                            trace, health)


@functools.partial(jax.jit, static_argnames=("params", "pull_sum_fn",
                                             "trace", "health"))
def _nd_pagerank(dg: DeviceGraph, r_prev: jnp.ndarray,
                 params: PRParams = PRParams(), pull_sum_fn=None,
                 trace: bool = False, health: bool = False):
    n = dg.n
    on = jnp.ones((n,), jnp.bool_)
    off = jnp.zeros((n,), jnp.bool_)
    tb = trace_init(params.max_iter, r_prev.dtype, "nd") if trace else None
    return _loop(dg, r_prev, on, off, params, expand=False, prune=False,
                 closed_form=False, pull_sum_fn=pull_sum_fn, tb=tb,
                 health=health)


def dt_pagerank(dg, dg_prev, r_prev: jnp.ndarray, batch: DeviceBatch,
                params: PRParams = PRParams(), pull_sum_fn=None,
                trace: bool = False, health: bool = False):
    """Dynamic Traversal (Desikan et al.): mark everything reachable from the
    updated vertices in G^{t-1} ∪ G^t, then iterate on that frozen set."""
    with get_registry().span("solve.dt", annotate=True):
        return _dt_pagerank(as_device_graph(dg), as_device_graph(dg_prev),
                            r_prev, batch, params, pull_sum_fn, trace, health)


@functools.partial(jax.jit, static_argnames=("params", "pull_sum_fn",
                                             "trace", "health"))
def _dt_pagerank(dg: DeviceGraph, dg_prev: DeviceGraph, r_prev: jnp.ndarray,
                 batch: DeviceBatch, params: PRParams = PRParams(),
                 pull_sum_fn=None, trace: bool = False,
                 health: bool = False):
    n = dg.n
    seeds = jnp.zeros((n,), jnp.bool_)
    seeds = seeds.at[batch.del_src].set(True, mode="drop")
    seeds = seeds.at[batch.del_dst].set(True, mode="drop")
    seeds = seeds.at[batch.ins_src].set(True, mode="drop")
    seeds = seeds.at[batch.ins_dst].set(True, mode="drop")
    affected = reach_affected(dg, seeds) | reach_affected(dg_prev, seeds)
    off = jnp.zeros((n,), jnp.bool_)
    tb = trace_init(params.max_iter, r_prev.dtype, "dt") if trace else None
    return _loop(dg, r_prev, affected, off, params, expand=False, prune=False,
                 closed_form=False, pull_sum_fn=pull_sum_fn, tb=tb,
                 health=health)


def _df_like(dg: DeviceGraph, r_prev: jnp.ndarray, batch: DeviceBatch,
             params: PRParams, *, prune: bool, pull_sum_fn=None,
             trace: bool = False, fwd=None, caps=None,
             health: bool = False):
    n = dg.n
    dv, dn = initial_affected(n, batch.del_src, batch.del_dst, batch.ins_src)
    fs0 = None
    if caps is not None:
        # this Python body runs only when the jitted driver (re)traces —
        # the counter is the recompile telemetry the streamed-session
        # zero-recompile acceptance reads (bench_frontier.py)
        get_registry().inc("frontier.retrace")
        fs0 = fstats_init(len(dg.buckets))
    if caps is not None and fwd is not None:
        # paper line 9: initial expansion, via the compacted out-edge walk
        dv, est = expand_frontier(dg, fwd, dv, dn, caps)
        fs0 = fs0.at[FS_EXPAND_WORK].add(est[0]) \
                 .at[FS_PUSH].add(est[1]).at[FS_PULL].add(est[2])
    else:
        dv = expand_affected(dg, dv, dn)  # paper line 9: initial expansion
    dn0 = jnp.zeros((n,), jnp.bool_)
    tb = trace_init(params.max_iter, r_prev.dtype,
                    "dfp" if prune else "df") if trace else None
    return _loop(dg, r_prev, dv, dn0, params, expand=True, prune=prune,
                 closed_form=prune, pull_sum_fn=pull_sum_fn, tb=tb,
                 fwd=fwd, caps=caps, fs0=fs0, health=health)


def _resolve_frontier(dg, fwd, frontier_caps):
    """(fwd DeviceGraph|None, caps) for the compacted path. Snapshots carry
    their own forward layout (`.fwd_dg`); with caps but no forward layout
    the loop still compacts the rank pull and keeps the dense expansion."""
    if frontier_caps is None:
        return None, None
    if fwd is None:
        fwd = getattr(dg, "fwd_dg", None)
    return (as_device_graph(fwd) if fwd is not None else None), frontier_caps


def _publish(out, caps, trace):
    """Pop the fstats vector off a compacted driver's output, fold it into
    the host registry, and return the legacy (r, iters[, tb]) shape."""
    if caps is None:
        return out
    *rest, fs = out
    publish_fstats(fs)
    return tuple(rest)


def df_pagerank(dg, r_prev: jnp.ndarray, batch: DeviceBatch,
                params: PRParams = PRParams(), pull_sum_fn=None,
                trace: bool = False, fwd=None, frontier_caps=None,
                health: bool = False):
    """Dynamic Frontier: incremental expansion, no pruning (Eq. 1 update).

    `frontier_caps` (core.frontier.FrontierCaps / caps_for) switches on the
    compacted execution path — active gather lists + push expansion, full
    sweep only on capacity overflow; identical results either way."""
    fwdd, caps = _resolve_frontier(dg, fwd, frontier_caps)
    with get_registry().span("solve.df", annotate=True):
        out = _df_pagerank(as_device_graph(dg), fwdd, r_prev, batch, params,
                           pull_sum_fn, trace, caps, health)
    return _publish(out, caps, trace)


@functools.partial(jax.jit, static_argnames=("params", "pull_sum_fn",
                                             "trace", "caps", "health"))
def _df_pagerank(dg: DeviceGraph, fwd, r_prev: jnp.ndarray,
                 batch: DeviceBatch, params: PRParams = PRParams(),
                 pull_sum_fn=None, trace: bool = False, caps=None,
                 health: bool = False):
    return _df_like(dg, r_prev, batch, params, prune=False,
                    pull_sum_fn=pull_sum_fn, trace=trace, fwd=fwd, caps=caps,
                    health=health)


def dfp_pagerank(dg, r_prev: jnp.ndarray, batch: DeviceBatch,
                 params: PRParams = PRParams(), pull_sum_fn=None,
                 trace: bool = False, fwd=None, frontier_caps=None,
                 health: bool = False):
    """Dynamic Frontier with Pruning: expansion + pruning, closed form Eq. 2.

    See `df_pagerank` for the `frontier_caps` compacted path."""
    fwdd, caps = _resolve_frontier(dg, fwd, frontier_caps)
    with get_registry().span("solve.dfp", annotate=True):
        out = _dfp_pagerank(as_device_graph(dg), fwdd, r_prev, batch, params,
                            pull_sum_fn, trace, caps, health)
    return _publish(out, caps, trace)


@functools.partial(jax.jit, static_argnames=("params", "pull_sum_fn",
                                             "trace", "caps", "health"))
def _dfp_pagerank(dg: DeviceGraph, fwd, r_prev: jnp.ndarray,
                  batch: DeviceBatch, params: PRParams = PRParams(),
                  pull_sum_fn=None, trace: bool = False, caps=None,
                  health: bool = False):
    return _df_like(dg, r_prev, batch, params, prune=True,
                    pull_sum_fn=pull_sum_fn, trace=trace, fwd=fwd, caps=caps,
                    health=health)
