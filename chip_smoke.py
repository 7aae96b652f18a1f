#!/usr/bin/env python3
"""Chip smoke test: drive the streaming DF-P session once on a TPU.

    python chip_smoke.py                  # one chip: phases a-d
    python chip_smoke.py --chips 4        # four chips: phases a and e only
    python chip_smoke.py --tiny [--chips 4]   # CPU rehearsal at toy sizes

Phases (each must pass):
  a. device     — platform, device kind and count; anything but a TPU fails.
  b. oracle     — `static_pagerank` on the device vs the float64 NumPy
                  reference (`numpy_pagerank`) on a ~1.5e6-edge graph.
  c. served     — `StreamSession(g, guard=GuardConfig())` on a power-law
                  graph (paper §5.1.3 class), 5 random 80/20 batches of
                  1e-4·|E|, then the chained ranks vs a from-scratch static
                  solve on the updated graph.
  d. kernel     — one batch through `scatter_impl="pallas"` (the Mosaic
                  `stream_scatter` kernel); its device layout must equal the
                  `jnp` scatter path's.
  e. (--chips 4) the sharded session over a 4-device mesh vs a
                  from-scratch single-device static solve on the updated
                  graph; bytes per device and steady-state compiles.

The last line of stdout is ``{"ok": true, "device": {...}}`` only when every
phase passed on a TPU at the stated sizes. The timings printed on earlier
lines are smoke facts, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# Phase b tolerance. Both sides run the same float64 power iteration for the
# same number of sweeps, so they differ only by summation order: O(1e-16)
# relative per entry, ~1e-13 in L1 over unit mass. Arithmetic that lost
# float64 precision on the chip (float32-level: eps 6e-8 over unit mass)
# would show ~1e-7. 1e-10 sits three orders from each.
ORACLE_L1_TOL = 1e-10
# Phase c/e tolerance: the bound StreamSession's default frontier thresholds
# (tau_f = tau_p = 1e-9) are chosen to keep every chained batch within.
SESSION_L1_TOL = 1e-8
# Phase c/e size: log2 of the edges drawn, |V| = |E| / 16. Time, not
# memory, bounds it on one chip: at 2^25 (1.18e7 unique edges) phases b and
# c passed on a v5e in about 1190 s and the 1300 s call was cut in phase d,
# past the 1200 s this script is allowed; one halving, 2^24, is the size
# run. Memory would bind at 2^26 (PERF.md §5). Four chips run 2^22, a
# quarter of the pull layout on each.
LOG_EDGES_1 = 24
LOG_EDGES_4 = 22
N_BATCHES = 5
BATCH_FRAC = 1e-4


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-session phase (e)")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes on any backend (rehearsal; never ok)")
    return ap.parse_args()


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


class CompileCounter:
    """Counts programs lowered (one per new jit signature) and seconds spent
    in backend compiles, from JAX's own monitoring events."""
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.names: list = []
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.LOWER:
            self.names.append(str(kw.get("fun_name", "?")))
        elif event == self.COMPILE:
            self.compile_s += duration


def peak_bytes(dev):
    st = dev.memory_stats()
    return None if st is None else st.get("peak_bytes_in_use")


def main() -> int:
    args = parse_args()
    if args.tiny and args.chips == 4:
        # rehearsal on the CPU: four virtual host devices (no effect on a TPU)
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import jax
        from repro.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", True)
    use_compile_cache()
    counter = CompileCounter(jax)

    # -- a. device ----------------------------------------------------------
    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    print(f"[a] device platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    on_tpu = d0.platform == "tpu"
    if not on_tpu and not args.tiny:
        fail(f"no TPU: JAX sees platform {d0.platform!r} "
             "(use --tiny to rehearse the checks off-chip)")
    if args.chips > len(devs):
        fail(f"--chips {args.chips} but JAX sees {len(devs)} device(s)")

    log_e = (16 if args.tiny else
             LOG_EDGES_4 if args.chips == 4 else LOG_EDGES_1)
    try:
        if args.chips == 4:
            phase_sharded(jax, args, log_e, counter)
        else:
            phase_oracle(jax, args)
            phase_served(jax, args, log_e, counter)
            phase_kernel(jax, args)
    except Exception:
        traceback.print_exc()
        fail("a phase raised")

    if args.tiny or not on_tpu:
        print(f"every check passed, but not a chip result: platform="
              f"{d0.platform}, tiny={args.tiny}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def medium_graph(args):
    from repro.core import powerlaw_graph
    ln, lm = (10, 14) if args.tiny else (18, 22)
    return powerlaw_graph(1 << ln, 1 << lm, seed=args.seed)


def phase_oracle(jax, args) -> None:
    """b. static_pagerank on the device vs numpy_pagerank (float64)."""
    import numpy as np
    from repro.core import (PRParams, init_ranks, l1_error, numpy_pagerank,
                            static_pagerank)
    from repro.stream import DeviceSnapshot
    g = medium_graph(args)
    dg = DeviceSnapshot(g).dg
    t0 = time.perf_counter()
    r, iters = static_pagerank(dg, init_ranks(g.n), PRParams())
    r = np.asarray(jax.block_until_ready(r))
    dev_s = time.perf_counter() - t0
    iters = int(iters)
    _, it_np = numpy_pagerank(g)
    r_np, _ = numpy_pagerank(g, tau=0.0, max_iter=iters)
    gap = l1_error(r, r_np)
    print(f"[b] oracle |V|={g.n} |E|={g.m} device_iters={iters} "
          f"numpy_iters={it_np} L1(device, numpy@{iters})={gap!r} "
          f"tol={ORACLE_L1_TOL} wall_s={dev_s:.3f} (incl. compile)",
          flush=True)
    if not np.all(np.isfinite(r)):
        fail("oracle: non-finite ranks")
    if abs(iters - it_np) > 1:
        fail(f"oracle: iteration counts {iters} vs {it_np}")
    if not gap <= ORACLE_L1_TOL:
        fail(f"oracle: L1 {gap!r} > {ORACLE_L1_TOL}")


def check_batch(st, r) -> None:
    """The served path's failure conditions for one applied batch."""
    import jax.numpy as jnp
    if st.escalations > 0:
        fail(f"batch escalated {st.escalations} rung(s) (health={st.health})")
    if st.health != 0:
        fail(f"batch health word {st.health}")
    if st.quarantined:
        fail(f"batch quarantined {st.quarantined} updates")
    if not bool(jnp.all(jnp.isfinite(r))):
        fail("non-finite ranks")


def run_batches(sess, batches, counter, tag: str) -> list:
    """Apply the batches, print one line each; returns the names of the
    programs lowered after the first batch (steady-state compiles)."""
    mark = None
    for t, b in enumerate(batches):
        if t == 1:
            mark = len(counter.names)
        r = sess.apply(b)
        st = sess.history[-1]
        print(f"[{tag}] batch {t} size={st.batch_size} engine={st.engine} "
              f"iters={st.iters} ingest_s={st.ingest_s:.4f} "
              f"snapshot_host_s={st.snapshot.host_s:.4f} "
              f"solve_s={st.solve_s:.4f} total_s={st.total_s:.4f} "
              f"rebuilt={st.snapshot.rebuilt}", flush=True)
        check_batch(st, r)
    late = counter.names[mark:] if mark is not None else []
    print(f"[{tag}] compiles after batch 0: {len(late)} {sorted(set(late))}",
          flush=True)
    return late


def phase_served(jax, args, log_e: int, counter) -> None:
    """c. StreamSession at full size: warm start + 5 guarded batches."""
    from repro.core import powerlaw_graph, random_batch
    from repro.guard import GuardConfig
    from repro.stream import DeviceSnapshot, StreamSession
    t0 = time.perf_counter()
    g = powerlaw_graph(1 << (log_e - 4), 1 << log_e, seed=args.seed)
    t1 = time.perf_counter()
    snap = DeviceSnapshot(g)
    jax.block_until_ready(snap.dg)
    t2 = time.perf_counter()
    c0 = counter.compile_s
    sess = StreamSession(g, snapshot=snap, guard=GuardConfig())
    jax.block_until_ready(sess.ranks)
    t3 = time.perf_counter()
    print(f"[c] graph |V|={g.n} |E|={g.m} (2^{log_e} drawn, self-loops "
          f"included) gen_s={t1 - t0:.2f} snapshot_build_s={t2 - t1:.2f} "
          f"warm_start_s={t3 - t2:.2f} (static iters={int(sess._init_iters)},"
          f" compile_s={counter.compile_s - c0:.2f})", flush=True)
    batches = [random_batch(g, BATCH_FRAC, seed=args.seed + 1 + t)
               for t in range(N_BATCHES)]
    run_batches(sess, batches, counter, "c")
    gap = scratch_gap(sess, "c")
    print(f"[c] peak_bytes_in_use={peak_bytes(jax.devices()[0])}", flush=True)
    if not gap <= SESSION_L1_TOL:
        fail(f"served: L1 {gap!r} > {SESSION_L1_TOL}")


def scratch_gap(sess, tag: str) -> float:
    """L1 of the session's ranks to a from-scratch single-device static
    solve on the updated graph, rebuilt from the host's edge keys (not from
    the device layout the session maintains)."""
    import numpy as np
    from repro.core import device_graph, init_ranks, l1_error, static_pagerank
    g2 = sess.snap.graph()
    r_ref, it = static_pagerank(device_graph(g2, d_p=64, tile=256),
                                init_ranks(g2.n), sess.params)
    gap = l1_error(np.asarray(sess.flat_ranks()), np.asarray(r_ref))
    print(f"[{tag}] L1(session, from-scratch static on updated |E|={g2.m}, "
          f"{int(it)} iters)={gap!r} tol={SESSION_L1_TOL}", flush=True)
    return gap


def phase_kernel(jax, args) -> None:
    """d. One batch through the Pallas stream_scatter kernel vs jnp."""
    import numpy as np
    from repro.core import random_batch
    from repro.guard import GuardConfig
    from repro.obs.spans import get_registry
    from repro.stream import StreamSession
    g = medium_graph(args)
    b = random_batch(g, 1e-3, seed=args.seed + 100)
    sess = {impl: StreamSession(g, guard=GuardConfig(), scatter_impl=impl)
            for impl in ("jnp", "pallas")}
    calls0 = get_registry().counter("kernels.stream_scatter.calls")
    for s in sess.values():
        s.apply(b)
        check_batch(s.history[-1], s.ranks)
    calls = get_registry().counter("kernels.stream_scatter.calls") - calls0
    la = jax.tree.leaves((sess["jnp"].snap.dg, sess["jnp"].snap.fwd_dg))
    lb = jax.tree.leaves((sess["pallas"].snap.dg, sess["pallas"].snap.fwd_dg))
    same = all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))
    rgap = float(np.abs(np.asarray(sess["jnp"].ranks)
                        - np.asarray(sess["pallas"].ranks)).sum())
    st = sess["pallas"].history[-1]
    print(f"[d] kernel batch size={st.batch_size} rows_touched="
          f"{st.snapshot.rows_touched} tiles_touched="
          f"{st.snapshot.tiles_touched} kernel_builds={calls} "
          f"layout_equal={same} L1(ranks pallas, jnp)={rgap!r}", flush=True)
    if calls == 0:
        fail("kernel: stream_scatter was never called")
    if not same:
        fail("kernel: device layout differs from the jnp scatter path")


def phase_sharded(jax, args, log_e: int, counter) -> None:
    """e. StreamSession(mesh=) over four devices vs one device from scratch."""
    import jax.numpy as jnp
    from repro.core import powerlaw_graph, random_batch
    from repro.guard import GuardConfig
    from repro.stream import StreamSession
    devs = jax.devices()[:4]
    mesh = jax.make_mesh((4,), ("i",), devices=devs)
    t0 = time.perf_counter()
    g = powerlaw_graph(1 << (log_e - 4), 1 << log_e, seed=args.seed)
    t1 = time.perf_counter()
    c0 = counter.compile_s
    sess = StreamSession(g, mesh=mesh, guard=GuardConfig())
    jax.block_until_ready(sess.ranks)
    t2 = time.perf_counter()
    sg = sess.snap.sg
    lay = sum(x.nbytes for x in jax.tree.leaves(sg) if hasattr(x, "nbytes"))
    print(f"[e] graph |V|={g.n} |E|={g.m} (2^{log_e} drawn) gen_s="
          f"{t1 - t0:.2f} session_build_s={t2 - t1:.2f} (compile_s="
          f"{counter.compile_s - c0:.2f}) layout_bytes={lay} "
          f"per_shard={lay // 4}", flush=True)
    placed = sorted(s.device.id for s in sg.hi_tiles.addressable_shards)
    print(f"[e] hi_tiles shards on devices {placed}", flush=True)
    batches = [random_batch(g, BATCH_FRAC, seed=args.seed + 1 + t)
               for t in range(N_BATCHES)]
    late = run_batches(sess, batches, counter, "e")
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    peak = [peak_bytes(d) for d in devs]
    print(f"[e] bytes_in_use per device={used} peak={peak}", flush=True)
    gap = scratch_gap(sess, "e")
    if not bool(jnp.all(jnp.isfinite(sess.flat_ranks()))):
        fail("sharded: non-finite ranks")
    if not gap <= SESSION_L1_TOL:
        fail(f"sharded: L1 {gap!r} > {SESSION_L1_TOL}")
    if len(set(placed)) != 4:
        fail("sharded: the layout is not split over four devices")
    if all(u is not None for u in used) and min(used) < 0.5 * max(used):
        fail(f"sharded: device memory is lopsided: {used}")
    # a row scatter compiles once per new pow2 row count; nothing else may
    # compile after batch 0
    other = [n for n in late if "scatter" not in n]
    if other:
        fail(f"sharded: compiled in steady state: {sorted(set(other))}")


if __name__ == "__main__":
    sys.exit(main())
