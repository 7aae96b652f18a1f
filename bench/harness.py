"""The benchmark harness: one run of one cell of ``BENCHMARK.json``.

A run loads the cell's configuration (``bench/configs``) and traffic mix
(``bench/traffic``), builds the graph and every batch from ``--seed``,
builds a ``StreamSession`` on the device, warms it up, then drives it in a
closed loop for ``--seconds``: one client applies batches back to back (or,
for a ``static`` mix, asks for from-scratch solves back to back). No new
call starts after the deadline; the call that straddles it counts, with all
of its time. After the window the run reads the peak of device memory,
frees the session and checks the ranks the window produced against the
float64 reference (``bench/reference.py``) on graphs rebuilt from the
generator's own edge lists. Metrics are read by the readers under
``bench/metrics``, one file per metric, found by the names in
``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from .gen.traffic import make_traffic
from .named import load
from .reference import ReferenceStream, l1_gap

__all__ = ["ROOT", "NoChip", "Run", "load_cell", "run_cell", "main"]

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


#: windows of clean call time that a run which compiled inside its window
#: goes on for, unmeasured, to fill the persistent compile cache
CACHE_FILL = 1.5


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take it."""
    mix: dict
    n: int
    #: unique edges of the base graph, self-loops included
    edges: int
    setup_s: float
    window_s: float
    #: one dict per call in the window: wall_s, and for batches engine,
    #: iters, ingest_s, snapshot_host_s, solve_s, size
    calls: List[dict]
    peaks: Optional[dict] = None
    trace: Optional[object] = None
    #: sweeps of the reference's static solve of the base graph (the work
    #: one from-scratch solve has to do, fixed by the configuration)
    ref_sweeps: Optional[int] = None

    @property
    def batches(self) -> List[dict]:
        return self.calls if self.mix["loop"] == "stream" else []

    @property
    def solves(self) -> List[dict]:
        return self.calls if self.mix["loop"] == "static" else []


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str):
    """(spec, cell, config, mix, limits) for one workload of
    ``BENCHMARK.json``."""
    spec = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    mix = _load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    limits = _load_json(os.path.join(BENCH, "limits", workload + ".json"))
    return spec, cell, config, mix, limits


def _metrics_for(spec: dict, workload: str, group: str) -> List[dict]:
    return [m for m in spec[group]
            if "workloads" not in m or workload in m["workloads"]]


def _reader(name: str) -> Callable:
    return load("metrics", name).read


class CompileCounter:
    """Counts, from JAX's own monitoring events, the programs lowered (one
    per new jit signature), the programs compiled because the persistent
    cache lacked them, and the seconds spent tracing, lowering and
    compiling or loading them."""
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE_S = ("/jax/core/compile/jaxpr_trace_duration", LOWER,
                 "/jax/core/compile/backend_compile_duration")
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self, jax):
        self.names: List[str] = []
        self.compiled = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if event == self.LOWER:
            self.names.append(str(kw.get("fun_name", "?")))
        if event in self.COMPILE_S:
            self.seconds += duration

    def _on_event(self, event, **kw):
        if event == self.MISS:
            self.compiled += 1

    def mark(self):
        return len(self.names), self.compiled, self.seconds

    def since(self, mark) -> dict:
        return {"lowered": len(self.names) - mark[0],
                "compiled": self.compiled - mark[1],
                "compile_s": self.seconds - mark[2]}


def _device(jax, chips: int, rehearse: bool) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" and not rehearse:
        raise NoChip(f"no TPU: JAX sees platform {d0.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips}


def _peak_bytes(jax, chips: int) -> int:
    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats()
        if st and "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def _rehearsal(config: dict) -> dict:
    """The configuration at its rehearsal size (CPU, never a chip result)."""
    cfg = json.loads(json.dumps(config))
    cfg["graph"].update(cfg.get("rehearsal", {}).get("graph", {}))
    return cfg


def _session_params(cfg: dict):
    from repro.core import PRParams
    g = cfg["guarantees"]
    return PRParams(alpha=g["alpha"], tau=g["tau"], tau_f=g["tau_f"],
                    tau_p=g["tau_p"], max_iter=g["max_iter"])


def check_outputs(traffic, cfg: dict, warm: int, outputs: List[np.ndarray],
                  stream: bool, dtype=np.float64):
    """L1 gap of each window output from the reference: for a stream, the
    reference replays the warm start and every batch (warm-up included)
    and is compared after each window batch; for static solves, every
    output is compared with one static solve of the base graph. Returns
    the gaps and the reference."""
    ref = ReferenceStream(traffic.n, traffic.base_src, traffic.base_dst,
                          cfg["guarantees"], dtype)
    if not stream:
        return [l1_gap(r, ref.ranks) for r in outputs], ref
    gaps = []
    for i, b in enumerate(traffic.batches[:warm + len(outputs)]):
        want = ref.apply(b.del_src, b.del_dst, b.ins_src, b.ins_dst)
        if i >= warm:
            gaps.append(l1_gap(outputs[i - warm], want))
    return gaps, ref


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, trace_dir: Optional[str] = None,
             t_start: Optional[float] = None, log=print) -> dict:
    """One run; returns its result object (printed as the last line of
    standard output). ``rehearse`` allows any backend and the
    configuration's rehearsal size; such a result is never a chip result.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    spec, cell, config, mix, limits = load_cell(workload)
    cfg = _rehearsal(config) if rehearse else config

    import jax
    jax.config.update("jax_enable_x64", True)
    # the persistent compile cache: where JAX_COMPILATION_CACHE_DIR says
    # (JAX reads it), else at a fixed path in the checkout; every program
    # is cached, however fast it compiles, so that set-up stays steady
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = _device(jax, cell["chips"], rehearse)
    peaks = None
    if not rehearse:
        from .peaks import peaks_for
        peaks = peaks_for(device["kind"])
    counter = CompileCounter(jax)

    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import BatchUpdate, build_graph
    from repro.guard import GuardConfig
    from repro.stream import StreamSession

    traffic = make_traffic(cfg, mix, seed)
    n = traffic.n
    base = build_graph(n, traffic.base_src, traffic.base_dst,
                       self_loops=True)
    edges = base.m
    sess = StreamSession(base, params=_session_params(cfg),
                         guard=GuardConfig())
    stream = mix["loop"] == "stream"
    program_batches = [BatchUpdate(b.del_src, b.del_dst, b.ins_src,
                                   b.ins_dst) for b in traffic.batches]

    def call(i: int):
        if stream:
            with jax.profiler.TraceAnnotation("bench.apply"):
                return sess.apply(program_batches[i])
        with jax.profiler.TraceAnnotation("bench.apply"):
            return sess.recompute()

    # warm-up: calls from the cell's own traffic until the mix's
    # ``warmup_clean`` calls in a row lower no new program (an engine or a
    # capacity may not have come up yet after fewer), up to its cap
    warm = clean = 0
    warm_log = []
    while clean < mix["warmup_clean"] and warm < mix["warmup_cap"] and (
            not stream or warm < len(program_batches)):
        before = len(counter.names)
        c0 = time.perf_counter()
        jax.block_until_ready(call(warm))
        warm_log.append((time.perf_counter() - c0,
                         len(counter.names) - before))
        warm += 1
        clean = clean + 1 if len(counter.names) == before else 0
    setup_mark = counter.mark()

    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tmp)
    records: List[dict] = []
    outputs = []
    r = None
    i = warm

    def timed_call():
        nonlocal i
        mark = counter.mark()
        c0 = time.perf_counter()
        out = jax.block_until_ready(call(i))
        wall = time.perf_counter() - c0
        st = sess.history[-1]
        i += 1
        return out, dict({"wall_s": wall, "start_s": c0 - w0,
                          "engine": st.engine,
                          "iters": st.iters, "ingest_s": st.ingest_s,
                          "snapshot_host_s": st.snapshot.host_s,
                          "solve_s": st.solve_s, "size": st.batch_size,
                          "escalations": st.escalations,
                          "health": st.health}, **counter.since(mark))

    setup_s = time.perf_counter() - t_start
    w0 = time.perf_counter()
    deadline = w0 + seconds
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() < deadline:
            if stream and i >= len(program_batches):
                log("the traffic ran out of batches before the deadline")
                break
            r, rec = timed_call()
            records.append(rec)
            outputs.append(r)
    window_s = time.perf_counter() - w0
    window = counter.since(setup_mark)
    device["memory_peak_bytes"] = _peak_bytes(jax, cell["chips"])
    summary = None
    if trace:
        jax.profiler.stop_trace()
        from .trace_reduce import find_xplane, reduce_trace
        path = find_xplane(tmp)
        if trace_dir:
            os.makedirs(trace_dir, exist_ok=True)
            shutil.copy(path, os.path.join(
                trace_dir, f"{workload}.{seed}.xplane.pb"))
        try:
            summary = reduce_trace(path)
        except ValueError as e:
            if not rehearse:
                raise
            log(f"trace: {e}")
        shutil.rmtree(tmp, ignore_errors=True)

    # cache fill: a window that compiled a program met calls the set-up had
    # not reached; go on, unmeasured and unchecked, until the calls made
    # since the window opened would fill CACHE_FILL windows without their
    # compile time, so that the persistent cache holds what the next run's
    # window needs and that window compiles nothing
    filled = 0
    if window["compiled"]:
        clean_s = sum(rec["wall_s"] - rec["compile_s"] for rec in records)
        while clean_s < CACHE_FILL * seconds and (
                not stream or i < len(program_batches)):
            _, rec = timed_call()
            clean_s += rec["wall_s"] - rec["compile_s"]
            filled += 1

    engines: Dict[str, int] = {}
    for rec in records:
        engines[rec["engine"]] = engines.get(rec["engine"], 0) + 1
    log(f"programs lowered in set-up: {setup_mark[0]}, compiled "
        f"{setup_mark[1]}; warm-up calls: {warm}")
    for k, (wall, lowered) in enumerate(warm_log):
        log(f"warm-up call {k}: {wall:.6f} s, {lowered} programs lowered")
    log(f"programs inside the window: lowered {window['lowered']} "
        f"{sorted(set(counter.names[setup_mark[0]:][:window['lowered']]))}"
        f", compiled {window['compiled']} in {window['compile_s']:.6f} s")
    log(f"window calls: {len(records)} in {window_s:.6f} s; engines: "
        f"{engines}; escalations: "
        f"{sum(rec['escalations'] for rec in records)}")
    if filled:
        log(f"cache fill after the window: {filled} calls")
    log(f"peak_bytes_in_use: {device['memory_peak_bytes']}")
    for k, rec in enumerate(records):
        log(f"call {k}: {rec['wall_s']:.6f} s {rec['engine']} "
            f"{rec['iters']} sweeps, {rec['lowered']} programs lowered, "
            f"{rec['compiled']} compiled, {rec['compile_s']:.6f} s, "
            f"started {rec['start_s']:.3f} s into the window")

    # the check: every window output against the reference, after the
    # session is freed
    got = [np.asarray(o) for o in outputs]
    del outputs, r, call, timed_call, sess
    gc.collect()
    t_check = time.perf_counter()
    gaps, ref = check_outputs(traffic, cfg, warm, got, stream)
    check_s = time.perf_counter() - t_check
    limit = limits["l1_gap"]["limit"]
    failed = sum(1 for x in gaps if not x <= limit)
    correct = bool(gaps) and failed == 0
    # a non-finite gap has no number; it fails the check all the same
    worst = max(gaps) if gaps and all(np.isfinite(gaps)) else None
    log(f"check: {len(gaps)} window outputs compared in {check_s:.3f} s; "
        f"largest L1 gap {worst!r}")

    run = Run(mix=mix, n=n, edges=edges,
              setup_s=setup_s, window_s=window_s, calls=records,
              peaks=peaks, trace=summary, ref_sweeps=ref.static_sweeps)
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in _metrics_for(spec, workload, group):
        v = _reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if trace and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.op_s[:10]],
            "idle_gaps": [[k, v] for k, v in summary.gaps[:10]]}
        log(f"idle by host span: {summary.gap_totals()}")
    # programs the window lowered (each a new jit signature, met by a call
    # the set-up had not reached) and compiled (missing from the cache)
    result["window_programs"] = window
    result["checks"] = {"l1_gap_max": {"value": worst, "limit": limit}}
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="any backend, rehearsal sizes; prints no result")
    ap.add_argument("--trace-dir", default=None,
                    help="keep a copy of the trace's .xplane.pb here")
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), rehearse=args.rehearse,
                       trace_dir=args.trace_dir, t_start=t_start,
                       log=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in res["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr,
              flush=True)
    if args.rehearse:
        print("rehearsal: not a chip result; correct="
              f"{res['correct']} metrics={res['metrics']}", flush=True)
        return 3
    print(json.dumps(res), flush=True)
    return 0
