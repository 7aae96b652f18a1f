"""Reduce a profiler trace (``.xplane.pb``) to the device's busy and idle
time, per-operation time and idle gaps attributed to host spans.

Busy time is the union of the intervals in which an operation ran on a
device ("XLA Ops" lines of the ``/device:TPU:<i>`` planes), clipped to the
window that the harness marks with a ``bench.window`` annotation, and
averaged over the devices. An idle gap is a stretch of the window in which
no operation ran; it is attributed to the innermost host span that covers
its midpoint: ``session.solve`` (the program's solve, dispatch and host
synchronisation included), ``bench.apply`` (the rest of the call into the
session) or ``harness`` (the benchmark's own loop).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

import numpy as np

__all__ = ["TraceSummary", "find_xplane", "reduce_trace", "stable_op_name",
           "union_length", "gaps"]

WINDOW = "bench.window"
#: host spans a gap may be attributed to, innermost first
GAP_SPANS = ("session.solve", "bench.apply")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    devices: int
    #: device seconds per stable op name (ops that hold no other op),
    #: largest first
    op_s: List[Tuple[str, float]]
    #: every idle gap in the window as (attribution, seconds), longest first
    gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def gap_totals(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for name, s in self.gaps:
            out[name] = out.get(name, 0.0) + s
        return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return paths[0]


_NUM = re.compile(r"[._]\d+$")
_LAYOUT = re.compile(r"\{[^}]*\}")


def stable_op_name(text: str, module: str) -> str:
    """A device op's name that survives a recompile: its program, its HLO
    opcode name without the number the compiler gives it, and its result
    type without layout. The trace names an op by its HLO text,
    ``%fusion.155 = f32[2097152]{0:T(1024)} fusion(...)``; of program
    ``jit__static_pagerank(123)`` that is
    ``jit__static_pagerank/fusion f32[2097152]``."""
    head, _, rest = text.partition(" = ")
    base = head.strip().lstrip("%")
    while _NUM.search(base):
        base = _NUM.sub("", base)
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        result = rest[:i + 1]
    else:
        result = rest.split(" ", 1)[0]
    module = module.split("(", 1)[0]
    name = f"{base} {result}" if result else base
    return f"{module}/{name}" if module else name


def union_length(iv: np.ndarray) -> float:
    """Total length of the union of [start, end) intervals (rows)."""
    if iv.size == 0:
        return 0.0
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    total, cur_s, cur_e = 0.0, iv[0, 0], iv[0, 1]
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    return float(total + cur_e - cur_s)


def gaps(iv: np.ndarray, w0: float, w1: float) -> List[Tuple[float, float]]:
    """The stretches of [w0, w1) that no interval covers."""
    out, t = [], w0
    if iv.size:
        iv = iv[np.argsort(iv[:, 0], kind="stable")]
        for s, e in iv:
            if s > t:
                out.append((t, min(s, w1)))
            t = max(t, e)
            if t >= w1:
                break
    if t < w1:
        out.append((t, w1))
    return [(a, b) for a, b in out if b > a]


def _module_at(t: float, modules: List[Tuple[float, float, str]]) -> str:
    """Name of the program running at device time ``t`` ("" if none)."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and t < modules[lo - 1][1]:
        return modules[lo - 1][2]
    return ""


def _leaves(ops):
    """The ops that hold no other op: a ``while`` op spans every op of its
    body on the same line, and its time is theirs."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    return [o for i, o in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][0] >= o[1]]


def _attribute(t: float, spans: Dict[str, np.ndarray]) -> str:
    """The innermost of GAP_SPANS open at time ``t``, else "harness".
    Spans of one name do not overlap: the one that starts last before
    ``t`` is the only candidate."""
    for name in GAP_SPANS:
        iv = spans[name]
        i = int(np.searchsorted(iv[:, 0], t, side="right")) - 1
        if i >= 0 and t < iv[i, 1]:
            return name
    return "harness"


def reduce_trace(path: str) -> TraceSummary:
    """Reduce one ``.xplane.pb``; raises if it holds no device operation or
    no ``bench.window`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    spans: Dict[str, List[Tuple[float, float]]] = {k: [] for k in GAP_SPANS}
    dev_ops: List[List[Tuple[float, float, str, str]]] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in spans:
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name) for ev in line.events]
                elif line.name == MODULES_LINE:
                    modules = sorted((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name)
                                     for ev in line.events)
            dev_ops.append([(s, e, name, _module_at(s, modules))
                            for s, e, name in ops])
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in {path}")
    if not any(dev_ops):
        raise ValueError(f"no device operation in {path}")
    w0, w1 = window
    sorted_spans = {k: np.array(sorted(v), np.float64).reshape(-1, 2)
                    for k, v in spans.items()}
    busy, op_ns, gap_list = [], {}, []
    for ops in dev_ops:
        iv = np.array([(max(s, w0), min(e, w1)) for s, e, _, _ in ops
                       if e > w0 and s < w1], np.float64).reshape(-1, 2)
        busy.append(union_length(iv))
        for s, e, name, module in _leaves(ops):
            if e > w0 and s < w1:
                key = stable_op_name(name, module)
                op_ns[key] = op_ns.get(key, 0.0) + (min(e, w1) - max(s, w0))
        for a, b in gaps(iv, w0, w1):
            gap_list.append((_attribute(0.5 * (a + b), sorted_spans),
                             (b - a) * 1e-9))
    nd = len(dev_ops)
    op_s = sorted(((k, v * 1e-9 / nd) for k, v in op_ns.items()),
                  key=lambda kv: -kv[1])
    gap_list.sort(key=lambda g: -g[1])
    return TraceSummary(busy_s=float(np.mean(busy)) * 1e-9,
                        window_s=(w1 - w0) * 1e-9, devices=nd, op_s=op_s,
                        gaps=gap_list)
