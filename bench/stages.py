"""Device time by stage and idle gaps by program span, from a profiler trace
(``.xplane.pb``): what ``bench/trace_reduce.py`` does not read yet.

``jax.profiler.ProfileData`` gives each device op's HLO text, but not its JAX
op name: the ``tf_op`` stat of the op's event metadata, which carries the
``jax.named_scope`` names that the program gives each stage of a sweep
(``pr.pull``, ``pr.update``, ``pr.converge``, ``pr.compact``, ``pr.expand``,
``snapshot.scatter``). `op_names` reads it with a small protobuf wire-format
reader, so that no tensorflow is needed where the trace is read.

`reduce_stages` clips the device ops to the ``bench.window`` span and, over
the ops that hold no other op (as ``trace_reduce`` counts them), gives

* ``stage_s``: device seconds per stage, the innermost stage name in the
  op's ``tf_op`` (``""`` for an op in no named stage);
* ``span_gaps``: every idle gap of the window, given to the innermost of
  `PROGRAM_SPANS` open at its midpoint (``"harness"`` where none is).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterator, List, Tuple

import numpy as np

from .trace_reduce import OPS_LINE, WINDOW, _leaves, gaps, union_length

__all__ = ["STAGES", "PROGRAM_SPANS", "StageSummary", "op_names", "stage_of",
           "innermost_span", "reduce_stages"]

#: the stage names the program gives its device ops (``jax.named_scope``)
STAGES = ("pr.pull", "pr.update", "pr.converge", "pr.compact", "pr.expand",
          "snapshot.scatter")
#: the program's annotated host spans an idle gap may be given to
PROGRAM_SPANS = (
    "session.ingest", "snapshot.apply_net_delta", "snapshot.host_edit",
    "snapshot.device_refresh", "session.plan", "session.solve",
    "solve.static", "solve.nd", "solve.dt", "solve.df", "solve.dfp",
    "solve.df_compact", "solve.dfp_compact", "compact.plan",
    "compact.check", "compact.finish", "session.recompute")
_DEVICE = re.compile(r"/device:TPU:\d+")


# -- protobuf wire format -----------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message in ``buf[i:end]``: an int for a
    varint, a (start, end) pair for a length-delimited field; fixed-width
    fields are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entries(buf: bytes, spans) -> Iterator[Tuple[int, tuple]]:
    for s, e in spans:
        key, val = 0, (s, s)
        for f, v in _fields(buf, s, e):
            if f == 1:
                key = v
            elif f == 2:
                val = v
        yield key, val


def _plane_tf_ops(buf: bytes, lines, event_md, stat_md) -> List[str]:
    # XStatMetadata: 1 id, 2 name
    stat_name = {}
    for key, (s, e) in _map_entries(buf, stat_md):
        for f, v in _fields(buf, s, e):
            if f == 2:
                stat_name[key] = _text(buf, v)
    tf_op_ids = {k for k, name in stat_name.items() if name == "tf_op"}
    # XEventMetadata: 5 stats; XStat: 1 metadata_id, 5 str, 7 ref
    tf_op: Dict[int, str] = {}
    for key, (s, e) in _map_entries(buf, event_md):
        for f, v in _fields(buf, s, e):
            if f != 5:
                continue
            sid, val = None, None
            for g, w in _fields(buf, *v):
                if g == 1:
                    sid = w
                elif g == 5:
                    val = _text(buf, w)
                elif g == 7:
                    val = stat_name.get(w, "")
            if sid in tf_op_ids and val is not None:
                tf_op[key] = val
    # XLine: 2 name, 4 events; XEvent: 1 metadata_id
    for s, e in lines:
        name, events = "", []
        for f, v in _fields(buf, s, e):
            if f == 2:
                name = _text(buf, v)
            elif f == 4:
                events.append(v)
        if name != OPS_LINE:
            continue
        out = []
        for es, ee in events:
            md = 0
            for f, v in _fields(buf, es, ee):
                if f == 1:
                    md = v
                    break
            out.append(tf_op.get(md, ""))
        return out
    return []


def op_names(path: str) -> Dict[str, List[str]]:
    """For each device plane, the ``tf_op`` of every event of its ``XLA
    Ops`` line, in the file's order (the order ``ProfileData`` gives)."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    # XSpace: 1 planes; XPlane: 2 name, 3 lines, 4 event_metadata,
    # 5 stat_metadata (maps: 1 key, 2 value)
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, lines, event_md, stat_md = "", [], [], []
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g == 3:
                lines.append(v)
            elif g == 4:
                event_md.append(v)
            elif g == 5:
                stat_md.append(v)
        if _DEVICE.fullmatch(name):
            out[name] = _plane_tf_ops(buf, lines, event_md, stat_md)
    return out


# -- reduction ----------------------------------------------------------------

def stage_of(tf_op: str) -> str:
    """The innermost of `STAGES` among the scopes of a JAX op name
    (``jit(_loop)/while/body/pr.pull/jit(_take)/gather`` -> ``pr.pull``)."""
    for part in reversed(tf_op.rsplit(":", 1)[0].split("/")):
        if part in STAGES:
            return part
    return ""


def innermost_span(t: float, spans: Dict[str, np.ndarray]) -> str:
    """The program span open at time ``t`` that started last (spans nest,
    so that is the innermost), else "harness". Spans of one name do not
    overlap: the one that starts last before ``t`` is its only candidate.
    ``spans`` maps a name to its sorted [start, end) rows."""
    best, best_start = "harness", -np.inf
    for name, iv in spans.items():
        i = int(np.searchsorted(iv[:, 0], t, side="right")) - 1
        if i >= 0 and t < iv[i, 1] and iv[i, 0] > best_start:
            best, best_start = name, iv[i, 0]
    return best


@dataclasses.dataclass
class StageSummary:
    busy_s: float
    window_s: float
    devices: int
    #: device seconds per stage (`stage_of`), largest first
    stage_s: Dict[str, float]
    #: device seconds per full ``tf_op``, largest first
    tf_op_s: Dict[str, float]
    #: every idle gap in the window as (program span, seconds), longest first
    span_gaps: List[Tuple[str, float]]
    #: program spans that overlap the window, by name
    span_counts: Dict[str, int]


def _by_value(d: Dict[str, float], scale: float) -> Dict[str, float]:
    return dict(sorted(((k, v * scale) for k, v in d.items()),
                       key=lambda kv: -kv[1]))


def reduce_stages(path: str) -> StageSummary:
    """Reduce one ``.xplane.pb``; raises if it holds no device operation or
    no ``bench.window`` span, or if the ``tf_op`` reader and
    ``ProfileData`` disagree on a device's ops."""
    from jax.profiler import ProfileData

    names = op_names(path)
    pd = ProfileData.from_file(path)
    window = None
    spans: Dict[str, List[Tuple[float, float]]] = {
        k: [] for k in PROGRAM_SPANS}
    dev_ops = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in spans:
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
        elif _DEVICE.fullmatch(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in line.events]
            tf_ops = names.get(plane.name, [])
            if len(tf_ops) != len(ops):
                raise ValueError(f"{plane.name}: {len(ops)} ops but "
                                 f"{len(tf_ops)} tf_op names in {path}")
            dev_ops.append([(s, e, op) for (s, e), op in zip(ops, tf_ops)])
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in {path}")
    if not any(dev_ops):
        raise ValueError(f"no device operation in {path}")
    w0, w1 = window
    sorted_spans = {k: np.array(sorted(v), np.float64).reshape(-1, 2)
                    for k, v in spans.items() if v}
    counts = {k: int(np.sum((iv[:, 1] > w0) & (iv[:, 0] < w1)))
              for k, iv in sorted_spans.items()}
    busy, stage_ns, op_ns, gap_list = [], {}, {}, []
    for ops in dev_ops:
        iv = np.array([(max(s, w0), min(e, w1)) for s, e, _ in ops
                       if e > w0 and s < w1], np.float64).reshape(-1, 2)
        busy.append(union_length(iv))
        for s, e, op in _leaves(ops):
            if e > w0 and s < w1:
                d = min(e, w1) - max(s, w0)
                stage = stage_of(op)
                stage_ns[stage] = stage_ns.get(stage, 0.0) + d
                op_ns[op] = op_ns.get(op, 0.0) + d
        for a, b in gaps(iv, w0, w1):
            gap_list.append((innermost_span(0.5 * (a + b), sorted_spans),
                             float(b - a) * 1e-9))
    nd = len(dev_ops)
    gap_list.sort(key=lambda g: -g[1])
    return StageSummary(
        busy_s=float(np.mean(busy)) * 1e-9, window_s=(w1 - w0) * 1e-9,
        devices=nd, stage_s=_by_value(stage_ns, 1e-9 / nd),
        tf_op_s=_by_value(op_ns, 1e-9 / nd), span_gaps=gap_list,
        span_counts={k: v for k, v in counts.items() if v})
