"""Device time by stage and idle gaps by program span (bench/stages.py): the
wire-format reader of each op's JAX name on the traces recorded on a TPU v5e
(``data/``), span attribution on synthetic intervals, and the readers of the
metrics that the trace reduction feeds as it is."""
import glob
import os

import numpy as np
import pytest

from bench.harness import Run, _reader
from bench.stages import (PROGRAM_SPANS, innermost_span, op_names,
                          reduce_stages, stage_of)
from bench.trace_reduce import TraceSummary, reduce_trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
STATIC = os.path.join(DATA, "rmat.static.7000000030.xplane.pb")
#: DF-P windows traced with the stage names in place, kept apart from
#: ``data/*.xplane.pb``: their custom-call ops of no length, and the compact
#: loop's ``while`` op recorded as 1 ns after its body, fail two checks of
#: test_trace_reduce (every op took time; no ``while`` op is a leaf)
DFP = sorted(glob.glob(os.path.join(DATA, "stages", "*.xplane.pb")))
TRACES = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb"))) + DFP


def test_static_trace_op_names():
    """The pull of a static sweep is its gathers and their scatter-add."""
    names = op_names(STATIC)
    assert list(names) == ["/device:TPU:0"]
    s = reduce_stages(STATIC)
    total = sum(s.tf_op_s.values())
    share = {k: 100 * v / total for k, v in s.tf_op_s.items()}
    gather = sum(v for k, v in share.items()
                 if k.startswith("jit(_static_pagerank)/")
                 and k.rstrip(":").endswith("jit(_take)/gather"))
    scatter = sum(v for k, v in share.items()
                  if k.rstrip(":").endswith("/scatter-add"))
    assert 79 <= gather <= 81
    assert 19 <= scatter <= 21


@pytest.mark.parametrize("path", TRACES)
def test_stages_agree_with_the_trace_reduction(path):
    s, t = reduce_stages(path), reduce_trace(path)
    assert s.busy_s == t.busy_s and s.window_s == t.window_s
    leaves = sum(v for _, v in t.op_s)
    assert abs(sum(s.stage_s.values()) - leaves) <= 1e-9 * leaves
    assert abs(sum(s.tf_op_s.values()) - leaves) <= 1e-9 * leaves
    # every idle gap is given to exactly one span
    idle = sum(g for _, g in t.gaps)
    assert abs(sum(g for _, g in s.span_gaps) - idle) <= 1e-9 * idle + 1e-12
    assert {n for n, _ in s.span_gaps} <= set(PROGRAM_SPANS) | {"harness"}


@pytest.mark.parametrize("path", DFP)
def test_recorded_dfp_trace(path):
    """test_trace_reduce's checks of a recorded trace, with ops of no length
    and ``while`` leaves of a few nanoseconds allowed."""
    s = reduce_trace(path)
    assert s.devices == 1
    assert 0 < s.busy_s <= s.window_s
    idle = sum(g for _, g in s.gaps)
    assert abs(s.busy_s + idle - s.window_s) < 1e-6 * s.window_s + 1e-6
    assert s.op_s and all(v >= 0 for _, v in s.op_s)
    leaves = sum(v for _, v in s.op_s)
    assert 0.5 * s.busy_s < leaves <= s.busy_s * (1 + 1e-9)
    assert sum(v for k, v in s.op_s if "/while " in k) < 1e-6
    assert {name for name, _ in s.gaps} <= {"session.solve", "bench.apply",
                                            "harness"}


@pytest.mark.parametrize("path", DFP)
def test_named_stages_cover_the_dfp_trace(path):
    s = reduce_stages(path)
    named = sum(v for k, v in s.stage_s.items() if k)
    assert named >= 0.95 * s.busy_s
    for stage in ("pr.pull", "pr.update", "pr.compact", "pr.expand",
                  "snapshot.scatter"):
        assert s.stage_s.get(stage, 0) > 0, stage
    for span in ("session.ingest", "session.plan", "session.solve",
                 "compact.plan", "compact.check", "snapshot.host_edit"):
        assert s.span_counts.get(span, 0) >= 1, span


def test_stage_of_takes_the_innermost_stage():
    assert stage_of("jit(_loop)/while/body/pr.pull/jit(_take)/gather:") \
        == "pr.pull"
    assert stage_of("jit(f)/pr.expand/pr.compact/sort") == "pr.compact"
    assert stage_of("jit(_static_pagerank)/while/body/scatter-add:") == ""
    assert stage_of("") == ""


def test_innermost_span_on_nested_intervals():
    spans = {
        "session.solve": np.array([[0, 100], [200, 300]], np.float64),
        "solve.dfp_compact": np.array([[10, 90]], np.float64),
        "compact.check": np.array([[40, 60]], np.float64),
        "session.ingest": np.array([[150, 160]], np.float64),
    }
    assert innermost_span(5, spans) == "session.solve"
    assert innermost_span(20, spans) == "solve.dfp_compact"
    assert innermost_span(50, spans) == "compact.check"
    assert innermost_span(60, spans) == "solve.dfp_compact"  # ends at 60
    assert innermost_span(155, spans) == "session.ingest"
    assert innermost_span(120, spans) == "harness"
    assert innermost_span(250, spans) == "session.solve"
    assert innermost_span(300, spans) == "harness"


def _run(trace, iters=(20, 30)):
    calls = [{"wall_s": 3.0, "iters": it, "engine": "compact"}
             for it in iters]
    return Run(mix={"loop": "stream"}, n=100, edges=1000, setup_s=1.0,
               window_s=6.0, calls=calls, trace=trace)


def _summary(op_s, gaps):
    return TraceSummary(busy_s=5.0, window_s=6.0, devices=1, op_s=op_s,
                        gaps=gaps)


def test_snapshot_device_ms_reads_the_scatter_programs():
    read = _reader("snapshot_device_ms")
    op_s = [("jit__compact_loop/fusion f32[1024]", 4.0),
            ("jit__scatter_pair/scatter s32[512,16]", 0.003),
            ("jit__scatter_1d/scatter s32[65536]", 0.001),
            ("jit__scatter_pair_x/copy s32[8]", 9.0)]
    assert read(_run(_summary(op_s, []))) == pytest.approx(2.0)
    assert read(_run(None)) is None
    static = _run(_summary(op_s, []))
    static.mix = {"loop": "static"}
    assert read(static) is None


def test_solve_idle_ms_reads_the_gaps_in_the_solve():
    read = _reader("solve_idle_ms")
    gaps = [("session.solve", 0.010), ("bench.apply", 0.5),
            ("session.solve", 0.004), ("harness", 0.2)]
    assert read(_run(_summary([], gaps))) == pytest.approx(7.0)
    assert read(_run(_summary([], []))) == 0.0
    assert read(_run(None)) is None
