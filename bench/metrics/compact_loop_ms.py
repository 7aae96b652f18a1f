"""compact_loop_ms: device time of the compact engine's loop per window
batch: every op of the program ``_compact_loop`` (``core/compact.py``), the
sweeps over compacted active lists before any handoff, from the trace
(bench/trace_reduce.py)."""

PROGRAMS = ("jit__compact_loop",)


def read(run):
    if run.trace is None or not run.batches:
        return None
    s = sum(v for k, v in run.trace.op_s
            if k.split("/", 1)[0] in PROGRAMS)
    return 1e3 * s / len(run.batches)
