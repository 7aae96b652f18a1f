"""dense_finish_ms: device time of the compact engine's dense finish per
window batch: every op of the program ``_dense_finish``
(``core/compact.py``), the dense DF-P sweeps that follow the compact loop's
overflow, from the trace (bench/trace_reduce.py)."""

PROGRAMS = ("jit__dense_finish",)


def read(run):
    if run.trace is None or not run.batches:
        return None
    s = sum(v for k, v in run.trace.op_s
            if k.split("/", 1)[0] in PROGRAMS)
    return 1e3 * s / len(run.batches)
