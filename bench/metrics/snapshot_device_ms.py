"""snapshot_device_ms: device time of the snapshot's row scatters per window
batch: every op of the programs that write the edited rows and degrees into
the resident layout (``stream/snapshot.py``: ``_scatter_pair``,
``_scatter_1d``), from the trace (bench/trace_reduce.py)."""

PROGRAMS = ("jit__scatter_pair", "jit__scatter_1d")


def read(run):
    if run.trace is None or not run.batches:
        return None
    s = sum(v for k, v in run.trace.op_s
            if k.split("/", 1)[0] in PROGRAMS)
    return 1e3 * s / len(run.batches)
