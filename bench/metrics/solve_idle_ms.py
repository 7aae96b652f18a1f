"""solve_idle_ms: device idle time inside the program's solve per window
batch: the traced window's idle gaps given to ``session.solve``, the
innermost span the trace reduction knows there (it holds the ``solve.*`` and
``compact.*`` spans), from bench/trace_reduce.py."""


def read(run):
    if run.trace is None or not run.batches:
        return None
    return 1e3 * run.trace.gap_totals().get("session.solve", 0.0) \
        / len(run.batches)
