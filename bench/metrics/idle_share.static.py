"""idle_share.static: percent of the traced window of a static cell in which
no operation ran on the device (bench/trace_reduce.py)."""


def read(run):
    if run.trace is None or not run.solves:
        return None
    return 100.0 * run.trace.idle_share
