"""dfp_sweep_ms: solve time per DF-P sweep over the window, sum of
BatchStats.solve_s over sum of BatchStats.iters."""


def read(run):
    b = run.batches
    iters = sum(x["iters"] for x in b)
    return 1e3 * sum(x["solve_s"] for x in b) / iters if iters else None
