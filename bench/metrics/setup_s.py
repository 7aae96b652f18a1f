"""setup_s: seconds from the start of the run to the start of the window:
imports, graph and batch generation, the session's layout build and warm
start, and the warm-up calls (with any compilation)."""


def read(run):
    return run.setup_s
