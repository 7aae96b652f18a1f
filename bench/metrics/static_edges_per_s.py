"""static_edges_per_s: the paper's Table 1 rate, |E| x sweeps of every
from-scratch solve of the window over their total wall time. The sweeps of
a solve are the reference's static solve of the same graph (the work the
configuration asks for), not the program's own count, so that a program
that converges in fewer sweeps shows as faster and one that counts its
sweeps otherwise cannot move the rate."""


def read(run):
    wall = sum(s["wall_s"] for s in run.solves)
    if not wall or not run.ref_sweeps:
        return None
    return run.edges * run.ref_sweeps * len(run.solves) / wall
