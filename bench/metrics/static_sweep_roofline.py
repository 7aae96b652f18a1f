"""static_sweep_roofline: percent of the HBM roofline the window's static
solves reach. The least time of a solve is the reference's sweeps of the
same graph times bytes_per_sweep (bench/roofline.py), over the chip's HBM
bandwidth (bench/peaks.json); the time taken is the device's busy time in
the traced window, which holds nothing but the solves."""
from bench.roofline import bytes_per_sweep


def read(run):
    if run.trace is None or not run.solves or run.peaks is None \
            or not run.ref_sweeps:
        return None
    sweeps = run.ref_sweeps * len(run.solves)
    least_s = sweeps * bytes_per_sweep(run.n, run.edges) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace.busy_s
