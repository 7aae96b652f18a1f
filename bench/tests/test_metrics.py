"""The readers of the static cell take the work from the reference, not
from the program's own sweep count."""
from bench.harness import Run, _reader


def _run(iters, ref_sweeps):
    calls = [{"wall_s": 2.0, "iters": it} for it in iters]
    return Run(mix={"loop": "static"}, n=100, edges=1000, setup_s=1.0,
               window_s=4.0, calls=calls, ref_sweeps=ref_sweeps)


def test_static_rate_counts_the_reference_sweeps():
    read = _reader("static_edges_per_s")
    assert read(_run([75, 75], 75)) == 1000 * 75 * 2 / 4.0
    # a program that reports fewer or more sweeps does not move the rate
    assert read(_run([10, 10], 75)) == read(_run([200, 200], 75))
    assert read(_run([75], None)) is None
