"""The readers of the DF-P engine's two solve programs, the compact loop
and the dense finish, on synthetic op lists and on the DF-P trace recorded
on a TPU v5e."""
import pytest

from bench.harness import _reader
from bench.tests.test_stages import DFP, _run, _summary
from bench.trace_reduce import reduce_trace


@pytest.mark.parametrize("name,program", [
    ("compact_loop_ms", "jit__compact_loop"),
    ("dense_finish_ms", "jit__dense_finish")])
def test_solve_program_readers(name, program):
    read = _reader(name)
    op_s = [(program + "/fusion f32[1024]", 0.006),
            (program + "/gather f32[983040]", 0.002),
            ("jit__dfp_pagerank/fusion f32[1024]", 4.0),
            (program + "_x/copy s32[8]", 9.0)]
    assert read(_run(_summary(op_s, []))) == pytest.approx(4.0)
    assert read(_run(_summary([], []))) == 0.0
    assert read(_run(None)) is None
    static = _run(_summary(op_s, []))
    static.mix = {"loop": "static"}
    assert read(static) is None


@pytest.mark.parametrize("path", DFP)
def test_solve_program_readers_on_a_chip_trace(path):
    """The recorded DF-P batch overflowed its compact loop, so both of the
    engine's programs ran on the device."""
    run = _run(reduce_trace(path), iters=(20,))
    assert _reader("compact_loop_ms")(run) > 0
    assert _reader("dense_finish_ms")(run) > 0
