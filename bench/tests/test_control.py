"""The control: the float32 reference in the program's place fails the
check of every cell, at the rehearsal size (bench/control.py runs it at the
cells' own sizes)."""
import pytest

from bench.control import control_gaps


@pytest.mark.parametrize("cell", ["rmat.dfp_random", "rmat.static"])
def test_float32_control_fails(cell):
    gaps, limit = control_gaps(cell, seed=17, batches=6, warm=1,
                               rehearse=True)
    assert len(gaps) == 6
    assert max(gaps) > limit
