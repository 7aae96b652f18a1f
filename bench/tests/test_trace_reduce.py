"""The trace reduction: interval arithmetic, and a trace recorded on a
TPU v5e (``data/``, a short traced window of one cell)."""
import glob
import os

import numpy as np
import pytest

from bench.trace_reduce import (gaps, reduce_trace, stable_op_name,
                                union_length)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_length_merges_overlaps():
    iv = np.array([[0, 10], [5, 15], [20, 25], [21, 22]], np.float64)
    assert union_length(iv) == 20
    assert union_length(np.zeros((0, 2))) == 0


def test_gaps_are_the_complement_in_the_window():
    iv = np.array([[2, 4], [3, 6], [8, 9]], np.float64)
    assert gaps(iv, 0, 10) == [(0, 2), (6, 8), (9, 10)]
    assert gaps(np.zeros((0, 2)), 0, 5) == [(0, 5)]


def test_stable_op_name_drops_numbering_and_layout():
    text = ("%fusion.155 = f32[2097152]{0:T(1024)S(1)} fusion(f32[65536]"
            "{0:T(1024)S(1)} %copy-done.4), kind=kCustom")
    assert stable_op_name(text, "jit__static_pagerank(8170642894)") == \
        "jit__static_pagerank/fusion f32[2097152]"
    assert stable_op_name("copy-start.4.2", "") == "copy-start"
    tup = "%w.3 = (f32[8]{0:T(8)}, s32[]{:T(128)}) while(%t), body=%b"
    assert stable_op_name(tup, "m") == "m/w (f32[8], s32[])"


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.xplane.pb"))))
def test_recorded_chip_trace(path):
    s = reduce_trace(path)
    assert s.devices == 1
    assert 0 < s.busy_s <= s.window_s
    idle = sum(g for _, g in s.gaps)
    assert abs(s.busy_s + idle - s.window_s) < 1e-6 * s.window_s + 1e-6
    assert s.op_s and all(v > 0 for _, v in s.op_s)
    leaves = sum(v for _, v in s.op_s)
    assert 0.5 * s.busy_s < leaves <= s.busy_s * (1 + 1e-9)
    assert not any("/while " in k for k, _ in s.op_s)
    assert {name for name, _ in s.gaps} <= {"session.solve", "bench.apply",
                                            "harness"}
