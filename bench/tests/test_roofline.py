"""Bytes per sweep and the peak table."""
import pytest

from bench.peaks import peaks_for
from bench.roofline import bytes_per_sweep

from repro.core import build_hybrid, layout_slot_stats, powerlaw_graph


@pytest.mark.parametrize("n,m", [(1000, 20000), (4096, 65536)])
def test_bytes_per_sweep_within_the_layouts_bytes(n, m):
    g = powerlaw_graph(n, m, seed=1)
    st = layout_slot_stats(build_hybrid(g, d_p=64, tile=256))
    # the layout reads an index and a mask per slot, and the same
    # per-vertex vectors
    layout_bytes = 8 * st["gathered_slots"] + 20 * g.n
    assert st["real_edges"] == g.m
    assert bytes_per_sweep(g.n, g.m) <= layout_bytes


def test_known_device_has_peaks():
    p = peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_raises():
    with pytest.raises(KeyError):
        peaks_for("cpu")
