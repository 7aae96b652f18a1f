"""Compile-only tests for a TPU v5e: the served path's jitted steps and the
one kernel a session can reach, at real widths, compiled for a described
(not attached) v5e. Nothing runs; a refusal by the TPU compiler fails here
instead of on the chip.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every test worker
imports every test file. Where it cannot be described, every test skips
but the CPU lowering check of the pull, which needs no topology.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import device_graph, powerlaw_graph
from repro.core.distributed import _FIELDS, _solver
from repro.core.dynamic import DeviceBatch, _dfp_pagerank
from repro.core.frontier import caps_for_parts
from repro.core.pagerank import DeviceGraph, EllBlock, PRParams, \
    _static_pagerank, pull_sum
from repro.kernels.stream_scatter import scatter_rows
from repro.stream.snapshot import _scatter_pair

# The layout `DeviceSnapshot` builds for `powerlaw_graph(2**18, 2**22)`: its
# bucket widths and host capacities, tile 256 (the largest shapes its device
# arrays can take; they hold the rows in use plus a margin).
N = 1 << 18
WIDTHS = (1, 8, 16, 64)
BUCKET_CAPS = (2 * N, 2 * N, 2 * N, N // 16)
N_HI, T_CAP, TILE = 512, N // 16, 256
PARAMS = PRParams(tau_f=1e-9, tau_p=1e-9)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)


def _graph_shapes(sharding) -> DeviceGraph:
    s = _spec(sharding)
    return DeviceGraph(
        buckets=tuple(EllBlock(s((c,), jnp.int32), s((c, w), jnp.int32),
                               s((c, w), jnp.float32))
                      for c, w in zip(BUCKET_CAPS, WIDTHS)),
        bucket_of=s((N,), jnp.int32), slot_of=s((N,), jnp.int32),
        hi_ids=s((N_HI,), jnp.int32), hi_tiles=s((T_CAP, TILE), jnp.int32),
        hi_tmask=s((T_CAP, TILE), jnp.float32),
        hi_rowmap=s((T_CAP,), jnp.int32), is_low=s((N,), jnp.bool_),
        out_deg=s((N,), jnp.int32))


@pytest.fixture(scope="module")
def static_compiled(one_chip):
    r = jax.ShapeDtypeStruct((N,), jnp.float64, sharding=one_chip)
    return _static_pagerank.lower(_graph_shapes(one_chip), r,
                                  params=PARAMS).compile()


def test_static_pagerank_compiles(static_compiled):
    assert static_compiled.memory_analysis().temp_size_in_bytes > 0


def _hlo_ops(text, op):
    """Output types of every `op` instruction in compiled HLO text."""
    return re.findall(rf"^\s*(?:ROOT )?%\S+ = (.+?) {op}\(", text, re.M)


def test_static_pagerank_pull_reads_by_gather(static_compiled):
    """The pull writes each vertex by one gather: no scatter has an [N]
    output (the hi side's segment-sum is [N_HI]), and the one pull of the
    sweep reads [N] by at most one pair of f32 gathers (a float64 value is
    two f32 words on v5e)."""
    text = static_compiled.as_text()
    assert not [t for t in _hlo_ops(text, "scatter") if f"[{N}]" in t]
    reads = [t for t in _hlo_ops(text, "gather")
             if t.startswith(f"f32[{N}]{{")]
    assert 1 <= len(reads) <= 2


def _stablehlo_results(text, op):
    """Result types of every `stablehlo.<op>` in StableHLO text."""
    return re.findall(rf"stablehlo\.{op}\"?.*?\) -> (tensor<[^>]*>)",
                      text, re.S)


def test_pull_sum_lowers_without_vertex_scatter():
    """On any backend (no topology needed): `pull_sum` lowers with no
    scatter over [n] and reads [n] by exactly one gather."""
    g = powerlaw_graph(1000, 8000, seed=1)
    dg = device_graph(g, d_p=16, tile=8)
    parts = [b.rows.shape[0] for b in dg.buckets] + [dg.n_hi_cap]
    assert len(parts) > 2 and g.n not in parts
    text = jax.jit(pull_sum).lower(dg, jnp.ones(g.n)).as_text()
    vec = f"tensor<{g.n}xf64>"
    scatters = _stablehlo_results(text, "scatter")
    assert scatters and vec not in scatters       # the segment-sum remains
    assert _stablehlo_results(text, "gather").count(vec) == 1


def test_dfp_pagerank_frontier_caps_compiles(one_chip):
    s = _spec(one_chip)
    caps = caps_for_parts(BUCKET_CAPS, N_HI, T_CAP, N, est=2000)
    batch = DeviceBatch(*(s((1024,), jnp.int32) for _ in range(4)))
    c = _dfp_pagerank.lower(_graph_shapes(one_chip), _graph_shapes(one_chip),
                            s((N,), jnp.float64), batch, params=PARAMS,
                            caps=caps, health=True).compile()
    assert c.memory_analysis() is not None


@pytest.mark.parametrize("rows,width", [(2 * N, 16), (T_CAP, TILE)])
def test_snapshot_scatter_pair_compiles(one_chip, rows, width):
    s = _spec(one_chip)
    k = 512
    c = _scatter_pair.lower(s((rows, width), jnp.int32),
                            s((rows, width), jnp.float32),
                            s((k,), jnp.int32), s((k, width), jnp.int32),
                            s((k, width), jnp.float32)).compile()
    assert c.memory_analysis() is not None


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
@pytest.mark.parametrize("width", [1, 4, 8, 16, 32, 64, TILE])
def test_stream_scatter_kernel_compiles(one_chip, width, dtype):
    s = _spec(one_chip)
    k = 1024
    fn = jax.jit(lambda a, r, v: scatter_rows(a, r, v, interpret=False))
    c = fn.lower(s((2 * N, width), dtype), s((k,), jnp.int32),
                 s((k, width), dtype)).compile()
    assert "tpu_custom_call" in c.as_text()


def test_sharded_dfp_pagerank_compiles_4chips(topo):
    nd, n_loc = 4, N // 4
    mesh = Mesh(np.array(topo.devices[:4]), ("i",))
    s = _spec(NamedSharding(mesh, P(("i",))))
    caps_b = tuple(c // 4 for c in BUCKET_CAPS)
    sgd = {
        "buckets": tuple(EllBlock(s((nd, c), jnp.int32),
                                  s((nd, c, w), jnp.int32),
                                  s((nd, c, w), jnp.float32))
                         for c, w in zip(caps_b, WIDTHS)),
        "hi_pos": s((nd, N_HI), jnp.int32),
        "hi_tiles": s((nd, T_CAP, TILE), jnp.int32),
        "hi_tmask": s((nd, T_CAP, TILE), jnp.float32),
        "hi_rowmap": s((nd, T_CAP), jnp.int32),
        "out_deg": s((nd, n_loc), jnp.int32),
        "valid": s((nd, n_loc), jnp.bool_)}
    assert set(sgd) == set(_FIELDS)
    caps = caps_for_parts(caps_b, N_HI, T_CAP, n_loc, est=2000)
    fn = _solver(mesh, PARAMS, N, True, 1, False, caps, True)
    flags = s((nd, n_loc), jnp.bool_)
    c = fn.lower(sgd, s((nd, n_loc), jnp.float64), flags, flags).compile()
    text = c.as_text()
    assert "all-gather" in text and "all-reduce" in text
