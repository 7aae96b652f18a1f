"""Pallas TPU kernel: in-place row scatter for streaming snapshot updates.

A batch Δ^t touches O(|Δ|) rows of the [n, d_p] ELL index matrix (or tile
slots of the [t_cap, tile] pool); rebuilding or copying the whole array per
batch would reintroduce the O(|E|) cost the stream subsystem exists to
avoid. This kernel writes *only* the edited rows, with the destination
aliased to the source buffer (``input_output_aliases``) so the update is
genuinely in place — graph mutation as a first-class device operation.

Mechanics: grid = one program per edited row; row ids arrive via scalar
prefetch and drive the *output* index map (the Pallas idiom for a
data-dependent scatter). Rows not visited by any program keep the aliased
input contents. Duplicate row ids are permitted only when they carry
identical contents — the pad convention is "repeat entry 0", which
satisfies this by construction. The [n, d] arrays are viewed as [n, 1, d]
so each program's (1, d) block spans the array's last two dimensions
whole, the block shape Mosaic accepts for any d.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.spans import get_registry as _obs
from .common import default_interpret as _default_interpret

__all__ = ["scatter_rows", "ell_scatter_rows"]


# Block indices must be int32 for Mosaic; a bare 0 in an index map becomes an
# int64 constant when x64 is on (the float64 session always runs with it).
_ZERO = np.int32(0)


def _copy_kernel(rows_ref, dst_ref, new_ref, out_ref):
    del rows_ref, dst_ref  # rows feed the index map; dst is only aliased
    out_ref[...] = new_ref[...]


def scatter_rows(dst: jnp.ndarray, rows: jnp.ndarray, new_rows: jnp.ndarray,
                 *, interpret: bool | None = None) -> jnp.ndarray:
    """out = dst with out[rows[i]] = new_rows[i]; dst's buffer is reused.

    dst: [n, d] ; rows: [K] int32 (pad by repeating rows[0]) ; new_rows: [K, d].
    """
    interpret = _default_interpret() if interpret is None else interpret
    n, d = dst.shape
    k = new_rows.shape[0]
    # trace-time only (the call site is jitted): counts kernel *builds*, and
    # rows are counted per build — re-executions of the cached computation
    # are invisible to host counters by design.
    _obs().inc("kernels.stream_scatter.calls")
    _obs().inc("kernels.stream_scatter.rows", k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),        # aliased, never read
            pl.BlockSpec((None, 1, d), lambda i, rows: (i, _ZERO, _ZERO)),
        ],
        out_specs=pl.BlockSpec((None, 1, d),
                               lambda i, rows: (rows[i], _ZERO, _ZERO)),
    )
    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, d), dst.dtype),
        input_output_aliases={1: 0},   # dst (after the prefetch arg) -> out
        interpret=interpret,
    )(rows, dst.reshape(n, 1, d), new_rows.reshape(k, 1, d))
    return out.reshape(n, d)


def ell_scatter_rows(ell_idx: jnp.ndarray, ell_mask: jnp.ndarray,
                     rows: jnp.ndarray, new_idx: jnp.ndarray,
                     new_mask: jnp.ndarray, *, interpret: bool | None = None):
    """Scatter edited (index, mask) row pairs of an ELL layout in place."""
    return (scatter_rows(ell_idx, rows, new_idx, interpret=interpret),
            scatter_rows(ell_mask, rows, new_mask, interpret=interpret))
