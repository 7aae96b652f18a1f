"""CPU rehearsal of distributed PageRank on 8 forced host devices: 1-D
vertex partition vs the beyond-paper 2-D edge partition, both validated
against the oracle — plus a sharded StreamSession chaining DF-P over a live
update stream (mirrors examples/streaming_pagerank.py at multi-device scale).

  PYTHONPATH=src python examples/distributed_pagerank.py

It pins itself to the CPU (JAX_PLATFORMS=cpu): the 8 devices are virtual
host devices, not chips. The sharded session on real chips is phase e of
`python chip_smoke.py --chips 4`.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.core import l1_error, powerlaw_graph, reference_pagerank, temporal_stream
from repro.core.distributed import (build_sharded, distributed_static_pagerank,
                                    sharded_caps, unshard_vector)
from repro.core.distributed2d import build_sharded_2d, pagerank_2d
from repro.stream import StreamSession, replay

g = powerlaw_graph(2_000, 30_000, seed=1)
ref = reference_pagerank(g)

# 1-D: vertices over all 8 devices; per-iteration all-gather of c (V floats).
# Every shard block is laid out by the same `build_hybrid_rows` primitive as
# the single-device hybrid, and the loop runs the same `rank_step` math.
mesh = jax.make_mesh((4, 2), ("data", "model"))
sg = build_sharded(g, 8, d_p=16, tile=64)
r0 = jnp.full((8, sg.n_loc), 1.0 / g.n, jnp.float64)
r1, it1 = distributed_static_pagerank(mesh, sg, r0)
print(f"1-D: {int(it1)} iters, caps={sharded_caps(sg)}, L1 vs oracle = "
      f"{l1_error(unshard_vector(r1, g.n), ref):.2e}")

# 2-D: edge blocks on a 2x2 sub-mesh; per-iteration gather is V/2 per device
mesh2 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
sg2 = build_sharded_2d(g, 2, 2, d_p=8)
rc, blk = sg2.out_deg.shape
r0b = jnp.full((rc, blk), 1.0 / g.n, jnp.float64)
r2, it2 = pagerank_2d(mesh2, sg2, r0b)
print(f"2-D: {int(it2)} iters, L1 vs oracle = "
      f"{l1_error(np.asarray(r2).reshape(-1)[:g.n], ref):.2e}")

# --- sharded streaming: chained multi-device DF-P over an update stream ---
# The session shards the snapshot over the mesh, maintains every shard's
# hybrid layout in place (touched rows only — no O(|E|) re-partition), and
# seeds each batch's frontier device-side.
base, batches = temporal_stream(4_000, 60_000, n_batches=6, seed=0)
sess = StreamSession(base, mesh=mesh, d_p=16, tile=64)
print(f"\nsharded stream: base {base.n} vertices / {base.m} edges over "
      f"{sess.snap.nd} shards (n_loc={sess.snap.n_loc}); warm start "
      f"{int(sess._init_iters)} iters")
for rec in replay(sess, batches, verify_every=2):
    h = rec.stats
    err = ("" if rec.l1_vs_static is None
           else f"  L1 vs from-scratch: {rec.l1_vs_static:.2e}")
    print(f"batch {rec.t}: |Δ|={h.batch_size:5d}  engine={h.engine}"
          f"  iters={h.iters:3d}  rows_touched={h.snapshot.rows_touched:4d}"
          f"  rebuilt={h.snapshot.rebuilt}{err}")

ids, vals = sess.topk(5)
print("\ntop-5 vertices by rank:")
for i, v in zip(ids, vals):
    print(f"  vertex {i:5d}  rank {v:.6f}")
