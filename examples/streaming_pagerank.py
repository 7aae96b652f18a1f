"""Quickstart: streaming DF-P PageRank with `repro.stream.StreamSession`.

Loads 90% of a synthetic temporal edge stream as the base graph (paper
§5.1.4), then feeds the remaining edges through a session batch by batch.
Every batch keeps ranks, frontier state, and both hybrid graph layouts
device-resident; snapshot maintenance is O(|Δ|), not O(|E|).

Run:  PYTHONPATH=src python examples/streaming_pagerank.py
"""
import jax

jax.config.update("jax_enable_x64", True)

from repro.core import temporal_stream
from repro.stream import StreamSession, replay

N, EDGES, BATCHES = 5_000, 80_000, 12


def main():
    base, batches = temporal_stream(N, EDGES, n_batches=BATCHES, seed=0)
    print(f"base graph: {base.n} vertices, {base.m} edges; "
          f"{len(batches)} insertion batches incoming")

    sess = StreamSession(base, d_p=64, tile=256)
    print(f"warm start: static PageRank converged in "
          f"{int(sess._init_iters)} iterations")

    records = replay(sess, batches, verify_every=4)
    for rec in records:
        h = rec.stats
        err = ("" if rec.l1_vs_static is None
               else f"  L1 vs from-scratch: {rec.l1_vs_static:.2e}")
        print(f"batch {rec.t:2d}: |Δ|={h.batch_size:5d}  engine={h.engine:7s}"
              f"  iters={h.iters:3d}  maintain="
              f"{(h.ingest_s + h.snapshot.host_s) * 1e3:6.1f}ms"
              f"  solve={h.solve_s * 1e3:6.1f}ms{err}")

    ids, vals = sess.topk(5)
    print("\ntop-5 vertices by rank:")
    for i, v in zip(ids, vals):
        print(f"  vertex {i:5d}  rank {v:.6f}")


if __name__ == "__main__":
    main()
