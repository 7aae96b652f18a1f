"""Sharded streaming: ShardedSnapshot maintenance + mesh-mode StreamSession.

Acceptance bar (ISSUE 2): on a >= 2-shard host mesh, every batch of a
replayed stream ends within L1 1e-8 of a from-scratch static solve, with
per-batch maintenance restaging only touched rows — no O(|E|) re-partition.
Subprocess: XLA fixes the device count at first init.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np, jax.numpy as jnp
    from repro.core import temporal_stream, powerlaw_graph, l1_error
    from repro.core.distributed import sharded_caps
    from repro.stream import ShardedSnapshot, StreamSession, ingest, replay
    from repro.stream.replay import churn_workload

    assert len(jax.devices()) == 4, jax.devices()
    mesh = jax.make_mesh((2, 2), ("data", "model"))

    # --- insertion-only temporal stream (paper 5.1.4 protocol) ------------
    base, batches = temporal_stream(2500, 35000, n_batches=6, seed=3)
    sess = StreamSession(base, mesh=mesh, d_p=16, tile=64)
    caps0 = sharded_caps(sess.snap.sg)
    recs = replay(sess, batches, verify_every=1)
    for rec in recs:
        assert rec.l1_vs_static is not None and rec.l1_vs_static < 1e-8, (
            rec.t, rec.l1_vs_static)
        st = rec.stats
        assert st.engine == "sharded", st.engine
        # incremental maintenance, not O(|E|) re-partition: nothing rebuilt,
        # and the refresh touched only O(|batch|) rows of the stacked layout
        assert not st.snapshot.rebuilt, st.snapshot.rebuild_reason
        assert 0 < st.snapshot.rows_touched <= 4 * st.batch_size
    # capacity discipline: device shapes never changed across the stream
    assert sharded_caps(sess.snap.sg) == caps0

    # --- churn (deletions + degree crossings) on a power-law base ---------
    g = powerlaw_graph(1500, 25000, seed=4)
    sess2 = StreamSession(g, mesh=mesh, d_p=16, tile=64)
    for b in churn_workload(g, 0.003, 4, seed=9):
        sess2.apply(b)
        err = l1_error(np.asarray(sess2.flat_ranks()),
                       np.asarray(sess2.static_reference()))
        assert err < 1e-8, err
        assert not sess2.history[-1].snapshot.rebuilt

    # --- snapshot-level parity: maintained sg == freshly built sg ---------
    snap = sess2.snap
    from repro.core.distributed import build_sharded
    fresh = build_sharded(snap.graph(), snap.nd, d_p=16, tile=64,
                          **{k: v for k, v in sharded_caps(snap.sg).items()
                             if k in ("hi_cap", "t_cap")})
    # same edge multiset per shard row: compare row-sums of a random vector
    x = np.random.default_rng(0).random(snap.n_pad)
    from repro.core.distributed import _local_pull, _as_dict
    def pull_all(sg):
        d = _as_dict(sg)
        return np.stack([np.asarray(_local_pull(
            jax.tree.map(lambda v: np.asarray(v)[s], d), jnp.asarray(x)))
            for s in range(snap.nd)])
    np.testing.assert_allclose(pull_all(snap.sg), pull_all(fresh),
                               rtol=1e-12)

    # --- sharded session tracks the single-device session -----------------
    sess_sd = StreamSession(base, d_p=16, tile=64)
    sess_md = StreamSession(base, mesh=mesh, d_p=16, tile=64)
    for b in batches[:3]:
        sess_sd.apply(b)
        sess_md.apply(b)
    err = l1_error(np.asarray(sess_md.flat_ranks()),
                   np.asarray(sess_sd.flat_ranks()))
    assert err < 1e-8, err
    ids_sd, _ = sess_sd.topk(5)
    ids_md, _ = sess_md.topk(5)
    assert list(ids_sd) == list(ids_md), (ids_sd, ids_md)
    print("OK")
""")


@pytest.mark.slow
def test_sharded_stream_4dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
