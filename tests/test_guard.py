"""repro.guard — fault-tolerant streaming sessions (ISSUE 9).

Chaos recovery suite: every fault class the guard layer claims to survive
is injected deterministically (``ChaosMonkey``) and must be (a) detected —
the right ``guard.*`` counter/health bit fires — and (b) recovered — the
escalation ladder or ``StreamSession.restore`` lands the session within
L1 1e-8 of a trustworthy static solve, bit-identical for crash replay.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.graph import (BatchUpdate, apply_batch, build_graph,
                              random_batch, random_graph, temporal_stream)
from repro.core.dynamic import dfp_pagerank
from repro.core.compact import dfp_pagerank_compact
from repro.core.pagerank import (PRParams, device_graph, init_ranks,
                                 static_pagerank)
from repro.core.reference import l1_error
from repro.guard import (ChaosMonkey, DeltaJournal, GuardConfig,
                         H_MASS_DRIFT, H_MAX_ITER, H_NONFINITE, HEALTH_OK,
                         JournalRecord, QuarantineReport, ValidationError,
                         describe_health, health_flags, health_word,
                         journal_path, validate_batch)
from repro.obs.spans import get_registry, reset_registry
from repro.stream import DeviceSnapshot, StreamSession, ingest
from repro.stream.delta import Delta

pytestmark = pytest.mark.guard

N, M = 512, 4096


@pytest.fixture()
def g():
    return random_graph(N, M, seed=0)


@pytest.fixture(scope="module")
def tstream():
    """Acceptance-scale temporal stream (paper §5.1.4 protocol, same sizes
    as tests/test_sharded_stream.py): chained DF-P drift on graphs this
    size stays under the L1 1e-8 acceptance bound — the tiny ``g`` fixture
    drifts a few e-8 legitimately and is only used where the comparison
    anchor is exact (recompute / audit resync / bit-identity)."""
    return temporal_stream(2500, 35000, n_batches=8, seed=3)


@pytest.fixture(autouse=True)
def _fresh_registry():
    reset_registry()
    yield
    reset_registry()


def _empty_batch():
    z = np.zeros(0, np.int64)
    return BatchUpdate(del_src=z, del_dst=z, ins_src=z, ins_dst=z)


# ---------------------------------------------------------------------------
# piece 1: ingest validation & quarantine
# ---------------------------------------------------------------------------

def test_validate_strict_raises_out_of_range(g):
    chaos = ChaosMonkey(seed=1)
    bad = chaos.corrupt_batch(_empty_batch(), N, mode="out_of_range", k=4)
    with pytest.raises(ValidationError):
        validate_batch(bad, N)


def test_validate_quarantine_strips_and_counts(g):
    chaos = ChaosMonkey(seed=1)
    good = random_batch(g, 16, seed=3)
    bad = chaos.corrupt_batch(good, N, mode="out_of_range", k=4)
    clean, report = validate_batch(bad, N, policy="quarantine")
    assert isinstance(report, QuarantineReport) and report.size == 4
    assert bool(report)
    # the clean remainder is exactly the original batch's pairs
    assert clean.ins_src.shape[0] == bad.ins_src.shape[0] - 4
    assert get_registry().counter("guard.quarantined") == 4
    assert get_registry().counter("guard.quarantined_batches") == 1


@pytest.mark.parametrize("mangle", [
    lambda b: BatchUpdate(b.del_src, b.del_dst, b.ins_src[:-1], b.ins_dst),
    lambda b: BatchUpdate(b.del_src, b.del_dst,
                          b.ins_src.astype(np.float64), b.ins_dst),
    lambda b: BatchUpdate(b.del_src, b.del_dst,
                          b.ins_src.reshape(1, -1), b.ins_dst.reshape(1, -1)),
])
def test_validate_structural_always_fatal(g, mangle):
    b = mangle(random_batch(g, 8, seed=4))
    for policy in ("raise", "quarantine"):
        with pytest.raises(ValidationError):
            validate_batch(b, N, policy=policy)


def test_ingest_strict_default_rejects_aliasing_ids(g):
    """Satellite (a): ids outside [0, n) alias other edges under the
    src*n + dst key packing — strict ingest must refuse them."""
    chaos = ChaosMonkey(seed=2)
    bad = chaos.corrupt_batch(random_batch(g, 8, seed=5), N,
                              mode="out_of_range")
    with pytest.raises(ValidationError):
        ingest(bad, N)
    # quarantine policy ingests the clean remainder
    delta = ingest(bad, N, policy="quarantine")
    assert delta.size > 0
    assert (delta.ins_dst >= 0).all() and (delta.ins_dst < N).all()


def test_ingest_dup_flood_coalesces(g):
    chaos = ChaosMonkey(seed=3)
    flooded = chaos.corrupt_batch(_empty_batch(), N, mode="dup_flood", k=64)
    delta = ingest(flooded, N)
    assert delta.ni == 1  # 64 copies of one pair -> one edge


# ---------------------------------------------------------------------------
# piece 2: health word — unit + engine loops (satellite d)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("delta,iters,mass,expect", [
    (1e-12, 10, 1.0, HEALTH_OK),
    (1e-3, 500, 1.0, H_MAX_ITER),          # budget out, still above tau
    (1e-12, 500, 1.0, HEALTH_OK),          # converged ON the last sweep
    (np.nan, 1, np.nan, H_NONFINITE),
    (1e-12, 10, 1.5, H_MASS_DRIFT),
    (np.nan, 500, 1.5, H_NONFINITE | H_MASS_DRIFT),
])
def test_health_word_bits(delta, iters, mass, expect):
    w = int(health_word(jnp.asarray(delta), jnp.asarray(iters),
                        jnp.asarray(mass), tau=1e-10, max_iter=500))
    assert w == expect, (describe_health(w), describe_health(expect))


def test_health_flags_decode():
    assert health_flags(HEALTH_OK) == ()
    assert describe_health(HEALTH_OK) == "ok"
    assert health_flags(H_MAX_ITER | H_MASS_DRIFT) == ("max_iter",
                                                       "mass_drift")


def _solve_with_health(engine, g, params):
    """Run one engine loop with health=True; returns (r, iters, hw)."""
    dg = device_graph(g, d_p=16, tile=64)
    if engine == "static":
        return static_pagerank(dg, init_ranks(g.n), params, health=True)
    b = random_batch(g, 32, seed=9)
    delta = ingest(b, g.n)
    g2 = apply_batch(g, b)
    r0, _ = static_pagerank(dg, init_ranks(g.n), PRParams())
    snap = DeviceSnapshot(g2, d_p=16, tile=64)
    db = delta.to_device()
    if engine == "dense":
        return dfp_pagerank(snap, r0, db, params, health=True)
    return dfp_pagerank_compact(snap, None, r0, db, params, health=True)


@pytest.mark.parametrize("engine", ["static", "dense", "compact"])
def test_health_trips_exactly_at_budget_exhaustion(g, engine):
    """Satellite (d): across engine loops the H_MAX_ITER bit is set exactly
    when iters == max_iter AND the final L∞ delta is still above tau."""
    # full budget: converges, word clean
    r, iters, hw = _solve_with_health(engine, g, PRParams())
    assert int(hw) == HEALTH_OK, describe_health(int(hw))
    assert int(iters) < PRParams().max_iter
    # starved budget: exits at max_iter with delta > tau -> flag set
    r, iters, hw = _solve_with_health(engine, g,
                                      PRParams(max_iter=1))
    assert int(iters) == 1
    assert int(hw) & H_MAX_ITER, describe_health(int(hw))


@pytest.mark.parametrize("engine", ["static", "dense", "compact"])
def test_health_converged_on_final_sweep_is_clean(g, engine):
    """iters == max_iter alone must NOT trip: pin max_iter to the exact
    iteration count of the converged solve and re-run."""
    r, iters, hw = _solve_with_health(engine, g, PRParams())
    assert int(hw) == HEALTH_OK
    r2, iters2, hw2 = _solve_with_health(
        engine, g, PRParams(max_iter=int(iters)))
    assert int(iters2) == int(iters)
    assert int(hw2) == HEALTH_OK, describe_health(int(hw2))


def test_nan_poison_detected_in_one_sweep(g):
    """NaN > tau is False: a poisoned solve exits after ONE sweep with the
    nonfinite bit set instead of spinning to max_iter."""
    chaos = ChaosMonkey(seed=5)
    dg = device_graph(g, d_p=16, tile=64)
    r0, _ = static_pagerank(dg, init_ranks(g.n), PRParams())
    b = random_batch(g, 16, seed=11)
    delta = ingest(b, g.n)
    snap = DeviceSnapshot(apply_batch(g, b), d_p=16, tile=64)
    r_bad = chaos.poison_ranks(r0, mode="nan", k=2)
    r, iters, hw = dfp_pagerank(snap, r_bad, delta.to_device(), PRParams(),
                                health=True)
    assert int(hw) & H_NONFINITE
    assert int(iters) <= 2, int(iters)


# ---------------------------------------------------------------------------
# session integration: noop, recompute, ladder, audit
# ---------------------------------------------------------------------------

def test_empty_batch_is_noop(g):
    """Satellite (b): an empty delta skips snapshot, solve and journal."""
    sess = StreamSession(g, guard=GuardConfig())
    r_before = sess.ranks
    r = sess.apply(_empty_batch())
    st = sess.history[-1]
    assert st.engine == "noop" and st.batch_size == 0 and st.iters == 0
    assert st.snapshot.rows_touched == 0 and st.solve_s == 0.0
    assert r is r_before  # not even a copy
    assert get_registry().counter("session.engine.noop") == 1
    assert sess._batch_idx == 0  # noops hold no sequence number


def test_fully_quarantined_batch_is_noop(g):
    sess = StreamSession(g, guard=GuardConfig(policy="quarantine"))
    chaos = ChaosMonkey(seed=6)
    bad = chaos.corrupt_batch(_empty_batch(), N, mode="out_of_range", k=4)
    sess.apply(bad)
    st = sess.history[-1]
    assert st.engine == "noop" and st.quarantined == 4


def test_recompute_records_history_and_counter(g):
    """Satellite (c): recompute() is visible in the accounting stream."""
    sess = StreamSession(g)
    h0 = len(sess.history)
    r = sess.recompute()
    assert len(sess.history) == h0 + 1
    st = sess.history[-1]
    assert st.engine == "recompute" and st.iters > 0 and st.solve_s > 0
    assert get_registry().counter("session.recompute") == 1
    assert l1_error(np.asarray(sess.flat_ranks()),
                    np.asarray(sess.static_reference())) < 1e-12


def test_ladder_recovers_forced_nonconvergence(tstream):
    base, batches = tstream
    sess = StreamSession(base, d_p=16, tile=64, guard=GuardConfig())
    chaos = ChaosMonkey(seed=7)
    chaos.force_nonconvergence(sess)          # max_iter=1 per batch
    sess.apply(batches[0])
    st = sess.history[-1]
    assert st.health & H_MAX_ITER
    assert st.escalations >= 1
    obs = get_registry()
    assert obs.counter("guard.unhealthy") == 1
    assert obs.counter("guard.health.max_iter") == 1
    assert obs.counter("guard.escalate.dense") == 1
    assert obs.counter("guard.escalate.success") == 1
    # recovery used the full-budget recovery params: within 1e-8 of a
    # full-budget static solve on the updated snapshot
    ref, _ = static_pagerank(sess.snap.dg, init_ranks(sess.n),
                             sess.params._replace(max_iter=500))
    assert l1_error(np.asarray(sess.flat_ranks()), np.asarray(ref)) < 1e-8


def test_ladder_recovers_nan_poison(g):
    sess = StreamSession(g, guard=GuardConfig())
    chaos = ChaosMonkey(seed=8)
    sess.ranks = chaos.poison_ranks(sess.ranks, mode="nan", k=1, idx=[3])
    sess.apply(random_batch(g, 16, seed=13))
    st = sess.history[-1]
    assert st.health & H_NONFINITE
    assert st.escalations >= 1
    assert get_registry().counter("guard.escalate.success") == 1
    assert l1_error(np.asarray(sess.flat_ranks()),
                    np.asarray(sess.static_reference())) < 1e-8


def test_ladder_exhaustion_counted(g):
    """retry_budget=0 walks no rungs and reports exhaustion."""
    sess = StreamSession(g, guard=GuardConfig(retry_budget=0))
    ChaosMonkey(seed=9).force_nonconvergence(sess)
    sess.apply(random_batch(g, 32, seed=14))
    obs = get_registry()
    assert obs.counter("guard.unhealthy") == 1
    assert obs.counter("guard.escalate.exhausted") == 1
    assert obs.counter("guard.escalate.success") == 0


def test_audit_resyncs_frozen_lane_corruption(g):
    """A finite bit-flip OUTSIDE the batch frontier survives the solve (the
    lane is never re-swept — DF-P freezes unaffected vertices by design);
    the periodic drift audit must catch and resync it."""
    chaos = ChaosMonkey(seed=10)
    # huge mass_tol: the per-solve watchdog is blind here on purpose, so
    # detection must come from the audit
    sess = StreamSession(g, guard=GuardConfig(audit_every=1, audit_tol=1e-8,
                                              mass_tol=1e30))
    sess.ranks = chaos.poison_ranks(sess.ranks, mode="bitflip", k=1, idx=[2])
    sess.apply(random_batch(g, 8, seed=15))
    obs = get_registry()
    assert obs.counter("guard.audit.runs") == 1
    assert obs.counter("guard.audit.resync") == 1
    assert l1_error(np.asarray(sess.flat_ranks()),
                    np.asarray(sess.static_reference())) < 1e-8


def test_mass_tol_override_reaches_watchdog(g):
    """GuardConfig.mass_tol re-judges the engines' baked-in default."""
    sess = StreamSession(g, guard=GuardConfig(mass_tol=1e-12))
    sess.apply(random_batch(g, 16, seed=16))
    # healthy chained DF-P drifts Σ R by O(tau_f) > 1e-12: with a
    # pathologically tight tolerance the drift bit must fire
    st = sess.history[-1]
    assert st.health & H_MASS_DRIFT
    assert get_registry().counter("guard.health.mass_drift") >= 1


# ---------------------------------------------------------------------------
# piece 3: journal + checkpoint / restore
# ---------------------------------------------------------------------------

def _zigzag(n, k, seed):
    rng = np.random.default_rng(seed)
    return JournalRecord(
        seq=k, n=n,
        del_src=rng.integers(0, n, 3).astype(np.int32),
        del_dst=rng.integers(0, n, 3).astype(np.int32),
        ins_src=rng.integers(0, n, 5).astype(np.int32),
        ins_dst=rng.integers(0, n, 5).astype(np.int32))


def test_journal_roundtrip(tmp_path):
    path = journal_path(str(tmp_path))
    j = DeltaJournal(path)
    recs = [_zigzag(N, k, k) for k in range(1, 6)]
    for r in recs:
        j.append(r)
    j.close()
    out, truncated = DeltaJournal.scan(path)
    assert not truncated and len(out) == 5
    for a, b in zip(recs, out):
        assert a.seq == b.seq and a.n == b.n
        for f in ("del_src", "del_dst", "ins_src", "ins_dst"):
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


def test_journal_torn_tail_longest_prefix(tmp_path):
    path = journal_path(str(tmp_path))
    j = DeltaJournal(path)
    for k in range(1, 6):
        j.append(_zigzag(N, k, k))
    j.close()
    size = os.path.getsize(path)
    ChaosMonkey(seed=11).truncate_journal(path, nbytes=size - 7)
    out, truncated = DeltaJournal.scan(path)
    assert truncated
    assert len(out) == 4  # exactly the records before the tear
    assert [r.seq for r in out] == [1, 2, 3, 4]
    assert get_registry().counter("guard.journal.truncated") == 1


def test_restore_bit_identical(tmp_path, g):
    """Acceptance: kill-and-restore replay is BIT-identical — ranks and the
    full snapshot state (free-list order included)."""
    d = str(tmp_path)
    sess = StreamSession(g, guard=GuardConfig(), journal_dir=d,
                         checkpoint_every=2)
    for i in range(5):
        sess.apply(random_batch(sess.snap.graph(), 32, seed=20 + i))
    sess.close()

    restored = StreamSession.restore(d)
    assert restored._batch_idx == sess._batch_idx == 5
    assert np.array_equal(np.asarray(sess.ranks), np.asarray(restored.ranks))
    A, ea = sess.snap.state_dict()
    B, eb = restored.snap.state_dict()
    assert set(A) == set(B)
    for k in A:
        assert np.array_equal(np.asarray(A[k]), np.asarray(B[k])), k
    assert ea == eb
    assert get_registry().counter("guard.restores") == 1
    # and the restored session keeps streaming identically
    b = random_batch(sess.snap.graph(), 16, seed=99)
    r1, r2 = sess.apply(b), restored.apply(b)
    assert np.array_equal(np.asarray(r1), np.asarray(r2))


@pytest.mark.parametrize("scatter_impl", ["jnp", "pallas"])
def test_restore_bit_identical_after_extent_step(tmp_path, scatter_impl):
    """The same kill-and-restore after a device extent stepped: the
    checkpoint restores those extents, not the smaller ones a fresh build
    of its graph gets, and the ranks stay bit-identical."""
    n = 600
    v = np.arange(n, dtype=np.int32)
    ring = build_graph(n, v, (v + 1) % n)     # every row in bucket 0
    fan_in = BatchUpdate(del_src=np.zeros(0, np.int32),
                         del_dst=np.zeros(0, np.int32),
                         ins_src=np.full(20, 5, np.int32),
                         ins_dst=np.arange(7, 27, dtype=np.int32))
    d = str(tmp_path)
    sess = StreamSession(ring, d_p=8, tile=32, journal_dir=d,
                         checkpoint_every=2, scatter_impl=scatter_impl)
    sess.apply(fan_in)              # 20 rows into bucket 1, extent 8
    assert get_registry().counter("snapshot.extent_grows") == 1
    for i in range(2):
        sess.apply(random_batch(sess.snap.graph(), 16, seed=70 + i))
    sess.close()

    restored = StreamSession.restore(d)
    stepped = sess.snap.state_dict()[1]["extents"]
    assert restored.snap.state_dict()[1]["extents"] == stepped
    fresh = DeviceSnapshot(sess.snap.graph(), d_p=8, tile=32)
    assert fresh.state_dict()[1]["extents"] != stepped
    assert np.array_equal(np.asarray(sess.ranks), np.asarray(restored.ranks))
    b = random_batch(sess.snap.graph(), 16, seed=98)
    r1, r2 = sess.apply(b), restored.apply(b)
    assert np.array_equal(np.asarray(r1), np.asarray(r2))


def test_restore_survives_torn_journal(tmp_path, tstream):
    base, batches = tstream
    d = str(tmp_path)
    sess = StreamSession(base, d_p=16, tile=64, journal_dir=d,
                         checkpoint_every=3)
    for b in batches[:5]:
        sess.apply(b)
    sess.close()
    # tear the tail: the torn record is dropped, everything to the last
    # intact record replays on top of the step-3 checkpoint
    size = os.path.getsize(journal_path(d))
    ChaosMonkey(seed=12).truncate_journal(journal_path(d), nbytes=size - 3)
    restored = StreamSession.restore(d)
    assert 4 <= restored._batch_idx <= 5
    assert restored._batch_idx == 4
    ref = restored.static_reference()
    assert l1_error(np.asarray(restored.flat_ranks()),
                    np.asarray(ref)) < 1e-8


def test_restore_config_fidelity(tmp_path, g):
    d = str(tmp_path)
    guard = GuardConfig(policy="quarantine", retry_budget=3, audit_every=7)
    sess = StreamSession(g, params=PRParams(tau_f=1e-9, tau_p=1e-9,
                                            max_iter=321),
                         guard=guard, journal_dir=d, checkpoint_every=1,
                         engine="dense", d_p=32, tile=128)
    sess.apply(random_batch(g, 8, seed=50))
    sess.close()
    restored = StreamSession.restore(d)
    assert restored.params == sess.params
    assert restored.guard == guard
    assert restored.engine == "dense"
    assert restored._d_p == 32 and restored._tile == 128


def test_journal_write_ahead_ordering(tmp_path, g):
    """The journal record lands before the solve: a session killed right
    after apply() still has every applied batch on disk."""
    d = str(tmp_path)
    sess = StreamSession(g, journal_dir=d, checkpoint_every=0)
    for i in range(3):
        sess.apply(random_batch(sess.snap.graph(), 8, seed=60 + i))
    sess.close()
    recs, truncated = DeltaJournal.scan(journal_path(d))
    assert not truncated and [r.seq for r in recs] == [1, 2, 3]


# ---------------------------------------------------------------------------
# sharded session health (subprocess: XLA pins device count at first init)
# ---------------------------------------------------------------------------

_SHARDED = textwrap.dedent("""
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    from repro.core import l1_error, random_batch, random_graph
    from repro.guard import ChaosMonkey, GuardConfig, H_NONFINITE
    from repro.stream import StreamSession

    assert len(jax.devices()) == 4, jax.devices()
    mesh = jax.make_mesh((4,), ("i",))
    g = random_graph(1024, 8192, seed=1)
    sess = StreamSession(g, mesh=mesh, d_p=16, tile=64,
                         guard=GuardConfig())
    # healthy batch: clean word
    sess.apply(random_batch(g, 32, seed=2))
    assert sess.history[-1].health == 0, sess.history[-1]
    # NaN-poison a lane: sharded solve must flag + the ladder (sharded
    # retry -> recompute) must recover
    chaos = ChaosMonkey(seed=3)
    sess.ranks = chaos.poison_ranks(sess.ranks, mode="nan", k=1, idx=[5])
    sess.apply(random_batch(sess.snap.graph(), 16, seed=4))
    st = sess.history[-1]
    assert st.health & H_NONFINITE, st
    assert st.escalations >= 1
    err = l1_error(np.asarray(sess.flat_ranks()),
                   np.asarray(sess.static_reference()))
    assert err < 1e-8, err
    print("OK")
""")


@pytest.mark.slow
def test_sharded_guarded_session_4dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED], env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
