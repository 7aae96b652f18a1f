"""repro.obs: trace parity, spans/counters, structured sinks, the gate.

The load-bearing invariant is *telemetry neutrality*: `trace=True` threads a
TraceBuffer through every engine's while_loop but must not change a single
bit of the rank output or the iteration count. Host spans/counters live
entirely outside jit, so only their bookkeeping needs testing. The sharded
engines get the same parity check under a forced 4-device host mesh in a
subprocess (XLA fixes the device count at first init).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (apply_batch, batch_to_device, device_graph,
                        df_pagerank, df_pagerank_compact, dfp_pagerank,
                        dfp_pagerank_compact, dt_pagerank,
                        forward_device_graph, init_ranks, nd_pagerank,
                        powerlaw_graph, random_batch, static_pagerank)
from repro.obs.report import (RunReport, load_report, parse_derived,
                              validate_report)
from repro.obs.spans import Registry, get_registry, reset_registry
from repro.obs.trace import (ENGINE_IDS, maybe_summary, trace_init,
                             trace_record, trace_summary)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- TraceBuffer primitives ---------------------------------------------------

def test_trace_init_sentinels_and_record():
    tb = trace_init(8, jnp.float64, "dfp")
    assert int(tb.engine) == ENGINE_IDS["dfp"]
    assert np.all(np.isnan(np.asarray(tb.linf)))
    assert np.all(np.asarray(tb.frontier) == -1)
    tb = trace_record(tb, jnp.asarray(3), linf=0.5, frontier=7,
                      delta_n=2, pruned=1)
    assert np.asarray(tb.linf)[3] == 0.5
    assert np.asarray(tb.frontier)[3] == 7
    # untouched lanes keep their sentinels
    assert np.isnan(np.asarray(tb.linf)[0])
    assert np.asarray(tb.pruned)[0] == -1


def test_trace_record_out_of_cap_drops():
    tb = trace_init(4, jnp.float64, "static")
    tb2 = trace_record(tb, jnp.asarray(9), linf=1.0, frontier=1,
                       delta_n=0, pruned=0)
    np.testing.assert_array_equal(np.asarray(tb2.frontier),
                                  np.asarray(tb.frontier))


def test_trace_summary_trims_and_sanitizes():
    tb = trace_init(6, jnp.float64, "dfp_compact")
    tb = trace_record(tb, jnp.asarray(0), linf=jnp.inf, frontier=5,
                      delta_n=1, pruned=0)
    tb = trace_record(tb, jnp.asarray(1), linf=0.25, frontier=3,
                      delta_n=0, pruned=2)
    s = trace_summary(tb, 2)
    assert s["engine"] == "dfp_compact"
    assert s["iters"] == 2
    assert s["linf_delta"] == [None, 0.25]      # inf -> None (strict JSON)
    assert s["frontier"] == [5, 3]
    assert s["frontier_peak"] == 5 and s["frontier_final"] == 3
    assert s["linf_final"] == 0.25
    json.dumps(s, allow_nan=False)              # must be strict-JSON safe


def test_maybe_summary_passthrough():
    out, s = maybe_summary(("r", 3), False)
    assert out == ("r", 3) and s is None
    tb = trace_record(trace_init(4, jnp.float64, "nd"), jnp.asarray(0),
                      linf=0.1, frontier=2, delta_n=0, pruned=0)
    (r, it), s = maybe_summary(("r", 1, tb), True)
    assert r == "r" and it == 1 and s["engine"] == "nd"


# -- spans / counters ---------------------------------------------------------

def test_registry_spans_and_counters():
    reg = Registry()
    reg.inc("a")
    reg.inc("a", 4)
    assert reg.counter("a") == 5
    with reg.span("phase"):
        pass
    with reg.span("phase", annotate=True):
        pass
    st = reg.span_stats("phase")
    assert st.count == 2 and st.total_s >= 0.0
    rep = reg.report()
    assert rep["counters"]["a"] == 5
    assert rep["spans"]["phase"]["count"] == 2
    reg.reset()
    assert reg.report() == {"spans": {}, "counters": {}}


def test_default_registry_reset():
    reset_registry()
    get_registry().inc("x")
    assert get_registry().counter("x") == 1
    reset_registry()
    assert get_registry().counter("x") == 0


def test_span_timer_exceptions_still_recorded():
    reg = Registry()
    with pytest.raises(ValueError):
        with reg.span("boom"):
            raise ValueError()
    assert reg.span_stats("boom").count == 1


# -- engine parity: trace on == trace off (bit-identical) ---------------------

@pytest.fixture(scope="module")
def small_case():
    g0 = powerlaw_graph(800, 8000, seed=2)
    b = random_batch(g0, 0.003, seed=5)
    g = apply_batch(g0, b)
    caps = dict(d_p=16, tile=64)
    dg0 = device_graph(g0, **caps)
    dg = device_graph(g, **caps)
    fwd = forward_device_graph(g, **caps)
    db = batch_to_device(b, g.n)
    r_prev, _ = static_pagerank(dg0, init_ranks(g0.n))
    return dict(dg0=dg0, dg=dg, fwd=fwd, db=db, r_prev=r_prev, n=g.n)


def _assert_parity(run, engine, min_iters=1):
    r0, it0 = run(trace=False)
    r1, it1, tb = run(trace=True)
    np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))
    assert int(it0) == int(it1)
    s = trace_summary(tb, it1)
    assert s["engine"] == engine
    assert s["iters"] == int(it1) >= min_iters
    front = np.asarray(tb.frontier)
    assert np.all(front[:int(it1)] >= 0)        # every lane written
    if int(it1) < tb.cap:
        assert front[int(it1)] == -1            # and nothing beyond
    return s


def test_static_trace_parity(small_case):
    c = small_case
    s = _assert_parity(
        lambda trace: static_pagerank(c["dg"], init_ranks(c["n"]),
                                      trace=trace), "static", min_iters=2)
    assert s["frontier"] == [c["n"]] * s["iters"]


def test_nd_trace_parity(small_case):
    c = small_case
    _assert_parity(lambda trace: nd_pagerank(c["dg"], c["r_prev"],
                                             trace=trace), "nd")


def test_dt_trace_parity(small_case):
    c = small_case
    _assert_parity(
        lambda trace: dt_pagerank(c["dg"], c["dg0"], c["r_prev"], c["db"],
                                  trace=trace), "dt")


def test_df_dfp_dense_trace_parity(small_case):
    c = small_case
    _assert_parity(lambda trace: df_pagerank(c["dg"], c["r_prev"], c["db"],
                                             trace=trace), "df")
    s = _assert_parity(
        lambda trace: dfp_pagerank(c["dg"], c["r_prev"], c["db"],
                                   trace=trace), "dfp")
    assert all(p >= 0 for p in s["pruned"])


def test_compact_trace_parity(small_case):
    c = small_case
    _assert_parity(
        lambda trace: df_pagerank_compact(c["dg"], c["fwd"], c["r_prev"],
                                          c["db"], trace=trace), "df_compact")
    s = _assert_parity(
        lambda trace: dfp_pagerank_compact(c["dg"], c["fwd"], c["r_prev"],
                                           c["db"], trace=trace),
        "dfp_compact")
    # the frontier series must decay to a small tail (paper Fig. 3 shape)
    assert s["frontier"][-1] <= s["frontier_peak"]


_SHARDED_SCRIPT = textwrap.dedent("""
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np, jax.numpy as jnp
    from repro.core import powerlaw_graph, random_batch, apply_batch
    from repro.core.distributed import (build_sharded,
                                        distributed_static_pagerank,
                                        distributed_dfp_pagerank,
                                        initial_affected_sharded)
    from repro.core.distributed2d import build_sharded_2d, pagerank_2d
    from repro.obs.trace import trace_summary
    from repro.stream.delta import ingest

    assert len(jax.devices()) == 4, jax.devices()
    ND = 4
    g = powerlaw_graph(600, 5000, seed=3)
    mesh = jax.make_mesh((ND,), ("data",))
    sg = build_sharded(g, ND, d_p=8, tile=64)
    r0 = jnp.full((ND, sg.n_loc), 1.0 / g.n, jnp.float64)

    r, it = distributed_static_pagerank(mesh, sg, r0)
    rt, itt, tb = distributed_static_pagerank(mesh, sg, r0, trace=True)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(rt))
    assert int(it) == int(itt)
    s = trace_summary(tb, itt)
    assert s["engine"] == "static_1d" and s["frontier"][0] == g.n

    b = random_batch(g, 0.01, seed=4)
    g2 = apply_batch(g, b)
    sg2 = build_sharded(g2, ND, d_p=8, tile=64)
    db = ingest(b, g.n).to_device()
    dv0, dn0 = initial_affected_sharded(ND, sg2.n_loc, db)
    rd, itd = distributed_dfp_pagerank(mesh, sg2, r, dv0, dn0)
    rdt, itdt, tbd = distributed_dfp_pagerank(mesh, sg2, r, dv0, dn0,
                                              trace=True)
    np.testing.assert_array_equal(np.asarray(rd), np.asarray(rdt))
    assert int(itd) == int(itdt)
    sd = trace_summary(tbd, itdt)
    assert sd["engine"] == "dfp_1d" and sd["frontier_peak"] > 0

    mesh2 = jax.make_mesh((2, 2), ("data", "model"))
    sg2d = build_sharded_2d(g, 2, 2, d_p=8)
    rc, blk = sg2d.out_deg.shape
    r0b = jnp.full((rc, blk), 1.0 / g.n, jnp.float64)
    r2, it2 = pagerank_2d(mesh2, sg2d, r0b)
    r2t, it2t, tb2 = pagerank_2d(mesh2, sg2d, r0b, trace=True)
    np.testing.assert_array_equal(np.asarray(r2), np.asarray(r2t))
    assert int(it2) == int(it2t)
    assert trace_summary(tb2, it2t)["engine"] == "static_2d"
    print("OK")
""")


@pytest.mark.slow
def test_sharded_trace_parity_4dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# -- StreamSession threading --------------------------------------------------

def test_session_trace_and_counters():
    from repro.core import BatchUpdate
    from repro.stream import StreamSession
    g = powerlaw_graph(500, 4000, seed=6)
    g_ref = powerlaw_graph(500, 4000, seed=6)
    reset_registry()
    sess = StreamSession(g, d_p=16, tile=64, trace=True)
    ref = StreamSession(g_ref, d_p=16, tile=64)
    rng = np.random.default_rng(1)
    for _ in range(2):
        s = rng.integers(0, 500, 20).astype(np.int32)
        d = rng.integers(0, 500, 20).astype(np.int32)
        ok = s != d
        b = BatchUpdate(del_src=np.zeros(0, np.int32),
                        del_dst=np.zeros(0, np.int32),
                        ins_src=s[ok], ins_dst=d[ok])
        r = sess.apply(b)
        r_ref = ref.apply(b)
        np.testing.assert_array_equal(np.asarray(r), np.asarray(r_ref))
        st = sess.history[-1]
        assert st.trace is not None
        assert st.trace["iters"] == st.iters
        assert st.trace["engine"] in ("dfp", "dfp_compact")
        assert ref.history[-1].trace is None
    rep = get_registry().report()
    assert rep["counters"]["snapshot.inplace_batches"] == 4  # 2 sessions x 2
    assert sum(v for k, v in rep["counters"].items()
               if k.startswith("session.engine.")) == 4
    for name in ("session.ingest", "session.solve",
                 "snapshot.apply_net_delta", "snapshot.device_refresh"):
        assert rep["spans"][name]["count"] >= 2, name
    reset_registry()


# -- structured sinks ---------------------------------------------------------

def test_parse_derived():
    d = parse_derived("iters=25;edges_per_s=3.5e+07;tag=abc;flag")
    assert d["iters"] == 25.0
    assert d["edges_per_s"] == 3.5e7
    assert d["tag"] == "abc"
    assert d["flag"] is True
    assert parse_derived("") == {}


def test_report_roundtrip(tmp_path):
    rep = RunReport(name="t")
    rep.add("a/b", us_min=10.0, us_mean=12.0, us_std=1.0,
            derived={"iters": 5},
            trace={"engine": "static", "iters": 2,
                   "linf_delta": [0.5, None], "frontier": [3, 3],
                   "delta_n": [0, 0], "pruned": [0, 0],
                   "frontier_peak": 3, "frontier_final": 3,
                   "linf_final": None})
    rep.add("a/c", us_min=20.0)
    reg = Registry()
    reg.inc("k", 3)
    with reg.span("s"):
        pass
    rep.attach_registry(reg)

    pj = tmp_path / "r.json"
    pl = tmp_path / "r.jsonl"
    rep.write_json(str(pj))
    rep.write_jsonl(str(pl))
    for doc in (load_report(str(pj)), load_report(str(pl))):
        assert validate_report(doc) == []
        assert [b["name"] for b in doc["benchmarks"]] == ["a/b", "a/c"]
        assert doc["benchmarks"][1]["us_mean"] == 20.0   # defaulted to min
        assert doc["counters"]["k"] == 3
        assert doc["spans"]["s"]["count"] == 1


def test_validate_report_catches_breakage():
    assert validate_report({"schema": "nope", "benchmarks": []})
    assert validate_report({"schema": "repro.obs/bench-v1",
                            "benchmarks": [{"name": "x"}]})
    bad_trace = {"schema": "repro.obs/bench-v1", "benchmarks": [
        {"name": "x", "us_min": 1.0, "us_mean": 1.0, "us_std": 0.0,
         "trace": {"engine": "static"}}]}
    assert any("trace" in e for e in validate_report(bad_trace))
    good = {"schema": "repro.obs/bench-v1", "benchmarks": [
        {"name": "x", "us_min": 1.0, "us_mean": 1.0, "us_std": 0.0}]}
    assert validate_report(good) == []


# -- the regression gate ------------------------------------------------------

def _mk_report(path, scale=1.0, drop=None):
    rep = RunReport(name="gate")
    for name, us in [("b/fast", 400.0), ("b/slow", 90000.0)]:
        if name == drop:
            continue
        rep.add(name, us_min=us * scale, us_mean=us * scale, us_std=0.0)
    rep.write_json(str(path))


def test_check_gate(tmp_path):
    from repro.obs.check import main
    base = tmp_path / "base.json"
    same = tmp_path / "same.json"
    slow = tmp_path / "slow.json"
    miss = tmp_path / "miss.json"
    _mk_report(base)
    _mk_report(same)
    _mk_report(slow, scale=1.5)
    _mk_report(miss, drop="b/slow")
    assert main([str(same), str(base)]) == 0
    assert main([str(slow), str(base)]) != 0          # injected 50% slowdown
    assert main([str(slow), str(base), "--threshold", "0.6"]) == 0
    assert main([str(miss), str(base)]) != 0          # vanished benchmark
    assert main([str(base), str(slow)]) == 0          # faster is never a fail
    # --min-us skips sub-threshold benches entirely
    assert main([str(slow), str(base), "--min-us", "1e9"]) == 0
    # missing baseline: warn-and-pass, unless --strict
    gone = str(tmp_path / "gone.json")
    assert main([str(base), gone]) == 0
    assert main([str(base), gone, "--strict"]) != 0


def test_check_cli_subprocess(tmp_path):
    _mk_report(tmp_path / "a.json")
    _mk_report(tmp_path / "b.json", scale=1.5)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-m", "repro.obs.check",
         str(tmp_path / "b.json"), str(tmp_path / "a.json")],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    out2 = subprocess.run(
        [sys.executable, "-m", "repro.obs.check",
         str(tmp_path / "a.json"), str(tmp_path / "a.json")],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out2.returncode == 0, out2.stdout + out2.stderr


def test_seed_report_is_valid():
    doc = load_report(os.path.join(ROOT, "benchmarks", "seed",
                                   "BENCH_obs_seed.json"))
    assert validate_report(doc) == []
    names = {b["name"] for b in doc["benchmarks"]}
    assert any(n.startswith("static/") for n in names)
    assert any("dfp" in n for n in names)
    # the acceptance series: static + DF-P records carry iteration traces
    traces = {b["name"]: b["trace"] for b in doc["benchmarks"]
              if b.get("trace")}
    assert any(n.startswith("static/") for n in traces)
    assert any("dfp" in n for n in traces)


# -- stage names on the device (jax.named_scope) -------------------------------

def _stage_programs():
    """(name, lower thunk, scopes) for each program whose stages are named:
    the static sweep, the dense DF-P engine with frontier caps, the compact
    loop and its dense finish, and the snapshot's row scatter."""
    from repro.core.compact import _compact_loop, _dense_finish
    from repro.core.dynamic import _dfp_pagerank
    from repro.core.frontier import caps_for, initial_affected
    from repro.core.pagerank import PRParams, _static_pagerank
    from repro.stream.snapshot import _scatter_pair

    g0 = powerlaw_graph(300, 3000, seed=2)
    b = random_batch(g0, 0.01, seed=5)
    g = apply_batch(g0, b)
    dg = device_graph(g, d_p=16, tile=64)
    fwd = forward_device_graph(g, d_p=16, tile=64)
    db = batch_to_device(b, g.n)
    r = init_ranks(g.n)
    p = PRParams()
    dv, dn = initial_affected(g.n, db.del_src, db.del_dst, db.ins_src)
    caps = caps_for(dg, 64)
    kt = int(dg.hi_tiles.shape[0])
    solve = {"pr.pull", "pr.update", "pr.converge"}
    frontier = solve | {"pr.compact", "pr.expand"}
    idx = jnp.zeros((64, 8), jnp.int32)
    rows = jnp.arange(4, dtype=jnp.int32)
    return [
        ("static", lambda: _static_pagerank.lower(dg, r, p), solve),
        ("dfp_caps", lambda: _dfp_pagerank.lower(
            dg, fwd, r, db, p, None, False, caps, True), frontier),
        ("compact_loop", lambda: _compact_loop.lower(
            dg, fwd, r, dv, dn, p, 64, kt, 64, True), frontier),
        ("dense_finish", lambda: _dense_finish.lower(
            dg, r, dv, dn, p, True, None, jnp.asarray(0, jnp.int32), True),
         solve | {"pr.expand"}),
        ("scatter_pair", lambda: _scatter_pair.lower(
            idx, idx.astype(jnp.float32), rows, idx[:4] + 1,
            idx[:4].astype(jnp.float32)), {"snapshot.scatter"}),
    ]


_STAGE_CASES = ["static", "dfp_caps", "compact_loop", "dense_finish",
                "scatter_pair"]


def _stage_case(name):
    return {c[0]: c for c in _stage_programs()}[name]


@pytest.mark.parametrize("name", _STAGE_CASES)
def test_stage_scopes_in_lowered_program(name):
    import re
    _, lower, scopes = _stage_case(name)
    text = lower().as_text(debug_info=True)
    named = set(re.findall(r"(?:pr|snapshot)\.[a-z]+", text))
    assert scopes <= named, (scopes - named)


def _compiled_body(compiled):
    """The compiled HLO without op metadata and without the trailing
    source-location tables: the program as the device runs it."""
    import re
    tables = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
    blocks = [blk for blk in compiled.as_text().split("\n\n")
              if blk.strip().split("\n")[0] not in tables]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n\n".join(blocks))


@pytest.mark.parametrize("name", _STAGE_CASES)
def test_stage_scopes_leave_compiled_program_unchanged(name, monkeypatch):
    import contextlib
    import re
    _, lower, _ = _stage_case(name)
    body = _compiled_body(lower().compile())
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        plain = lower()
        assert "pr." not in plain.as_text(debug_info=True)
        bare = _compiled_body(plain.compile())
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    op = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = ", re.M)
    assert len(op.findall(body)) == len(op.findall(bare)) > 0
    assert body == bare


# -- host spans and compact counters -------------------------------------------

def test_span_stats_reach_the_annotation(monkeypatch):
    import repro.obs.spans as spans_mod
    seen = []

    class Ann:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(spans_mod, "_TraceAnnotation", Ann)
    reg = Registry()
    with reg.tagged(seq=3):
        with reg.span("a", annotate=True):
            pass
        with reg.tagged(seq=4, part=1):
            with reg.span("b", annotate=True, k=2):
                pass
        with reg.span("c"):
            pass
    with reg.span("d", annotate=True):
        pass
    assert seen == [("a", {"seq": 3}), ("b", {"seq": 4, "part": 1, "k": 2}),
                    ("d", {})]
    assert reg.span_stats("c").count == 1


def test_compact_counters_count_the_overflow():
    from repro.core.compact import _df_like_compact
    from repro.core.pagerank import PRParams
    g0 = powerlaw_graph(2000, 20000, seed=3)
    b = random_batch(g0, 0.001, seed=5)
    g = apply_batch(g0, b)
    dg = device_graph(g, d_p=16, tile=64)
    fwd = forward_device_graph(g, d_p=16, tile=64)
    r_prev, _ = static_pagerank(device_graph(g0, d_p=16, tile=64),
                                init_ranks(g0.n))
    reset_registry()
    reg = get_registry()
    # headroom 1: the capacity is the initial frontier, which expansion
    # outgrows in the first sweeps
    r, iters = _df_like_compact(dg, fwd, r_prev, batch_to_device(b, g.n),
                                PRParams(), prune=True, headroom=1)
    assert reg.counter("compact.batches") == 1
    assert reg.counter("compact.overflows") == 1
    assert reg.counter("compact.sweeps") < int(iters)
    assert reg.counter("compact.capacity") >= 16
    assert reg.span_stats("compact.finish").count == 1
    reset_registry()


def test_apply_records_the_new_spans():
    from repro.stream import StreamSession
    from repro.core import BatchUpdate
    g = powerlaw_graph(500, 4000, seed=4)
    sess = StreamSession(g, d_p=16, tile=64, engine="compact")
    reset_registry()
    rng = np.random.default_rng(2)
    s = rng.integers(0, 500, 10).astype(np.int32)
    d = rng.integers(0, 500, 10).astype(np.int32)
    ok = s != d
    sess.apply(BatchUpdate(del_src=np.zeros(0, np.int32),
                           del_dst=np.zeros(0, np.int32),
                           ins_src=s[ok], ins_dst=d[ok]))
    sess.recompute()
    rep = get_registry().report()
    for name in ("session.ingest", "snapshot.apply_net_delta",
                 "snapshot.host_edit", "snapshot.device_refresh",
                 "session.plan", "session.solve", "solve.dfp_compact",
                 "compact.plan", "compact.check", "session.recompute",
                 "solve.static"):
        assert rep["spans"][name]["count"] == 1, name
    assert rep["counters"]["compact.batches"] == 1
    assert rep["counters"]["compact.sweeps"] <= sess.history[0].iters
    st = sess.history[-1]
    assert st.engine == "recompute" and st.solve_s > 0
    assert not hasattr(sess.history[0].snapshot, "device_s")
    reset_registry()
