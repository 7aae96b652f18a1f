"""Benchmark harness: one module per paper table/figure.

  Table 1 / Fig. 2  -> bench_static     (throughput, edges/s)
  Table 2 / Fig. 3  -> bench_dynamic    (DF-P vs Static/ND/DT/DF, temporal)
  Fig. 4 / Fig. 5   -> bench_sweep      (random batch sweep: runtime + error)
  Fig. 1            -> bench_partition  (work-partitioning ablation)
  (beyond paper)    -> bench_fusion     (fused updateRanks accounting)
  (beyond paper)    -> bench_layout     (bucketed vs single-width ELL:
                       gathered-slot efficiency + per-iteration time)
  (beyond paper)    -> bench_stream     (incremental snapshot vs rebuild)
  (beyond paper)    -> bench_distributed (single vs 1-D vs 2-D sharded,
                       static + streamed DF-P; forced host mesh, subprocess)
  (beyond paper)    -> bench_frontier    (frontier-compacted active step vs
                       dense full sweep: density sweep + stream retraces)
  (beyond paper)    -> bench_guard       (guard-layer overhead on healthy
                       streams + recovery/restore latency)
  (beyond paper)    -> bench_obs2        (always-on obs layer overhead:
                       flight+histograms on vs REPRO_OBS_OFF baseline)

Prints ``name,us_per_call,derived`` CSV rows (unchanged format) and writes
the structured twin — a ``repro.obs/bench-v2`` RunReport with per-record
min/mean/std, tail percentiles (``us_p50/p95/p99``), parsed derived
metrics, iteration-trace summaries, the session's span/counter registry
and the flight-recorder summary — to ``--out`` (default BENCH_obs.json).
After the CSV a ``# pct`` block prints p50/p95 next to us_mean for every
record that carried samples. Gate a change against a previous run with
``python -m repro.obs.check`` (v2 gates us_p99 too).

Usage:
  python -m benchmarks.run [keys ...] [--smoke] [--out PATH] [--jsonl PATH]

``--smoke`` shrinks every bench to CI-viable sizes (same code paths, same
record schema); no keys = run everything.
"""
import argparse
import sys
from pathlib import Path

#: root-level per-PR perf snapshot (repro.obs/bench-v2, same payload as
#: --out) — the PR number tracks the repo's perf trajectory in-tree.
PR_JSON = Path(__file__).resolve().parents[1] / "BENCH_10.json"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.run", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("keys", nargs="*",
                    help="bench keys to run (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI sizes; same code paths and schema")
    ap.add_argument("--out", default="BENCH_obs.json",
                    help="structured report path ('' disables)")
    ap.add_argument("--pr-json", default=str(PR_JSON),
                    help="root-level per-PR perf snapshot ('' disables)")
    ap.add_argument("--jsonl", default="",
                    help="also write the JSONL form here")
    ap.add_argument("--name", default="bench",
                    help="report name recorded in the JSON header")
    args = ap.parse_args(argv)

    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    from . import common
    common.set_smoke(args.smoke)
    common.reset_records()

    from . import (bench_static, bench_dynamic, bench_sweep, bench_partition,
                   bench_fusion, bench_layout, bench_stream,
                   bench_distributed, bench_frontier, bench_guard,
                   bench_obs2)
    mods = {"static": bench_static, "dynamic": bench_dynamic,
            "sweep": bench_sweep, "partition": bench_partition,
            "fusion": bench_fusion, "layout": bench_layout,
            "stream": bench_stream, "distributed": bench_distributed,
            "frontier": bench_frontier, "guard": bench_guard,
            "obs2": bench_obs2}
    unknown = [k for k in args.keys if k not in mods]
    if unknown:
        ap.error(f"unknown bench keys {unknown}; choose from {list(mods)}")
    keys = args.keys or list(mods)

    print("name,us_per_call,derived")
    for key in keys:
        mods[key].run()

    pct_rows = [r for r in common.RECORDS if "us_p50" in r]
    if pct_rows:
        print("# pct: name, us_mean, us_p50, us_p95")
        for r in pct_rows:
            print(f"# pct,{r['name']},{r.get('us_mean', r['us_min']):.1f},"
                  f"{r['us_p50']:.1f},{r['us_p95']:.1f}")

    if args.out or args.jsonl or args.pr_json:
        from repro.obs.report import RunReport, parse_derived
        report = RunReport(name=args.name)
        for rec in common.RECORDS:
            report.add(rec["name"], us_min=rec["us_min"],
                       us_mean=rec.get("us_mean"),
                       us_std=rec.get("us_std"),
                       us_p50=rec.get("us_p50"), us_p95=rec.get("us_p95"),
                       us_p99=rec.get("us_p99"), us_max=rec.get("us_max"),
                       derived=parse_derived(rec.get("derived", "")),
                       trace=rec.get("trace"))
        report.attach_registry()
        report.attach_flight()
        if args.out:
            report.write_json(args.out)
            print(f"# wrote {args.out} ({len(report.benchmarks)} records)",
                  file=sys.stderr)
        if args.pr_json:
            report.write_json(args.pr_json)
            print(f"# wrote {args.pr_json}", file=sys.stderr)
        if args.jsonl:
            report.write_jsonl(args.jsonl)
    return 0


if __name__ == '__main__':
    sys.exit(main())
