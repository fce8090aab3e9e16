"""Benchmark harness entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig2,table2] [--fast]
                                            [--out BENCH_results.json]
                                            [--chips N]

Every bench returns a structured record (benchmarks.common.bench_record);
the harness mirrors each to experiments/bench/<name>.json and writes the
schema-versioned aggregate report (default: BENCH_results.json at the repo
root) covering every requested bench -- including failures (status
'failed', traceback in extra) and graceful skips (status 'skip', e.g.
roofline without dry-run artifacts), so the perf trajectory is machine-
readable run over run.  Schema: docs/benchmarks.md.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (backend_sweep, common, fig2_skew, fig7_secpe_sweep,
                        fig8_pagerank, fig9_evolving, moe_balance, recovery,
                        roofline, serving_service, serving_session,
                        table2_sota, table3_resources)

BENCHES = {
    "fig2": fig2_skew.run,
    "fig7": fig7_secpe_sweep.run,
    "table2": table2_sota.run,
    "table3": table3_resources.run,
    "fig8": fig8_pagerank.run,
    "fig9": fig9_evolving.run,
    "moe_balance": moe_balance.run,
    "backend_sweep": backend_sweep.run,
    "roofline": roofline.run,
    "serving_session": serving_session.run,
    "serving_service": serving_service.run,
    "recovery": recovery.run,
}

FAST_KW = {
    "fig2": dict(n_tuples=1 << 16),
    # fig7/table2 floors: the 1-chunk profiling window must stay a small
    # fraction of the stream or the paper-claim asserts (speedup > 8x,
    # Ditto >= 0.7x replication) fail for harness reasons, not model ones
    "fig7": dict(n_tuples=1 << 17),
    "table2": dict(n_tuples=1 << 16),
    "table3": dict(p_bits=10),
    "fig8": dict(num_vertices=1 << 10),
    "fig9": dict(total_chunks=128),
    "moe_balance": dict(tokens=512, d_model=32, d_ff=64, group=256),
    "backend_sweep": dict(t=1024, iters=1),
    "serving_session": dict(n_tuples=1 << 13, rounds=5, chunk=1024,
                            storm_sessions=64, storms=2, storm_chunk=128),
    # the acceptance floor: even the smoke run pushes >= 1k concurrent
    # tenants through the network front door
    "serving_service": dict(tenants=1024, appends_per_tenant=2),
    # fast sizes make the WAL/checkpoint I/O a large share of a tiny
    # compute budget, so the overhead bound is looser than the full
    # run's (it is still published + asserted via the headline)
    "recovery": dict(n_tuples=1 << 13, rounds=4, chunk=512,
                     sessions_sweep=(2,), overhead_bound=4.0),
}

# benches whose engine can shard its lanes over a mesh of --chips devices
MESH_BENCHES = ("serving_session", "serving_service")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--out", default=None,
                    help="aggregate report path (default: BENCH_results.json"
                         " at the repo root)")
    ap.add_argument("--chips", type=int, default=1,
                    help="devices the serving benches shard their lanes "
                         "over (default 1: unsharded)")
    args = ap.parse_args(argv)
    names = args.only.split(",") if args.only else list(BENCHES)

    records, failed = {}, []
    for name in names:
        fn = BENCHES[name]
        kw = dict(FAST_KW.get(name, {})) if args.fast else {}
        if name in MESH_BENCHES:
            kw["chips"] = args.chips
        print(f"\n##### bench: {name} #####", flush=True)
        t0 = time.time()
        try:
            rec = fn(**kw)
            if not isinstance(rec, dict) or "bench" not in rec:
                rec = common.bench_record(
                    name, name, [], extra={"returned": repr(rec)[:200]})
            print(f"[bench {name}] {rec['status'].upper()} "
                  f"in {time.time() - t0:.1f}s")
        except Exception:
            traceback.print_exc()
            rec = common.bench_record(
                name, name, [], status="failed",
                extra={"error": traceback.format_exc()[-2000:]})
            failed.append(name)
            print(f"[bench {name}] FAILED")
        rec["seconds"] = round(time.time() - t0, 3)
        common.save_record(rec)
        records[name] = rec

    report = common.write_report(records, args.out, fast=args.fast)
    summary_rows = [{"bench": n, **e}
                    for n, e in common.make_summary(records).items()]
    cols = ["bench", "status", "seconds"] + sorted(
        {k for r in summary_rows for k in r} - {"bench", "status", "seconds"})
    common.print_table(
        "summary (report['summary'] -- headline metrics per bench)",
        summary_rows, cols=cols)
    print(f"\nwrote {report} "
          f"({len(records)} bench records, schema v{common.SCHEMA_VERSION})")
    print(f"{len(names) - len(failed)}/{len(names)} benchmarks passed"
          + (f"; failed: {failed}" if failed else ""))
    return 1 if failed else 0


if __name__ == "__main__":
    from repro.core import compilemon
    compilemon.use_persistent_cache()
    sys.exit(main())
