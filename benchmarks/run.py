"""Benchmark harness entry point — one module per paper table/figure.

``python -m benchmarks.run``           quick pass (CI-sized)
``python -m benchmarks.run --full``    paper-scale pass
``python -m benchmarks.run --only streaming_throughput``
``python -m benchmarks.run --exec``    graph-size × placement sweep →
                                       BENCH_exec.json (crossover point)
``python -m benchmarks.run --exec "sharded(x)"``   one ExecutionSpec
                                       (legacy fixed-size head-to-head)
``python -m benchmarks.run --apps``    applications sweep (AMSF + SCAN per
                                       placement) → BENCH_apps.json
``python -m benchmarks.run --serve``   serving latency/throughput sweep
                                       (repro.serve) → BENCH_serve.json
``python -m benchmarks.run --dynamic`` batch-dynamic churn sweep
                                       (repro.dynamic) → BENCH_dynamic.json
``python -m benchmarks.run --scale``   out-of-core chunked-ingest sweep
                                       (repro.graphs.ingest) →
                                       BENCH_scale.json (max feasible n/m,
                                       edges/sec, survivor ratio, peak RSS)
``python -m benchmarks.run --tune``    autotuning sweep (repro.tune) →
                                       BENCH_tune.json (per-(backend,
                                       family) variant winners + tuned-vs-
                                       default block_m speedup)

Roofline terms come from the compiled dry-run (``repro.launch.dryrun``), not
from wall time — see benchmarks/roofline.py and EXPERIMENTS.md §Roofline.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from . import (amsf_bench, dynamic_bench, execution_bench, gather_edges,
               sampling_quality, scale_bench, scan_bench, serve_bench,
               static_connectivity, streaming_batchsize,
               streaming_throughput, synthetic_families, tune_bench)
# .common (imported above) puts src/ on sys.path
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "static_connectivity": static_connectivity.run,     # Table 3
    "sampling_quality": sampling_quality.run,           # Figure 2 / T6-7
    "streaming_throughput": streaming_throughput.run,   # Table 4
    "streaming_batchsize": streaming_batchsize.run,     # Table 5 / Fig 19
    "synthetic_families": synthetic_families.run,       # Figure 4
    "amsf": amsf_bench.run,                             # Figure 6
    "scan": scan_bench.run,                             # Figure 7
    "gather_edges": gather_edges.run,                   # Table 8 / C.5.1
    "execution": execution_bench.run,                   # placements sweep
    "serve": serve_bench.run,                           # serving layer
    "dynamic": dynamic_bench.run,                       # batch-dynamic churn
    "scale": scale_bench.run,                           # out-of-core ingest
}


def run_apps(quick: bool = True, smoke: bool = False,
             out: str = "BENCH_apps.json") -> dict:
    """Applications sweep (AMSF + SCAN per placement) → machine-readable
    ``BENCH_apps.json``: per-app, per-placement wall time + approximation
    ratio (AMSF: forest weight / exact MSF weight; SCAN: fraction of labels
    matching the sequential GS*-Query oracle). The repo's perf-trajectory
    artifact for the §5 workloads."""
    rows = (amsf_bench.placement_rows(quick=quick, smoke=smoke)
            + scan_bench.placement_rows(quick=quick, smoke=smoke))
    payload = {
        "suite": "apps",
        "scale": "smoke" if smoke else ("quick" if quick else "full"),
        "rows": rows,
    }
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"{'app':24} {'exec':16} {'time_s':>10} {'ratio':>8}")
    for r in rows:
        print(f"{r['app']:24} {r['exec']:16} {r['time_s']:>10} "
              f"{r['ratio']:>8}")
    print(f"wrote {out} ({len(rows)} rows)")
    return payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized pass (the default; explicit flag for CI)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (apps/serve/dynamic/exec suites)")
    ap.add_argument("--only", default=None, choices=sorted(SUITES),
                    metavar="SUITE")
    ap.add_argument("--exec", nargs="?", const="sweep", default=None,
                    metavar="SPEC", dest="exec_spec",
                    help="run the graph-size × placement sweep only and "
                         "write BENCH_exec.json (per-size wall time per "
                         "placement + the single→sharded crossover "
                         "point); with an argument, run the legacy "
                         "fixed-size head-to-head restricted to that "
                         "ExecutionSpec string (e.g. 'sharded(x):fused')")
    ap.add_argument("--apps", action="store_true",
                    help="run the applications sweep only and write "
                         "BENCH_apps.json (per-app, per-placement wall "
                         "time + approximation ratio)")
    ap.add_argument("--serve", action="store_true",
                    help="run the serving latency/throughput sweep only "
                         "and write BENCH_serve.json (p50/p95/p99 at "
                         "offered load + saturation QPS per placement)")
    ap.add_argument("--dynamic", action="store_true",
                    help="run the batch-dynamic churn sweep only and write "
                         "BENCH_dynamic.json (updates/sec + query p50/p95 "
                         "vs delete fraction per placement)")
    ap.add_argument("--scale", action="store_true",
                    help="run the out-of-core chunked-ingest sweep only and "
                         "write BENCH_scale.json (max feasible n/m, "
                         "edges/sec ingested, survivor ratio, peak "
                         "resident bytes)")
    ap.add_argument("--tune", action="store_true",
                    help="run the autotuning sweep only and write "
                         "BENCH_tune.json (per-(backend, family) variant "
                         "winners + tuned-vs-default block_m speedup)")
    ap.add_argument("--out", default=None,
                    help="output path for the --apps/--serve JSON artifact")
    args = ap.parse_args(argv)
    if args.full and args.quick:
        ap.error("--full and --quick are mutually exclusive")
    enable_compile_cache()
    t0 = time.time()
    if args.apps:
        if args.only or args.exec_spec or args.serve:
            ap.error("--apps is exclusive with --only/--exec/--serve")
        print("\n### apps " + "#" * 56)
        run_apps(quick=not args.full, smoke=args.smoke,
                 out=args.out or "BENCH_apps.json")
    elif args.serve:
        if args.only or args.exec_spec:
            ap.error("--serve is exclusive with --only/--exec")
        print("\n### serve " + "#" * 55)
        serve_bench.run(quick=not args.full, smoke=args.smoke,
                        out=args.out or "BENCH_serve.json")
    elif args.dynamic:
        if args.only or args.exec_spec:
            ap.error("--dynamic is exclusive with --only/--exec")
        print("\n### dynamic " + "#" * 53)
        dynamic_bench.run(quick=not args.full, smoke=args.smoke,
                          out=args.out or "BENCH_dynamic.json")
    elif args.scale:
        if args.only or args.exec_spec:
            ap.error("--scale is exclusive with --only/--exec")
        print("\n### scale " + "#" * 55)
        scale_bench.run(quick=not args.full, smoke=args.smoke,
                        out=args.out or "BENCH_scale.json")
    elif args.tune:
        if args.only or args.exec_spec:
            ap.error("--tune is exclusive with --only/--exec")
        print("\n### tune " + "#" * 56)
        payload = tune_bench.run(quick=not args.full, smoke=args.smoke)
        out = args.out or "BENCH_tune.json"
        with open(out, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {out}")
    elif args.exec_spec is not None:
        if args.only:
            ap.error("--exec and --only are mutually exclusive")
        print("\n### execution " + "#" * 51)
        if args.exec_spec == "sweep":
            execution_bench.sweep(quick=not args.full, smoke=args.smoke,
                                  out=args.out or "BENCH_exec.json")
        else:
            execution_bench.run(quick=not args.full,
                                execs=(args.exec_spec,))
    else:
        names = [args.only] if args.only else list(SUITES)
        for name in names:
            print(f"\n### {name} " + "#" * max(0, 60 - len(name)))
            SUITES[name](quick=not args.full)
    print(f"\nall benchmarks done in {time.time() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
