"""Connectivity serving CLI — a thin driver over ``repro.serve``.

The serving workload (paper §4's concurrent insert/query mix, the north
star's "heavy traffic" scenario) now lives in the ``repro.serve``
subsystem: async admission, batch coalescing onto pow2 compiled shapes,
double-buffered snapshot epochs, multi-tenancy. This module is only the
command line: build a session, start a server, drive a closed-loop load,
print the rates.

Two seed-era defects are fixed here: the CLI exposes ``--seed`` (runs are
reproducible from the command line), and warmup no longer inserts real
random edges into the served state — shapes are compiled against scratch
buffers (ServeConfig.warmup), so the measured workload and
``num_components()`` are exactly the requested traffic.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --n 65536 --clients 16
  PYTHONPATH=src python -m repro.launch.serve --exec "replicated(x)" \
      --variant none+uf_sync_full --batch 4096 --queries 1024 --seed 7
"""

from __future__ import annotations

import argparse
import sys

from .compile_cache import enable_compile_cache


def serve(n: int = 1 << 16, *, batches: int = 32, batch_edges: int = 4096,
          queries: int = 1024, clients: int = 8,
          variant: str = "none+uf_sync_full",
          exec: str = "single",  # noqa: A002 - mirrors the session API
          kernels: str | None = None, seed: int = 0,
          flush_ms: float = 1.0, verbose: bool = True):
    """Closed-loop serving run; returns (queries_per_s, server).

    ``batches`` is the total request budget (spread over ``clients``
    concurrent workers), kept for CLI compatibility with the old
    synchronous loop. The returned server is closed; use its sync
    ``query_now`` / ``commit_now`` for post-run inspection.
    """
    from ..api import ConnectIt
    from ..serve import closed_loop, run_sync

    ci = ConnectIt(variant, exec=exec, kernels=kernels)
    server = ci.serve(n, max_batch_edges=batch_edges,
                      max_batch_queries=max(queries, 1), flush_ms=flush_ms)
    per_client = max(batches // max(clients, 1), 1)
    res = run_sync(server, closed_loop, clients=clients,
                   requests_per_client=per_client, query_pairs=queries,
                   insert_every=1, insert_edges=batch_edges, seed=seed)
    if verbose:
        st = server.stats()
        print(f"[serve] {variant} exec={st.exec}: {res.inserts} insert "
              f"batches x {batch_edges} edges + {res.queries} query "
              f"requests x {queries} pairs "
              f"({res.achieved_qps * queries:,.0f} queries/s, "
              f"{res.edges_per_s:,.0f} edge inserts/s, "
              f"p50={res.p50_ms:.2f}ms p99={res.p99_ms:.2f}ms, "
              f"{st.devices} device(s))")
        print(f"[serve] epoch {st.epoch}, components now: "
              f"{server.num_components()} (commit shapes compiled: "
              f"{list(st.commit_shapes)}, query shapes: "
              f"{list(st.query_shapes)})")
        ms = lambda s, k: 1e3 * s / max(k, 1)  # noqa: E731
        print(f"[serve] host ms, mean: insert wait "
              f"{ms(st.insert_wait_s, res.inserts):.2f}, query wait "
              f"{ms(st.query_wait_s, res.queries):.2f} (admission to batch "
              f"cut); commit {ms(st.commit_s, st.commit_batches):.2f} a "
              f"batch (cut to acknowledgement); answer "
              f"{ms(st.answer_s, st.query_batches):.2f} a batch (cut to "
              f"answers on the host)")
    return res.achieved_qps * queries, server


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 16)
    ap.add_argument("--batches", type=int, default=32,
                    help="total request budget across clients")
    ap.add_argument("--batch", type=int, default=4096, dest="batch_edges")
    ap.add_argument("--queries", type=int, default=1024,
                    help="connectivity pairs per query request")
    ap.add_argument("--clients", type=int, default=8,
                    help="concurrent closed-loop clients")
    ap.add_argument("--variant", default="none+uf_sync_full")
    ap.add_argument("--exec", default="single", dest="exec_spec")
    ap.add_argument("--kernels", default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="traffic RNG seed (reproducible runs)")
    ap.add_argument("--flush-ms", type=float, default=1.0,
                    help="max-latency coalescing flush timer")
    args = ap.parse_args(argv)
    enable_compile_cache()
    serve(args.n, batches=args.batches, batch_edges=args.batch_edges,
          queries=args.queries, clients=args.clients, variant=args.variant,
          exec=args.exec_spec, kernels=args.kernels, seed=args.seed,
          flush_ms=args.flush_ms)
    return 0


if __name__ == "__main__":
    sys.exit(main())
