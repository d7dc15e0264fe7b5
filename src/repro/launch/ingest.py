"""Streaming-connectivity ingestion driver (the paper-native serving loop).

Builds a graph stream, feeds insert batches + connectivity queries through
``repro.core.streaming`` at a configurable batch size, reports throughput
(directed edges/second — Table 4/5 quantities) and query latency, and
checkpoints the labeling array for restart.

``--chunked`` switches to the out-of-core path (``repro.graphs.ingest``):
the edge stream is *generated* chunk-at-a-time (never materialized), run
through the sampling phase + survivor-buffer relabel pipeline, and reported
with spill/survivor accounting — the mode that scales to n=2^24+ where the
default mode's dense ``Graph`` build would dominate or OOM.

Usage:
  PYTHONPATH=src python -m repro.launch.ingest --n 100000 --edges 1000000 \
      --batch 65536 --finish uf_sync_full
  PYTHONPATH=src python -m repro.launch.ingest --chunked --n $((1<<22)) \
      --edges $((1<<24)) --batch $((1<<20))
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..legacy import checkpoint as ckpt
from ..core import streaming
from ..core.finish import resolve_finish
from ..legacy.data import EdgeStream
from ..graphs import generators as gen
from .compile_cache import enable_compile_cache


def run_ingest(n: int, edges: int, batch: int, finish: str = "uf_sync_full",
               graph: str = "rmat", seed: int = 0, query_frac: float = 0.0,
               ckpt_dir: str | None = None, verbose: bool = True):
    g = {"rmat": lambda: gen.rmat(n, edges, seed=seed),
         "ba": lambda: gen.barabasi_albert(n, max(edges // n, 1), seed=seed),
         }[graph]()
    s = np.asarray(g.senders)[: g.m]
    r = np.asarray(g.receivers)[: g.m]
    rng = np.random.default_rng(seed)
    perm = rng.permutation(g.m)
    stream = EdgeStream(s[perm], r[perm], batch, g.n, seed=seed)
    nq = max(int(batch * query_frac), 1)
    state = streaming.init_stream(g.n)
    start = 0
    manager = None
    if ckpt_dir:
        manager = ckpt.CheckpointManager(ckpt_dir, every=8)
        (state,), start = manager.resume_or((state,))
    # warmup compile
    b0 = stream.batch_at(start)
    qa = jnp.zeros((nq,), jnp.int32)
    qb = jnp.zeros((nq,), jnp.int32)
    finish_fn = resolve_finish(finish)
    streaming.process_batch_fn(state, b0["u"], b0["v"], qa, qb,
                               finish_fn)[0].P.block_until_ready()
    t0 = time.time()
    total_edges = 0
    for step in range(start, stream.num_batches()):
        b = stream.batch_at(step)
        qa = jax.random.randint(jax.random.PRNGKey(step), (nq,), 0, g.n)
        qb = jax.random.randint(jax.random.PRNGKey(step + 1), (nq,), 0, g.n)
        state, ans = streaming.process_batch_fn(state, b["u"], b["v"], qa, qb,
                                                finish_fn)
        total_edges += batch
        if manager:
            manager.maybe_save((state,), step + 1)
    state.P.block_until_ready()
    dt = time.time() - t0
    tput = total_edges / max(dt, 1e-9)
    if verbose:
        print(f"[ingest] n={n} edges={total_edges} batch={batch} "
              f"finish={finish}: {tput:.3e} directed edges/s ({dt:.2f}s)")
    return tput, state


def run_chunked(n: int, edges: int, chunk: int,
                variant: str = "kout_afforest_k2+uf_sync_full",
                graph: str = "rmat", seed: int = 0,
                survivor_cap: int | None = None, verbose: bool = True):
    """Out-of-core ingest: generate → relabel → survivor buffer, bounded
    memory end to end (docs/API.md §Out-of-core ingest)."""
    from ..api import ConnectIt
    make = {"rmat": gen.rmat_chunks, "powerlaw": gen.powerlaw_chunks}[graph]
    src = make(n, edges, chunk=chunk, seed=seed)
    ci = ConnectIt(variant)
    t0 = time.time()
    labels, stats = ci.from_chunks(src, survivor_cap=survivor_cap,
                                   return_stats=True)
    np.asarray(labels)
    dt = time.time() - t0
    tput = edges / max(dt, 1e-9)
    if verbose:
        print(f"[ingest --chunked] n={n} edges={edges} chunk={chunk} "
              f"variant={variant}: {tput:.3e} edges/s ({dt:.2f}s), "
              f"survivor_ratio={stats.survivor_ratio:.4f} "
              f"spills={stats.spills} chunks={stats.chunks}")
    return tput, labels


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 17)
    ap.add_argument("--edges", type=int, default=1 << 20)
    ap.add_argument("--batch", type=int, default=1 << 16,
                    help="insert batch size; chunk size under --chunked")
    ap.add_argument("--finish", default="uf_sync_full")
    ap.add_argument("--graph", default="rmat",
                    choices=["rmat", "ba", "powerlaw"])
    ap.add_argument("--query-frac", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunked", action="store_true",
                    help="out-of-core chunked ingest (repro.graphs.ingest) "
                         "— the edge list is never materialized")
    ap.add_argument("--variant", default="kout_afforest_k2+uf_sync_full",
                    help="VariantSpec for --chunked")
    ap.add_argument("--survivor-cap", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.chunked:
        if args.graph == "ba":
            ap.error("--chunked supports rmat | powerlaw")
        run_chunked(args.n, args.edges, args.batch, args.variant,
                    args.graph, args.seed, args.survivor_cap)
    else:
        run_ingest(args.n, args.edges, args.batch, args.finish, args.graph,
                   args.seed, args.query_frac, args.ckpt_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
