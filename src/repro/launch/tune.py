"""Offline autotuning driver: populate the selection cache for this backend.

Sweeps the per-primitive ``block_m`` ladder and the variant shortlist over
synthetic family proxies (the benchmark suite's scaled-down stand-ins for
the paper's Table 2 inputs), and persists every winner in the on-disk
selection cache (``repro.tune.cache``; location: ``--cache`` >
``REPRO_TUNE_CACHE`` > ``~/.cache/repro/tune.json``). After one run,
``ConnectIt("auto", ...)`` and the ``kernels.ops`` block-size resolution are
pure cache lookups on this backend.

Usage:
  PYTHONPATH=src python -m repro.launch.tune                # fast grid
  PYTHONPATH=src python -m repro.launch.tune --grid full --trials 5
  PYTHONPATH=src python -m repro.launch.tune --smoke        # CI gate:
      tiny proxies, then re-read the cache from disk and assert every
      winner resolves (exercises write → reload → resolve end to end)
"""

from __future__ import annotations

import argparse
import sys

from ..tune.cache import SelectionCache, cache_path, make_key
from ..tune.harness import PRIMITIVES
from ..tune.space import TuneSpec
from ..tune.tuner import resolve_block_m, resolve_variant, tune_block_m, \
    tune_families
from .compile_cache import enable_compile_cache


def family_proxies(scale: int = 1, *, smoke: bool = False) -> dict:
    """Synthetic stand-ins for the paper's input families, one per
    fingerprint regime (same families as ``benchmarks.common.graph_suite``,
    sized for tuning rather than benchmarking)."""
    from ..graphs import generators as gen
    if smoke:
        return {
            "grid(road)": gen.grid2d(16, 16),
            "rmat_small(LJ)": gen.rmat(1 << 8, 1 << 10, seed=1),
        }
    s = max(1, scale)
    return {
        "grid(road)": gen.grid2d(64 * s, 64 * s),
        "rmat_small(LJ)": gen.rmat(1 << 12, (1 << 14) * s, seed=1),
        "rmat_dense(CO)": gen.rmat(1 << 11, (1 << 15) * s, seed=2),
        "ba(FR)": gen.barabasi_albert((1 << 12) * s, 8, seed=3),
        "rmat_web(CW)": gen.rmat(1 << 13, (1 << 15) * s, seed=4,
                                 a=0.57, b=0.19, c=0.19),
    }


def run(spec: TuneSpec, *, cache: SelectionCache, scale: int = 1,
        smoke: bool = False, kernels=None) -> dict:
    """One full tuning pass: block sizes, then variants per family."""
    block_rows = tune_block_m(
        spec, cache=cache,
        n=1 << 8 if smoke else 1 << 12,
        policy=kernels)
    print(f"{'primitive':16} {'block_m':>8} {'time_s':>12}")
    for r in block_rows:
        mark = " *" if r["winner"] else ""
        print(f"{r['primitive']:16} {r['block_m']:>8} "
              f"{r['time_s']:>12.3e}{mark}")

    families = family_proxies(scale, smoke=smoke)
    fam_rows = tune_families(families, spec, cache=cache, kernels=kernels)
    print(f"\n{'family':20} {'fingerprint':16} {'winner':32} {'time_s':>12}")
    for r in fam_rows:
        print(f"{r['family']:20} {r['fingerprint']:16} {r['winner']:32} "
              f"{r['time_s']:>12.3e}")
    print(f"\nglobal winner: {resolve_variant(cache=cache)}")
    print(f"cache: {cache.path} ({len(cache)} entries)")
    return {"blocks": block_rows, "families": fam_rows}


def verify_roundtrip(path: str) -> None:
    """Re-read the cache from disk in a fresh instance and assert every
    tuned selection resolves — the ``--smoke`` CI gate (produce + re-read)."""
    fresh = SelectionCache(path)
    if not len(fresh):
        raise SystemExit(f"tune --smoke: cache {path} is empty after tuning")
    for prim in PRIMITIVES:
        if fresh.winner(make_key(f"block_m:{prim}")) is None:
            raise SystemExit(f"tune --smoke: no block_m winner for {prim}")
        block = resolve_block_m(prim, cache=fresh)
        if block < 128 or block & (block - 1):
            raise SystemExit(f"tune --smoke: bad block_m for {prim}: {block}")
    variant = resolve_variant(cache=fresh)
    print(f"smoke: cache re-read ok — {len(fresh)} entries, "
          f"global variant {variant}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", default="fast", choices=["fast", "full"])
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--scale", type=int, default=1,
                    help="proxy-graph size multiplier")
    ap.add_argument("--cache", default=None,
                    help="cache file (default: REPRO_TUNE_CACHE or "
                         "~/.cache/repro/tune.json)")
    ap.add_argument("--kernels", default=None,
                    choices=["pallas", "interpret", "ref"],
                    help="pin the kernel policy (default: the backend's "
                         "compiled path)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: tiny proxies, then assert the cache "
                         "round-trips through a fresh read")
    args = ap.parse_args(argv)
    enable_compile_cache()
    spec = TuneSpec(grid=args.grid, trials=args.trials, warmup=args.warmup)
    path = cache_path(args.cache)
    cache = SelectionCache(path)
    run(spec, cache=cache, scale=args.scale, smoke=args.smoke,
        kernels=args.kernels)
    if args.smoke:
        verify_roundtrip(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
