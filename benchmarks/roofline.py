"""§Roofline report: per (arch × shape × mesh) — the three roofline terms
derived from the compiled dry-run, dominant bottleneck, MODEL/HLO FLOPs
ratio, and the three hillclimb candidates.

Reads the CSV produced by ``python -m repro.launch.dryrun --all --mesh both
--csv dryrun_all.csv`` (the dry-run must run in its own process: it forces
512 host devices before importing jax).

``--kernels`` instead runs the per-primitive KernelPolicy smoke: each
connectivity hot-path op (scatter_min / pointer_jump / hook_compress /
edge_relabel / edge_rewrite) timed under the ``ref`` policy vs the Pallas
code path (``pallas`` on TPU, ``interpret`` elsewhere — the interpreted
numbers gate *correct wiring*, not speed; compiled speedups need a TPU).

``--collectives`` times the three label-merge exchange strategies the
sharded backend chooses between — full-array ``pmin``, ``all_to_all``
min-reduce-scatter (+ gather), and the frontier-compacted index/value
exchange — per device count (submeshes of the forced host devices), at a
fixed frontier density. Bytes-on-the-wire are modeled alongside wall time
so the table stays meaningful on hosts where devices share cores.
"""

from __future__ import annotations

import csv
import os
import sys


def load(path: str = "dryrun_all.csv"):
    if not os.path.exists(path):
        alt = os.path.join(os.path.dirname(__file__), "..", path)
        path = alt if os.path.exists(alt) else path
    with open(path) as f:
        return list(csv.DictReader(f))


def run(quick: bool = True, path: str = "dryrun_all.csv"):
    try:
        rows = load(path)
    except FileNotFoundError:
        print("roofline: dryrun_all.csv not found — run "
              "`python -m repro.launch.dryrun --all --mesh both --csv "
              "dryrun_all.csv` first")
        return []
    hdr = ["arch", "shape", "mesh", "dominant", "compute_term_s",
           "memory_term_s", "collective_term_s", "useful_flops_frac",
           "temp_bytes"]
    print(",".join(hdr))
    for r in rows:
        print(",".join(
            f"{float(r[h]):.3e}" if h.endswith("_s") or h == "useful_flops_frac"
            else r[h] for h in hdr))
    # hillclimb candidates (single-pod mesh): worst roofline fraction,
    # most collective-bound, most representative of the paper's technique
    single = [r for r in rows if r["mesh"] == "single"]

    def frac(r):
        dom = max(float(r["compute_term_s"]), float(r["memory_term_s"]),
                  float(r["collective_term_s"]))
        return float(r["compute_term_s"]) / dom if dom else 0.0

    def coll_ratio(r):
        tot = (float(r["compute_term_s"]) + float(r["memory_term_s"])
               + float(r["collective_term_s"]))
        return float(r["collective_term_s"]) / tot if tot else 0.0

    if single:
        worst = min(single, key=frac)
        collbound = max(single, key=coll_ratio)
        rep = next((r for r in single if r["arch"] == "connectit"), single[0])
        print("\nhillclimb candidates:")
        print(f"  worst-roofline-fraction: {worst['arch']} × {worst['shape']}"
              f" (compute fraction {frac(worst):.3f})")
        print(f"  most-collective-bound:   {collbound['arch']} × "
              f"{collbound['shape']} (collective share "
              f"{coll_ratio(collbound):.3f})")
        print(f"  paper-representative:    {rep['arch']} × {rep['shape']}")
    return rows


# ---------------------------------------------------------------------------
# Per-primitive KernelPolicy smoke (CI gate for the dispatch layer).
# ---------------------------------------------------------------------------

def run_kernels(quick: bool = True):
    """Time every hot-path primitive under ref vs the Pallas code path.

    Measurement runs through the shared autotuning harness
    (``repro.tune.harness``): the same drivers, workload, and timing
    discipline the block-size tuner sweeps — one definition, two consumers.
    Rows are ``(name, ref_s, compiled_s, ratio)``, unchanged."""
    import jax
    import numpy as np

    from repro.tune.harness import (PRIMITIVE_LABELS, PRIMITIVES,
                                    primitive_drivers, time_fn)

    n = 1 << 12 if quick else 1 << 20
    m = 4 * n
    compiled = "pallas" if jax.default_backend() == "tpu" else "interpret"
    reps = 3 if quick else 10

    drivers = primitive_drivers(n, m, seed=0)
    print(f"kernel smoke: n={n} m={m} backend={jax.default_backend()} "
          f"compiled-path={compiled}")
    print(f"{'primitive':36s} {'ref_ms':>10s} {compiled + '_ms':>14s} "
          f"{'ratio':>8s}")
    rows = []
    for prim in PRIMITIVES:
        name, call = PRIMITIVE_LABELS[prim], drivers[prim]
        t_ref = time_fn(call, "ref", trials=reps)
        t_krn = time_fn(call, compiled, trials=reps)
        ratio = t_krn / t_ref if t_ref else float("inf")
        rows.append((name, t_ref, t_krn, ratio))
        print(f"{name:36s} {t_ref * 1e3:10.3f} {t_krn * 1e3:14.3f} "
              f"{ratio:8.2f}")
        # parity gate: both paths must agree bit-for-bit
        a, b = call("ref"), call(compiled)
        a = a if isinstance(a, tuple) else (a,)
        b = b if isinstance(b, tuple) else (b,)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)
    print("parity: all primitives agree across policies")
    return rows


# ---------------------------------------------------------------------------
# Label-merge collective strategies vs device count (--collectives).
# ---------------------------------------------------------------------------

def run_collectives(quick: bool = True, density: float = 1 / 64):
    """Time the sharded backend's three merge-exchange strategies per
    device count.

    Each submesh round merges per-device candidate label arrays that
    differ from a shared base in ``density * n`` positions — the regime
    frontier compaction targets. Strategies:

    * ``pmin``: one full-array min all-reduce (the replicated merge).
    * ``rs_gather``: all_to_all min-reduce-scatter of n/k chunks, then
      all_gather (the ``fused`` sharded merge).
    * ``compacted``: per-device ``compact_mask`` of changed slots, gather
      of 2·k·F index/value words, local scatter_min (the frontier path).

    ``wire_bytes`` is the modeled per-device traffic; on forced host
    devices wall time also pays serialization of the compute, so the bytes
    column is the architecture-portable signal.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from benchmarks.common import timeit
    from repro.kernels import ops

    n = 1 << 16 if quick else 1 << 20
    F = max(1, int(n * density))
    devs = jax.devices()
    counts = [k for k in (1, 2, 4, 8, 16) if k <= len(devs)]
    rng = np.random.default_rng(0)
    base = jnp.arange(n, dtype=jnp.int32)

    print(f"collective smoke: n={n} frontier={F} "
          f"(density {density:.4f}) backend={jax.default_backend()} "
          f"devices={len(devs)}")
    hdr = f"{'devices':>8s} {'strategy':>12s} {'time_ms':>10s} " \
          f"{'wire_bytes':>12s}"
    print(hdr)
    rows = []
    for k in counts:
        mesh = Mesh(np.asarray(devs[:k]), ("x",))
        # per-device candidates: base lowered in F random slots
        X = np.tile(np.arange(n, dtype=np.int32), (k, 1))
        for d in range(k):
            idx = rng.choice(n, F, replace=False)
            X[d, idx] = rng.integers(0, n, F).astype(np.int32)
            X[d] = np.minimum(X[d], np.arange(n, dtype=np.int32))
        X = jnp.asarray(X)

        def body_pmin(x):
            return jax.lax.pmin(x, "x")

        def body_rs(x):
            chunk = x[0].reshape(k, n // k)
            chunk = jax.lax.all_to_all(chunk, "x", 0, 0, tiled=False)
            own = jnp.min(chunk, axis=0)
            return jax.lax.all_gather(own, "x", tiled=True)[None, :]

        def body_compact(x):
            row = x[0]
            diff = row < base
            fi, fv = ops.compact_mask(diff, row, F)
            gi = jax.lax.all_gather(fi, "x", tiled=True)
            gv = jax.lax.all_gather(fv, "x", tiled=True)
            pad = jnp.concatenate([base, base[-1:]])
            out = ops.scatter_min(pad, gi, gv, gi >= 0)[:n]
            return out[None, :]

        progs = {
            "pmin": (body_pmin, 2 * (k - 1) * (n // max(k, 1)) * 4 * 2),
            "rs_gather": (body_rs,
                          ((k - 1) * n // max(k, 1)) * 4 * 2),
            "compacted": (body_compact, 2 * (k - 1) * F * 4),
        }
        for name, (body, wire) in progs.items():
            fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("x"),),
                                       out_specs=P("x"), check_vma=False))
            t = timeit(fn, X, warmup=1, iters=3 if quick else 5)
            rows.append(dict(devices=k, strategy=name, time_s=t,
                             wire_bytes=wire))
            print(f"{k:8d} {name:>12s} {t * 1e3:10.3f} {wire:12d}")
    return rows


if __name__ == "__main__":
    argv = sys.argv[1:]
    if "--kernels" in argv:
        run_kernels(quick="--full" not in argv)
    elif "--collectives" in argv:
        run_collectives(quick="--full" not in argv)
    else:
        run(quick=False,
            path=argv[0] if argv and not argv[0].startswith("-")
            else "dryrun_all.csv")
