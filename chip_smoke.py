"""Smoke run of ConnectIt's static, streaming and serving paths on a TPU.

Drives the main path once through the public ``ConnectIt`` entry points on
an RMAT graph (n = 2^22 vertices, 2^25 generated edges, about 6.6e7
directed edges after symmetrization and deduplication), and checks every
answer against the host oracle (scipy connected components):

  static  ``connectivity`` labels equal the oracle's exactly;
          ``spanning_forest`` has n - #components edges of the graph and
          connects what the graph connects;
  stream  ``stream(n)`` takes insert batches of n/4 edges with queries
          between them; each answer equals the oracle over the prefix;
  serve   ``serve(n)`` answers closed-loop clients; each answer equals the
          oracle over the edges committed at the epoch it reports.

With ``--chips 4`` it runs only the mesh path instead: static connectivity
under ``single``, ``replicated(x)`` and ``sharded(x)`` over all devices
(identical labels, equal to the oracle), for the paper default and for an
unsampled variant whose finish takes every edge, then one ``sharded(x)``
stream.

Each phase prints one JSON line of bring-up facts (sizes, resolved kernels,
placement, wall and compile seconds, finish rounds, peak device memory).
The last line is ``{"ok": true, "device": {...}}`` only on a TPU with every
phase passed; a failing phase raises and ends the run with a traceback.
Without a TPU the script exits non-zero at once, unless ``--n`` asks for a
rehearsal at that size, which runs every phase and ends ``"ok": false``.

Usage:
  python chip_smoke.py                  # one chip
  python chip_smoke.py --chips 4        # mesh path on four chips
  JAX_PLATFORMS=cpu python chip_smoke.py --n 16384          # rehearsal
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python chip_smoke.py --n 16384 --chips 4
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"
VARIANT = "kout_hybrid_k2+uf_sync_full"  # the paper default, named explicitly
UNSAMPLED = "none+uf_sync_full"  # every edge reaches the mesh's finish
DEFAULT_N = 1 << 22
STREAM_BATCHES = 4
SERVE_CLIENTS = 4
SERVE_REQUESTS = 3
# lowering to MLIR and XLA compilation; tracing is left out because nested
# jits record their own trace events inside the outer one's
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Clock:
    """Wall time of a phase with JAX's compile time split out (lowering and
    backend compilation, persistent-cache reads included)."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, out: dict):
        c0, h0, t0 = self.compile_s, self.cache_hits, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        out["wall_s"] = out.get("wall_s", 0.0) + wall
        out["compile_s"] = out.get("compile_s", 0.0) + self.compile_s - c0
        out["cache_hits"] = out.get("cache_hits", 0) + self.cache_hits - h0


def expect(cond, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def same_partition(a, b) -> bool:
    """Two labelings name the same components (labels may differ)."""
    pairs = np.unique(np.stack([a, b], 1), axis=0).shape[0]
    return pairs == np.unique(a).shape[0] == np.unique(b).shape[0]


def report(name: str, facts: dict, dev) -> None:
    stats = dev.memory_stats() or {}
    facts["peak_hbm_bytes"] = stats.get("peak_bytes_in_use")
    facts["run_s"] = facts["wall_s"] - facts["compile_s"]
    print(json.dumps({"phase": name, **facts}), flush=True)


def undirected(g, seed: int):
    """The graph's undirected edges (one direction each), shuffled."""
    s = np.asarray(g.senders)[: g.m]
    r = np.asarray(g.receivers)[: g.m]
    keep = s < r
    order = np.random.default_rng(seed).permutation(int(keep.sum()))
    return s[keep][order], r[keep][order]


def query_pairs(rng, u, k: int):
    """Half the pairs between touched vertices (mostly connected on RMAT),
    half uniform."""
    pool = np.concatenate([u, rng.integers(0, u.max() + 1, u.shape[0])])
    qa = rng.choice(pool, k).astype(np.int32)
    qb = rng.choice(pool, k).astype(np.int32)
    return qa, qb


def static_phase(g, oracle, ci, key, clock, dev, base):
    facts = dict(base)
    with clock.phase(facts):
        labels = np.asarray(ci.connectivity(g, key=key))
    st = ci.stats
    expect(np.array_equal(labels, oracle), "static labels differ from oracle")
    warm = {}
    with clock.phase(warm):
        again = np.asarray(ci.connectivity(g, key=key))
    expect(np.array_equal(again, oracle), "warm static labels differ")
    forest_facts = {}
    with clock.phase(forest_facts):
        forest = ci.spanning_forest(g, key=key)
    ncomp = int(np.unique(oracle).shape[0])
    expect(forest.shape[0] == g.n - ncomp,
           f"forest has {forest.shape[0]} edges, want {g.n - ncomp}")
    keys = np.asarray(g.senders)[: g.m].astype(np.int64) * g.n \
        + np.asarray(g.receivers)[: g.m]
    fkeys = forest[:, 0].astype(np.int64) * g.n + forest[:, 1]
    hit = np.searchsorted(keys, fkeys)
    expect(np.all(keys[np.minimum(hit, keys.shape[0] - 1)] == fkeys),
           "forest holds an edge that is not in the graph")
    from repro.graphs import edges_oracle
    expect(np.array_equal(edges_oracle(g.n, forest[:, 0], forest[:, 1]),
                          oracle), "forest does not span the components")
    facts.update(placement=st.placement, devices=st.devices,
                 finish_rounds=st.finish_rounds, lmax_count=st.lmax_count,
                 edges_finish=st.edges_finish, components=ncomp,
                 warm_wall_s=warm["wall_s"], forest_edges=int(forest.shape[0]),
                 forest_wall_s=forest_facts["wall_s"],
                 forest_compile_s=forest_facts["compile_s"])
    facts["wall_s"] += forest_facts["wall_s"]
    facts["compile_s"] += forest_facts["compile_s"]
    report("static", facts, dev)
    return labels


def stream_phase(ci, n, u, v, rng, clock, dev, base, name="stream",
                 batches=STREAM_BATCHES):
    from repro.graphs import edges_oracle
    batch = n // 4
    expect(u.shape[0] >= batches * batch, "graph too small for the stream")
    facts = dict(base, batch_edges=batch, batches=batches)
    queries = positives = 0
    with clock.phase(facts):
        st = ci.stream(n)
    for i in range(batches):
        bu, bv = u[i * batch:(i + 1) * batch], v[i * batch:(i + 1) * batch]
        qa, qb = query_pairs(rng, bu, n // 64)
        with clock.phase(facts):
            st.insert(bu, bv)
            ans = np.asarray(st.query(qa, qb))
        prefix = edges_oracle(n, u[:(i + 1) * batch], v[:(i + 1) * batch])
        expect(np.array_equal(ans, prefix[qa] == prefix[qb]),
               f"{name}: batch {i} answers differ from the prefix oracle")
        queries += qa.shape[0]
        positives += int(ans.sum())
    expect(same_partition(np.asarray(st.labels), prefix),
           f"{name}: final labels differ from the prefix oracle")
    stats = st.stats
    facts.update(placement=stats.placement, devices=stats.devices,
                 edges_inserted=st.edges_inserted, queries=queries,
                 positives=positives, finish_rounds=stats.finish_rounds,
                 edges_per_device=list(stats.edges_per_device),
                 dispatch_sizes=list(stats.dispatch_sizes))
    report(name, facts, dev)


def serve_phase(ci, n, u, v, rng, clock, dev, base):
    from repro.graphs import edges_oracle
    k = n // 16
    total = SERVE_CLIENTS * SERVE_REQUESTS
    expect(u.shape[0] >= total * k, "graph too small for the serve phase")
    server = ci.serve(n, max_batch_edges=4 * k, max_pending_edges=8 * k,
                      max_batch_queries=n // 64)
    inserts, answers = [], []   # (epoch, request index), (qa, qb, ans, epoch)
    plan = [[rng.integers(0, n, (2, n // 256), dtype=np.int32)
             for _ in range(SERVE_REQUESTS)] for _ in range(SERVE_CLIENTS)]

    async def client(c):
        for i in range(SERVE_REQUESTS):
            j = c * SERVE_REQUESTS + i
            epoch = await server.submit_inserts(u[j * k:(j + 1) * k],
                                                v[j * k:(j + 1) * k])
            inserts.append((epoch, j))
            qa, qb = plan[c][i]
            ans, at = await server.query(qa, qb)
            answers.append((qa, qb, ans, at))

    async def run():
        async with server:
            await asyncio.wait_for(
                asyncio.gather(*(client(c) for c in range(SERVE_CLIENTS))),
                timeout=900)

    facts = dict(base, clients=SERVE_CLIENTS, requests=total,
                 insert_edges=k, query_pairs=n // 256)
    with clock.phase(facts):
        asyncio.run(run())
    expect(len(answers) == total, "serve: a request went unanswered")
    log = server.epoch_edges
    oracles = {}
    for qa, qb, ans, at in answers:
        if at not in oracles:
            idx = [j for e, j in inserts if e <= at]
            expect(log[at] == k * len(idx),
                   f"serve: epoch {at} committed {log[at]} edges, "
                   f"clients saw {k * len(idx)}")
            es = np.concatenate([u[j * k:(j + 1) * k] for j in idx] or [[]])
            er = np.concatenate([v[j * k:(j + 1) * k] for j in idx] or [[]])
            oracles[at] = edges_oracle(n, es.astype(np.int64),
                                       er.astype(np.int64))
        lab = oracles[at]
        expect(np.array_equal(ans, lab[qa] == lab[qb]),
               f"serve: answers at epoch {at} differ from the oracle")
    st = server.stats()
    facts.update(placement=ci.exec.placement, devices=st.devices,
                 epochs=st.epoch, epochs_checked=len(oracles),
                 edges_committed=st.edges_committed,
                 commit_batches=st.commit_batches,
                 query_batches=st.query_batches,
                 finish_rounds=st.finish_rounds,
                 commit_shapes=list(st.commit_shapes))
    report("serve", facts, dev)


def mesh_phase(g, oracle, variant, session, key, clock, dev):
    """Static labels under every placement over all devices: identical to
    ``single`` and to the oracle, with edges on every device."""
    single = None
    for exec_str in ("single", "replicated(x)", "sharded(x)"):
        ci, base = session(variant, exec_str)
        facts = dict(base)
        with clock.phase(facts):
            labels = np.asarray(ci.connectivity(g, key=key))
        st = ci.stats
        expect(np.array_equal(labels, oracle),
               f"{variant} {exec_str}: labels differ from the oracle")
        single = labels if single is None else single
        expect(np.array_equal(labels, single),
               f"{variant} {exec_str}: labels differ from single")
        if exec_str != "single":
            expect(st.devices > 1 and len(st.edges_per_device) == st.devices
                   and min(st.edges_per_device) > 0,
                   f"{variant} {exec_str}: edges not on every device")
        facts.update(placement=st.placement, devices=st.devices,
                     finish_rounds=st.finish_rounds,
                     edges_finish=st.edges_finish,
                     edges_per_device=list(st.edges_per_device),
                     dispatch_sizes=list(st.dispatch_sizes))
        report(f"static {exec_str}", facts, dev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=None,
                    help=f"vertices (default {DEFAULT_N}); without a TPU, "
                         f"a size asks for a rehearsal")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the mesh path over all devices, nothing else")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the package is not at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.n is None:
        print(f"chip_smoke: no TPU (JAX found {device}); pass --n to "
              f"rehearse at a small size", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.api import ConnectIt
    from repro.graphs import components_oracle, generators
    from repro.kernels import ops
    from repro.tune.harness import PRIMITIVES

    n = DEFAULT_N if args.n is None else args.n
    clock = Clock(jax)
    print(json.dumps({"device": device, "compile_cache": cache_dir}))
    t0 = time.perf_counter()
    g = generators.rmat(n, 8 * n, seed=args.seed)
    oracle = components_oracle(g)
    print(json.dumps({"phase": "setup", "n": n, "m": g.m,
                      "host_s": time.perf_counter() - t0}), flush=True)
    key = jax.random.PRNGKey(args.seed)
    u, v = undirected(g, args.seed)
    rng = np.random.default_rng(args.seed)

    def session(variant, exec_str):
        ci = ConnectIt(variant, exec=exec_str)
        impl = {p: ops.resolve_policy(ci.exec.kernels) for p in PRIMITIVES}
        base = {"n": n, "m": g.m, "variant": str(ci.spec),
                "exec": str(ci.exec), "kernels": impl,
                "device_kind": dev.device_kind}
        return ci, base

    if args.chips == 1:
        ci, base = session(VARIANT, "single")
        static_phase(g, oracle, ci, key, clock, dev, base)
        stream_phase(ci, n, u, v, rng, clock, dev, base)
        serve_phase(ci, n, u, v, rng, clock, dev, base)
    else:
        for variant in (VARIANT, UNSAMPLED):
            mesh_phase(g, oracle, variant, session, key, clock, dev)
        ci, base = session(VARIANT, "sharded(x)")
        stream_phase(ci, n, u, v, rng, clock, dev, base,
                     name="stream sharded(x)", batches=3)

    print(json.dumps({"ok": on_tpu, "device": device}))
    return 0 if on_tpu else 1


if __name__ == "__main__":
    sys.exit(main())
