"""Static connectivity, closed loop: one caller, back-to-back calls.

Set-up builds the configuration's graph on the device and one ``ConnectIt``
session, and makes ``warmup_calls`` calls, each with a sampling key of its
own, through the same public entry, to compile every program. Under the
default ``exec`` the finish's dispatch size is the power of two above the
edges that sampling leaves it, which varies a little with the key, and a
call whose count crosses a power of two compiles inside the window (it shows
in ``compiles_in_window``); a configuration fixes the size with the
ExecutionSpec's ``pad=<slots>``. The
window then calls ``connectivity`` on the resident graph, each call with a
fresh sampling key and ended by ``block_until_ready``, until ``--seconds``
have passed; ``solve_s`` is the window's length over the calls completed.

A sample of the window's calls, drawn from the seed, keeps its labels; once
the window has closed they are compared, vertex by vertex, with the plain
reference's labels of the same graph.
"""

from __future__ import annotations

import time

import numpy as np


def control_labels(indptr, indices, seed: int, call: int):
    """The control: components of a 2-out sample alone (each vertex's first
    edge and one drawn at random), that is, the finish left out."""
    from bench import reference

    n = indptr.shape[0] - 1
    deg = np.diff(indptr)
    rng = np.random.default_rng([seed % (1 << 63), call])
    has = deg > 0
    v = np.flatnonzero(has)
    first = indices[indptr[v]]
    other = indices[indptr[v] + rng.integers(0, deg[v])]
    u = np.concatenate([v, v])
    w = np.concatenate([first, other])
    return reference.edge_components(n, u, w)


def run(run) -> dict:
    import jax

    from bench import graphs, reference
    from bench.harness import Window, noted, peak_bytes, seed_key, span
    from repro.api import ConnectIt

    cfg, mix = run.config, run.mix
    gen = cfg["generator"]
    k_graph, k_calls = jax.random.split(seed_key(run.seed))
    setup = {}
    with noted("graph_s", setup), span("bench.graph"):
        make = graphs.generator(gen["kind"])
        s, r, n = make.edges(gen, k_graph)
        g = graphs.build(s, r, n=n, m_pad=make.m_pad(gen))
        del s, r
    ci = ConnectIt(cfg["variant"], exec=cfg["exec"], kernels=cfg["kernels"])

    def call(i):
        with span("bench.connectivity"):
            labels = ci.connectivity(g, key=jax.random.fold_in(k_calls, i))
            return labels.block_until_ready()

    warm = int(mix["warmup_calls"])
    with noted("warmup_s", setup):
        for i in range(warm):
            call(i)
    setup_s = time.perf_counter() - run.t_start
    run.note(phase="setup", n=n, m=g.m, m_pad=g.m_pad, setup_s=setup_s,
             **setup)

    # reservoir sample of the window's calls, drawn from the seed
    keep = int(mix["checked_calls"])
    rng = np.random.default_rng([run.seed % (1 << 63), 1])
    sample, stats, took = {}, [], []
    with Window(run.trace, run.platform, run.compiles) as w:
        i = 0
        while True:
            t0 = time.perf_counter()
            labels = call(warm + i)
            took.append(time.perf_counter() - t0)
            stats.append(ci.stats)
            if i < keep:
                sample[i] = labels
            else:
                j = int(rng.integers(0, i + 1))
                if j < keep:
                    victim = sorted(sample)[j]
                    del sample[victim]
                    sample[i] = labels
            del labels
            i += 1
            if time.perf_counter() - w.t0 >= run.seconds:
                break
    peak = peak_bytes(run.devices[:run.cell.chips])
    calls = len(stats)
    run.note(phase="window", calls=calls, call_s=took,
             edges_finish=[s.edges_finish for s in stats],
             finish_rounds=[s.finish_rounds for s in stats])

    got = {i: np.asarray(lab) for i, lab in sorted(sample.items())}
    indptr = np.asarray(g.indptr)[: n + 1]
    indices = np.asarray(g.indices)[: g.m]
    del sample, g, ci
    t0 = time.perf_counter()
    with span("bench.reference"):
        want = reference.csr_components(indptr, indices)
    run.note(phase="reference", reference_s=time.perf_counter() - t0,
             calls_checked=len(got), components=int(np.unique(want).size))
    if run.control:
        got = {i: control_labels(indptr, indices, run.seed, i) for i in got}
    wrong = max(int(np.count_nonzero(lab != want)) for lab in got.values())
    return {
        "metrics": {"setup_s": setup_s, "solve_s": w.seconds / calls},
        "facts": {"calls": stats, "compiles_in_window": w.compiled,
                  "trace": w.reduced, "window_s": w.seconds},
        "attempted": calls,
        "failed": 0,
        "memory_peak_bytes": peak,
        "checks": [("labels_wrong", wrong, 0)],
    }
