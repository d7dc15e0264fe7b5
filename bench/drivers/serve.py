"""Snapshot-isolated serving under open-loop Poisson load.

Set-up starts ``ConnectIt.serve(n)`` from singletons with the mix's server
settings, compiling every dispatch shape on scratch buffers, and lays out
the whole schedule: two Poisson streams, inserts and queries, each a fixed
count (its rate times ``--seconds``) at times drawn from the mix's
``schedule_seed``. The run's seed draws what they carry: inserts carry the
next ``insert_edges`` edges of the configuration's generator in its random
order; queries carry ``query_pairs`` pairs, ``query_inserted_share`` of them
between endpoints already sent and the rest uniform.

The server runs on an event loop in its own thread. The main thread is the
generator: it sleeps until each request is due, hands it to the loop, and
records how late it was. Every request is timed from when it was due to
when its answer arrived. Once the window has closed the generator waits up
to ``drain_s`` for the answers still due; one that never comes fails.

The check: each insert's acknowledged epoch gives the edges committed at
every epoch; the server's epoch log must agree, and every query's answers
must equal the reference's over exactly the edges committed at the epoch
the answer reports. The control reads every answer one epoch stale: over
the edges committed before the epoch the answer reports.
"""

from __future__ import annotations

import asyncio
import threading
import time

import numpy as np


def schedule(mix: dict, seconds: float):
    """Due times (s from the window's start) and kinds (True: insert) of
    every request, in time order. Inserts and queries are two Poisson
    streams, each a fixed count (its rate times ``seconds``) at uniform
    times. They are drawn from the mix's ``schedule_seed``, not from the
    run's seed, so every seed offers the same load at the same moments and
    varies only what the requests carry."""
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    times, kinds = [], []
    for is_ins, rate in ((True, mix["insert_rate_per_s"]),
                         (False, mix["query_rate_per_s"])):
        count = max(1, int(round(rate * seconds)))
        times.append(rng.uniform(0.0, seconds, count))
        kinds.append(np.full(count, is_ins))
    times, kinds = np.concatenate(times), np.concatenate(kinds)
    order = np.argsort(times, kind="stable")
    return times[order], kinds[order]


def query_pairs(rng, pool_u, pool_v, sent: int, pairs: int, inserted: float,
                n: int):
    """``pairs`` pairs: a share between endpoints of the first ``sent``
    edges, the rest uniform over all vertices."""
    k_in = int(round(pairs * inserted)) if sent else 0
    idx = rng.integers(0, max(sent, 1), (2, k_in))
    side = rng.integers(0, 2, (2, k_in)).astype(bool)
    ends = np.where(side, pool_u[idx], pool_v[idx])
    uni = rng.integers(0, n, (2, pairs - k_in))
    qa = np.concatenate([ends[0], uni[0]]).astype(np.int32)
    qb = np.concatenate([ends[1], uni[1]]).astype(np.int32)
    return qa, qb


def answers_by_epoch(n: int, u, v, ack, queries):
    """The reference's answers to each query at the epoch it read and at
    the epoch before. ``ack[i]`` is the epoch at which insert request ``i``
    (rows ``i*size .. i*size+size-1`` of ``u``, ``v``) was acknowledged;
    ``queries`` is ``[(epoch, qa, qb), ...]``. A union-find on the host:
    each epoch's edges join the components of their endpoints' roots, and
    every root joined points at the least of them. A query at epoch ``e``
    is answered before and after epoch ``e``'s edges are applied."""
    from bench import reference

    size = u.shape[0] // max(ack.shape[0], 1)
    by_epoch = {}
    for j, (e, qa, qb) in enumerate(queries):
        by_epoch.setdefault(e, []).append(j)
    last = max(by_epoch, default=0)
    parent = np.arange(n, dtype=np.int32)

    def find(x):
        while True:
            up = parent[x]
            if np.array_equal(up, x):
                return x
            x = up

    def same(j):
        _, qa, qb = queries[j]
        return find(qa) == find(qb)

    now = [None] * len(queries)
    before = [None] * len(queries)
    for e in range(0, last + 1):
        for j in by_epoch.get(e, []):
            before[j] = same(j)
        reqs = np.flatnonzero(ack == e) if e else np.empty(0, np.int64)
        if reqs.size:
            rows = (reqs[:, None] * size + np.arange(size)).ravel()
            a, b = find(u[rows]), find(v[rows])
            touched = np.unique(np.concatenate([a, b]))
            comp = reference.edge_components(
                touched.size, np.searchsorted(touched, a),
                np.searchsorted(touched, b))
            parent[touched] = touched[comp]
        for j in by_epoch.get(e, []):
            now[j] = same(j)
    return now, before


def run(run) -> dict:
    import jax

    from bench import graphs
    from bench.harness import Window, noted, peak_bytes, seed_key, span
    from repro.api import ConnectIt

    cfg, mix = run.config, run.mix
    k_edges = jax.random.split(seed_key(run.seed))[0]
    rng = np.random.default_rng([run.seed % (1 << 63), 2])
    setup = {}
    due, kinds = schedule(mix, run.seconds)
    n_ins = int(kinds.sum())
    size, pairs = int(mix["insert_edges"]), int(mix["query_pairs"])
    gen = cfg["generator"]
    with noted("stream_s", setup), span("bench.stream"):
        eu, ev, n = graphs.generator(gen["kind"]).stream(gen, k_edges,
                                                         n_ins * size)
    requests, sent = [], 0
    for kind in kinds:
        if kind:
            requests.append((True, eu[sent:sent + size], ev[sent:sent + size]))
            sent += size
        else:
            qa, qb = query_pairs(rng, eu, ev, sent, pairs,
                                 mix["query_inserted_share"], n)
            requests.append((False, qa, qb))

    ci = ConnectIt(cfg["variant"], exec=cfg["exec"], kernels=cfg["kernels"])
    server = ci.serve(n, **mix["server"])
    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever, name="server-loop",
                              daemon=True)
    thread.start()

    def on_loop(coro, timeout=None):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(timeout)

    async def stats():
        return server.stats()

    with noted("server_start_s", setup):
        on_loop(server.start())
        # the server compiles its cap shapes. Queries change no state, so
        # every size a coalesced query batch can take (whole requests up to
        # the cap: its pow2 dispatch and the slice of its answers) is
        # compiled here by sending one such query alone
        for k in range(pairs, server.config.max_batch_queries + 1, pairs):
            z = np.zeros(k, np.int32)
            on_loop(server.query(z, z))
    setup_s = time.perf_counter() - run.t_start
    run.note(phase="setup", n=n, requests=len(requests), inserts=n_ins,
             setup_s=setup_s, **setup)

    total = len(requests)
    done_at = np.full(total, np.nan)
    results = [None] * total
    late = np.zeros(total)
    futures = []

    def finished(j):
        def cb(fut):
            done_at[j] = time.perf_counter()
            if not fut.cancelled() and fut.exception() is None:
                results[j] = fut.result()
        return cb

    stats0 = on_loop(stats())
    with Window(run.trace, run.platform, run.compiles) as w:
        t0 = w.t0
        for j, (is_ins, a, b) in enumerate(requests):
            wait = t0 + due[j] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[j] = time.perf_counter() - (t0 + due[j])
            with span("bench.submit"):
                coro = (server.submit_inserts(a, b) if is_ins
                        else server.query(a, b))
                fut = asyncio.run_coroutine_threadsafe(coro, loop)
            fut.add_done_callback(finished(j))
            futures.append(fut)
        wait = t0 + run.seconds - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        stats1 = on_loop(stats())
    end = t0 + run.seconds
    peak = peak_bytes(run.devices[:run.cell.chips])
    deadline = end + float(mix["drain_s"])
    for fut in futures:
        try:
            fut.result(max(0.0, deadline - time.perf_counter()))
        except Exception:  # noqa: BLE001 - an answer that never came
            pass
    log = list(server.epoch_edges)
    on_loop(server.close(), timeout=float(mix["drain_s"]))
    loop.call_soon_threadsafe(loop.stop)
    thread.join(timeout=30)
    del server, ci

    answered = np.array([r is not None for r in results])
    lat_ms = (done_at - (t0 + due)) * 1e3
    lat_ms[~answered] = (deadline - t0) * 1e3  # over every limit
    is_q = ~kinds
    acked = answered & kinds & (done_at <= end)
    run.note(phase="generator", late_p50_ms=float(np.median(late) * 1e3),
             late_p99_ms=float(np.percentile(late, 99) * 1e3),
             late_max_ms=float(late.max() * 1e3),
             unanswered=int((~answered).sum()),
             insert_ms=[round(float(x), 1) for x in lat_ms[kinds]],
             inserts_acked_in_window=int(acked.sum()))

    # committed edges per epoch from the acknowledgements; the log agrees
    ack = np.array([results[j] if answered[j] else 0
                    for j in np.flatnonzero(kinds)], np.int64)
    ins_ok = answered[kinds]
    log_wrong = sum(
        int(log[e] != size * int(((ack <= e) & ins_ok).sum()))
        for e in range(len(log)))
    q_idx = np.flatnonzero(is_q & answered)
    t_ref = time.perf_counter()
    with span("bench.reference"):
        now, stale = answers_by_epoch(
            n, eu, ev, np.where(ins_ok, ack, -1),
            [(results[j][1], requests[j][1], requests[j][2]) for j in q_idx])
    want = stale if run.control else now
    wrong = sum(int(np.count_nonzero(results[j][0] != w))
                for j, w in zip(q_idx, want))
    run.note(phase="reference", reference_s=time.perf_counter() - t_ref,
             epochs=len(log) - 1, queries_checked=int(q_idx.size))

    d = {k: getattr(stats1, k) - getattr(stats0, k)
         for k in ("edges_committed", "commit_batches", "query_batches",
                   "queries_answered")}
    return {
        "metrics": {
            "setup_s": setup_s,
            "update_edges_per_s": size * int(acked.sum()) / run.seconds,
            "query_p95_ms": float(np.percentile(lat_ms[is_q], 95)),
        },
        "facts": {"server": d, "compiles_in_window": w.compiled,
                  "trace": w.reduced},
        "attempted": total,
        "failed": int((~answered).sum()),
        "memory_peak_bytes": peak,
        "checks": [("answers_wrong", wrong, 0),
                   ("epoch_log_wrong", log_wrong, 0),
                   ("unanswered", int((~answered).sum()), 0)],
    }
