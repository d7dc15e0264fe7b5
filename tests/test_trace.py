"""Observability: the device scopes in the lowered programs, the host spans in
a profiler trace, and the phase counters beside the wall time the caller saw
(docs/API.md, Observability)."""

import asyncio
import re
import tempfile
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ConnectIt
from repro.core import driver
from repro.core.finish import make_finish
from repro.core.sampling import make_sampler
from repro.graphs import generators as gen
from repro.kernels import ops

VARIANT = "kout_hybrid_k2+uf_sync_full"  # the paper default the benchmark runs


def scopes(lowered) -> set:
    """Every scope segment of the named locations in a lowered program."""
    text = lowered.as_text(debug_info=True)
    return {seg for path in re.findall(r'"(jit\([^"]*)"', text)
            for seg in path.split("/")}


@pytest.fixture(scope="module")
def graph():
    return gen.rmat(1024, 1 << 13, seed=0)


@pytest.fixture(scope="module")
def programs(graph):
    """Scope segments of the default variant's three programs."""
    key = jax.random.PRNGKey(0)
    sampler = make_sampler("kout", k=2, variant="hybrid")
    P = sampler(graph, key)
    finish = make_finish("uf_sync", compress="full")
    s, r = graph.senders, graph.receivers
    return {
        "sampler": scopes(sampler.lower(graph, key)),
        "lmax": scopes(driver._prep_sampled.lower(P, graph)),
        "finish": scopes(driver._finish_phase.lower(P, s, r, finish)),
    }


@pytest.mark.parametrize("program, scope", [
    ("sampler", "sample"), ("sampler", "hook_compress"),
    ("sampler", "pointer_jump"),
    ("lmax", "lmax"), ("lmax", "pointer_jump"), ("lmax", "rows"),
    ("finish", "finish"), ("finish", "canon"), ("finish", "hook_compress"),
    ("finish", "pointer_jump"), ("finish", "scatter_min"),
])
def test_default_programs_carry_scopes(programs, program, scope):
    assert scope in programs[program]


def test_fused_program_carries_scopes(graph):
    P = make_sampler("kout", k=2, variant="hybrid")(graph,
                                                    jax.random.PRNGKey(0))
    finish = make_finish("uf_sync", compress="full")
    got = scopes(driver._fused_phase.lower(P, graph.senders, graph.receivers,
                                           finish, True))
    assert {"lmax", "finish", "canon"} <= got


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("make_graph", [
    lambda: gen.rmat(1024, 1 << 13, seed=0), lambda: gen.path(300),
], ids=["rmat", "path"])
def test_lmax_gathers_at_edge_slots(make_graph):
    """L_max's mask reads no label at the edge slots: it spreads a flag per
    vertex over the CSR rows instead."""
    g = make_graph()
    P = make_sampler("kout", k=2, variant="hybrid")(g, jax.random.PRNGKey(0))
    assert g.m_pad != P.shape[0]
    jaxpr = jax.make_jaxpr(driver._prep_sampled)(P, g).jaxpr
    at_slots = [e for e in _eqns(jaxpr) if e.primitive.name == "gather"
                and e.outvars[0].aval.size == g.m_pad]
    assert at_slots == []
    assert "lmax" in scopes(driver._prep_sampled.lower(P, g))


def _primitive(name, policy):
    P = jnp.arange(1025, dtype=jnp.int32)
    e = jnp.arange(512, dtype=jnp.int32)
    call = {
        "scatter_min": lambda P, s, r: ops.scatter_min(P, s, r,
                                                       policy=policy),
        "pointer_jump": lambda P, s, r: ops.pointer_jump(P, policy=policy),
        "hook_compress": partial(ops.hook_compress, policy=policy),
        "edge_relabel": partial(ops.edge_relabel, policy=policy),
        "edge_rewrite": partial(ops.edge_rewrite, policy=policy),
        "compact_mask": lambda P, s, r: ops.compact_mask(s > 7, r, 64,
                                                         policy=policy),
    }[name]
    return jax.jit(call).lower(P, e, e[::-1])


PRIMITIVES = ["scatter_min", "pointer_jump", "hook_compress", "edge_relabel",
              "edge_rewrite", "compact_mask"]


@pytest.mark.parametrize("policy", ["ref", "interpret"])
@pytest.mark.parametrize("name", PRIMITIVES)
def test_each_primitive_carries_its_name(name, policy):
    assert name in scopes(_primitive(name, policy))


# -- host spans in a profiler trace ----------------------------------------

def traced_spans(work) -> list:
    """Run ``work`` under the profiler; the ``connectit.*`` host spans and
    the ``test.*`` ones as (start_ns, end_ns, name), by start."""
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            work()
        finally:
            jax.profiler.stop_trace()
        path = next(Path(d).rglob("*.xplane.pb"))
        planes = list(ProfileData.from_file(str(path)).planes)
    return sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                  for plane in planes if plane.name == "/host:CPU"
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith(("connectit.", "test.")))


def test_connectivity_spans_nest_in_order(graph):
    ci = ConnectIt(VARIANT)
    ci.connectivity(graph).block_until_ready()  # compile outside the trace
    spans = traced_spans(lambda: ci.connectivity(graph).block_until_ready())
    by = {name: (s, e) for s, e, name in spans}
    assert [name for *_, name in spans] == [
        "connectit.connectivity", "connectit.sample", "connectit.compact",
        "connectit.finish"]
    lo, hi = by["connectit.connectivity"]
    t = lo
    for phase in ("sample", "compact", "finish"):
        s, e = by[f"connectit.{phase}"]
        assert t <= s < e <= hi
        t = e


def serve_session(server, inserts=3, queries=5, seed=0):
    """Concurrent inserts and queries against a started-and-closed server;
    returns each kind's latencies in seconds as the caller saw them."""
    rng = np.random.default_rng(seed)
    n = server.n
    lat = {"ins": [], "q": []}

    async def timed(kind, coro):
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        await coro
        lat[kind].append(loop.time() - t0)

    async def main():
        async with server:
            await asyncio.gather(
                *[timed("ins", server.submit_inserts(
                    rng.integers(0, n, 64), rng.integers(0, n, 64)))
                  for _ in range(inserts)],
                *[timed("q", server.query(rng.integers(0, n, 16),
                                          rng.integers(0, n, 16)))
                  for _ in range(queries)])

    asyncio.run(main())
    return lat


def test_serve_spans_in_a_trace():
    server = ConnectIt(VARIANT).serve(256, max_batch_edges=64,
                                      max_batch_queries=64)
    serve_session(server)  # compiles; the traced session reuses the shapes

    def work():
        with jax.profiler.TraceAnnotation("test.session"):
            serve_session(server, seed=1)

    spans = traced_spans(work)
    (lo, hi), = [(s, e) for s, e, name in spans if name == "test.session"]
    names = [name for *_, name in spans if name.startswith("connectit.")]
    assert {"connectit.serve.coalesce", "connectit.serve.commit",
            "connectit.serve.answer"} <= set(names)
    assert names.count("connectit.serve.commit") >= 1
    for s, e, name in spans:
        assert lo <= s < e <= hi, name


# -- counters ----------------------------------------------------------------

def test_phase_seconds_within_the_call(graph):
    ci = ConnectIt(VARIANT)
    ci.connectivity(graph).block_until_ready()
    t0 = time.perf_counter()
    ci.connectivity(graph).block_until_ready()
    wall = time.perf_counter() - t0
    st = ci.stats
    assert st.sample_s > 0 and st.compact_s > 0 and st.finish_s > 0
    assert st.sample_s + st.compact_s + st.finish_s <= wall


@pytest.mark.parametrize("variant, fused, zero, timed", [
    ("none+uf_sync_full", False, ("sample_s", "compact_s"), ("finish_s",)),
    (VARIANT, True, ("sample_s", "compact_s", "finish_s"), ()),
])
def test_phase_seconds_stay_zero_without_a_sync(graph, variant, fused, zero,
                                                timed):
    ci = ConnectIt(variant)
    ci.connectivity(graph, fused=fused).block_until_ready()
    for field in zero:
        assert getattr(ci.stats, field) == 0.0
    for field in timed:
        assert getattr(ci.stats, field) > 0.0


def test_server_counters_below_caller_latencies():
    server = ConnectIt(VARIANT).serve(256, max_batch_edges=64,
                                      max_batch_queries=64)
    lat = serve_session(server, inserts=4, queries=6)
    st = server.stats()
    for field in ("insert_wait_s", "query_wait_s", "commit_s", "answer_s"):
        assert getattr(st, field) >= 0.0
    assert st.commit_s > 0 and st.answer_s > 0
    assert st.insert_wait_s + st.commit_s < sum(lat["ins"])
    assert st.query_wait_s + st.answer_s < sum(lat["q"])
