"""ConnectIt sampling phase (paper §3.2, Appendix C.5).

Three schemes, each returning a *partial* connectivity labeling (Def. 3.1)
plus (optionally) partial spanning-forest edges (Def. B.2):

  * k-out   — per-vertex edge selection, four variants (Appendix C.5):
              afforest | pure | hybrid (paper default, k=2) | maxdeg
  * BFS     — label-spreading BFS from ≤ num_sources random sources, accept
              when the discovered component covers > threshold of vertices
  * LDD     — one round of Miller–Peng–Xu with exponential shifts (β)

All three are implemented as bulk-synchronous frontier/scatter programs; the
paper's direction-optimization becomes frontier masking over the static COO
edge list (DESIGN.md §2).

The registry maps *scheme names* to spec-parameterized factories::

    make_sampler("kout", k=2, variant="hybrid") -> SamplerFn
    make_sampler("bfs", num_sources=3, threshold=0.1) -> SamplerFn
    make_sampler("ldd", beta=0.2) -> SamplerFn

rather than one registration per (scheme, parameter) combination. Factories
are memoized so equal parameterizations share one callable (stable ``jit``
cache identity). The old flat keys ("kout_hybrid", "bfs", ...) survive as a
deprecation shim: ``get_sampler``.
"""

from __future__ import annotations

import functools
import warnings
from typing import Callable

import jax
import jax.numpy as jnp

from ..graphs.containers import Graph
from .finish import ForestState, make_finish, uf_sync_forest
from .primitives import INT_MAX, full_compress, init_forest, init_labels
from .registry import FactoryRegistry, make_legacy_resolver

SamplerFn = Callable[..., object]  # (g, key, *, want_forest=False)


def _jit_sampler(fn: SamplerFn) -> SamplerFn:
    # jit at instantiation (memoized ⇒ stable identity ⇒ stable compile
    # cache): every sampler is trace-safe, and eager lax.while_loop closures
    # would otherwise re-lower on each call. The whole body, edge selection
    # and union-find alike, runs under the device scope "sample".
    @functools.wraps(fn)
    def sample(g: Graph, key: jax.Array, *, want_forest: bool = False):
        with jax.named_scope("sample"):
            return fn(g, key, want_forest=want_forest)

    return jax.jit(sample, static_argnames=("want_forest",))


_REGISTRY = FactoryRegistry("sampling scheme", wrap=_jit_sampler)
register_scheme = _REGISTRY.register


def scheme_names() -> list[str]:
    return _REGISTRY.names()


def make_sampler(scheme: str, **params) -> SamplerFn:
    """Build (or fetch the memoized) sampler callable for a parameterization.

    Cache keys are normalized with the factory's defaults, so e.g.
    ``make_sampler("kout")`` and ``make_sampler("kout", k=2,
    variant="hybrid")`` share one (jitted) callable."""
    return _REGISTRY.make(scheme, **params)


# ---------------------------------------------------------------------------
# k-out sampling (Algorithm 4 + the four selection variants of Appendix C.5)
# ---------------------------------------------------------------------------

KOUT_VARIANTS = ("afforest", "pure", "hybrid", "maxdeg")


def _select_kout_edges(g: Graph, key: jax.Array, k: int, variant: str):
    """Return (senders, receivers) of the ~n*k selected directed edges."""
    n = g.n
    deg = (g.indptr[1 : n + 1] - g.indptr[:n]).astype(jnp.int32)  # (n,)
    base = g.indptr[:n].astype(jnp.int32)
    ids = jnp.arange(n, dtype=jnp.int32)
    has = deg > 0

    def take(offsets):  # offsets (n,) into each row; invalid rows → self edge
        pos = base + jnp.minimum(offsets, jnp.maximum(deg - 1, 0))
        nbr = g.indices[jnp.minimum(pos, g.m_pad - 1)]
        return jnp.where(has, nbr, ids)

    cols = []
    if variant == "afforest":
        for j in range(k):
            cols.append(jnp.where(j < deg, take(jnp.full((n,), j, jnp.int32)), ids))
    elif variant in ("pure", "hybrid", "maxdeg"):
        n_rand = k if variant == "pure" else k - 1
        keys = jax.random.split(key, max(n_rand, 1))
        if variant == "hybrid":
            cols.append(take(jnp.zeros((n,), jnp.int32)))  # first edge
        elif variant == "maxdeg":
            # neighbor of maximum degree: two-pass segment-max (deg, then id)
            degs_all = (g.indptr[1:] - g.indptr[:-1]).astype(jnp.int32)
            dnbr = jnp.where(g.edge_mask, degs_all[g.receivers], -1)
            dbuf = jnp.full((n + 1,), -1, jnp.int32).at[g.senders].max(dnbr)
            hit = g.edge_mask & (dnbr == dbuf[g.senders])
            nbuf = jnp.full((n + 1,), -1, jnp.int32).at[g.senders].max(
                jnp.where(hit, g.receivers, -1))
            cols.append(jnp.where(nbuf[:n] >= 0, nbuf[:n], ids))
        for j in range(n_rand):
            r = jax.random.randint(keys[j], (n,), 0, jnp.maximum(deg, 1))
            cols.append(take(r.astype(jnp.int32)))
    else:
        raise ValueError(variant)
    receivers = jnp.concatenate(cols)
    senders = jnp.tile(ids, len(cols))
    # drop self-edges introduced for isolated vertices: point them at the dump
    bad = senders == receivers
    senders = jnp.where(bad, n, senders)
    receivers = jnp.where(bad, n, receivers)
    return senders, receivers


@register_scheme("kout")
def make_kout(k: int = 2, variant: str = "hybrid") -> SamplerFn:
    if variant not in KOUT_VARIANTS:
        raise ValueError(f"unknown k-out variant {variant!r}; have {KOUT_VARIANTS}")
    if k < 1:
        raise ValueError(f"k-out needs k >= 1, got {k}")

    def kout(g: Graph, key: jax.Array, *, want_forest: bool = False):
        s, r = _select_kout_edges(g, key, k, variant)
        P = init_labels(g.n)
        if want_forest:
            st, _ = uf_sync_forest(P, s, r, compress="full")
            P = full_compress(st.P)
            return ForestState(P, st.fu, st.fv)
        P, _ = make_finish("uf_sync", compress="full")(P, s, r)
        return full_compress(P)

    kout.__name__ = f"kout_{variant}_k{k}"
    return kout


# ---------------------------------------------------------------------------
# BFS sampling (Algorithm 5): label-spreading BFS + coverage gate.
# ---------------------------------------------------------------------------

def _bfs_from(g: Graph, src: jax.Array, enabled: jax.Array, *,
              max_rounds: int = 1 << 20):
    """Frontier BFS; returns (visited, parent_vertex) both (n+1,).

    ``enabled`` is a traced scalar bool: when False the loop body never runs
    (zero rounds), so a source that is only being evaluated for the masked
    accept-gate after an earlier acceptance costs one predicate evaluation,
    not a full traversal.
    """
    n = g.n
    visited = jnp.zeros((n + 1,), jnp.bool_).at[src].set(True)
    parent = jnp.full((n + 1,), -1, jnp.int32)

    def cond(st):
        _, _, frontier, i = st
        return enabled & jnp.any(frontier) & (i < max_rounds)

    def body(st):
        visited, parent, frontier, i = st
        act = frontier[g.senders]
        # discovery: min sender wins the parent slot of each new vertex
        prop = jnp.where(act & ~visited[g.receivers], g.senders, INT_MAX)
        buf = jnp.full((n + 1,), INT_MAX, jnp.int32).at[g.receivers].min(prop)
        new = (buf < INT_MAX) & ~visited
        parent = jnp.where(new, jnp.minimum(buf, n), parent)
        visited = visited | new
        return visited, parent, new, i + 1

    visited, parent, _, _ = jax.lax.while_loop(
        cond, body, (visited, parent, visited, 0))
    return visited, parent


@register_scheme("bfs")
def make_bfs(num_sources: int = 3, threshold: float = 0.1) -> SamplerFn:
    """BFS sampler: try up to ``num_sources`` random sources, accept the first
    whose component covers more than ``threshold * n`` vertices.

    Trace-safe: the accept-gate is a masked select on a carried ``done`` flag
    (no ``bool()`` host sync), so the sampler composes with ``jax.jit``. The
    acceptance semantics and key-consumption order match the seed's host-side
    early-return exactly, so results are bit-identical for a given key.
    """
    if num_sources < 1:
        raise ValueError(f"bfs needs num_sources >= 1, got {num_sources}")
    if not 0.0 < threshold <= 1.0:
        raise ValueError(f"bfs threshold must be in (0, 1], got {threshold}")

    def bfs(g: Graph, key: jax.Array, *, want_forest: bool = False):
        n = g.n
        P = init_labels(n)
        ids = jnp.arange(n + 1, dtype=jnp.int32)
        fu, fv = init_forest(n) if want_forest else (None, None)
        done = jnp.bool_(False)
        min_cover = int(threshold * n)
        for _ in range(num_sources):
            key, sub = jax.random.split(key)
            src = jax.random.randint(sub, (), 0, n, dtype=jnp.int32)
            visited, parent = _bfs_from(g, src, ~done)
            ok = jnp.sum(visited[:n]) > min_cover
            accept = ok & ~done
            lab = jnp.where(visited, src.astype(jnp.int32), ids).at[n].set(n)
            P = jnp.where(accept, lab, P)
            if want_forest:
                sel = accept & visited & (parent >= 0) & (ids < n) & (ids != src)
                fu = jnp.where(sel, parent, fu)
                fv = jnp.where(sel, ids, fv)
            done = done | ok
        if want_forest:
            return ForestState(P, fu, fv)
        return P

    bfs.__name__ = f"bfs_c{num_sources}"
    return bfs


# ---------------------------------------------------------------------------
# LDD sampling (Algorithm 6): MPX with exponential shifts, ties by min center.
# ---------------------------------------------------------------------------

@register_scheme("ldd")
def make_ldd(beta: float = 0.2, max_rounds: int = 1 << 20) -> SamplerFn:
    if not beta > 0.0:
        raise ValueError(f"ldd needs beta > 0, got {beta}")

    def ldd(g: Graph, key: jax.Array, *, want_forest: bool = False):
        n = g.n
        shifts = jax.random.exponential(key, (n,)) / beta
        shifts = jnp.minimum(shifts, jnp.float32(max_rounds - 2))
        # MPX: vertex v starts its own cluster at time δ_max − δ_v (the
        # LARGEST shift races first; most vertices are covered before they
        # ever wake)
        wake = jnp.floor(jnp.max(shifts) - shifts).astype(jnp.int32)
        P = jnp.full((n + 1,), INT_MAX, jnp.int32).at[n].set(n)
        parent = jnp.full((n + 1,), -1, jnp.int32)
        ids = jnp.arange(n + 1, dtype=jnp.int32)
        wake_pad = jnp.concatenate([wake, jnp.array([INT_MAX], jnp.int32)])

        def cond(st):
            P, _, _, i = st
            return jnp.any(P[:n] == INT_MAX) & (i < max_rounds)

        def body(st):
            P, parent, frontier, i = st
            # uncovered vertices whose shift has elapsed become centers
            start = (P == INT_MAX) & (wake_pad <= i) & (ids < n)
            P = jnp.where(start, ids, P)
            frontier = frontier | start
            # grow all clusters one hop; min center id wins contested vertices
            act = frontier[g.senders]
            prop = jnp.where(act & (P[g.receivers] == INT_MAX),
                             P[g.senders], INT_MAX)
            buf = jnp.full((n + 1,), INT_MAX, jnp.int32).at[g.receivers].min(prop)
            new = (buf < INT_MAX) & (P == INT_MAX)
            # record the discovery edge (min sender among achievers of buf)
            pprop = jnp.where(
                act & new[g.receivers] & (P[g.senders] == buf[g.receivers]),
                g.senders, INT_MAX)
            pbuf = jnp.full((n + 1,), INT_MAX, jnp.int32).at[g.receivers].min(pprop)
            parent = jnp.where(new, jnp.minimum(pbuf, n), parent)
            P = jnp.where(new, buf, P)
            return P, parent, new, i + 1

        frontier0 = jnp.zeros((n + 1,), jnp.bool_)
        P, parent, _, _ = jax.lax.while_loop(cond, body, (P, parent, frontier0, 0))
        if want_forest:
            fu, fv = init_forest(n)
            sel = (parent >= 0) & (ids < n)
            fu = jnp.where(sel, parent, fu)
            fv = jnp.where(sel, ids, fv)
            return ForestState(P, fu, fv)
        return P

    ldd.__name__ = f"ldd_b{beta:g}"
    return ldd


# ---------------------------------------------------------------------------
# Legacy string-keyed entrypoints (deprecation shims).
# ---------------------------------------------------------------------------

_LEGACY_SAMPLERS: dict[str, tuple[str, dict]] = {
    "kout": ("kout", {}),  # paper default: hybrid, k=2
    "kout_afforest": ("kout", {"variant": "afforest"}),
    "kout_pure": ("kout", {"variant": "pure"}),
    "kout_hybrid": ("kout", {"variant": "hybrid"}),
    "kout_maxdeg": ("kout", {"variant": "maxdeg"}),
    "bfs": ("bfs", {}),
    "ldd": ("ldd", {}),
}


# silent resolver (internal drivers never pass per-call kwargs)
resolve_sampler = make_legacy_resolver(_LEGACY_SAMPLERS, make_sampler,
                                       "sampler")

# the seed's sampler callables accepted per-call keyword parameters; the
# deprecation shim translates them onto the factory parameterization
_LEGACY_CALL_KW: dict[str, dict[str, str]] = {
    "kout": {},
    "bfs": {"c": "num_sources", "threshold": "threshold"},
    "ldd": {"beta": "beta", "max_rounds": "max_rounds"},
}


def get_sampler(name: str) -> SamplerFn:
    """Deprecated: use ``make_sampler(scheme, **params)`` or ``repro.api``.

    Returns a wrapper preserving the seed's call surface, including its
    per-call keyword parameters (``c``/``threshold``/``beta``/...)."""
    warnings.warn(
        "get_sampler(name) with flat string keys is deprecated; use "
        "make_sampler(scheme, **params) or repro.api.SamplingSpec/VariantSpec",
        DeprecationWarning, stacklevel=2)
    if name not in _LEGACY_SAMPLERS:
        raise KeyError(
            f"unknown sampler {name!r}; have {sorted(_LEGACY_SAMPLERS)}")
    scheme, base_params = _LEGACY_SAMPLERS[name]

    def legacy_sampler(g, key, *, want_forest: bool = False, **kw):
        params = dict(base_params)
        for k, v in kw.items():
            if k not in _LEGACY_CALL_KW[scheme]:
                raise TypeError(f"{name} sampler got an unexpected keyword "
                                f"argument {k!r}")
            params[_LEGACY_CALL_KW[scheme][k]] = v
        return make_sampler(scheme, **params)(g, key, want_forest=want_forest)

    legacy_sampler.__name__ = name
    return legacy_sampler


def sampler_names() -> list[str]:
    """Legacy flat name list (kept for the string-keyed shim surface)."""
    return sorted(_LEGACY_SAMPLERS)
