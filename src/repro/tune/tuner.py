"""The tuner: sweep the (variant × kernel policy × block size) grid against
the live backend, persist winners in the selection cache.

Three entry points, all cheap to call repeatedly (winners persist):

* ``tune_block_m`` — per-primitive pow2 block-size sweep on the compiled
  kernel path; winners feed ``kernels.ops`` trace-time resolution (the old
  hard-coded ``block_m=8192``).
* ``tune_variant`` — times candidate variants end-to-end on an actual
  graph; the winner is recorded under the graph's family fingerprint and
  resolves ``ConnectIt("auto", ...)`` for every later graph of that family.
* ``tune_families`` — the CLI/benchmark driver: proxy graphs per synthetic
  family, variant winner per family, plus the backend-global (``"*"``)
  winner by majority vote across families.

Resolution (``resolve_variant`` / ``resolve_block_m``) never measures
anything and never fails: a cold cache falls back to the paper's
recommended default (``kout_hybrid_k2+uf_sync_full`` — §5 guidance), and a
corrupt winner is ignored. The query path stays tuning-free by
construction.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax

from .cache import (
    SelectionCache,
    default_cache,
    fingerprint_graph,
    make_key,
)
from .harness import PRIMITIVES, primitive_drivers, time_fn
from .space import TuneSpec, TuneSpecLike, as_tune_spec

__all__ = [
    "PAPER_DEFAULT_VARIANT", "resolve_variant", "resolve_block_m",
    "tune_block_m", "tune_variant", "tune_families", "compiled_policy",
]

# §5 guidance: k-out sampling (hybrid, k=2) + union-find with full path
# compression is the paper's recommended default across inputs
PAPER_DEFAULT_VARIANT = "kout_hybrid_k2+uf_sync_full"

DEFAULT_BLOCK_M = 8192


def compiled_policy() -> str:
    """The Pallas code path to sweep block sizes on (they only matter
    there): ``pallas`` where ``auto`` dispatches the compiled kernels, else
    the interpreter, which runs on every backend."""
    from ..kernels import ops  # lazy: the kernels layer imports this package
    return "pallas" if ops._backend_policy() == "pallas" else "interpret"


def _valid_variant(text) -> Optional[str]:
    from ..api import VariantSpec  # lazy: api imports the kernels layer
    if not isinstance(text, str) or text.strip().lower() == "auto":
        return None
    try:
        return str(VariantSpec.parse(text))
    except ValueError:
        return None


def resolve_variant(family: Optional[str] = None, *,
                    cache: Optional[SelectionCache] = None) -> str:
    """Resolve the ``auto`` variant for a graph family: family winner >
    backend-global (``"*"``) winner > paper default. Pure lookup — never
    tunes, never raises."""
    cache = default_cache() if cache is None else cache
    for fam in ([family] if family and family != "*" else []) + ["*"]:
        winner = _valid_variant(cache.winner(make_key("variant", fam)))
        if winner is not None:
            return winner
    return PAPER_DEFAULT_VARIANT


def resolve_block_m(primitive: str, *, default: int = DEFAULT_BLOCK_M,
                    cache: Optional[SelectionCache] = None) -> int:
    """Resolve the tuned edge-block size for one primitive: cached winner
    (validated: a positive power of two) or ``default``."""
    cache = default_cache() if cache is None else cache
    winner = cache.winner(make_key(f"block_m:{primitive}"))
    try:
        v = int(winner)
    except (TypeError, ValueError):
        return default
    if v < 128 or v & (v - 1):
        return default
    return v


# ---------------------------------------------------------------------------
# Tuning sweeps.
# ---------------------------------------------------------------------------

def tune_block_m(spec: TuneSpecLike = TuneSpec(), *,
                 cache: Optional[SelectionCache] = None,
                 n: int = 1 << 12, m: Optional[int] = None,
                 policy: Optional[str] = None,
                 primitives: Optional[Sequence[str]] = None,
                 timer: Optional[Callable[[], float]] = None,
                 seed: int = 0) -> list:
    """Sweep the pow2 ``block_m`` ladder per primitive on the compiled
    kernel path and persist each winner.

    Returns rows ``{"primitive", "block_m", "time_s", "winner"}`` (one row
    per measured point; ``winner`` marks the argmin — ties break to the
    smaller block, deterministically). Winners are stored under the
    backend-global family (block sizes are resolved at trace time, before
    any graph is seen)."""
    spec = as_tune_spec(spec)
    cache = default_cache() if cache is None else cache
    policy = compiled_policy() if policy is None else policy
    m = 4 * n if m is None else m
    names = PRIMITIVES if primitives is None else tuple(primitives)
    drivers = primitive_drivers(n, m, seed=seed)
    rows = []
    for name in names:
        call = drivers[name]
        timed = []
        for block in spec.block_m_candidates():
            t = time_fn(call, policy, block_m=block,
                        trials=spec.trials, warmup=spec.warmup, timer=timer)
            timed.append((t, block))
        best_t, best_b = min(timed)  # tie → smaller block (sorted tuple)
        cache.put(make_key(f"block_m:{name}"), int(best_b), time_s=best_t,
                  policy=policy, n=n, m=m,
                  candidates={str(b): t for t, b in timed})
        for t, b in timed:
            rows.append(dict(primitive=name, block_m=b, time_s=t,
                             winner=(b == best_b)))
    return rows


def tune_variant(g, spec: TuneSpecLike = TuneSpec(), *,
                 cache: Optional[SelectionCache] = None,
                 exec: str = "single",  # noqa: A002 - mirrors the API
                 kernels: Optional[str] = None,
                 family: Optional[str] = None,
                 candidates: Optional[Sequence[str]] = None,
                 timer: Optional[Callable[[], float]] = None,
                 key: Optional[jax.Array] = None) -> str:
    """Time candidate variants end-to-end on ``g`` and persist the winner
    under the graph's family fingerprint.

    Measurement = one full ``connectivity`` dispatch per trial with a fixed
    PRNG key, so sampling variants are charged for their sampling phase.
    Ties break to candidate order (the fast grid lists the paper default
    first). Returns the winning variant string."""
    from ..api import ConnectIt  # lazy: api imports this package

    spec = as_tune_spec(spec)
    cache = default_cache() if cache is None else cache
    family = fingerprint_graph(g) if family is None else family
    names = tuple(spec.variant_candidates() if candidates is None
                  else candidates)
    if not names:
        raise ValueError("no variant candidates to tune over")
    key = jax.random.PRNGKey(0) if key is None else key
    best = None  # (time, index); index keeps ties deterministic
    table = {}
    for i, name in enumerate(names):
        session = ConnectIt(name, exec=exec, kernels=kernels)
        t = time_fn(lambda: session.connectivity(g, key=key),
                    trials=spec.trials, warmup=spec.warmup, timer=timer)
        table[name] = t
        if best is None or t < best[0]:
            best = (t, i)
    winner = names[best[1]]
    cache.put(make_key("variant", family), winner, time_s=best[0],
              exec=exec, n=g.n, m=g.m, candidates=table)
    return winner


def tune_families(families: dict, spec: TuneSpecLike = TuneSpec(), *,
                  cache: Optional[SelectionCache] = None,
                  exec: str = "single",  # noqa: A002 - mirrors the API
                  kernels: Optional[str] = None,
                  candidates: Optional[Sequence[str]] = None,
                  timer: Optional[Callable[[], float]] = None) -> list:
    """Tune the variant per graph family and elect the backend-global
    (``"*"``) winner by majority vote across families (ties break to the
    winner of the first family, deterministically).

    ``families`` maps display names to built ``Graph``s. Returns rows
    ``{"family", "fingerprint", "winner", "time_s"}``."""
    spec = as_tune_spec(spec)
    cache = default_cache() if cache is None else cache
    rows = []
    votes: list = []
    for name, g in families.items():
        fam = fingerprint_graph(g)
        winner = tune_variant(g, spec, cache=cache, exec=exec,
                              kernels=kernels, family=fam,
                              candidates=candidates, timer=timer)
        entry = cache.get(make_key("variant", fam)) or {}
        rows.append(dict(family=name, fingerprint=fam, winner=winner,
                         time_s=entry.get("time_s")))
        votes.append(winner)
    if votes:
        tally = {v: votes.count(v) for v in votes}
        global_winner = max(votes, key=lambda v: (tally[v], -votes.index(v)))
        cache.put(make_key("variant", "*"), global_winner,
                  families=len(votes))
    return rows
