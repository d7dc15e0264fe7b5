"""ConnectIt two-phase driver (paper Algorithm 1 / Algorithm 2).

``run_connectivity(g, sampler_fn, finish_fn, key)`` is the host-level
orchestrator behind the ``repro.api.ConnectIt`` session object:

  1. run the sampling phase (jit) → partial labeling P
  2. identify L_max (most frequent label) and pin it to the virtual minimum
     label -1 (Theorem 4's "smallest possible ID" relabeling)
  3. *compact* the finish-phase edge list: edges internal to L_max are
     dropped on the host (this is where the paper's m - X + Y edge saving
     is realized — masked edges would still cost memory bandwidth). The
     device marks the CSR rows of the senders outside L_max, and the host
     adds back the reverse of each kept edge into L_max
  4. run the finish phase (jit) on the compacted edges
  5. compress + restore -1 → canonical min-vertex-id labels

``run_connectivity_fused`` is the fully-jitted single-dispatch variant (no
host compaction; L_max-internal edges are no-ops under write_min) used by the
distributed/dry-run paths. Both paths fill the same ``ConnectivityStats``.

The string-keyed ``connectivity(g, sample=..., finish=...)`` /
``spanning_forest`` entrypoints remain as thin deprecation shims.
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..graphs.containers import Graph, round_up
from .finish import resolve_finish, uf_sync_forest
from .primitives import (
    full_compress,
    init_labels,
    min_vertex_labels,
    most_frequent,
    relabel_lmax,
    restore_lmax,
)
from .sampling import resolve_sampler
from .tracing import timed_span


@dataclasses.dataclass
class ConnectivityStats:
    """Paper Figure 2 quantities, consistent across every execution path
    (compacted, fused, replicated, sharded — one stats object for all).

    ``edges_finish`` is always the number of *real* directed edges handed to
    the finish phase (``edges_total`` when nothing was dropped), and
    ``edges_finish_padded`` the static dispatch size actually scattered.
    ``edges_per_device``/``dispatch_sizes`` break those down per edge shard
    (single-device paths report one entry each). ``exec`` is the canonical
    ``ExecutionSpec`` string of the backend that produced the run.
    """

    variant: str = ""          # canonical VariantSpec string ("" for legacy)
    exec: str = "single"       # canonical ExecutionSpec string
    placement: str = "single"  # single | replicated | sharded
    devices: int = 1           # mesh size the dispatch ran on
    edges_total: int = 0       # real directed edges in the input graph
    edges_finish: int = 0      # real directed edges processed by finish
    edges_finish_padded: int = 0  # static padded finish-phase dispatch size
    edges_per_device: tuple = ()  # real finish edges per edge shard
    dispatch_sizes: tuple = ()    # padded dispatch size per edge shard
    batch_shapes: tuple = ()      # streams: distinct compiled batch shapes
    lmax_count: int = 0        # vertices in L_max after sampling (0 = none)
    finish_rounds: int = 0     # (outer) rounds the finish dispatch ran
    fused: bool = False        # single: one-dispatch; sharded: rs-merge
    # application runs (paper §5) fill the same object, plus:
    app: str = ""              # canonical AppSpec string ("" for core paths)
    buckets: int = 0           # AMSF: weight buckets swept
    edges_per_bucket: tuple = ()  # AMSF: in-bucket candidate edges (capped)
    # chunked out-of-core ingest (repro.graphs.ingest) fills these too:
    chunks: int = 0            # edge chunks streamed through relabel
    spills: int = 0            # survivor-buffer overflow flushes
    survivor_ratio: float = 0.0  # survivors kept / real edges streamed
    # host seconds per phase (run_connectivity only; see the docstring)
    sample_s: float = 0.0      # sampling + L_max, until the edge mask is ready
    compact_s: float = 0.0     # host compaction: copy, index, pad, upload
    finish_s: float = 0.0      # finish + canonical labels, until rounds on host


def _canonical(P, kernels=None):
    """The closing compress, L_max restored, min-vertex-id labels."""
    with jax.named_scope("canon"):
        P = full_compress(P, kernels=kernels)
        return min_vertex_labels(restore_lmax(P), kernels=kernels)


@partial(jax.jit, static_argnames=("finish_fn", "kernels"))
def _finish_phase(P, senders, receivers, finish_fn, kernels=None):
    with jax.named_scope("finish"):
        P, rounds = finish_fn(P, senders, receivers)
    return _canonical(P, kernels), rounds


def _rows_of(flag, indptr, m_pad: int):
    """Spread a per-vertex flag over the vertex's CSR row: slot ``i`` reads
    its sender's flag, padding reads False. One scatter of the n + 1
    row-start differences (empty rows share a start; their differences add
    up) and one prefix sum, in place of a gather at every slot."""
    with jax.named_scope("rows"):
        n = flag.shape[0] - 1
        flag = flag.at[n].set(False).astype(jnp.int32)
        step = flag - jnp.concatenate([jnp.zeros((1,), jnp.int32), flag[:-1]])
        starts = jnp.zeros((m_pad + 1,), jnp.int32).at[indptr[: n + 1]].add(
            step)
        return jnp.cumsum(starts[:m_pad]) != 0


@jax.jit
def _prep_sampled(P, g: Graph):
    """Compress the sample, find L_max, pin it to -1, and mark the slots
    whose edges the finish may not skip: the rows of the senders outside
    L_max. An edge from L_max out of it is left to its reverse, which
    ``_finish_edges`` adds back (the graph stores both directions). Padding
    is never marked, so the compacted list and ``edges_finish`` count real
    edges only."""
    with jax.named_scope("lmax"):
        P = full_compress(P)
        lmax, cnt = most_frequent(P)
        keep = _rows_of(P != lmax, g.indptr, g.m_pad)
        P = relabel_lmax(P, lmax)
        return P, keep, lmax, cnt


def bucket_size(k: int, *, pad: str = "pow2", pad_multiple: int = 8,
                shards: int = 1, floor: int = 8) -> int:
    """Static dispatch size for ``k`` real elements under an ExecutionSpec
    pad policy — the single definition shared by host compaction here and
    the mesh/stream dispatch sizing in ``core.execution``.

    ``pow2`` buckets to the next power of two (one compiled shape per
    doubling — a ragged final batch reuses an earlier bucket instead of
    triggering a fresh compile); ``multiple`` rounds up to ``pad_multiple``.
    The result is always a positive multiple of ``shards`` so distributed
    dispatches split evenly across edge shards."""
    k = max(int(k), 1)
    if pad == "pow2":
        size = max(floor, 1 << (k - 1).bit_length())
    else:
        size = max(round_up(k, pad_multiple), pad_multiple)
    return round_up(size, shards)


def _finish_edges(g: Graph, keep):
    """The finish's edge list on the host, from ``_prep_sampled``'s mask: the
    marked slots and the reverse of each marked edge whose receiver has no
    marked row (it lies in L_max), so that both directions of every edge
    leaving L_max reach the finish."""
    keep = np.asarray(keep)
    s = np.asarray(g.senders)[keep]
    r = np.asarray(g.receivers)[keep]
    marked = np.zeros((g.n + 1,), bool)
    marked[s] = True
    back = ~marked[r]
    return np.concatenate([s, r[back]]), np.concatenate([r, s[back]])


def _pad_edges(s: np.ndarray, r: np.ndarray, dump: int, size: int):
    """Host edges into ``size`` device slots, padded with the dump id."""
    out_s = np.full((size,), dump, np.int32)
    out_r = np.full((size,), dump, np.int32)
    out_s[: s.shape[0]] = s
    out_r[: r.shape[0]] = r
    return jnp.asarray(out_s), jnp.asarray(out_r)


def _compact(g: Graph, keep, pad_multiple: int = 8, pad: str = "multiple"):
    s, r = _finish_edges(g, keep)
    kept = int(s.shape[0])
    size = bucket_size(kept, pad=pad, pad_multiple=pad_multiple)
    return (*_pad_edges(s, r, g.n, size), kept)


def run_connectivity(
    g: Graph,
    sampler_fn: Optional[Callable],
    finish_fn: Callable,
    key: Optional[jax.Array] = None,
    *,
    variant: str = "",
    compact_pad: int = 8,
    pad: str = "multiple",
    kernels: Optional[str] = None,
) -> tuple[jax.Array, ConnectivityStats]:
    """Two-phase connectivity on resolved callables → (labels, stats).

    ``compact_pad``/``pad`` set the padding policy of the compacted
    finish-phase edge list — ``pad="multiple"`` rounds up to ``compact_pad``,
    ``pad="pow2"`` buckets to the next power of two (fewer distinct compiled
    shapes across graphs, a few more dump-slot scatters). ``kernels`` is the
    KernelPolicy for the driver's own finish-phase dispatches (compression +
    canonicalization; the finish callable carries its policy internally).

    Each phase is a host span (``connectit.sample``, ``connectit.compact``,
    ``connectit.finish``) whose seconds land in ``stats.<phase>_s``; every
    boundary is a wait the phases need anyway.
    """
    key = jax.random.PRNGKey(0) if key is None else key
    stats = ConnectivityStats(variant=variant, edges_total=g.m)
    if sampler_fn is None:
        P = init_labels(g.n)
        senders, receivers = g.senders, g.receivers
        stats.edges_finish = g.m
        stats.edges_finish_padded = g.m_pad
    else:
        with timed_span("connectit.sample", stats, "sample_s"):
            P = sampler_fn(g, key)
            P, keep, lmax, cnt = _prep_sampled(P, g)
            keep.block_until_ready()
        with timed_span("connectit.compact", stats, "compact_s"):
            senders, receivers, kept = _compact(g, keep, compact_pad, pad)
            stats.lmax_count = int(cnt)
        stats.edges_finish = kept
        stats.edges_finish_padded = int(senders.shape[0])
    with timed_span("connectit.finish", stats, "finish_s"):
        P, rounds = _finish_phase(P, senders, receivers, finish_fn, kernels)
        stats.finish_rounds = int(rounds)
    stats.edges_per_device = (stats.edges_finish,)
    stats.dispatch_sizes = (stats.edges_finish_padded,)
    return P[: g.n], stats


@partial(jax.jit, static_argnames=("finish_fn", "sampled", "kernels"))
def _fused_phase(P, senders, receivers, finish_fn, sampled: bool,
                 kernels=None):
    if sampled:
        with jax.named_scope("lmax"):
            P = full_compress(P, kernels=kernels)
            lmax, cnt = most_frequent(P)
            P = relabel_lmax(P, lmax)
    else:
        cnt = jnp.int32(0)
    with jax.named_scope("finish"):
        P, rounds = finish_fn(P, senders, receivers)
    return _canonical(P, kernels), rounds, cnt


def run_connectivity_fused(
    g: Graph,
    sampler_fn: Optional[Callable],
    finish_fn: Callable,
    key: Optional[jax.Array] = None,
    *,
    variant: str = "",
    kernels: Optional[str] = None,
) -> tuple[jax.Array, ConnectivityStats]:
    """Single-dispatch connectivity (no host compaction) → (labels, stats)."""
    key = jax.random.PRNGKey(0) if key is None else key
    stats = ConnectivityStats(variant=variant, edges_total=g.m, fused=True,
                              edges_finish=g.m, edges_finish_padded=g.m_pad)
    if sampler_fn is None:
        P = init_labels(g.n)
        sampled = False
    else:
        P = sampler_fn(g, key)
        sampled = True
    P, rounds, cnt = _fused_phase(P, g.senders, g.receivers, finish_fn,
                                  sampled, kernels)
    stats.finish_rounds = int(rounds)
    stats.lmax_count = int(cnt)
    stats.edges_per_device = (stats.edges_finish,)
    stats.dispatch_sizes = (stats.edges_finish_padded,)
    return P[: g.n], stats


def run_spanning_forest(
    g: Graph,
    sampler_fn: Optional[Callable],
    key: Optional[jax.Array] = None,
    *,
    compress: str = "full",
    compact_pad: int = 8,
    pad: str = "multiple",
    kernels: Optional[str] = None,
) -> np.ndarray:
    """Spanning forest via root-based finish (paper Algorithm 2). Returns a
    host-side (k, 2) array of forest edges."""
    key = jax.random.PRNGKey(0) if key is None else key
    if sampler_fn is None:
        P = init_labels(g.n)
        with jax.named_scope("finish"):
            st, _ = uf_sync_forest(P, g.senders, g.receivers,
                                   compress=compress, kernels=kernels)
    else:
        st0 = sampler_fn(g, key, want_forest=True)
        P, keep, lmax, cnt = _prep_sampled(st0.P, g)
        senders, receivers, _ = _compact(g, keep, compact_pad, pad)
        with jax.named_scope("finish"):
            st, _ = uf_sync_forest(P, senders, receivers,
                                   fu=st0.fu, fv=st0.fv, compress=compress,
                                   kernels=kernels)
    fu = np.asarray(st.fu)
    fv = np.asarray(st.fv)
    sel = (fu >= 0) & (fv >= 0)
    return np.stack([fu[sel], fv[sel]], axis=1)


# ---------------------------------------------------------------------------
# Legacy string-keyed entrypoints (deprecation shims over the impl above).
# ---------------------------------------------------------------------------

_DEPRECATION = ("%s with flat string keys is deprecated; build a "
                "repro.api.VariantSpec and use repro.api.ConnectIt instead")


def connectivity(
    g: Graph,
    *,
    sample: Optional[str] = None,
    finish: str = "uf_sync",
    key: Optional[jax.Array] = None,
    return_stats: bool = False,
):
    """Deprecated: use ``repro.api.ConnectIt(spec).connectivity(g)``."""
    warnings.warn(_DEPRECATION % "connectivity(g, sample=..., finish=...)",
                  DeprecationWarning, stacklevel=2)
    sampler_fn = None if sample is None else resolve_sampler(sample)
    labels, stats = run_connectivity(
        g, sampler_fn, resolve_finish(finish), key,
        variant=f"{sample or 'none'}+{finish}")
    if return_stats:
        return labels, stats
    return labels


def connectivity_fused(P, senders, receivers, finish: str = "uf_sync",
                       use_sampling_relabel: bool = False):
    """Deprecated single-dispatch connectivity on a (pre-sampled) labeling.

    ``run_connectivity_fused`` (or ``ConnectIt(spec).connectivity(g,
    fused=True)``) is the replacement and also reports ``finish_rounds``/
    ``lmax_count`` via ConnectivityStats. Note: labels are now min-vertex-id
    canonical (the representative of each component may differ from the seed's
    arbitrary-member output).
    """
    warnings.warn(_DEPRECATION % "connectivity_fused(..., finish=...)",
                  DeprecationWarning, stacklevel=2)
    P, rounds, _ = _fused_phase(P, senders, receivers, resolve_finish(finish),
                                use_sampling_relabel)
    return P, rounds


def spanning_forest(
    g: Graph,
    *,
    sample: Optional[str] = None,
    key: Optional[jax.Array] = None,
) -> np.ndarray:
    """Deprecated: use ``repro.api.ConnectIt(spec).spanning_forest(g)``."""
    warnings.warn(_DEPRECATION % "spanning_forest(g, sample=...)",
                  DeprecationWarning, stacklevel=2)
    sampler_fn = None if sample is None else resolve_sampler(sample)
    return run_spanning_forest(g, sampler_fn, key)


def connected_components(g: Graph, **kw) -> np.ndarray:
    """Convenience: numpy canonical labels (delegates to the legacy shim)."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return np.asarray(connectivity(g, **kw))
