"""Parallel batch-incremental connectivity (paper §3.5 / Appendix B.4).

``process_batch_fn`` applies one batch of edge insertions and connectivity
queries as a single synchronous dispatch — the TPU-native realization of the
paper's Type (1)/(2) streaming algorithms (DESIGN.md §2). The labeling array
is the persistent state; queries are answered against the post-insertion
labeling (the paper's batch-incremental correctness definition: operations in
a batch linearize against the state at batch start, with inserts before
queries — our phase split matches the paper's Type (3) phase-concurrency).

The labeling is kept *fully compressed* between batches so queries are O(1)
gathers — mirroring the paper's observation that compression work shifts
latency from queries to inserts. Compression also powers the *streaming
relabel path*: because the labeling is compressed, rewriting each incoming
batch endpoint to its parent (one ``edge_rewrite`` kernel dispatch) maps it
to its component representative, so the finish method hooks roots directly
instead of re-walking chains — the paper's edge-relabeling optimization
applied per batch.

The ``*_fn`` functions take a resolved finish *callable* (static jit arg)
plus an optional ``kernels`` KernelPolicy (static; see repro.kernels.ops)
for the relabel/compress dispatches around it; they back the
``repro.api.ConnectIt(spec).stream(n)`` handle. The old string-keyed
``insert_batch``/``process_batch`` remain as deprecation shims.
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .finish import resolve_finish
from .primitives import full_compress, init_labels, rewrite_edges


class StreamState(NamedTuple):
    P: jax.Array  # (n + 1,) compressed labeling


def init_stream(n: int, dtype=jnp.int32) -> StreamState:
    return StreamState(init_labels(n, dtype))


@partial(jax.jit, static_argnames=("finish_fn", "kernels"))
def insert_batch_fn(state: StreamState, batch_u, batch_v,
                    finish_fn: Callable,
                    kernels: Optional[str] = None) -> StreamState:
    """Apply a batch of edge insertions. Batches are symmetrized internally
    (min-based finish methods hook along the lower-endpoint direction, so
    both directions must be visible — static graphs carry both by
    construction) and endpoint-relabeled against the compressed state (see
    module docstring). Padded slots must point at the dump id n."""
    u = jnp.concatenate([batch_u, batch_v])
    v = jnp.concatenate([batch_v, batch_u])
    u, v = rewrite_edges(state.P, u, v, kernels=kernels)
    with jax.named_scope("finish"):
        P, _ = finish_fn(state.P, u, v)
    return StreamState(full_compress(P, kernels=kernels))


@jax.jit
def query_batch(state: StreamState, qa, qb) -> jax.Array:
    """IsConnected for each (qa[i], qb[i]) against the compressed labeling."""
    return state.P[qa] == state.P[qb]


@partial(jax.jit, static_argnames=("finish_fn", "kernels"))
def process_batch_fn(state: StreamState, batch_u, batch_v, qa, qb,
                     finish_fn: Callable, kernels: Optional[str] = None):
    """Inserts then queries, one dispatch (paper Algorithm 3 ProcessBatch)."""
    state = insert_batch_fn(state, batch_u, batch_v, finish_fn, kernels)
    return state, query_batch(state, qa, qb)


# Rounds-reporting variants: same dispatches, but the finish round count is
# returned (lazily, as a device scalar) so the execution-aware
# ``repro.api.Stream`` can fill ConnectivityStats without a host sync per
# batch. Kept separate so the established *_fn return shapes stay stable.

@partial(jax.jit, static_argnames=("finish_fn", "kernels"))
def insert_batch_rounds_fn(state: StreamState, batch_u, batch_v,
                           finish_fn: Callable,
                           kernels: Optional[str] = None):
    u = jnp.concatenate([batch_u, batch_v])
    v = jnp.concatenate([batch_v, batch_u])
    u, v = rewrite_edges(state.P, u, v, kernels=kernels)
    with jax.named_scope("finish"):
        P, rounds = finish_fn(state.P, u, v)
    return StreamState(full_compress(P, kernels=kernels)), rounds


@partial(jax.jit, static_argnames=("finish_fn", "kernels"))
def process_batch_rounds_fn(state: StreamState, batch_u, batch_v, qa, qb,
                            finish_fn: Callable,
                            kernels: Optional[str] = None):
    state, rounds = insert_batch_rounds_fn(state, batch_u, batch_v,
                                           finish_fn, kernels)
    return state, query_batch(state, qa, qb), rounds


# ---------------------------------------------------------------------------
# Snapshot plumbing (repro.serve): double-buffered epochs.
#
# The serving subsystem keeps TWO label buffers per logical graph: the
# *committed* snapshot (read-only — every in-flight query gathers against
# it) and the *shadow* buffer (the previous epoch's labels, no longer
# reachable by queries). A commit computes the next epoch's labels from the
# committed snapshot and — when donation is on — reuses the shadow buffer's
# device memory for the result, so steady-state serving allocates nothing:
# the two buffers alternate roles every epoch. The committed buffer is never
# donated; queries racing an in-flight commit always read a stable snapshot
# (the torn-read-freedom the serve layer's epoch contract relies on).
# ---------------------------------------------------------------------------


def snapshot_query(P: jax.Array, qa, qb) -> jax.Array:
    """IsConnected against a raw compressed label buffer (single-device
    snapshot read; mesh placements have their own shard_map query), under
    the device scope ``query``."""
    with jax.named_scope("query"):
        return P[qa] == P[qb]


_snapshot_query_jit = jax.jit(snapshot_query)


def make_snapshot_commit(finish_fn: Callable, *,
                         kernels: Optional[str] = None,
                         donate: bool = False) -> Callable:
    """Build the single-device snapshot-commit program
    ``(committed, shadow, u, v) -> (new_labels, rounds)``.

    ``committed`` is read, never written; ``shadow`` is dead state whose
    buffer is donated to the output when ``donate`` is set (double-buffer
    rotation — see the section comment above). The program runs under the
    device scope ``commit``. Mesh placements build the equivalent program
    from their stream insert programs (``core.execution``)."""

    def commit(committed, shadow, u, v):
        del shadow  # donated: its device buffer backs the new epoch
        with jax.named_scope("commit"):
            state, rounds = insert_batch_rounds_fn(
                StreamState(committed), u, v, finish_fn, kernels)
            return state.P, rounds

    return jax.jit(commit, donate_argnums=(1,) if donate else ())


# ---------------------------------------------------------------------------
# Legacy string-keyed entrypoints (deprecation shims).
# ---------------------------------------------------------------------------

_DEPRECATION = ("%s with flat string finish keys is deprecated; use "
                "repro.api.ConnectIt(spec).stream(n) or the *_fn variants "
                "with a resolved finish callable")


def insert_batch(state: StreamState, batch_u, batch_v,
                 finish: str = "uf_sync_full") -> StreamState:
    """Deprecated: use ``insert_batch_fn`` / ``repro.api`` stream handles."""
    warnings.warn(_DEPRECATION % "insert_batch(..., finish=...)",
                  DeprecationWarning, stacklevel=2)
    return insert_batch_fn(state, batch_u, batch_v, resolve_finish(finish))


def process_batch(state: StreamState, batch_u, batch_v, qa, qb,
                  finish: str = "uf_sync_full"):
    """Deprecated: use ``process_batch_fn`` / ``repro.api`` stream handles."""
    warnings.warn(_DEPRECATION % "process_batch(..., finish=...)",
                  DeprecationWarning, stacklevel=2)
    return process_batch_fn(state, batch_u, batch_v, qa, qb,
                            resolve_finish(finish))
