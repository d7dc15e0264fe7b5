"""Static-shape graph containers for JAX.

Conventions (see DESIGN.md §7):
  * Vertices are ``0..n-1``. A *dump vertex* with id ``n`` absorbs padded edges:
    label/feature arrays sized over vertices are allocated with ``n + 1`` rows so
    scatter ops on padded edges are harmless.
  * Edge lists are COO ``(senders, receivers)`` int32 arrays padded to a static
    length with the sentinel ``n`` at both endpoints.
  * Undirected graphs store each edge in both directions (the paper counts
    directed edges; symmetrization happens at build time).
  * CSR (``indptr``, ``indices``) is carried alongside COO for per-vertex edge
    selection (k-out sampling, neighbor sampling).

Two containers exist alongside the dense ``Graph`` for the out-of-core scale
path (``repro.graphs.ingest``):

  * ``ChunkedEdgeSource`` — the protocol chunked ingest consumes: anything
    with an ``n`` attribute and a ``chunks()`` iterator of ``(k, 2)`` edge
    arrays. ``ArrayEdgeSource`` wraps an in-memory edge array; the streamed
    generators in ``repro.graphs.generators`` and ``CompressedEdgeBlocks``
    below implement it without ever materializing the full edge list.
  * ``CompressedEdgeBlocks`` — sorted edge blocks with byte-wide sender
    deltas and int16 receiver deltas (patched with an exception list where
    a delta overflows), plus a block directory. Blocks decode one at a time
    on device with a handful of cumsum/scatter ops, so a graph can stay
    compressed on host at ~3 bytes/edge and never exist as a full COO.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Iterator, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

INT32_MAX = np.iinfo(np.int32).max


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """COO + CSR static graph. All arrays are device arrays."""

    senders: jax.Array      # (m_pad,) int32, sentinel = n for padding
    receivers: jax.Array    # (m_pad,) int32
    indptr: jax.Array       # (n + 2,) int32 CSR offsets (row n = dump, empty)
    indices: jax.Array      # (m_pad,) int32 CSR column ids, sentinel-padded
    n: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))  # real directed edges

    @property
    def m_pad(self) -> int:
        return self.senders.shape[0]

    @property
    def edge_mask(self) -> jax.Array:
        return jnp.arange(self.m_pad, dtype=jnp.int32) < self.m

    def degrees(self) -> jax.Array:
        return self.indptr[1:] - self.indptr[:-1]  # (n + 1,), dump row last


def _pad_to(x: np.ndarray, size: int, fill) -> np.ndarray:
    out = np.full((size,), fill, dtype=x.dtype)
    out[: x.shape[0]] = x
    return out


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def sort_dedup_edges(edges: np.ndarray, n: int, *, symmetrize: bool = True,
                     dedup: bool = True) -> np.ndarray:
    """Self-loop drop + symmetrize + one sort-based dedup pass → sorted
    (k, 2) int32 directed edges.

    Peak memory is one int32 copy of the (symmetrized) edge list plus one
    int64 sort key per edge — the previous path materialized the full list
    three times in int64 (symmetrize concat, ``np.unique``'s sort copy, and
    a second lexsort), which at 2^26+ edges was the difference between
    fitting and OOM. Raises instead of silently wrapping when the directed
    edge count would overflow int32 (the dtype every device edge array and
    CSR offset uses)."""
    if n >= INT32_MAX:
        raise ValueError(f"n={n} does not fit int32 vertex ids")
    edges = np.asarray(edges)
    if edges.dtype != np.int32:
        if edges.size and (edges.min() < np.iinfo(np.int32).min
                           or edges.max() > INT32_MAX):
            raise ValueError("edge endpoints overflow int32")
        edges = edges.astype(np.int32)
    edges = edges.reshape(-1, 2)
    edges = edges[edges[:, 0] != edges[:, 1]]  # drop self loops
    k = edges.shape[0]
    if (2 * k if symmetrize else k) > INT32_MAX:
        raise ValueError(
            f"{2 * k if symmetrize else k} directed edges overflow the int32 "
            f"edge indexing (m must stay < 2^31; shard the graph or ingest "
            f"it chunked via repro.graphs.ingest)")
    if symmetrize:
        both = np.empty((2 * k, 2), dtype=np.int32)
        both[:k] = edges
        both[k:, 0] = edges[:, 1]
        both[k:, 1] = edges[:, 0]
        edges = both
    if edges.shape[0]:
        # sort by (sender, receiver) once: CSR order AND the dedup key. One
        # int64 key (sender high, receiver offset to unsigned low) sorts
        # about 25x faster than a two-key lexsort (2^24 edges, Xeon host)
        key = (edges[:, 0].astype(np.int64) << 32) \
            + (edges[:, 1].astype(np.int64) - np.iinfo(np.int32).min)
        del edges
        key.sort()
        if dedup:
            first = np.empty(key.shape[0], dtype=bool)
            first[0] = True
            np.not_equal(key[1:], key[:-1], out=first[1:])
            key = key[first]
        edges = np.empty((key.shape[0], 2), dtype=np.int32)
        edges[:, 0] = key >> 32
        edges[:, 1] = (key & 0xFFFFFFFF) + np.iinfo(np.int32).min
    return edges


def build_graph(
    edges: np.ndarray,
    n: int,
    *,
    symmetrize: bool = True,
    dedup: bool = True,
    pad_multiple: int = 8,
) -> Graph:
    """Build a Graph from a host-side (k, 2) int array of undirected edges."""
    edges = sort_dedup_edges(edges, n, symmetrize=symmetrize, dedup=dedup)
    m = int(edges.shape[0])
    m_pad = max(round_up(m, pad_multiple), pad_multiple)
    senders = _pad_to(edges[:, 0], m_pad, n)
    receivers = _pad_to(edges[:, 1], m_pad, n)
    counts = np.bincount(edges[:, 0], minlength=n + 1)
    indptr = np.zeros((n + 2,), dtype=np.int32)
    indptr[1:] = np.cumsum(counts)
    return Graph(
        senders=jnp.asarray(senders),
        receivers=jnp.asarray(receivers),
        indptr=jnp.asarray(indptr),
        indices=jnp.asarray(receivers),  # sorted-by-sender ⇒ CSR columns
        n=n,
        m=m,
    )


def graph_spec(n: int, m_pad: int, *, m: Optional[int] = None,
               idx_dtype=jnp.int32) -> Graph:
    """ShapeDtypeStruct stand-in Graph for dry-run lowering (no allocation).

    ``m`` is the *real* directed edge count the stand-in represents; it
    defaults to ``m_pad`` for shape-only uses, but dry-run paths that report
    ConnectivityStats should pass the true ``m`` so padded dump-slot edges
    are not reported as real work."""
    m = m_pad if m is None else int(m)
    if not 0 <= m <= m_pad:
        raise ValueError(f"m={m} must be in [0, m_pad={m_pad}]")
    sds = jax.ShapeDtypeStruct
    return Graph(
        senders=sds((m_pad,), idx_dtype),
        receivers=sds((m_pad,), idx_dtype),
        indptr=sds((n + 2,), idx_dtype),
        indices=sds((m_pad,), idx_dtype),
        n=n,
        m=m,
    )


def to_numpy_edges(g: Graph) -> np.ndarray:
    s = np.asarray(g.senders)[: g.m]
    r = np.asarray(g.receivers)[: g.m]
    return np.stack([s, r], axis=1)


def num_components_oracle(g: Graph) -> int:
    """Host-side connectivity-count oracle (tests / benchmarks only)."""
    return len(np.unique(components_oracle(g)))


def components_oracle(g: Graph) -> np.ndarray:
    """Host-side oracle labels: component id = min vertex id in component.

    scipy's ``connected_components`` (C union-find) relabeled to the
    min-vertex-id convention — the pure-Python per-edge union-find this
    replaces was O(n·m) in the worst case and dominated large-graph
    application tests. The matrix data is int8 (scipy only tests nonzero
    structure) and the edgeless case short-circuits — at the scale-test
    sizes the float64 ones array alone was 8 bytes/edge of pure overhead."""
    return edges_oracle(g.n, np.asarray(g.senders)[: g.m],
                        np.asarray(g.receivers)[: g.m])


def edges_oracle(n: int, senders, receivers) -> np.ndarray:
    """``components_oracle`` over a host edge list on ``n`` vertices (either
    direction suffices; duplicates are harmless)."""
    s = np.asarray(senders)
    r = np.asarray(receivers)
    if s.shape[0] == 0:
        return np.arange(n, dtype=np.int64)  # n singletons, min-id = self
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components as scipy_cc
    mat = csr_matrix((np.ones(len(s), dtype=np.int8), (s, r)), shape=(n, n))
    _, lab = scipy_cc(mat, directed=False)
    reps = np.full(int(lab.max()) + 1 if n else 1, n, dtype=np.int64)
    np.minimum.at(reps, lab, np.arange(n))
    return reps[lab]


# ---------------------------------------------------------------------------
# Out-of-core containers (repro.graphs.ingest): the scale path.
# ---------------------------------------------------------------------------


@runtime_checkable
class ChunkedEdgeSource(Protocol):
    """Anything chunked ingest can consume: ``n`` vertices plus an iterator
    of ``(k, 2)`` edge arrays (numpy or jax, any int dtype; endpoints in
    ``[0, n)``). Chunks may be any size, need not be sorted or deduped, and
    the full edge list never has to exist at once. ``total_edges`` is an
    optional generation-count hint (-1 = unknown)."""

    n: int

    def chunks(self) -> Iterator:
        ...


@dataclasses.dataclass(frozen=True)
class ArrayEdgeSource:
    """ChunkedEdgeSource view over an in-memory (or memory-mapped) edge
    array — the bridge between the one-shot and chunked ingest paths, and
    the reader for ``np.memmap``-backed edge files."""

    edges: np.ndarray  # (m, 2) int array (np.memmap works: slices stay lazy)
    n: int
    chunk: int = 1 << 20

    def __post_init__(self):
        if self.chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {self.chunk}")

    @property
    def total_edges(self) -> int:
        return int(self.edges.shape[0])

    @property
    def num_chunks(self) -> int:
        return max(-(-self.total_edges // self.chunk), 1)

    def chunks(self) -> Iterator[np.ndarray]:
        m = self.total_edges
        if m == 0:
            yield np.zeros((0, 2), np.int32)
            return
        for lo in range(0, m, self.chunk):
            yield np.asarray(self.edges[lo: lo + self.chunk])


def open_edge_file(path: str, n: int, *, chunk: int = 1 << 20
                   ) -> ArrayEdgeSource:
    """Memory-mapped ChunkedEdgeSource over a raw int32 (m, 2) edge file
    (see ``write_edge_file``) — chunks are read lazily from disk."""
    mm = np.memmap(path, dtype=np.int32, mode="r")
    if mm.shape[0] % 2:
        raise ValueError(f"{path}: odd element count, not an (m, 2) edge file")
    return ArrayEdgeSource(mm.reshape(-1, 2), n, chunk=chunk)


def write_edge_file(path: str, source: "ChunkedEdgeSource") -> int:
    """Stream a ChunkedEdgeSource to a raw int32 (m, 2) edge file, one chunk
    at a time (bounded memory). Returns the edge count written."""
    total = 0
    with open(path, "wb") as f:
        for c in source.chunks():
            arr = np.ascontiguousarray(np.asarray(c, dtype=np.int32))
            f.write(arr.tobytes())
            total += arr.shape[0]
    return total


_DS_ESCAPE = 255          # uint8 sender-delta escape -> exception list
_DR_ESCAPE = -(1 << 15)   # int16 receiver-delta escape -> exception list


@dataclasses.dataclass(frozen=True)
class CompressedEdgeBlocks:
    """Sorted edge blocks with delta-encoded ids and a block directory.

    Edges are sorted by (sender, receiver) and split into fixed-size blocks.
    Within a block both columns are prefix-delta coded against the previous
    edge — senders as uint8 (sorted senders move slowly, deltas are tiny
    non-negative), receivers as int16 (within a sender run receivers are
    sorted; across runs the jump can be large). A delta that overflows its
    narrow dtype is *patched*: the slot holds an escape code and the true
    delta lives in a per-block exception list (classic patched
    frame-of-reference). The directory carries each block's first edge and
    real length, so any block decodes independently — on device, as two
    scatter-patched cumsums (``decode_block``) — without touching its
    neighbours.

    At ~3 bytes/edge vs 8 for int32 COO this keeps graphs 2x+ past the
    dense ceiling resident, and the block iterator makes it a
    ``ChunkedEdgeSource`` for ``repro.graphs.ingest``.
    """

    n: int
    m: int                    # real encoded edges (directed as given)
    block_size: int           # edges per block (last block ragged)
    ds: np.ndarray            # (nb, B) uint8 sender deltas (escape 255)
    dr: np.ndarray            # (nb, B) int16 receiver deltas (escape -2^15)
    first_s: np.ndarray       # (nb,) int32 first sender per block
    first_r: np.ndarray       # (nb,) int32 first receiver per block
    block_len: np.ndarray     # (nb,) int32 real edges per block
    exc_s_pos: np.ndarray     # (Es,) int32 within-block sender-exception pos
    exc_s_val: np.ndarray     # (Es,) int32 true sender deltas at exceptions
    exc_s_start: np.ndarray   # (nb + 1,) int32 per-block offsets into exc_s_*
    exc_r_pos: np.ndarray     # (Er,) int32 within-block receiver-exception pos
    exc_r_val: np.ndarray     # (Er,) int32 true receiver deltas at exceptions
    exc_r_start: np.ndarray   # (nb + 1,) int32 per-block offsets into exc_r_*

    @property
    def num_blocks(self) -> int:
        return int(self.ds.shape[0])

    @property
    def nbytes(self) -> int:
        """Compressed footprint (all arrays)."""
        return sum(a.nbytes for a in (
            self.ds, self.dr, self.first_s, self.first_r, self.block_len,
            self.exc_s_pos, self.exc_s_val, self.exc_s_start,
            self.exc_r_pos, self.exc_r_val, self.exc_r_start))

    @property
    def ratio(self) -> float:
        """Compression ratio vs int32 COO (8 bytes/edge); > 1 is smaller."""
        return (8.0 * self.m / self.nbytes) if self.nbytes else 0.0

    @property
    def total_edges(self) -> int:
        return self.m

    def _exc_slice(self, start, pos, val, i: int):
        lo, hi = int(start[i]), int(start[i + 1])
        cap = _exc_bucket(hi - lo, self.block_size)
        p = np.full((cap,), self.block_size, np.int32)  # pad -> patch no slot
        v = np.zeros((cap,), np.int32)
        p[: hi - lo] = pos[lo:hi]
        v[: hi - lo] = val[lo:hi]
        return jnp.asarray(p), jnp.asarray(v)

    def decode_block(self, i: int):
        """Decode block ``i`` → (senders, receivers) int32 device arrays of
        static length ``block_size``, dump-padded (``n``) past the block's
        real length. Pure jnp — runs on device."""
        sp, sv = self._exc_slice(self.exc_s_start, self.exc_s_pos,
                                 self.exc_s_val, i)
        rp, rv = self._exc_slice(self.exc_r_start, self.exc_r_pos,
                                 self.exc_r_val, i)
        return _decode_block(
            jnp.asarray(self.ds[i]), jnp.asarray(self.dr[i]),
            sp, sv, rp, rv,
            jnp.int32(self.first_s[i]), jnp.int32(self.first_r[i]),
            jnp.int32(self.block_len[i]), self.n)

    def chunks(self) -> Iterator:
        for i in range(self.num_blocks):
            s, r = self.decode_block(i)
            k = int(self.block_len[i])
            yield jnp.stack([s[:k], r[:k]], axis=1)


def _exc_bucket(k: int, block_size: int) -> int:
    """Pow2 bucket for a block's exception count, so decode shapes (and jit
    caches) stay logarithmic in the exception-count spread."""
    return min(max(8, 1 << (max(k, 1) - 1).bit_length()), block_size)


@partial(jax.jit, static_argnames=("n",))
def _decode_block(ds_u8, dr16, sp, sv, rp, rv, first_s, first_r, blen, n):
    B = ds_u8.shape[0]
    j = jnp.arange(B, dtype=jnp.int32)
    # widen, then scatter the true deltas over the escape slots (exception
    # positions are padded with B: those updates land in the dropped tail row)
    ds = jnp.zeros((B + 1,), jnp.int32).at[:B].set(ds_u8.astype(jnp.int32))
    ds = ds.at[sp].set(sv)[:B]
    dr = jnp.zeros((B + 1,), jnp.int32).at[:B].set(dr16.astype(jnp.int32))
    dr = dr.at[rp].set(rv)[:B]
    senders = first_s + jnp.cumsum(ds)
    receivers = first_r + jnp.cumsum(dr)
    live = j < blen
    return (jnp.where(live, senders, n).astype(jnp.int32),
            jnp.where(live, receivers, n).astype(jnp.int32))


def _delta_exceptions(d: np.ndarray, exc: np.ndarray, escape: int, dtype):
    """Split per-block deltas into a narrow array (escape code at overflow
    positions) plus flat (pos, val, start) exception lists."""
    nb = d.shape[0]
    out = np.where(exc, escape, d).astype(dtype)
    bi, bj = np.nonzero(exc)
    start = np.zeros((nb + 1,), np.int32)
    start[1:] = np.cumsum(np.bincount(bi, minlength=nb))
    return out, bj.astype(np.int32), d[bi, bj].astype(np.int32), start


def compress_edges(edges: np.ndarray, n: int, *, block_size: int = 1 << 16,
                   symmetrize: bool = False, dedup: bool = True
                   ) -> CompressedEdgeBlocks:
    """Sort + delta-encode a host edge array into ``CompressedEdgeBlocks``.

    ``symmetrize=False`` (default) encodes each input pair once — the right
    setting for ingest sources (ingest symmetrizes per flush);
    ``symmetrize=True`` encodes both directions (CSR parity with ``Graph``).
    """
    if block_size < 2:
        raise ValueError(f"block_size must be >= 2, got {block_size}")
    edges = sort_dedup_edges(edges, n, symmetrize=symmetrize, dedup=dedup)
    m = int(edges.shape[0])
    B = int(block_size)
    nb = max(-(-m // B), 1)
    s = np.zeros((nb * B,), np.int32)
    r = np.zeros((nb * B,), np.int32)
    s[:m] = edges[:, 0]
    r[:m] = edges[:, 1]
    if m:  # pad tail repeats the last edge: deltas 0, sliced off by block_len
        s[m:] = s[m - 1]
        r[m:] = r[m - 1]
    s2 = s.reshape(nb, B)
    r2 = r.reshape(nb, B)
    ds = np.zeros((nb, B), np.int64)
    ds[:, 1:] = s2[:, 1:].astype(np.int64) - s2[:, :-1]
    dr = np.zeros((nb, B), np.int64)
    dr[:, 1:] = r2[:, 1:].astype(np.int64) - r2[:, :-1]
    ds_out, s_pos, s_val, s_start = _delta_exceptions(
        ds, ds >= _DS_ESCAPE, _DS_ESCAPE, np.uint8)
    dr_out, r_pos, r_val, r_start = _delta_exceptions(
        dr, (dr <= _DR_ESCAPE) | (dr > np.iinfo(np.int16).max),
        _DR_ESCAPE, np.int16)
    lens = np.full((nb,), B, np.int32)
    lens[-1] = m - (nb - 1) * B  # 0 for the empty-edge single block
    return CompressedEdgeBlocks(
        n=n, m=m, block_size=B,
        ds=ds_out, dr=dr_out,
        first_s=s2[:, 0].copy(), first_r=r2[:, 0].copy(),
        block_len=lens,
        exc_s_pos=s_pos, exc_s_val=s_val, exc_s_start=s_start,
        exc_r_pos=r_pos, exc_r_val=r_val, exc_r_start=r_start)


def compress_graph(g: Graph, *, block_size: int = 1 << 16
                   ) -> CompressedEdgeBlocks:
    """Compress a dense ``Graph``'s (already sorted, symmetrized) edge list
    into blocks — the migration path from device COO+CSR to the compressed
    container."""
    return compress_edges(to_numpy_edges(g), g.n, block_size=block_size,
                          symmetrize=False, dedup=False)
