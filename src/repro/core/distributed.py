"""Mesh programs for distributed connectivity (DESIGN.md §5).

Two placements, both shard_map programs over a named mesh, now parameterized
by a *finish callable* drawn from the ``VariantSpec`` layer (any of the
paper's finish × compression methods) instead of hardwired pointer-jumping:

  * **replicated labels** (n ≤ ~16M): edges sharded over every mesh axis,
    labels replicated. Per outer round each shard runs the finish method to
    a local fixpoint on its edge shard, then the labelings are merged with
    an elementwise ``lax.pmin`` over all edge axes. Every finish method is
    min-based and monotone, so the merged labeling is again a valid partial
    labeling and the outer loop converges to the global fixpoint.

  * **sharded labels** (hyperlink-scale): labels sharded over one axis,
    edges over the remaining axes (or the same axis on a 1-D mesh; on the
    2-D ``sharded(x,y)`` mesh edges shard over both axes and labels over
    the last). Per outer round: all-gather labels along the label axis →
    local finish → min-merge back to shards. The merge is *frontier
    compacted* by default: each shard exchanges only the (index, value)
    pairs its finish actually lowered this round (``ops.compact_mask``
    into fixed-cap buffers, gated on a mesh-reduced frontier count), so
    rounds get cheaper as components merge; rounds whose frontier exceeds
    the cap fall back to the dense merge — a full ``pmin`` + slice, or
    with ``reduce_scatter`` an all_to_all + local min (a
    min-reduce-scatter, ~1/|label axis| of the wire bytes). With
    ``overlap`` the edge shard splits into two blocks that alternate per
    round and round r's frontier exchange is applied at the top of round
    r+1, so the collective overlaps with the next block's local
    hook+compress (double-buffered labels).

The outer loop runs to a global fixpoint by default (``rounds=0``) or for a
fixed number of rounds (dry-run / throughput programs). Correctness argument
for the merge: labels only decrease, every value a shard writes is the id of
a vertex in the same component (or the virtual minimum ``-1``), and the
merged labeling is stable only when every shard's finish is a no-op — i.e.
when every edge in the graph is satisfied.

The planning layer that picks meshes, pads dispatches, and exposes these as
``ConnectIt(spec, exec=...)`` lives in ``repro.core.execution``. The old
``make_replicated_step`` / ``make_sharded_step`` / ``make_streaming_ingest``
factories (fixed ``jumps=2`` pointer-jumping, no spec integration) remain
below as ``DeprecationWarning`` shims.
"""

from __future__ import annotations

import warnings
from functools import partial
from math import prod
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..graphs.containers import round_up
from ..kernels import ops
from .apps.amsf import _skip_lmax_mask
from .finish import _compress
from .primitives import (
    INT_MAX,
    full_compress,
    iterate_to_fixpoint,
    parents_of,
)

# Fixpoint-detection cap floor for the outer merge loop (rounds=0). Label
# information crosses at least one shard boundary per outer round, so the
# worst case is the edge-shard count; the cap defaults to that count (plus
# slack) and never below this floor.
DEFAULT_OUTER_ROUNDS = 256


def _fixpoint_cap(mesh: Mesh, edge_axes: Sequence[str],
                  max_rounds: Optional[int]) -> int:
    """Default outer-round cap: enough for the min label to cross every edge
    shard even when it moves one shard boundary per merge round."""
    if max_rounds is not None:
        return max_rounds
    shards = prod(mesh.shape[a] for a in edge_axes)
    return max(DEFAULT_OUTER_ROUNDS, 2 * shards + 8)


def _outer_loop(body, labels, rounds: int, max_rounds: int,
                changed_fn: Callable = lambda ch: ch):
    """Run ``body: labels -> labels`` for ``rounds`` fixed iterations, or to
    fixpoint (``rounds=0``) capped at ``max_rounds``. Returns (labels, k).

    The while condition must be uniform across the mesh: pass a
    ``changed_fn`` that reduces the local changed flag over the mesh axes
    when the labels carried are per-shard (the default identity is for
    merged, device-identical labelings). The fixpoint branch is the shared
    ``primitives.iterate_to_fixpoint`` loop with the mesh reduction wrapped
    into its convergence predicate."""
    if rounds > 0:
        out = jax.lax.fori_loop(0, rounds, lambda i, L: body(L), labels)
        return out, jnp.int32(rounds)
    return iterate_to_fixpoint(
        body, labels, max_rounds,
        changed_fn=lambda old, new: changed_fn(jnp.any(new != old)))


def _outer_loop_flagged(body, labels, rounds: int, cap: int):
    """``_outer_loop`` for bodies that report their own (already
    mesh-uniform) continue flag: ``body: labels -> (labels, go)``. Avoids
    the old-vs-new array compare *and* its flag-reduction collective — the
    flag comes free from the merge itself."""
    if rounds > 0:
        out = jax.lax.fori_loop(0, rounds, lambda i, L: body(L)[0], labels)
        return out, jnp.int32(rounds)

    def cond(st):
        return st[1] & (st[2] < cap)

    def step(st):
        L2, go = body(st[0])
        return L2, go, st[2] + 1

    L, _, k = jax.lax.while_loop(
        cond, step, (labels, jnp.bool_(True), jnp.int32(0)))
    return L, k


# ---------------------------------------------------------------------------
# Replicated-label programs (spec-parameterized).
# ---------------------------------------------------------------------------

def make_replicated_finish(mesh: Mesh, axes: Sequence[str],
                           finish_fn: Callable, *, rounds: int = 0,
                           max_rounds: Optional[int] = None,
                           symmetrize: bool = False):
    """Distributed finish: edges sharded over ``axes``, labels replicated.

    Returns a jit-able ``(labels, senders, receivers) -> (labels, rounds)``
    on ``(n + 1,)`` labels and dump-padded COO shards (sentinel ``n``).

    ``symmetrize=True`` mirrors each edge shard locally inside the program
    (streaming batches carry one direction per edge; min-based hooks need
    both visible). Local mirroring keeps (u, v) and (v, u) in the same shard
    — an equally valid edge distribution — and avoids resharding a globally
    concatenated array."""
    axes = tuple(axes)
    espec = P(axes)
    cap = _fixpoint_cap(mesh, axes, max_rounds)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), espec, espec),
             out_specs=(P(), P()), check_vma=False)
    def program(labels, s, r):
        if symmetrize:
            s, r = (jnp.concatenate([s, r]), jnp.concatenate([r, s]))

        def body(L):
            L2, _ = finish_fn(L, s, r)
            return jax.lax.pmin(L2, axes)

        return _outer_loop(body, labels, rounds, cap)

    return program


# ---------------------------------------------------------------------------
# Sharded-label programs (spec-parameterized).
# ---------------------------------------------------------------------------

def _auto_frontier(n1: int, ngather: int) -> int:
    """Auto per-device frontier cap. The compacted exchange moves
    ``2 * ngather * F`` int32s per round vs the dense merge's ``n1``-wide
    reduce, so the cap sits near ``n1 / (4 * ngather)`` (lane-rounded up):
    sparse rounds are cheaper than dense by construction, and rounds whose
    frontier exceeds the cap fall back to dense."""
    return min(n1, max(128, round_up(max(n1 // (4 * ngather), 1), 128)))


def make_sharded_finish(mesh: Mesh, edge_axes: Sequence[str], label_axis: str,
                        finish_fn: Callable, *, reduce_scatter: bool = False,
                        rounds: int = 0,
                        max_rounds: Optional[int] = None,
                        symmetrize: bool = False,
                        frontier: int = -1, overlap: bool = False,
                        kernels: Optional[str] = None):
    """Distributed finish with labels sharded over ``label_axis``.

    The label array length must divide evenly by the label-axis size (pad
    with self-rooted slots above the dump row; see execution.py). On a 1-D
    mesh ``edge_axes`` may equal ``(label_axis,)``: edges and labels then
    shard over the same axis and the merge reduces over it once; on a 2-D
    mesh the label axis may be one of the edge axes (``sharded(x,y)``) and
    labels replicate over the rest. ``symmetrize`` mirrors edge shards
    locally (see make_replicated_finish).

    ``frontier`` caps the compacted merge exchange per device (-1 auto from
    n and the mesh, 0 dense-only, N explicit). ``overlap`` runs the
    double-buffered two-block pipeline: round r's frontier exchange is
    consumed *after* round r+1's local finish on the other edge block, so
    the collective and the next block's compute can overlap. Correctness of
    the deferred application rests on monotonicity: a finish on stale
    labels only proposes valid (component-internal, possibly larger) label
    values, and min-folding the late exchange can only lower them further.
    Convergence requires two consecutive clean rounds (both blocks verified
    on settled labels with no exchange in flight)."""
    edge_axes = tuple(edge_axes)
    extra_axes = tuple(a for a in edge_axes if a != label_axis)
    merge_axes = tuple(dict.fromkeys(edge_axes + (label_axis,)))
    nshards = mesh.shape[label_axis]
    ngather = prod(mesh.shape[a] for a in merge_axes)
    # the continue flag reduces over *every* mesh axis so the while cond is
    # uniform even on user meshes with axes the spec does not use
    flag_axes = tuple(mesh.axis_names)
    espec = P(edge_axes)
    lspec = P(label_axis)
    cap = _fixpoint_cap(mesh, edge_axes, max_rounds)

    def dense_candidate(full2, shard_len):
        """Dense merge: the candidate shard slice min-reduced over the mesh."""
        if reduce_scatter:
            # min-reduce-scatter: all_to_all over label chunks + local
            # min moves 1/|label| of the bytes of a full all-reduce
            chunks = full2.reshape(nshards, shard_len)
            mine = jax.lax.all_to_all(chunks, label_axis, split_axis=0,
                                      concat_axis=0, tiled=False)
            mine = jnp.min(mine, axis=0)
            if extra_axes:
                mine = jax.lax.pmin(mine, extra_axes)
            return mine
        merged = jax.lax.pmin(full2, merge_axes)
        idx = jax.lax.axis_index(label_axis)
        return jax.lax.dynamic_slice_in_dim(merged, idx * shard_len,
                                            shard_len)

    def gather_frontier(fi, fv):
        """Exchange compacted (global index, value) frontier buffers."""
        for a in merge_axes:
            fi = jax.lax.all_gather(fi, a, tiled=True)
            fv = jax.lax.all_gather(fv, a, tiled=True)
        return fi, fv

    def apply_frontier(shard, fi, fv, kernels=kernels):
        """Scatter an exchanged frontier into the local shard window (out-
        of-window and unused ``-1`` slots dump; see ops.scatter_min)."""
        shard_len = shard.shape[0]
        offset = jax.lax.axis_index(label_axis) * shard_len
        pad = jnp.concatenate([shard, shard[-1:]])
        out = ops.scatter_min(pad, fi - offset, fv, fi >= 0, policy=kernels)
        return out[:shard_len]

    def resolve_cap(shard_len: int) -> int:
        n1 = shard_len * nshards
        if frontier == 0:
            return 0
        if frontier > 0:
            return min(frontier, n1)
        return _auto_frontier(n1, ngather)

    @partial(jax.shard_map, mesh=mesh, in_specs=(lspec, espec, espec),
             out_specs=(lspec, P()), check_vma=False)
    def program(lab_shard, s, r):
        if symmetrize:
            s, r = (jnp.concatenate([s, r]), jnp.concatenate([r, s]))
        shard_len = lab_shard.shape[0]
        F = resolve_cap(shard_len)

        def body(shard):
            full = jax.lax.all_gather(shard, label_axis, tiled=True)
            full2, _ = finish_fn(full, s, r)
            diff = full2 < full
            cnt = jnp.sum(diff, dtype=jnp.int32)
            gmax = jax.lax.pmax(cnt, flag_axes)
            if F > 0:
                def sparse(_):
                    fi, fv = ops.compact_mask(diff, full2, F)
                    return apply_frontier(shard, *gather_frontier(fi, fv))

                def dense(_):
                    return jnp.minimum(shard,
                                       dense_candidate(full2, shard_len))

                # gmax <= F guarantees no shard overflows its cap, and the
                # pmax-reduced count makes the branch mesh-uniform
                shard2 = jax.lax.cond(gmax <= F, sparse, dense, None)
            else:
                shard2 = jnp.minimum(shard, dense_candidate(full2, shard_len))
            # gmax == 0 ⟺ no shard's finish lowered any label ⟺ every
            # edge satisfied: the fixpoint flag comes free from the merge
            return shard2, gmax > 0

        return _outer_loop_flagged(body, lab_shard, rounds, cap)

    @partial(jax.shard_map, mesh=mesh, in_specs=(lspec, espec, espec),
             out_specs=(lspec, P()), check_vma=False)
    def program_overlap(lab_shard, s, r):
        shard_len = lab_shard.shape[0]
        F = resolve_cap(shard_len)
        m = s.shape[0]
        if m >= 2:
            blocks = ((s[: m // 2], r[: m // 2]), (s[m // 2:], r[m // 2:]))
        else:
            blocks = ((s, r), (s, r))
        if symmetrize:
            # mirror per block so each block sees both edge directions
            blocks = tuple((jnp.concatenate([bs, br]),
                            jnp.concatenate([br, bs])) for bs, br in blocks)
        empty_i = jnp.full((ngather * F,), -1, jnp.int32)
        empty_v = jnp.full((ngather * F,), INT_MAX, lab_shard.dtype)

        def local_finish(full, k):
            return jax.lax.cond(
                k % 2 == 0,
                lambda L: finish_fn(L, *blocks[0])[0],
                lambda L: finish_fn(L, *blocks[1])[0], full)

        def step(st):
            shard, pi, pv, streak, k = st
            full = jax.lax.all_gather(shard, label_axis, tiled=True)
            # local finish on the round's block reads the *stale* labels —
            # it does not depend on the in-flight exchange below, so the
            # scheduler can overlap the two
            full2 = local_finish(full, k)
            diff = full2 < full
            gmax = jax.lax.pmax(jnp.sum(diff, dtype=jnp.int32), flag_axes)
            if F > 0:
                # consume last round's exchange only now
                mine = apply_frontier(shard, pi, pv)
                pend = jnp.any(pi >= 0)
            else:
                mine, pend = shard, jnp.bool_(False)
            offset = jax.lax.axis_index(label_axis) * shard_len
            own = jnp.minimum(mine, jax.lax.dynamic_slice_in_dim(
                full2, offset, shard_len))
            if F > 0:
                def sparse(_):
                    fi, fv = ops.compact_mask(diff, full2, F)
                    fi, fv = gather_frontier(fi, fv)
                    return own, fi, fv

                def dense(_):
                    return (jnp.minimum(own,
                                        dense_candidate(full2, shard_len)),
                            empty_i, empty_v)

                shard2, pi2, pv2 = jax.lax.cond(gmax <= F, sparse, dense,
                                                None)
            else:
                shard2 = jnp.minimum(own, dense_candidate(full2, shard_len))
                pi2, pv2 = empty_i, empty_v
            # clean ⟺ this block found nothing on settled labels and no
            # exchange was in flight; two consecutive clean rounds cover
            # both blocks ⇒ global fixpoint (pend/gmax are device-identical)
            clean = (gmax == 0) & ~pend
            streak = jnp.where(clean, streak + 1, jnp.int32(0))
            return shard2, pi2, pv2, streak, k + 1

        init = (lab_shard, empty_i, empty_v, jnp.int32(0), jnp.int32(0))
        if rounds > 0:
            st = jax.lax.fori_loop(0, rounds, lambda i, t: step(t), init)
            k = jnp.int32(rounds)
        else:
            st = jax.lax.while_loop(
                lambda t: (t[3] < 2) & (t[4] < cap), step, init)
            k = st[4]
        shard = st[0]
        if F > 0:  # drain the trailing in-flight exchange
            shard = apply_frontier(shard, st[1], st[2])
        return shard, k

    return program_overlap if overlap else program


def make_sharded_compress(mesh: Mesh, label_axis: str,
                          kernels: Optional[str] = None):
    """Full pointer-jump compression of a label-sharded array (one gather)."""
    lspec = P(label_axis)

    @partial(jax.shard_map, mesh=mesh, in_specs=(lspec,), out_specs=lspec,
             check_vma=False)
    def compress(lab_shard):
        shard_len = lab_shard.shape[0]
        idx = jax.lax.axis_index(label_axis)
        full = jax.lax.all_gather(lab_shard, label_axis, tiled=True)
        full = full_compress(full, kernels=kernels)
        return jax.lax.dynamic_slice_in_dim(full, idx * shard_len, shard_len)

    return compress


# ---------------------------------------------------------------------------
# Application programs (paper §5): the distributed AMSF bucket forest.
#
# Forest-edge recording across shards needs deterministic tie-breaking (one
# recorded edge per hooked root, Theorem 6), so the per-bucket forest round
# is *globally synchronized*: every shard computes its local min-hook
# proposals, the winning (value, edge id, endpoints) buffers are pmin-merged
# over the edge axes, and only then do all shards apply the hook and record
# the unique global winner — the min-merge outer loop of the PR 2 machinery
# applied per round instead of per local fixpoint. The whole bucket sweep
# (geometric bucket ids → masked per-bucket forest fixpoints) runs inside
# one shard_map dispatch: zero per-bucket host syncs on the mesh paths too.
# ---------------------------------------------------------------------------

def _global_forest_round(P, fu, fv, s, r, gid, active, axes, *,
                         compress: str = "full",
                         kernels: Optional[str] = None):
    """One globally-merged forest hook round (+ compression) on an edge
    shard → ``(P, fu, fv, changed)``.

    ``gid`` is the globally-unique edge id of each local slot; ``axes`` are
    the mesh axes the proposal buffers merge over. Labels in/out are the
    full replicated array; fu/fv are replicated forest buffers.

    Pass 1 alone decides whether any root hooks this round; the edge-id and
    endpoint passes plus the compression run under a ``lax.cond`` on that
    flag (mesh-uniform — the value buffer is pmin-merged before the test),
    so the fixpoint-confirmation round every bucket pays costs one scatter
    and one pmin instead of the full three-pass round. The ``changed`` flag
    is local: all inputs are replicated-identical and all merged buffers
    identical by construction, so no flag-reduction collective is needed."""
    n1 = P.shape[0]
    act = active & (P[s] != P[r])
    pu = P[s]
    pv = P[r]
    root_u = parents_of(P, pu) == pu
    mask = act & root_u & (pv < pu)
    big = jnp.full((n1,), INT_MAX, P.dtype)
    # pass 1: winning hook value per root, merged across shards
    vbuf = ops.scatter_min(big, pu, pv, mask, policy=kernels)
    vbuf = jax.lax.pmin(vbuf, axes)
    hooked = jnp.any(vbuf < INT_MAX)

    def rest(_):
        # pass 2: winning global edge id among achievers of the value
        safe_pu = jnp.clip(pu, 0, n1 - 1)
        achieve = mask & (pv == vbuf[safe_pu])
        ebuf = ops.scatter_min(jnp.full((n1,), INT_MAX, jnp.int32), pu, gid,
                               achieve, policy=kernels)
        ebuf = jax.lax.pmin(ebuf, axes)
        # pass 3: the unique winning shard publishes *both* edge endpoints
        # through one stacked (2·n1+1,) buffer — one scatter + one pmin
        # where separate sender/receiver buffers would cost two of each
        mine = achieve & (gid == ebuf[safe_pu])
        uw = ops.scatter_min(
            jnp.full((2 * n1 + 1,), INT_MAX, jnp.int32),
            jnp.concatenate([pu, pu + n1]), jnp.concatenate([s, r]),
            jnp.concatenate([mine, mine]), policy=kernels)
        uw = jax.lax.pmin(uw[: 2 * n1], axes)
        # apply: hook roots to the winning values, record first-time hooks
        sel = (ebuf < INT_MAX) & (fu == -1)
        fu2 = jnp.where(sel, uw[:n1], fu)
        fv2 = jnp.where(sel, uw[n1:], fv)
        P2 = _compress(jnp.minimum(P, vbuf), compress, kernels=kernels)
        return P2, fu2, fv2

    if compress == "full":
        # P stays fully compressed between rounds, so "no root hooked" is
        # exactly the bucket fixpoint — skip compression on the no-op round
        P2, fu2, fv2 = jax.lax.cond(hooked, rest,
                                    lambda _: (P, fu, fv), None)
        return P2, fu2, fv2, hooked
    # partial compression can unlock hooks later even on a hook-free round,
    # so it must still run; the changed flag then tracks P itself
    P2, fu2, fv2 = jax.lax.cond(
        hooked, rest,
        lambda _: (_compress(P, compress, kernels=kernels), fu, fv), None)
    return P2, fu2, fv2, hooked | jnp.any(P2 != P)


def _bucket_sweep(P, fu, fv, s, r, bids, gid, axes, *, compress: str,
                  skip: bool, kernels: Optional[str], cap: int):
    """The shared device-side bucket sweep body (full replicated labels).

    The per-bucket fixpoint is flag-driven: the forest round reports its
    own changed flag (device-identical by construction), so convergence
    costs no old-vs-new array compare and no flag-reduction collective."""
    bmax_local = jnp.max(jnp.where(bids < INT_MAX, bids, -1))
    bmax = jax.lax.pmax(bmax_local, axes)

    def bucket_cond(st):
        return st[3] <= bmax

    def bucket_body(st):
        P, fu, fv, b, tot = st
        active = bids == b
        if skip:
            active &= _skip_lmax_mask(P, s, r, kernels)

        def round_cond(st2):
            return st2[3] & (st2[4] < cap)

        def round_body(st2):
            P, fu, fv, _, k = st2
            P, fu, fv, ch = _global_forest_round(
                P, fu, fv, s, r, gid, active, axes, compress=compress,
                kernels=kernels)
            return P, fu, fv, ch, k + 1

        P, fu, fv, _, rounds = jax.lax.while_loop(
            round_cond, round_body,
            (P, fu, fv, jnp.bool_(True), jnp.int32(0)))
        return P, fu, fv, b + 1, tot + rounds

    P, fu, fv, b, tot = jax.lax.while_loop(
        bucket_cond, bucket_body,
        (P, fu, fv, jnp.int32(0), jnp.int32(0)))
    return P, fu, fv, b, tot


def _shard_gid(mesh: Mesh, axes: Sequence[str], m_local):
    """Globally-unique int32 edge ids for a shard's local slots."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx * m_local + jnp.arange(m_local, dtype=jnp.int32)


def make_replicated_amsf(mesh: Mesh, axes: Sequence[str], *,
                         compress: str = "full", skip: bool = False,
                         kernels: Optional[str] = None,
                         max_rounds: Optional[int] = None):
    """Distributed AMSF bucket sweep: edges (and bucket ids) sharded over
    ``axes``, labels and forest buffers replicated. One dispatch for the
    whole sweep: ``(P, fu, fv, senders, receivers, bids) -> (P, fu, fv,
    buckets, rounds)``."""
    axes = tuple(axes)
    espec = P(axes)
    cap = _fixpoint_cap(mesh, axes, max_rounds)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(), P(), P(), espec, espec, espec),
             out_specs=(P(), P(), P(), P(), P()), check_vma=False)
    def program(labels, fu, fv, s, r, bids):
        gid = _shard_gid(mesh, axes, s.shape[0])
        return _bucket_sweep(labels, fu, fv, s, r, bids, gid, axes,
                             compress=compress, skip=skip, kernels=kernels,
                             cap=cap)

    return program


def make_sharded_amsf(mesh: Mesh, edge_axes: Sequence[str], label_axis: str,
                      *, compress: str = "full", skip: bool = False,
                      kernels: Optional[str] = None,
                      max_rounds: Optional[int] = None):
    """Distributed AMSF with labels sharded over ``label_axis``: the labels
    are gathered once, the sweep runs on the full array with merges over the
    edge axes, and the final labeling is resharded. Forest buffers stay
    replicated (they are the output being compacted host-side anyway)."""
    edge_axes = tuple(edge_axes)
    espec = P(edge_axes)
    lspec = P(label_axis)
    cap = _fixpoint_cap(mesh, edge_axes, max_rounds)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(lspec, P(), P(), espec, espec, espec),
             out_specs=(lspec, P(), P(), P(), P()), check_vma=False)
    def program(lab_shard, fu, fv, s, r, bids):
        shard_len = lab_shard.shape[0]
        labels = jax.lax.all_gather(lab_shard, label_axis, tiled=True)
        gid = _shard_gid(mesh, edge_axes, s.shape[0])
        labels, fu, fv, b, tot = _bucket_sweep(
            labels, fu, fv, s, r, bids, gid, edge_axes, compress=compress,
            skip=skip, kernels=kernels, cap=cap)
        idx = jax.lax.axis_index(label_axis)
        shard = jax.lax.dynamic_slice_in_dim(labels, idx * shard_len,
                                             shard_len)
        return shard, fu, fv, b, tot

    return program


# ---------------------------------------------------------------------------
# Streaming programs (paper §3.5 / Algorithm 3 at mesh scale).
# ---------------------------------------------------------------------------

class StreamPrograms(NamedTuple):
    """Mesh programs behind an execution-aware ``repro.api.Stream``."""

    insert: Callable   # (labels, u, v) -> (labels, rounds)
    query: Callable    # (labels, qa, qb) -> bool[q]
    process: Callable  # (labels, u, v, qa, qb) -> (labels, ans, rounds)


def make_replicated_stream(mesh: Mesh, axes: Sequence[str],
                           finish_fn: Callable, *, rounds: int = 0,
                           max_rounds: Optional[int] = None,
                           kernels: Optional[str] = None
                           ) -> StreamPrograms:
    """Batch insert+query with labels replicated, batches/queries sharded."""
    axes = tuple(axes)
    espec = P(axes)
    run = make_replicated_finish(mesh, axes, finish_fn, rounds=rounds,
                                 max_rounds=max_rounds, symmetrize=True)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), espec, espec),
             out_specs=espec, check_vma=False)
    def query(labels, qa, qb):
        return labels[qa] == labels[qb]

    def insert(labels, u, v):
        labels, k = run(labels, u, v)
        # keep the labeling fully compressed between batches (O(1) queries)
        return full_compress(labels, kernels=kernels), k

    def process(labels, u, v, qa, qb):
        labels, k = insert(labels, u, v)
        return labels, query(labels, qa, qb), k

    return StreamPrograms(insert, query, process)


def make_sharded_stream(mesh: Mesh, edge_axes: Sequence[str], label_axis: str,
                        finish_fn: Callable, *, reduce_scatter: bool = False,
                        rounds: int = 0,
                        max_rounds: Optional[int] = None,
                        frontier: int = -1, overlap: bool = False,
                        kernels: Optional[str] = None
                        ) -> StreamPrograms:
    """Batch insert+query with labels sharded over ``label_axis``."""
    edge_axes = tuple(edge_axes)
    espec = P(edge_axes)
    lspec = P(label_axis)
    run = make_sharded_finish(mesh, edge_axes, label_axis, finish_fn,
                              reduce_scatter=reduce_scatter, rounds=rounds,
                              max_rounds=max_rounds, symmetrize=True,
                              frontier=frontier, overlap=overlap,
                              kernels=kernels)
    compress = make_sharded_compress(mesh, label_axis, kernels=kernels)

    @partial(jax.shard_map, mesh=mesh, in_specs=(lspec, espec, espec),
             out_specs=espec, check_vma=False)
    def query(lab_shard, qa, qb):
        full = jax.lax.all_gather(lab_shard, label_axis, tiled=True)
        return full[qa] == full[qb]

    def insert(labels, u, v):
        labels, k = run(labels, u, v)
        return compress(labels), k

    def process(labels, u, v, qa, qb):
        labels, k = insert(labels, u, v)
        return labels, query(labels, qa, qb), k

    return StreamPrograms(insert, query, process)


# ---------------------------------------------------------------------------
# Batch-dynamic programs (repro.dynamic at mesh scale).
#
# The delete/rebuild machinery is the engine's (repro.dynamic.engine); the
# only distributed ingredient is the forest hook round, which must record a
# *deterministic* cross-shard winner per hooked root — exactly the
# 3-pass pmin-merged ``_global_forest_round`` the AMSF programs already use.
# Labels and forest buffers stay replicated across the edge shards (merged
# every round), the edge log is sharded like stream batches, and the delete
# batch is replicated so every shard tombstones its own log slots and all
# shards agree on forest hits without any collective.
# ---------------------------------------------------------------------------

class DynamicPrograms(NamedTuple):
    """Mesh programs behind an execution-aware ``repro.api.DynamicStream``."""

    update: Callable   # (P, fu, fv, log_u, log_v, du, dv, bu, bv) -> (...)
    query: Callable    # (labels, qa, qb) -> bool[q]
    used: Callable     # (log_u) -> (edge_shards,) live log entries


def _dynamic_body(labels, fu, fv, log_u, log_v, du, dv, bu, bv, *, n: int,
                  mesh: Mesh, axes: Sequence[str], compress: str,
                  search_rounds: int, kernels: Optional[str], cap: int):
    """Per-shard mixed-batch update on full replicated labels.

    Mirrors ``engine.make_update`` with the hook round swapped for the
    globally-merged forest round; every label/forest/flag value is identical
    on all shards after each merge, so the ``lax.cond`` predicates and while
    conditions are mesh-uniform with *local* flags — no reduction
    collective in the convergence check."""
    from ..dynamic import engine

    ids = jnp.arange(n + 1, dtype=labels.dtype)

    def changed(old, new):
        return jnp.any(old[0] != new[0])

    def round_(st, s, r, gid):
        P2, fu2, fv2, _ = _global_forest_round(
            st[0], st[1], st[2], s, r, gid, s < n, axes, compress=compress,
            kernels=kernels)
        return P2, fu2, fv2

    # -- delete phase -------------------------------------------------------
    slo, shi = engine.sorted_pairs(du, dv, n)
    dead = engine.pairs_member(slo, shi, log_u, log_v)
    log_u = jnp.where(dead, jnp.asarray(n, log_u.dtype), log_u)
    log_v = jnp.where(dead, jnp.asarray(n, log_v.dtype), log_v)
    hit = engine.pairs_member(slo, shi, fu, fv)

    def rebuild(st):
        P1, fu1, fv1 = st
        aff = engine.affected_mask(P1, fu1, hit)
        P1 = jnp.where(aff, ids, P1)
        fu2 = jnp.where(aff, jnp.asarray(-1, fu1.dtype), fu1)
        fv2 = jnp.where(aff, jnp.asarray(-1, fv1.dtype), fv1)
        s, r = engine.masked_log_edges(log_u, log_v, aff, n)
        gid = _shard_gid(mesh, axes, s.shape[0])
        st2, k1 = iterate_to_fixpoint(
            lambda t: round_(t, s, r, gid), (P1, fu2, fv2), search_rounds,
            changed_fn=changed)
        st2, k2 = jax.lax.cond(
            k1 >= search_rounds,
            lambda t: iterate_to_fixpoint(
                lambda q: round_(q, s, r, gid), t, cap, changed_fn=changed),
            lambda t: (t, 0), st2)
        return st2, (k1 + k2).astype(jnp.int32)

    (labels, fu, fv), drounds = jax.lax.cond(
        jnp.any(hit), rebuild,
        lambda st: (st, jnp.int32(0)), (labels, fu, fv))

    # -- insert phase -------------------------------------------------------
    bu2, bv2 = engine.sanitize_pairs(bu, bv, n)
    log_u, log_v = engine.append_log(log_u, log_v, bu2, bv2, n)
    s = jnp.concatenate([bu2, bv2])
    r = jnp.concatenate([bv2, bu2])
    gid = _shard_gid(mesh, axes, s.shape[0])
    (labels, fu, fv), irounds = iterate_to_fixpoint(
        lambda t: round_(t, s, r, gid), (labels, fu, fv), cap,
        changed_fn=changed)
    labels = full_compress(labels, kernels=kernels)
    return labels, fu, fv, log_u, log_v, drounds + irounds.astype(jnp.int32)


def make_replicated_dynamic(mesh: Mesh, axes: Sequence[str], n: int, *,
                            compress: str = "full", search_rounds: int = 4,
                            kernels: Optional[str] = None,
                            max_rounds: Optional[int] = None
                            ) -> DynamicPrograms:
    """Batch-dynamic programs with labels/forest replicated, the edge log
    and insert batches sharded over ``axes``, delete batches replicated."""
    axes = tuple(axes)
    espec = P(axes)
    cap = _fixpoint_cap(mesh, axes, max_rounds)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(), P(), P(), espec, espec, P(), P(), espec, espec),
             out_specs=(P(), P(), P(), espec, espec, P()), check_vma=False)
    def update(labels, fu, fv, log_u, log_v, du, dv, bu, bv):
        return _dynamic_body(labels, fu, fv, log_u, log_v, du, dv, bu, bv,
                             n=n, mesh=mesh, axes=axes, compress=compress,
                             search_rounds=search_rounds, kernels=kernels,
                             cap=cap)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), espec, espec),
             out_specs=espec, check_vma=False)
    def query(labels, qa, qb):
        return labels[qa] == labels[qb]

    @partial(jax.shard_map, mesh=mesh, in_specs=(espec,), out_specs=espec,
             check_vma=False)
    def used(log_u):
        return jnp.sum(log_u < n, dtype=jnp.int32)[None]

    return DynamicPrograms(update, query, used)


def make_sharded_dynamic(mesh: Mesh, edge_axes: Sequence[str],
                         label_axis: str, n: int, *,
                         compress: str = "full", search_rounds: int = 4,
                         kernels: Optional[str] = None,
                         max_rounds: Optional[int] = None
                         ) -> DynamicPrograms:
    """Batch-dynamic programs with labels sharded over ``label_axis``: the
    labels are gathered once per update (the forest-carrying precedent,
    ``make_sharded_amsf``), the mixed-batch body runs on the full array with
    merges over the edge axes, and the labeling is resharded at the end. The
    padded tail above the dump row is sliced off before the body and rebuilt
    after — tail slots are self-rooted and no edge can reference them."""
    edge_axes = tuple(edge_axes)
    espec = P(edge_axes)
    lspec = P(label_axis)
    cap = _fixpoint_cap(mesh, edge_axes, max_rounds)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(lspec, P(), P(), espec, espec, P(), P(), espec,
                       espec),
             out_specs=(lspec, P(), P(), espec, espec, P()), check_vma=False)
    def update(lab_shard, fu, fv, log_u, log_v, du, dv, bu, bv):
        shard_len = lab_shard.shape[0]
        full = jax.lax.all_gather(lab_shard, label_axis, tiled=True)
        length = full.shape[0]
        labels, fu, fv, log_u, log_v, rounds = _dynamic_body(
            full[: n + 1], fu, fv, log_u, log_v, du, dv, bu, bv, n=n,
            mesh=mesh, axes=edge_axes, compress=compress,
            search_rounds=search_rounds, kernels=kernels, cap=cap)
        if length > n + 1:
            tail = jnp.arange(n + 1, length, dtype=labels.dtype)
            labels = jnp.concatenate([labels, tail])
        idx = jax.lax.axis_index(label_axis)
        shard = jax.lax.dynamic_slice_in_dim(labels, idx * shard_len,
                                             shard_len)
        return shard, fu, fv, log_u, log_v, rounds

    @partial(jax.shard_map, mesh=mesh, in_specs=(lspec, espec, espec),
             out_specs=espec, check_vma=False)
    def query(lab_shard, qa, qb):
        full = jax.lax.all_gather(lab_shard, label_axis, tiled=True)
        return full[qa] == full[qb]

    @partial(jax.shard_map, mesh=mesh, in_specs=(espec,), out_specs=espec,
             check_vma=False)
    def used(log_u):
        return jnp.sum(log_u < n, dtype=jnp.int32)[None]

    return DynamicPrograms(update, query, used)


# ---------------------------------------------------------------------------
# Legacy factories (deprecation shims; pre-ExecutionSpec behavior preserved).
#
# These hardwire ``jumps``-round pointer jumping, run a fixed number of
# rounds, and share no stats with the session layer. New code should build an
# ``repro.api.ExecutionSpec`` (or use ``repro.core.execution.make_backend``)
# so the finish/compression comes from the VariantSpec.
# ---------------------------------------------------------------------------

_DEPRECATION = (
    "%s is deprecated; declare the placement with repro.api.ExecutionSpec "
    "(e.g. ConnectIt(spec, exec='replicated(x)')) or build programs via "
    "repro.core.execution.make_backend — see docs/API.md")


def _local_proposals(labels, s, r, big):
    """Scatter-min proposals of sender labels into receiver slots (+reverse)."""
    n1 = labels.shape[0]
    buf = jnp.full((n1,), big, labels.dtype)
    buf = buf.at[r].min(labels[s])
    buf = buf.at[s].min(labels[r])
    return buf


def make_replicated_step(mesh: Mesh, axes: Sequence[str], *, jumps: int = 2,
                         _warn: bool = True):
    """Deprecated: one fixed pointer-jump round; see make_replicated_finish."""
    if _warn:
        warnings.warn(_DEPRECATION % "make_replicated_step",
                      DeprecationWarning, stacklevel=2)
    axes = tuple(axes)
    espec = P(axes)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), espec, espec),
             out_specs=P(), check_vma=False)
    def step(labels, s, r):
        big = jnp.asarray(jnp.iinfo(labels.dtype).max, labels.dtype)
        prop = _local_proposals(labels, s, r, big)
        prop = jax.lax.pmin(prop, axes)
        labels = jnp.minimum(labels, prop)
        for _ in range(jumps):
            labels = jnp.minimum(labels, labels[labels])
        return labels

    return step


def make_replicated_connectivity(mesh: Mesh, axes: Sequence[str], *,
                                 rounds: int, jumps: int = 2):
    """Deprecated: fixed-round replicated connectivity (pre-ExecutionSpec)."""
    warnings.warn(_DEPRECATION % "make_replicated_connectivity",
                  DeprecationWarning, stacklevel=2)
    step = make_replicated_step(mesh, axes, jumps=jumps, _warn=False)

    def run(labels, senders, receivers):
        def body(i, labels):
            return step(labels, senders, receivers)
        return jax.lax.fori_loop(0, rounds, body, labels)

    return run


def make_sharded_step(mesh: Mesh, edge_axes: Sequence[str], label_axis: str,
                      *, jumps: int = 2, use_reduce_scatter: bool = False,
                      _warn: bool = True):
    """Deprecated: one sharded-label pointer-jump round."""
    if _warn:
        warnings.warn(_DEPRECATION % "make_sharded_step",
                      DeprecationWarning, stacklevel=2)
    edge_axes = tuple(edge_axes)
    espec = P(edge_axes)
    lspec = P(label_axis)
    nshards = mesh.shape[label_axis]

    @partial(jax.shard_map, mesh=mesh, in_specs=(lspec, espec, espec),
             out_specs=lspec, check_vma=False)
    def step(labels_shard, s, r):
        dtype = labels_shard.dtype
        big = jnp.asarray(jnp.iinfo(dtype).max, dtype)
        labels = jax.lax.all_gather(labels_shard, label_axis, tiled=True)
        prop = _local_proposals(labels, s, r, big)
        if use_reduce_scatter:
            shard_len = labels_shard.shape[0]
            chunks = prop.reshape(nshards, shard_len)
            mine = jax.lax.all_to_all(
                chunks, label_axis, split_axis=0, concat_axis=0, tiled=False)
            prop_local = jnp.min(mine, axis=0)
            prop_local = jax.lax.pmin(prop_local, edge_axes)
        else:
            prop = jax.lax.pmin(prop, edge_axes + (label_axis,))
            idx = jax.lax.axis_index(label_axis)
            shard_len = labels_shard.shape[0]
            prop_local = jax.lax.dynamic_slice_in_dim(
                prop, idx * shard_len, shard_len)
        new_shard = jnp.minimum(labels_shard, prop_local)
        full = jax.lax.all_gather(new_shard, label_axis, tiled=True)
        for _ in range(jumps):
            full = jnp.minimum(full, full[full])
        idx = jax.lax.axis_index(label_axis)
        shard_len = labels_shard.shape[0]
        return jax.lax.dynamic_slice_in_dim(full, idx * shard_len, shard_len)

    return step


def make_sharded_connectivity(mesh: Mesh, edge_axes: Sequence[str],
                              label_axis: str, *, rounds: int, jumps: int = 2,
                              use_reduce_scatter: bool = False):
    """Deprecated: fixed-round sharded connectivity (pre-ExecutionSpec)."""
    warnings.warn(_DEPRECATION % "make_sharded_connectivity",
                  DeprecationWarning, stacklevel=2)
    step = make_sharded_step(mesh, edge_axes, label_axis, jumps=jumps,
                             use_reduce_scatter=use_reduce_scatter,
                             _warn=False)

    def run(labels, senders, receivers):
        def body(i, labels):
            return step(labels, senders, receivers)
        return jax.lax.fori_loop(0, rounds, body, labels)

    return run


def make_sharded_step_fused(mesh: Mesh, edge_axes: Sequence[str],
                            label_axis: str, *, jumps: int = 2,
                            _warn: bool = True):
    """Deprecated: single-gather sharded round (use ExecutionSpec ':fused')."""
    if _warn:
        warnings.warn(_DEPRECATION % "make_sharded_step_fused",
                      DeprecationWarning, stacklevel=2)
    edge_axes = tuple(edge_axes)
    espec = P(edge_axes)
    lspec = P(label_axis)
    nshards = mesh.shape[label_axis]

    @partial(jax.shard_map, mesh=mesh, in_specs=(lspec, espec, espec),
             out_specs=lspec, check_vma=False)
    def step(labels_shard, s, r):
        dtype = labels_shard.dtype
        big = jnp.asarray(jnp.iinfo(dtype).max, dtype)
        shard_len = labels_shard.shape[0]
        labels = jax.lax.all_gather(labels_shard, label_axis, tiled=True)
        prop = _local_proposals(labels, s, r, big)
        jumped = jnp.minimum(labels, prop)
        for _ in range(jumps):
            jumped = jnp.minimum(jumped, labels[jumped])
        chunks = jumped.reshape(nshards, shard_len)
        mine = jax.lax.all_to_all(chunks, label_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        prop_local = jnp.min(mine, axis=0)
        prop_local = jax.lax.pmin(prop_local, edge_axes)
        return jnp.minimum(labels_shard, prop_local)

    return step


def make_sharded_connectivity_fused(mesh: Mesh, edge_axes: Sequence[str],
                                    label_axis: str, *, rounds: int,
                                    jumps: int = 2):
    """Deprecated: fixed-round fused sharded connectivity."""
    warnings.warn(_DEPRECATION % "make_sharded_connectivity_fused",
                  DeprecationWarning, stacklevel=2)
    step = make_sharded_step_fused(mesh, edge_axes, label_axis, jumps=jumps,
                                   _warn=False)

    def run(labels, senders, receivers):
        def body(i, labels):
            return step(labels, senders, receivers)
        return jax.lax.fori_loop(0, rounds, body, labels)

    return run


def make_streaming_ingest(mesh: Mesh, axes: Sequence[str], *, rounds: int = 4,
                          jumps: int = 2):
    """Deprecated: folded into the execution-aware ``repro.api.Stream``
    (``ConnectIt(spec, exec='replicated(...)').stream(n)``)."""
    warnings.warn(_DEPRECATION % "make_streaming_ingest",
                  DeprecationWarning, stacklevel=2)
    step = make_replicated_step(mesh, axes, jumps=jumps, _warn=False)
    axes = tuple(axes)
    qspec = P(axes)

    def ingest(labels, bu, bv, qa, qb):
        def body(i, labels):
            return step(labels, bu, bv)
        labels = jax.lax.fori_loop(0, rounds, body, labels)

        @partial(jax.shard_map, mesh=mesh, in_specs=(P(), qspec, qspec),
                 out_specs=qspec, check_vma=False)
        def answer(labels, qa, qb):
            return labels[qa] == labels[qb]

        return labels, answer(labels, qa, qb)

    return ingest
