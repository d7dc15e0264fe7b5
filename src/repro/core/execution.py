"""ExecutionSpec: one declarative execution surface for connectivity.

``repro.api.VariantSpec`` says *what* to run (sampling × finish ×
compression); ``ExecutionSpec`` says *where and how* to dispatch it:

    placement := single | replicated | sharded
    exec      := placement [ "(" axes ")" ] [ ":" opt ("," opt)* ]
    axes      := axis ("," axis)* [ "|" label_axis ]      # sharded only
    opt       := "fused" | "overlap" | "donate"
               | "frontier=" INT | "pad=" ("pow2" | INT) | "rounds=" INT
               | "dynamic" | "log=" INT | "tune"
               | "kernels=" ("auto" | "pallas" | "interpret" | "ref")

Examples (canonical strings round-trip, ``ExecutionSpec.parse(str(s)) == s``):

    single                     one device, compacted finish dispatch
    single:fused               one device, single-dispatch (no compaction)
    single:pad=256             compacted list padded to multiples of 256
    single:kernels=interpret   Pallas kernels under interpret=True (CPU CI)
    replicated(pod,data)       edges sharded over pod×data, labels replicated
    sharded(x)                 1-D mesh: edges AND labels sharded over x
    sharded(x,y)               2-D mesh: edges over x×y, labels over y
    sharded(pod,data|model)    edges over pod×data, labels over model
    sharded(x):fused,rounds=8  min-reduce-scatter merge, 8 fixed rounds
    sharded(x):frontier=1024   compacted merge capped at 1024 ids per shard
    sharded(x):overlap         double-buffered merge/compute overlap

Knob semantics per placement (unused knobs are pinned to their defaults on
construction, so equality and round-trips are canonical — same discipline as
``VariantSpec``):

  * ``fused`` — single: one-dispatch path (no host compaction of the
    finish-phase edge list); sharded: merge labelings with an all_to_all
    min-reduce-scatter instead of a full pmin (≈1/|label| wire bytes).
    Pinned False for replicated (its merge is already a single pmin).
  * ``frontier`` — sharded: the per-device cap of the *compacted* merge
    exchange. Each round only the labels a shard actually lowered are
    exchanged (index/value buffers, ``kernels.ops.compact_mask``), so
    rounds get cheaper as components merge; rounds whose frontier exceeds
    the cap fall back to the dense merge. ``-1`` (default) sizes the cap
    automatically from n and the mesh, ``0`` disables compaction (always
    dense), ``N`` pins the cap. Pinned -1 for single/replicated.
  * ``overlap`` — sharded: double-buffered merge. Edge shards split into
    two blocks that alternate per round and the frontier exchange of round
    r is applied at the top of round r+1, so the collective overlaps with
    the next block's local hook+compress. Pinned False for
    single/replicated.
  * ``pad`` — dispatch-shape bucketing for the compacted finish edge list
    and stream batches: ``pow2`` (default) buckets to the next power of two,
    ``pad=N`` to multiples of N. Either way distributed dispatches are
    rounded up to a multiple of the edge-shard count.
  * ``donate`` — donate the label buffer to the finish dispatch (in-place
    update on backends that support donation; a no-op warning on CPU).
    Pinned False for single.
  * ``rounds`` — fixed outer merge rounds for distributed placements
    (dry-run / fixed-budget programs); ``0`` runs to a global fixpoint.
    Pinned 0 for single (finish methods run to their own fixpoint).
  * ``dynamic`` — streams accept mixed insert/delete/query batches
    (``repro.dynamic``): the state carries a spanning forest and a
    tombstoned edge log alongside the labels. Meaningful for every
    placement.
  * ``log`` — total edge-log capacity for dynamic streams (a power of two;
    ``log=0``, the default, sizes the log automatically from ``n``). Only
    valid together with ``dynamic``.
  * ``kernels`` — the KernelPolicy (``repro.kernels.ops``) the dispatched
    programs route their hot-path primitives through: ``auto`` (default;
    defers to ``REPRO_KERNELS`` then backend detection) | ``pallas`` |
    ``interpret`` | ``ref``. Meaningful for every placement, so placement
    and kernel policy travel together in one spec.
  * ``tune`` — force re-tuning of ``auto`` selections: a
    ``ConnectIt("auto", exec="single:tune")`` session re-measures the
    variant shortlist on the first graph of each family it sees (once per
    family per session) and persists the winners in the selection cache
    (``repro.tune``) instead of trusting cached entries. Without it, auto
    resolution is a pure cache lookup. Meaningful for every placement.

Backends are planned once per (spec, mesh) and memoized: the same
``FactoryRegistry`` machinery that keeps sampler/finish callables stable for
jit caches (core/registry.py) keeps execution programs stable across
sessions. ``ConnectIt(spec, exec=...)`` is the front-end.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graphs.containers import round_up
from ..kernels.ops import KERNEL_POLICIES
from . import driver, streaming
from .apps import amsf as amsf_impl
from .apps import scan as scan_impl
from ..dynamic import engine as dyn_engine
from .distributed import (
    make_replicated_amsf,
    make_replicated_dynamic,
    make_replicated_finish,
    make_replicated_stream,
    make_sharded_amsf,
    make_sharded_dynamic,
    make_sharded_finish,
    make_sharded_stream,
)
from .primitives import (
    INT_MAX,
    canonical_labels,
    init_forest,
    init_labels,
    num_components,
)
from .registry import FactoryRegistry

__all__ = [
    "ExecutionSpec", "PLACEMENTS", "KERNEL_POLICIES", "make_backend",
    "plan_mesh", "make_axis_mesh", "bucket_size", "StreamOps", "SnapshotOps",
    "DynamicOps", "DynamicSnapshotOps",
]

PLACEMENTS = ("single", "replicated", "sharded")
PAD_POLICIES = ("pow2", "multiple")

_AXIS_RE = re.compile(r"[a-z][a-z0-9_]*")
_HEAD_RE = re.compile(r"([a-z_]+)(?:\((.*)\))?")

# pinned defaults per placement (the rest of the fields stay meaningful);
# single source of truth for canonicalization in __post_init__
_PINNED = {
    "single": ("axes", "label_axis", "donate", "rounds", "frontier",
               "overlap"),
    "replicated": ("label_axis", "fused", "frontier", "overlap"),
    "sharded": (),
}
_EXEC_DEFAULTS: dict = {}


@dataclasses.dataclass(frozen=True)
class ExecutionSpec:
    """Declarative execution configuration (placement + dispatch policy)."""

    placement: str = "single"
    axes: tuple = ()            # mesh axes carrying edges
    label_axis: str = ""        # sharded: mesh axis carrying labels
    fused: bool = False
    frontier: int = -1          # sharded merge: -1 auto | 0 dense | N cap
    overlap: bool = False       # sharded: double-buffered merge/compute
    pad: str = "pow2"           # dispatch-shape bucketing policy
    pad_multiple: int = 8       # pad="multiple": granularity
    donate: bool = False
    rounds: int = 0             # distributed outer rounds; 0 = fixpoint
    dynamic: bool = False       # mixed insert/delete/query streams
    log: int = 0                # dynamic edge-log capacity; 0 = auto
    tune: bool = False          # force re-tuning of auto selections
    kernels: str = "auto"       # KernelPolicy: auto | pallas | interpret | ref

    def __post_init__(self):
        if self.placement not in PLACEMENTS:
            raise ValueError(f"unknown placement {self.placement!r}; "
                             f"have {PLACEMENTS}")
        if self.kernels not in KERNEL_POLICIES:
            raise ValueError(f"unknown kernel policy {self.kernels!r}; "
                             f"have {KERNEL_POLICIES}")
        object.__setattr__(self, "axes", tuple(self.axes))
        for name in ("pad_multiple", "rounds", "log", "frontier"):
            v = getattr(self, name)
            if int(v) != v:
                raise ValueError(f"{name} must be an integer, got {v!r}")
            object.__setattr__(self, name, int(v))
        if self.frontier < -1:
            raise ValueError(
                f"frontier must be -1 (auto), 0 (dense), or a positive "
                f"per-device cap, got {self.frontier}")
        if self.pad not in PAD_POLICIES:
            raise ValueError(f"unknown pad policy {self.pad!r}; have "
                             f"{PAD_POLICIES} (or pad=<int> in spec strings)")
        if self.pad_multiple < 1:
            raise ValueError(f"pad_multiple must be >= 1, "
                             f"got {self.pad_multiple}")
        if self.rounds < 0:
            raise ValueError(f"rounds must be >= 0, got {self.rounds}")
        if self.log and not self.dynamic:
            raise ValueError(
                f"log={self.log} requires the dynamic opt (the edge log "
                "only exists on dynamic streams)")
        if self.log < 0 or (self.log and self.log & (self.log - 1)):
            raise ValueError(
                f"log must be a power of two (dispatch-shape discipline), "
                f"got {self.log}")
        if self.placement != "single":
            axes = self.axes or ("x",)
            for a in axes:
                if not _AXIS_RE.fullmatch(a):
                    raise ValueError(f"bad mesh axis name {a!r}")
            if len(set(axes)) != len(axes):
                raise ValueError(f"duplicate mesh axes in {axes}")
            object.__setattr__(self, "axes", tuple(axes))
        if self.placement == "sharded":
            lab = self.label_axis or self.axes[-1]
            if not _AXIS_RE.fullmatch(lab):
                raise ValueError(f"bad label axis name {lab!r}")
            object.__setattr__(self, "label_axis", lab)
        # canonicalize: pin knobs the placement does not use to their defaults
        for name in _PINNED[self.placement]:
            object.__setattr__(self, name, _EXEC_DEFAULTS[name])
        if self.pad == "pow2":
            object.__setattr__(self, "pad_multiple",
                               _EXEC_DEFAULTS["pad_multiple"])

    # -- views ---------------------------------------------------------------

    @property
    def mesh_axes(self) -> tuple:
        """All mesh axis names this placement needs, in mesh order."""
        if self.placement == "single":
            return ()
        if self.placement == "replicated":
            return self.axes
        return tuple(dict.fromkeys(self.axes + (self.label_axis,)))

    def __str__(self) -> str:
        if self.placement == "single":
            head = "single"
        elif self.placement == "replicated":
            head = f"replicated({','.join(self.axes)})"
        elif self.axes and self.label_axis == self.axes[-1]:
            # canonical no-bar form: the last edge axis carries the labels
            # (1-D ``sharded(x)`` and the 2-D ``sharded(x,y)`` mesh)
            head = f"sharded({','.join(self.axes)})"
        else:
            head = f"sharded({','.join(self.axes)}|{self.label_axis})"
        opts = []
        if self.fused:
            opts.append("fused")
        if self.overlap:
            opts.append("overlap")
        if self.frontier != -1:
            opts.append(f"frontier={self.frontier}")
        if self.pad == "multiple":
            opts.append(f"pad={self.pad_multiple}")
        if self.donate:
            opts.append("donate")
        if self.rounds:
            opts.append(f"rounds={self.rounds}")
        if self.dynamic:
            opts.append("dynamic")
        if self.log:
            opts.append(f"log={self.log}")
        if self.tune:
            opts.append("tune")
        if self.kernels != "auto":
            opts.append(f"kernels={self.kernels}")
        return head + (":" + ",".join(opts) if opts else "")

    @classmethod
    def parse(cls, text: str) -> "ExecutionSpec":
        t = text.strip()
        head, _, optpart = t.partition(":")
        m = _HEAD_RE.fullmatch(head.strip())
        if not m:
            raise ValueError(f"bad execution spec {text!r}")
        placement, axespart = m.group(1), m.group(2)
        if placement not in PLACEMENTS:
            raise ValueError(f"unknown placement {placement!r} in {text!r}; "
                             f"have {PLACEMENTS}")
        kw: dict = {}
        if axespart is not None:
            if placement == "single":
                raise ValueError(
                    f"placement 'single' takes no mesh axes: {text!r}")
            if not axespart.strip():
                raise ValueError(f"empty mesh axis list in {text!r}")
            epart, bar, lpart = axespart.partition("|")
            names = tuple(a.strip() for a in epart.split(","))
            if bar:
                if placement != "sharded":
                    raise ValueError(
                        f"'|label_axis' is only valid for sharded: {text!r}")
                kw["axes"] = names
                kw["label_axis"] = lpart.strip()
            elif placement == "sharded":
                # without '|': edge blocks shard over *every* listed axis
                # and the last axis also carries the labels — ``sharded(x)``
                # is the 1-D mesh, ``sharded(x,y)`` the 2-D multi-host mesh
                # (labels over y, replicated over x; merges over both)
                kw["label_axis"] = names[-1]
                kw["axes"] = names
            else:
                kw["axes"] = names
        for opt in filter(None, (o.strip() for o in optpart.split(","))):
            key, eq, val = opt.partition("=")
            if key == "fused" and not eq:
                kw["fused"] = True
            elif key == "overlap" and not eq:
                kw["overlap"] = True
            elif key == "frontier" and eq:
                kw["frontier"] = int(val)
            elif key == "donate" and not eq:
                kw["donate"] = True
            elif key == "rounds" and eq:
                kw["rounds"] = int(val)
            elif key == "dynamic" and not eq:
                kw["dynamic"] = True
            elif key == "log" and eq:
                kw["log"] = int(val)
            elif key == "tune" and not eq:
                kw["tune"] = True
            elif key == "kernels" and eq:
                kw["kernels"] = val.strip()
            elif key == "pad" and eq:
                if val == "pow2":
                    kw["pad"] = "pow2"
                else:
                    kw["pad"] = "multiple"
                    kw["pad_multiple"] = int(val)
            else:
                raise ValueError(f"bad execution option {opt!r} in {text!r}")
        return cls(placement=placement, **kw)


_EXEC_DEFAULTS.update({
    f.name: f.default for f in dataclasses.fields(ExecutionSpec)
    if f.name != "placement"
})

def as_execution_spec(exec) -> ExecutionSpec:  # noqa: A002 - mirrors the API
    if isinstance(exec, str):
        return ExecutionSpec.parse(exec)
    if isinstance(exec, ExecutionSpec):
        return exec
    raise TypeError(f"exec must be an ExecutionSpec or string, "
                    f"got {type(exec).__name__}")


# ---------------------------------------------------------------------------
# Mesh planning.
# ---------------------------------------------------------------------------

def _balanced_factors(ndev: int, naxes: int) -> tuple:
    """Split ``ndev`` into ``naxes`` integer factors, as balanced as the
    prime factorization allows (8, 3 → (2, 2, 2); 12, 2 → (4, 3))."""
    primes = []
    d, k = 2, ndev
    while d * d <= k:
        while k % d == 0:
            primes.append(d)
            k //= d
        d += 1
    if k > 1:
        primes.append(k)
    sizes = [1] * naxes
    for p in sorted(primes, reverse=True):
        sizes[int(np.argmin(sizes))] *= p
    return tuple(sorted(sizes, reverse=True))


def make_axis_mesh(axis_names: Sequence[str],
                   devices: Optional[Sequence] = None) -> Mesh:
    """Build a mesh over ``axis_names`` from the available devices, with the
    device count factored as evenly as possible across the axes."""
    axis_names = tuple(axis_names)
    devices = list(jax.devices()) if devices is None else list(devices)
    sizes = _balanced_factors(len(devices), len(axis_names))
    return Mesh(np.asarray(devices).reshape(sizes), axis_names)


def plan_mesh(spec: ExecutionSpec, mesh: Optional[Mesh] = None
              ) -> Optional[Mesh]:
    """Resolve the device mesh for a spec: validate a user-provided mesh or
    build one over all available devices."""
    names = spec.mesh_axes
    if not names:
        return None
    if mesh is not None:
        missing = [a for a in names if a not in mesh.axis_names]
        if missing:
            raise ValueError(
                f"mesh axes {mesh.axis_names} do not provide {missing} "
                f"required by {str(spec)!r}")
        return mesh
    return make_axis_mesh(names)


# ---------------------------------------------------------------------------
# Dispatch-shape bucketing (pad policy).
# ---------------------------------------------------------------------------

bucket_size = driver.bucket_size  # one pad-policy definition (driver.py)


def _per_chunk_counts(k: int, size: int, shards: int) -> tuple:
    """Real-element count per contiguous shard chunk of a padded dispatch
    whose first ``k`` slots are real (padding is always a suffix)."""
    per = size // shards
    return tuple(max(min((i + 1) * per, k) - i * per, 0)
                 for i in range(shards))


def _resize_device_edges(arrs: tuple, fills: tuple, size: int) -> tuple:
    """Resize device edge-aligned arrays to a dispatch ``size`` without a
    host round-trip: grow with sentinel tails, or drop tail padding (callers
    guarantee real entries occupy the first ``min(size, m_pad)`` slots)."""
    m = int(arrs[0].shape[0])
    if size > m:
        return tuple(
            jnp.concatenate([a, jnp.full((size - m,), fill, a.dtype)])
            for a, fill in zip(arrs, fills))
    if size < m:
        return tuple(a[:size] for a in arrs)
    return arrs


# ---------------------------------------------------------------------------
# Application helpers shared by the backends (paper §5).
# ---------------------------------------------------------------------------

def _fill_amsf_stats(stats, nb, rounds, counts, *, size: int, m_real: int,
                     shards: int) -> None:
    """Fill the AMSF slice of ConnectivityStats from device results.

    ``edges_finish`` counts finite-weight real edges (each belongs to
    exactly one bucket); masked-sweep dispatches scatter the full ``size``
    list once per bucket, hence ``edges_finish_padded = buckets * size``."""
    nb = int(nb)
    counts = np.asarray(counts)
    stats.buckets = nb
    stats.finish_rounds = int(rounds)
    stats.edges_per_bucket = tuple(
        int(c) for c in counts[: min(nb, counts.shape[0])])
    stats.edges_finish = int(counts.sum())
    stats.edges_finish_padded = nb * size
    stats.edges_per_device = _per_chunk_counts(min(m_real, size), size, shards)
    stats.dispatch_sizes = (size // shards,) * shards


def _amsf_coo_host(backend, g, weights, app, forest_fn, stats):
    """AMSF-COO parity path: host bucket compaction is inherently a
    single-device loop (the spanning-forest precedent on mesh backends —
    results and stats surfaces are unchanged)."""
    _, fu, fv, nb, rounds, counts, sizes = amsf_impl.amsf_coo_run(
        g, weights, eps=app.eps, forest_fn=forest_fn,
        pad=backend.spec.pad, pad_multiple=backend.spec.pad_multiple)
    cap = amsf_impl.STATS_BUCKET_CAP
    if len(counts) > cap:  # fold overflow like the device histogram
        counts = counts[: cap - 1] + [sum(counts[cap - 1:])]
    stats.buckets = nb
    stats.finish_rounds = rounds
    stats.edges_per_bucket = tuple(counts)
    stats.edges_finish = sum(counts)
    stats.edges_finish_padded = sum(sizes)
    stats.edges_per_device = (sum(counts),)
    stats.dispatch_sizes = tuple(sizes)
    return fu, fv


# ---------------------------------------------------------------------------
# Stream ops: the backend-facing surface behind ``repro.api.Stream``.
# ---------------------------------------------------------------------------

class StreamOps(NamedTuple):
    """Planned streaming programs for one (ExecutionSpec, finish) pair."""

    init: Callable       # () -> state
    insert: Callable     # (state, u, v) -> (state, rounds)
    process: Callable    # (state, u, v, qa, qb) -> (state, ans, rounds)
    query: Callable      # (state, qa, qb) -> ans
    labels: Callable     # (state) -> (n,) labels
    ncomp: Callable      # (state) -> component count (device scalar)
    edge_shards: int     # devices a batch dispatch splits across
    batch_size: Callable  # (k) -> padded dispatch size under the pad policy


class SnapshotOps(NamedTuple):
    """Planned snapshot-epoch programs behind ``repro.serve`` (one per
    (ExecutionSpec, n, finish) triple).

    The state is a raw label buffer on every placement (placed/padded per
    the backend), so the serve layer can double-buffer it: ``commit`` reads
    the committed snapshot and — under ``ExecutionSpec.donate`` — reuses
    the shadow buffer's memory for the new epoch's labels. ``query`` reads
    any label buffer without touching it, so queries racing an in-flight
    commit still see a stable snapshot (core/streaming.py, Snapshot
    plumbing)."""

    init: Callable       # () -> labels (one placed epoch buffer)
    commit: Callable     # (committed, shadow, u, v) -> (labels, rounds)
    query: Callable      # (labels, qa, qb) -> ans
    labels: Callable     # (labels) -> (n,) real-vertex labels
    ncomp: Callable      # (labels) -> component count (device scalar)
    edge_shards: int     # devices a batch dispatch splits across
    batch_size: Callable  # (k) -> padded dispatch size under the pad policy


class DynamicOps(NamedTuple):
    """Planned batch-dynamic programs behind ``repro.api.DynamicStream``
    (one per (ExecutionSpec, n, variant) triple; see ``repro.dynamic``).

    The state is a ``DynamicState`` pytree placed per the backend (labels
    per placement, forest replicated, edge log sharded like stream
    batches). ``update`` applies one mixed batch — deletes, then inserts,
    then queries — in a single dispatch."""

    init: Callable        # () -> DynamicState (placed)
    update: Callable      # (state, du, dv, u, v, qa, qb) -> (state, ans, k)
    query: Callable       # (state, qa, qb) -> ans
    labels: Callable      # (state) -> (n,) labels
    ncomp: Callable       # (state) -> component count (device scalar)
    used: Callable        # (state) -> (edge_shards,) live log entries
    forest: Callable      # (state) -> (fu, fv) replicated forest buffers
    edge_shards: int      # devices insert/query dispatches split across
    batch_size: Callable  # (k) -> padded insert/query dispatch size
    delete_size: Callable  # (k) -> padded delete dispatch size (replicated)
    log_cap: int          # total edge-log capacity across shards


class DynamicSnapshotOps(NamedTuple):
    """Snapshot-epoch programs for dynamic serving: ``SnapshotOps`` whose
    state is a full ``DynamicState`` and whose commit applies deletes before
    inserts (``Server.submit_deletes`` coalesces into the same pow2
    commit pipeline; the presence of ``log_cap`` is how the serve layer
    detects a dynamic ops bundle)."""

    init: Callable        # () -> DynamicState (one placed epoch state)
    commit: Callable      # (committed, shadow, du, dv, u, v) -> (state, k)
    query: Callable       # (state, qa, qb) -> ans
    labels: Callable      # (state) -> (n,) labels
    ncomp: Callable       # (state) -> component count (device scalar)
    used: Callable        # (state) -> (edge_shards,) live log entries
    edge_shards: int
    batch_size: Callable
    delete_size: Callable
    log_cap: int


# ---------------------------------------------------------------------------
# Backends.
# ---------------------------------------------------------------------------

class _Backend:
    """Shared planning state: one backend per (ExecutionSpec, mesh)."""

    def __init__(self, spec: ExecutionSpec, mesh: Optional[Mesh] = None):
        self.spec = spec
        self.mesh = plan_mesh(spec, mesh)
        self._programs: dict = {}

    @property
    def devices(self) -> int:
        return 1 if self.mesh is None else self.mesh.size

    @property
    def edge_shards(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.spec.axes]))

    def _bucket(self, k: int) -> int:
        return bucket_size(k, pad=self.spec.pad,
                           pad_multiple=self.spec.pad_multiple,
                           shards=self.edge_shards)

    def _delete_bucket(self, k: int) -> int:
        # delete batches are replicated on every placement (each shard
        # tombstones its own log slots), so no shard-multiple constraint
        return bucket_size(k, pad=self.spec.pad,
                           pad_multiple=self.spec.pad_multiple, shards=1)

    def _log_cap(self, n: int, log: int) -> int:
        cap = log or self.spec.log or dyn_engine.default_log_cap(n)
        return round_up(cap, self.edge_shards)

    @property
    def kernels(self) -> Optional[str]:
        """The spec's KernelPolicy, normalized so the default shares jit
        caches with policy-less call sites (auto ≡ None)."""
        return None if self.spec.kernels == "auto" else self.spec.kernels

    def _base_stats(self, variant: str) -> driver.ConnectivityStats:
        return driver.ConnectivityStats(
            variant=variant, exec=str(self.spec),
            placement=self.spec.placement, devices=self.devices,
            fused=self.spec.fused)


class SingleBackend(_Backend):
    """One-device dispatch: the two-phase driver (compacted or fused)."""

    placement = "single"

    def connectivity(self, g, sampler_fn, finish_fn, key=None, *,
                     variant: str = "", fused: Optional[bool] = None):
        fused = self.spec.fused if fused is None else fused
        if fused:
            labels, stats = driver.run_connectivity_fused(
                g, sampler_fn, finish_fn, key, variant=variant,
                kernels=self.kernels)
        else:
            labels, stats = driver.run_connectivity(
                g, sampler_fn, finish_fn, key, variant=variant,
                compact_pad=self.spec.pad_multiple, pad=self.spec.pad,
                kernels=self.kernels)
        # report the spec that actually ran: a per-call fused override must
        # show up in stats.exec, not just stats.fused
        stats.exec = str(dataclasses.replace(self.spec, fused=fused))
        stats.placement = "single"
        stats.devices = 1
        return labels, stats

    def spanning_forest(self, g, sampler_fn, key=None, *,
                        compress: str = "full"):
        return driver.run_spanning_forest(
            g, sampler_fn, key, compress=compress,
            compact_pad=self.spec.pad_multiple, pad=self.spec.pad,
            kernels=self.kernels)

    def stream_ops(self, n: int, finish_fn) -> StreamOps:
        def insert(state, u, v):
            return streaming.insert_batch_rounds_fn(state, u, v, finish_fn,
                                                    self.kernels)

        def process(state, u, v, qa, qb):
            return streaming.process_batch_rounds_fn(state, u, v, qa, qb,
                                                     finish_fn, self.kernels)

        return StreamOps(
            init=lambda: streaming.init_stream(n),
            insert=insert,
            process=process,
            query=streaming.query_batch,
            labels=lambda state: state.P[:n],
            ncomp=lambda state: num_components(state.P),
            edge_shards=1,
            batch_size=self._bucket,
        )

    def snapshot_ops(self, n: int, finish_fn, *,
                     donate: Optional[bool] = None) -> SnapshotOps:
        # donation is an override, not spec.donate: single pins donate=False
        # for the finish dispatch, but the serve double-buffer rotation can
        # donate its *shadow* buffer safely on any placement
        donate = bool(donate) if donate is not None else self.spec.donate
        key = ("snapshot", n, finish_fn, donate)
        if key not in self._programs:
            self._programs[key] = streaming.make_snapshot_commit(
                finish_fn, kernels=self.kernels, donate=donate)
        commit = self._programs[key]
        return SnapshotOps(
            init=lambda: init_labels(n),
            commit=commit,
            query=streaming._snapshot_query_jit,
            labels=lambda P: P[:n],
            ncomp=lambda P: num_components(P[: n + 1]),
            edge_shards=1,
            batch_size=self._bucket,
        )

    # -- batch-dynamic (repro.dynamic) --------------------------------------

    def _dynamic_update(self, n: int, compress: str, search_rounds: int):
        key = ("dynamic", n, compress, search_rounds)
        if key not in self._programs:
            upd = dyn_engine.make_update(n, compress=compress,
                                         search_rounds=search_rounds,
                                         kernels=self.kernels)

            def update(state, du, dv, u, v, qa, qb):
                state, rounds = upd(state, du, dv, u, v)
                return state, state.P[qa] == state.P[qb], rounds

            self._programs[key] = (upd, jax.jit(update),
                                   jax.jit(dyn_engine.query_state))
        return self._programs[key]

    def dynamic_ops(self, n: int, *, compress: str = "full", log: int = 0,
                    search_rounds: int = dyn_engine.DEFAULT_SEARCH_ROUNDS
                    ) -> DynamicOps:
        cap = self._log_cap(n, log)
        _, update, query = self._dynamic_update(n, compress, search_rounds)
        return DynamicOps(
            init=lambda: dyn_engine.init_dynamic(n, cap),
            update=update,
            query=query,
            labels=lambda st: st.P[:n],
            ncomp=lambda st: num_components(st.P),
            used=lambda st: dyn_engine.used_slots(st, n),
            forest=lambda st: (st.fu, st.fv),
            edge_shards=1,
            batch_size=self._bucket,
            delete_size=self._delete_bucket,
            log_cap=cap,
        )

    def dynamic_snapshot_ops(self, n: int, *, compress: str = "full",
                             log: int = 0,
                             search_rounds: int =
                             dyn_engine.DEFAULT_SEARCH_ROUNDS,
                             donate: Optional[bool] = None
                             ) -> DynamicSnapshotOps:
        donate = bool(donate) if donate is not None else self.spec.donate
        cap = self._log_cap(n, log)
        upd, _, query = self._dynamic_update(n, compress, search_rounds)
        key = ("dynsnap", n, compress, search_rounds, donate)
        if key not in self._programs:

            def commit(committed, shadow, du, dv, u, v):
                del shadow  # donated: its buffers back the new epoch
                return upd(committed, du, dv, u, v)

            self._programs[key] = jax.jit(
                commit, donate_argnums=(1,) if donate else ())
        return DynamicSnapshotOps(
            init=lambda: dyn_engine.init_dynamic(n, cap),
            commit=self._programs[key],
            query=query,
            labels=lambda st: st.P[:n],
            ncomp=lambda st: num_components(st.P),
            used=lambda st: dyn_engine.used_slots(st, n),
            edge_shards=1,
            batch_size=self._bucket,
            delete_size=self._delete_bucket,
            log_cap=cap,
        )

    # -- applications (paper §5) --------------------------------------------

    def amsf(self, g, weights, app, forest_fn, *, compress: str, stats):
        if app.mode == "coo":
            return _amsf_coo_host(self, g, weights, app, forest_fn, stats)
        P0 = init_labels(g.n)
        fu0, fv0 = init_forest(g.n)
        _, fu, fv, nb, rounds, counts = amsf_impl.amsf_device(
            P0, fu0, fv0, g.senders, g.receivers, weights,
            eps=app.eps, skip=(app.skip == "lmax"), forest_fn=forest_fn,
            kernels=self.kernels)
        _fill_amsf_stats(stats, nb, rounds, counts, size=g.m_pad,
                         m_real=g.m, shards=1)
        return fu, fv

    def scan(self, g, sims, app, finish_fn, stats):
        labels, is_core, rounds, edges_core = scan_impl.gs_query_device(
            g.senders, g.receivers, g.edge_mask, sims, eps=app.eps,
            mu=app.mu, finish_fn=finish_fn, kernels=self.kernels, n=g.n)
        stats.finish_rounds = int(rounds)
        stats.edges_finish = int(edges_core)
        stats.edges_finish_padded = g.m_pad
        stats.edges_per_device = (int(edges_core),)
        stats.dispatch_sizes = (g.m_pad,)
        return labels, is_core


class _MeshBackend(_Backend):
    """Shared distributed machinery: edge dispatch prep + canonicalization."""

    def _finish_program(self, finish_fn) -> Callable:
        key = ("finish", finish_fn)
        if key not in self._programs:
            prog = self._build_finish(finish_fn)
            donate = (0,) if self.spec.donate else ()
            self._programs[key] = jax.jit(prog, donate_argnums=donate)
        return self._programs[key]

    def finish_program(self, finish_fn) -> Callable:
        """Raw (labels, senders, receivers) -> (labels, rounds) mesh program
        (for dry-run lowering; ``connectivity`` is the session path)."""
        return self._finish_program(finish_fn)

    def _prep_edges(self, g, sampler_fn, key, stats):
        """Sampling phase + host compaction + shard-even padding.

        Without sampling there is nothing to compact, so the graph's
        device-resident COO arrays are resized on device (pad slots carry
        the dump id ``n`` by construction) — no device→host round-trip of
        the edge list in the very regime the mesh placements target."""
        key = jax.random.PRNGKey(0) if key is None else key
        if sampler_fn is None:
            P0 = init_labels(g.n)
            kept = g.m
            size = self._bucket(kept)
            # bucket >= m, so only dump pad is grown or dropped
            senders, receivers = _resize_device_edges(
                (g.senders, g.receivers), (g.n, g.n), size)
        else:
            P0 = sampler_fn(g, key)
            P0, keep, _, cnt = driver._prep_sampled(P0, g)
            s, r = driver._finish_edges(g, keep)
            stats.lmax_count = int(cnt)
            kept = int(s.shape[0])
            size = self._bucket(kept)
            senders, receivers = driver._pad_edges(s, r, g.n, size)
        stats.edges_finish = kept
        stats.edges_finish_padded = size
        shards = self.edge_shards
        stats.edges_per_device = _per_chunk_counts(kept, size, shards)
        stats.dispatch_sizes = (size // shards,) * shards
        return P0, senders, receivers

    def connectivity(self, g, sampler_fn, finish_fn, key=None, *,
                     variant: str = "", fused: Optional[bool] = None):
        if fused is not None and fused != self.spec.fused:
            if self.spec.placement == "replicated":
                raise ValueError(
                    "the replicated placement has no fused variant (its "
                    "merge is already a single pmin); drop the fused "
                    "override or use a sharded placement")
            want = dataclasses.replace(self.spec, fused=fused)
            raise ValueError(
                "fused is part of the ExecutionSpec for distributed "
                f"placements — build the session with exec={str(want)!r} "
                "instead of overriding per call")
        stats = self._base_stats(variant)
        stats.edges_total = g.m
        P0, senders, receivers = self._prep_edges(g, sampler_fn, key, stats)
        program = self._finish_program(finish_fn)
        labels, rounds = program(self._place_labels(P0), senders, receivers)
        stats.finish_rounds = int(rounds)
        labels = canonical_labels(labels[: g.n + 1], kernels=self.kernels)
        return labels[: g.n], stats

    def spanning_forest(self, g, sampler_fn, key=None, *,
                        compress: str = "full"):
        # Forest-edge recording needs tie-breaking across shards (one edge
        # per hooked root, paper §3.4); the mesh variant is future work, so
        # the forest path runs the single-device driver (documented in
        # docs/API.md).
        return driver.run_spanning_forest(
            g, sampler_fn, key, compress=compress,
            compact_pad=self.spec.pad_multiple, pad=self.spec.pad,
            kernels=self.kernels)

    def _stream_programs(self, n: int, finish_fn):
        key = ("stream", n, finish_fn)
        if key not in self._programs:
            progs = self._build_stream(n, finish_fn)
            donate = (0,) if self.spec.donate else ()
            self._programs[key] = (
                jax.jit(progs.insert, donate_argnums=donate),
                jax.jit(progs.process, donate_argnums=donate),
                jax.jit(progs.query),
            )
        return self._programs[key]

    def stream_ops(self, n: int, finish_fn) -> StreamOps:
        insert, process, query = self._stream_programs(n, finish_fn)

        return StreamOps(
            init=lambda: self._init_state(n),
            insert=insert,
            process=process,
            query=query,
            labels=lambda state: state[:n],
            ncomp=lambda state: num_components(state[: n + 1]),
            edge_shards=self.edge_shards,
            batch_size=self._bucket,
        )

    def snapshot_ops(self, n: int, finish_fn, *,
                     donate: Optional[bool] = None) -> SnapshotOps:
        donate = bool(donate) if donate is not None else self.spec.donate
        key = ("snapshot", n, finish_fn, donate)
        if key not in self._programs:
            progs = self._build_stream(n, finish_fn)

            def commit(committed, shadow, u, v):
                del shadow  # donated: its buffer backs the new epoch
                return progs.insert(committed, u, v)

            self._programs[key] = (
                jax.jit(commit, donate_argnums=(1,) if donate else ()),
                jax.jit(progs.query),
            )
        commit, query = self._programs[key]
        return SnapshotOps(
            init=lambda: self._init_state(n),
            commit=commit,
            query=query,
            labels=lambda P: P[:n],
            ncomp=lambda P: num_components(P[: n + 1]),
            edge_shards=self.edge_shards,
            batch_size=self._bucket,
        )

    # -- batch-dynamic (repro.dynamic) --------------------------------------

    def _init_dynamic_state(self, n: int, cap: int):
        st = dyn_engine.init_dynamic(n, cap)
        rep = NamedSharding(self.mesh, P())
        esh = NamedSharding(self.mesh, P(self.spec.axes))
        return dyn_engine.DynamicState(
            P=self._place_labels(st.P),
            fu=jax.device_put(st.fu, rep),
            fv=jax.device_put(st.fv, rep),
            log_u=jax.device_put(st.log_u, esh),
            log_v=jax.device_put(st.log_v, esh),
        )

    def _dynamic_programs(self, n: int, compress: str, search_rounds: int):
        key = ("dynamic", n, compress, search_rounds)
        if key not in self._programs:
            progs = self._build_dynamic(n, compress=compress,
                                        search_rounds=search_rounds)

            def raw_update(state, du, dv, u, v):
                out = progs.update(state.P, state.fu, state.fv, state.log_u,
                                   state.log_v, du, dv, u, v)
                return dyn_engine.DynamicState(*out[:5]), out[5]

            def update(state, du, dv, u, v, qa, qb):
                state, rounds = raw_update(state, du, dv, u, v)
                return state, progs.query(state.P, qa, qb), rounds

            donate = (0,) if self.spec.donate else ()
            self._programs[key] = (
                raw_update,
                jax.jit(update, donate_argnums=donate),
                jax.jit(lambda st, qa, qb: progs.query(st.P, qa, qb)),
                jax.jit(lambda st: progs.used(st.log_u)),
            )
        return self._programs[key]

    def dynamic_ops(self, n: int, *, compress: str = "full", log: int = 0,
                    search_rounds: int = dyn_engine.DEFAULT_SEARCH_ROUNDS
                    ) -> DynamicOps:
        cap = self._log_cap(n, log)
        _, update, query, used = self._dynamic_programs(n, compress,
                                                        search_rounds)
        return DynamicOps(
            init=lambda: self._init_dynamic_state(n, cap),
            update=update,
            query=query,
            labels=lambda st: st.P[:n],
            ncomp=lambda st: num_components(st.P[: n + 1]),
            used=used,
            forest=lambda st: (st.fu, st.fv),
            edge_shards=self.edge_shards,
            batch_size=self._bucket,
            delete_size=self._delete_bucket,
            log_cap=cap,
        )

    def dynamic_snapshot_ops(self, n: int, *, compress: str = "full",
                             log: int = 0,
                             search_rounds: int =
                             dyn_engine.DEFAULT_SEARCH_ROUNDS,
                             donate: Optional[bool] = None
                             ) -> DynamicSnapshotOps:
        donate = bool(donate) if donate is not None else self.spec.donate
        cap = self._log_cap(n, log)
        raw_update, _, query, used = self._dynamic_programs(n, compress,
                                                            search_rounds)
        key = ("dynsnap", n, compress, search_rounds, donate)
        if key not in self._programs:

            def commit(committed, shadow, du, dv, u, v):
                del shadow  # donated: its buffers back the new epoch
                return raw_update(committed, du, dv, u, v)

            self._programs[key] = jax.jit(
                commit, donate_argnums=(1,) if donate else ())
        return DynamicSnapshotOps(
            init=lambda: self._init_dynamic_state(n, cap),
            commit=self._programs[key],
            query=query,
            labels=lambda st: st.P[:n],
            ncomp=lambda st: num_components(st.P[: n + 1]),
            used=used,
            edge_shards=self.edge_shards,
            batch_size=self._bucket,
            delete_size=self._delete_bucket,
            log_cap=cap,
        )

    # -- applications (paper §5) --------------------------------------------

    def _amsf_program(self, *, compress: str, skip: bool):
        key = ("amsf", compress, skip)
        if key not in self._programs:
            # the label/forest buffers are built fresh per call, so donation
            # is always safe — it keeps the round boundary copy-free
            donate = (0, 1, 2) if self.spec.donate else ()
            self._programs[key] = jax.jit(
                self._build_amsf(compress=compress, skip=skip),
                donate_argnums=donate)
        return self._programs[key]

    def amsf(self, g, weights, app, forest_fn, *, compress: str, stats):
        if app.mode == "coo":
            return _amsf_coo_host(self, g, weights, app, forest_fn, stats)
        size = self._bucket(g.m)
        senders, receivers = _resize_device_edges(
            (g.senders, g.receivers), (g.n, g.n), size)
        bids = amsf_impl.bucket_ids(weights, app.eps)
        (bids,) = _resize_device_edges((bids,), (INT_MAX,), size)
        bids = jnp.where(senders < g.n, bids, INT_MAX)
        counts = amsf_impl.bucket_histogram(bids)
        P0 = self._place_labels(init_labels(g.n))
        fill = jnp.int32(-1)
        fu0 = jnp.full((P0.shape[0],), fill)
        fv0 = jnp.full((P0.shape[0],), fill)
        program = self._amsf_program(compress=compress,
                                     skip=(app.skip == "lmax"))
        _, fu, fv, nb, rounds = program(P0, fu0, fv0, senders, receivers,
                                        bids)
        _fill_amsf_stats(stats, nb, rounds, counts, size=size, m_real=g.m,
                         shards=self.edge_shards)
        return fu, fv

    def scan(self, g, sims, app, finish_fn, stats):
        s, r, is_core, core_pad, similar, edges_core = scan_impl.scan_pre(
            g.senders, g.receivers, g.edge_mask, sims, eps=app.eps,
            mu=app.mu, n=g.n)
        size = self._bucket(g.m)
        s, r = _resize_device_edges((s, r), (g.n, g.n), size)
        # the core-core connectivity — the heavy phase — dispatches through
        # the placement's finish program (per-shard finish + min-merge loop)
        program = self._finish_program(finish_fn)
        P, rounds = program(self._place_labels(init_labels(g.n)), s, r)
        labels = scan_impl.scan_attach(P[: g.n + 1], g.senders, g.receivers,
                                       core_pad, similar,
                                       kernels=self.kernels)
        stats.finish_rounds = int(rounds)
        stats.edges_finish = int(edges_core)
        stats.edges_finish_padded = size
        shards = self.edge_shards
        stats.edges_per_device = tuple(
            np.asarray(jnp.sum((s < g.n).reshape(shards, -1), axis=1,
                               dtype=jnp.int32)).tolist())
        stats.dispatch_sizes = (size // shards,) * shards
        return labels, is_core


class ReplicatedBackend(_MeshBackend):
    """Edges sharded over every spec axis, labels replicated per device."""

    placement = "replicated"

    def _build_finish(self, finish_fn):
        return make_replicated_finish(self.mesh, self.spec.axes, finish_fn,
                                      rounds=self.spec.rounds)

    def _build_stream(self, n, finish_fn):
        return make_replicated_stream(self.mesh, self.spec.axes, finish_fn,
                                      rounds=self.spec.rounds,
                                      kernels=self.kernels)

    def _build_amsf(self, *, compress: str, skip: bool):
        return make_replicated_amsf(self.mesh, self.spec.axes,
                                    compress=compress, skip=skip,
                                    kernels=self.kernels)

    def _build_dynamic(self, n, *, compress: str, search_rounds: int):
        return make_replicated_dynamic(self.mesh, self.spec.axes, n,
                                       compress=compress,
                                       search_rounds=search_rounds,
                                       kernels=self.kernels)

    def _place_labels(self, P0):
        return jax.device_put(P0, NamedSharding(self.mesh, P()))

    def _init_state(self, n):
        return self._place_labels(init_labels(n))


class ShardedBackend(_MeshBackend):
    """Labels sharded over ``label_axis``; the huge-n regime."""

    placement = "sharded"

    @property
    def label_shards(self) -> int:
        return self.mesh.shape[self.spec.label_axis]

    def _build_finish(self, finish_fn):
        return make_sharded_finish(
            self.mesh, self.spec.axes, self.spec.label_axis, finish_fn,
            reduce_scatter=self.spec.fused, rounds=self.spec.rounds,
            frontier=self.spec.frontier, overlap=self.spec.overlap,
            kernels=self.kernels)

    def _build_stream(self, n, finish_fn):
        return make_sharded_stream(
            self.mesh, self.spec.axes, self.spec.label_axis, finish_fn,
            reduce_scatter=self.spec.fused, rounds=self.spec.rounds,
            frontier=self.spec.frontier, overlap=self.spec.overlap,
            kernels=self.kernels)

    def _build_amsf(self, *, compress: str, skip: bool):
        return make_sharded_amsf(
            self.mesh, self.spec.axes, self.spec.label_axis,
            compress=compress, skip=skip, kernels=self.kernels)

    def _build_dynamic(self, n, *, compress: str, search_rounds: int):
        return make_sharded_dynamic(
            self.mesh, self.spec.axes, self.spec.label_axis, n,
            compress=compress, search_rounds=search_rounds,
            kernels=self.kernels)

    def _place_labels(self, P0):
        # pad (n + 1,) to divide the label axis; extra slots are self-rooted
        # ids above the dump row, so they are fixed points of every finish
        n1 = P0.shape[0]
        L = round_up(n1, self.label_shards)
        if L != n1:
            tail = jnp.arange(n1, L, dtype=P0.dtype)
            P0 = jnp.concatenate([P0, tail])
        sharding = NamedSharding(self.mesh, P(self.spec.label_axis))
        return jax.device_put(P0, sharding)

    def _init_state(self, n):
        return self._place_labels(init_labels(n))


# ---------------------------------------------------------------------------
# Backend registry (memoized planning, same machinery as sampler/finish).
# ---------------------------------------------------------------------------

_BACKENDS = FactoryRegistry("execution backend")


@_BACKENDS.register("single")
def _make_single(spec: ExecutionSpec = ExecutionSpec(), mesh=None):
    return SingleBackend(spec, mesh)


@_BACKENDS.register("replicated")
def _make_replicated(spec: ExecutionSpec = None, mesh=None):
    return ReplicatedBackend(spec, mesh)


@_BACKENDS.register("sharded")
def _make_sharded(spec: ExecutionSpec = None, mesh=None):
    return ShardedBackend(spec, mesh)


def make_backend(exec="single", mesh: Optional[Mesh] = None):
    """Plan (or fetch the memoized) execution backend for a spec.

    Backends are memoized per (placement, spec, mesh) so equal
    parameterizations share shard_map programs and jit caches."""
    spec = as_execution_spec(exec)
    return _BACKENDS.make(spec.placement, spec=spec, mesh=mesh)
