"""Unified ``VariantSpec`` × ``ExecutionSpec`` API: one declarative front-end.

ConnectIt's central contribution is that *any* sampling scheme composes with
*any* finish/compression scheme (paper §3, Table 1). This module makes that
cross-product a first-class, declarative object instead of stringly-typed
registry keys — and pairs it with an *execution* spec that says where and
how the variant dispatches (single device, replicated labels, or sharded
labels over a named mesh):

    spec = VariantSpec.parse("kout_hybrid_k2+uf_sync_full")
    ci = ConnectIt(spec, exec="sharded(x)")
    labels = ci.connectivity(g)          # static connectivity
    forest = ci.spanning_forest(g)       # paper §3.4 (root-based finish only)
    h = ci.stream(n)                     # batch-incremental handle (§3.5)
    edges = ci.amsf(g, w, "amsf(skip=lmax)")   # applications (paper §5):
    labs, cores = ci.scan(g, sims, "scan")     #   AppSpec grammar, any
    ci.stats                             # placement × kernel policy; stats
                                         # of the last run

Variant grammar (canonical strings round-trip,
``VariantSpec.parse(str(s)) == s``):

    variant  := sampling "+" finish
    sampling := "none"
              | "kout_" kvariant "_k" INT
              | "bfs_c" INT ["_t" FLOAT]
              | "ldd_b" FLOAT
    kvariant := "afforest" | "pure" | "hybrid" | "maxdeg"
    finish   := "uf_sync_" compress
              | "shiloach_vishkin" | "label_prop" | "stergiou"
              | "liu_tarjan_" LTCODE          # 16 valid rule combinations
    compress := "naive" | "halve" | "full"

Execution grammar (same round-trip discipline; see core/execution.py):

    exec      := placement [ "(" axes ")" ] [ ":" opt ("," opt)* ]
    placement := "single" | "replicated" | "sharded"
    axes      := axis ("," axis)* [ "|" label_axis ]     # sharded only
    opt       := "fused" | "overlap" | "donate" | "frontier=" INT
               | "pad=" ("pow2" | INT) | "rounds=" INT
               | "kernels=" ("auto" | "pallas" | "interpret" | "ref")

``sharded(x,y)`` (no bar) shards edges over both axes and labels over the
last; ``frontier``/``overlap`` tune the sharded min-merge (frontier-
compacted exchange and collective/compute overlap — see docs/API.md).

``enumerate_variants()`` materializes the paper's sampling × finish ×
compression cross-product with the paper's documented incompatibilities
excluded (see its docstring); every enumerated variant runs under every
placement. docs/API.md has the grammar reference and the migration tables
from the old flat string keys and ``make_replicated_*``/``make_sharded_*``
factories.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .core import driver
from .core.apps import amsf as _amsf_impl
from .core.apps.spec import (
    APPS,
    AppSpec,
    AppSpecLike,
    as_app_spec,
    default_app_grid,
)
from .core.execution import (
    ExecutionSpec,
    KERNEL_POLICIES,
    PLACEMENTS,
    _per_chunk_counts,
    as_execution_spec,
    make_backend,
)
from .dynamic.engine import DEFAULT_SEARCH_ROUNDS
from .core.finish import (
    COMPRESS_MODES,
    FOREST_METHODS,
    LIU_TARJAN_VARIANTS,
    make_finish,
    make_forest_finish,
    method_names,
)
from .core.sampling import KOUT_VARIANTS, make_sampler

__all__ = [
    "SamplingSpec", "FinishSpec", "VariantSpec", "ExecutionSpec", "AppSpec",
    "ConnectIt", "Stream", "DynamicStream", "enumerate_variants",
    "is_compatible",
    "default_app_grid", "KOUT_VARIANTS", "COMPRESS_MODES",
    "LIU_TARJAN_VARIANTS", "PLACEMENTS", "KERNEL_POLICIES", "APPS",
    "FOREST_METHODS",
]

SAMPLING_SCHEMES = ("none", "kout", "bfs", "ldd")
CONNECT_RULES = ("connect", "parent", "extended")
SHORTCUT_RULES = ("S", "F")

# reverse map: Liu–Tarjan rule options -> code ("CRFA", ...)
_LT_CODE_BY_OPTS = {opts: code for code, opts in LIU_TARJAN_VARIANTS.items()}

# which SamplingSpec knobs are meaningful per scheme; the rest are pinned to
# their defaults on construction so equality and string round-trips are
# canonical (SamplingSpec("bfs", k=7) == SamplingSpec("bfs")).
_SAMPLING_FIELDS = {
    "none": (),
    "kout": ("k", "variant"),
    "bfs": ("num_sources", "threshold"),
    "ldd": ("beta",),
}
# single source of truth for parameter defaults: the dataclass fields
# themselves (populated right after the SamplingSpec definition below)
_SAMPLING_DEFAULTS: dict = {}


def _fmt_float(x: float) -> str:
    # repr round-trips exactly through float() ("%g" would quantize to 6
    # significant digits and break parse(str(spec)) == spec)
    return repr(float(x))


@dataclasses.dataclass(frozen=True)
class SamplingSpec:
    """Declarative sampling-phase configuration (paper §3.2)."""

    scheme: str = "none"
    k: int = 2                 # kout: edges selected per vertex
    variant: str = "hybrid"    # kout: afforest | pure | hybrid | maxdeg
    beta: float = 0.2          # ldd: exponential-shift parameter
    num_sources: int = 3       # bfs: max sources tried
    threshold: float = 0.1     # bfs: coverage accept-gate fraction

    def __post_init__(self):
        if self.scheme not in SAMPLING_SCHEMES:
            raise ValueError(f"unknown sampling scheme {self.scheme!r}; "
                             f"have {SAMPLING_SCHEMES}")
        # coerce numeric types up front; reject non-integral counts rather
        # than silently truncating them
        for name in ("k", "num_sources"):
            v = getattr(self, name)
            if int(v) != v:
                raise ValueError(f"{name} must be an integer, got {v!r}")
            object.__setattr__(self, name, int(v))
        object.__setattr__(self, "beta", float(self.beta))
        object.__setattr__(self, "threshold", float(self.threshold))
        if self.scheme == "kout":
            if self.variant not in KOUT_VARIANTS:
                raise ValueError(f"unknown k-out variant {self.variant!r}; "
                                 f"have {KOUT_VARIANTS}")
            if not 1 <= self.k <= 64:
                raise ValueError(f"kout k must be in [1, 64], got {self.k}")
        if self.scheme == "ldd" and not self.beta > 0.0:
            raise ValueError(f"ldd beta must be > 0, got {self.beta}")
        if self.scheme == "bfs":
            if self.num_sources < 1:
                raise ValueError(
                    f"bfs num_sources must be >= 1, got {self.num_sources}")
            if not 0.0 < self.threshold <= 1.0:
                raise ValueError(
                    f"bfs threshold must be in (0, 1], got {self.threshold}")
        # canonicalize: pin knobs the scheme does not use to their defaults
        live = _SAMPLING_FIELDS[self.scheme]
        for name, default in _SAMPLING_DEFAULTS.items():
            if name not in live:
                object.__setattr__(self, name, default)

    @property
    def enabled(self) -> bool:
        return self.scheme != "none"

    def factory_kwargs(self) -> dict:
        """kwargs for ``repro.core.sampling.make_sampler(self.scheme, ...)``."""
        if self.scheme == "kout":
            return dict(k=self.k, variant=self.variant)
        if self.scheme == "bfs":
            return dict(num_sources=self.num_sources, threshold=self.threshold)
        if self.scheme == "ldd":
            return dict(beta=self.beta)
        return {}

    def build(self):
        """Resolve to the (memoized) sampler callable, or None for 'none'."""
        if not self.enabled:
            return None
        return make_sampler(self.scheme, **self.factory_kwargs())

    def __str__(self) -> str:
        if self.scheme == "none":
            return "none"
        if self.scheme == "kout":
            return f"kout_{self.variant}_k{self.k}"
        if self.scheme == "bfs":
            s = f"bfs_c{self.num_sources}"
            if self.threshold != _SAMPLING_DEFAULTS["threshold"]:
                s += f"_t{_fmt_float(self.threshold)}"
            return s
        return f"ldd_b{_fmt_float(self.beta)}"

    @classmethod
    def parse(cls, text: str) -> "SamplingSpec":
        t = text.strip()
        if t in ("", "none"):
            return cls()
        parts = t.split("_")
        scheme = parts[0]
        if scheme == "kout":
            kw: dict = {}
            for p in parts[1:]:
                if p in KOUT_VARIANTS:
                    kw["variant"] = p
                elif p[:1] == "k" and p[1:].isdigit():
                    kw["k"] = int(p[1:])
                else:
                    raise ValueError(f"bad kout token {p!r} in {text!r}")
            return cls("kout", **kw)
        if scheme == "bfs":
            kw = {}
            for p in parts[1:]:
                if p[:1] == "c" and p[1:].isdigit():
                    kw["num_sources"] = int(p[1:])
                elif p[:1] == "t":
                    kw["threshold"] = float(p[1:])
                else:
                    raise ValueError(f"bad bfs token {p!r} in {text!r}")
            return cls("bfs", **kw)
        if scheme == "ldd":
            kw = {}
            for p in parts[1:]:
                if p[:1] == "b":
                    kw["beta"] = float(p[1:])
                else:
                    raise ValueError(f"bad ldd token {p!r} in {text!r}")
            return cls("ldd", **kw)
        raise ValueError(f"unknown sampling scheme in {text!r}; "
                         f"have {SAMPLING_SCHEMES}")


_SAMPLING_DEFAULTS.update({
    f.name: f.default for f in dataclasses.fields(SamplingSpec)
    if f.name != "scheme"
})


@dataclasses.dataclass(frozen=True)
class FinishSpec:
    """Declarative finish-phase configuration (paper §3.3).

    ``compress`` selects the pointer-jumping aggressiveness of the uf_sync
    family (FindNaive/FindHalve/FindCompress, DESIGN.md §2); it is pinned to
    its default for the other methods. The Liu–Tarjan rule options live on
    ``VariantSpec`` (connect/rootup/shortcut/alter)."""

    method: str = "uf_sync"
    compress: str = "naive"

    def __post_init__(self):
        if self.method not in method_names():
            raise ValueError(f"unknown finish method {self.method!r}; "
                             f"have {method_names()}")
        if self.method == "uf_sync":
            if self.compress not in COMPRESS_MODES:
                raise ValueError(f"unknown compress mode {self.compress!r}; "
                                 f"have {COMPRESS_MODES}")
        else:
            object.__setattr__(self, "compress", "naive")

    def __str__(self) -> str:
        if self.method == "uf_sync":
            return f"uf_sync_{self.compress}"
        return self.method


def _parse_finish_part(text: str) -> tuple[FinishSpec, dict]:
    """finish token -> (FinishSpec, Liu–Tarjan option overrides)."""
    t = text.strip()
    if t == "uf_sync":  # legacy alias: FindNaive analogue
        return FinishSpec("uf_sync", "naive"), {}
    if t.startswith("uf_sync_"):
        return FinishSpec("uf_sync", t[len("uf_sync_"):]), {}
    if t in ("shiloach_vishkin", "label_prop", "stergiou"):
        return FinishSpec(t), {}
    if t == "liu_tarjan":  # legacy alias: paper-fastest LT variant
        t = "liu_tarjan_CRFA"
    if t.startswith("liu_tarjan_"):
        code = t[len("liu_tarjan_"):]
        if code not in LIU_TARJAN_VARIANTS:
            raise ValueError(f"unknown Liu-Tarjan code {code!r}; "
                             f"have {sorted(LIU_TARJAN_VARIANTS)}")
        connect, rootup, shortcut, alter = LIU_TARJAN_VARIANTS[code]
        return FinishSpec("liu_tarjan"), dict(
            connect=connect, rootup=rootup, shortcut=shortcut, alter=alter)
    raise ValueError(f"unknown finish method in {text!r}")


@dataclasses.dataclass(frozen=True)
class VariantSpec:
    """One point of the paper's sampling × finish × compression space."""

    sampling: SamplingSpec = SamplingSpec()
    finish: FinishSpec = FinishSpec()
    # Liu–Tarjan rule options (paper §3.3.2 / Appendix D.4); meaningful only
    # when finish.method == "liu_tarjan", pinned to defaults otherwise. The
    # defaults spell CRFA — the paper-fastest LT variant — matching the bare
    # "liu_tarjan" alias everywhere else.
    connect: str = "connect"   # Connect | ParentConnect | ExtendedConnect
    rootup: bool = True        # update roots only (R) vs unconditional (U)
    shortcut: str = "F"        # one jump round (S) vs compress to fixpoint (F)
    alter: bool = True         # rewrite edge endpoints to parent ids

    def __post_init__(self):
        if self.finish.method == "liu_tarjan":
            if self.connect not in CONNECT_RULES:
                raise ValueError(f"unknown connect rule {self.connect!r}; "
                                 f"have {CONNECT_RULES}")
            if self.shortcut not in SHORTCUT_RULES:
                raise ValueError(f"unknown shortcut rule {self.shortcut!r}; "
                                 f"have {SHORTCUT_RULES}")
            opts = (self.connect, bool(self.rootup), self.shortcut,
                    bool(self.alter))
            if opts not in _LT_CODE_BY_OPTS:
                raise ValueError(
                    f"Liu-Tarjan rule combination {opts} is not one of the "
                    f"paper's valid variants (Table 1); valid codes: "
                    f"{sorted(LIU_TARJAN_VARIANTS)}")
        else:
            object.__setattr__(self, "connect", "connect")
            object.__setattr__(self, "rootup", True)
            object.__setattr__(self, "shortcut", "F")
            object.__setattr__(self, "alter", True)

    # -- constructors -------------------------------------------------------

    @classmethod
    def parse(cls, text: str) -> "VariantSpec":
        """Parse ``"<sampling>+<finish>"`` (or bare ``"<finish>"``).

        ``"auto"`` resolves through the tuned-selection cache
        (``repro.tune``): the backend-global winner if one was ever tuned on
        this backend, else the paper's recommended default — a resolution
        request, not a canonical form, so it does not round-trip."""
        if text.strip().lower() == "auto":
            from .tune.tuner import resolve_variant  # lazy: tune imports api
            return cls.parse(resolve_variant())
        if "+" in text:
            # split on the LAST '+': finish tokens never contain one, while
            # a float sampling parameter may (repr(1e16) == '1e+16')
            samp_part, fin_part = text.rsplit("+", 1)
        else:
            samp_part, fin_part = "none", text
        sampling = SamplingSpec.parse(samp_part)
        finish, lt_opts = _parse_finish_part(fin_part)
        return cls(sampling=sampling, finish=finish, **lt_opts)

    @classmethod
    def liu_tarjan(cls, code: str,
                   sampling: SamplingSpec = SamplingSpec()) -> "VariantSpec":
        """Convenience constructor from a Liu–Tarjan variant code."""
        if code not in LIU_TARJAN_VARIANTS:
            raise ValueError(f"unknown Liu-Tarjan code {code!r}; "
                             f"have {sorted(LIU_TARJAN_VARIANTS)}")
        connect, rootup, shortcut, alter = LIU_TARJAN_VARIANTS[code]
        return cls(sampling=sampling, finish=FinishSpec("liu_tarjan"),
                   connect=connect, rootup=rootup, shortcut=shortcut,
                   alter=alter)

    # -- views --------------------------------------------------------------

    @property
    def lt_code(self) -> Optional[str]:
        if self.finish.method != "liu_tarjan":
            return None
        return _LT_CODE_BY_OPTS[(self.connect, self.rootup, self.shortcut,
                                 self.alter)]

    @property
    def finish_str(self) -> str:
        if self.finish.method == "liu_tarjan":
            return f"liu_tarjan_{self.lt_code}"
        return str(self.finish)

    def finish_kwargs(self) -> dict:
        """kwargs for ``repro.core.finish.make_finish(self.finish.method)``."""
        if self.finish.method == "uf_sync":
            return dict(compress=self.finish.compress)
        if self.finish.method == "liu_tarjan":
            return dict(variant=self.lt_code)
        return {}

    def build_finish(self, kernels: Optional[str] = None):
        """Resolve to the (memoized) finish callable.

        ``kernels`` selects the KernelPolicy its hot loops dispatch through
        (``auto | pallas | interpret | ref``); policies are part of the
        memoization key, so each gets its own stable jit identity. ``None``
        and ``"auto"`` share the default callable."""
        kw = self.finish_kwargs()
        if kernels not in (None, "auto"):
            kw["kernels"] = kernels
        return make_finish(self.finish.method, **kw)

    @property
    def forest_capable(self) -> bool:
        """True iff the finish method supports root-based forest recording
        (paper §3.4 / Theorem 6): the uf_sync family and Shiloach-Vishkin."""
        return self.finish.method in FOREST_METHODS

    @property
    def forest_compress(self) -> str:
        """The per-round compression the forest step runs under (SV's round
        is hook + full compression by definition)."""
        return (self.finish.compress if self.finish.method == "uf_sync"
                else "full")

    def build_forest_finish(self, kernels: Optional[str] = None):
        """Resolve the (memoized) root-based forest step ``(P, s, r, fu, fv)
        -> (ForestState, rounds)`` — the per-bucket step of AMSF and the
        spanning-forest driver. Raises for non-forest-capable methods."""
        if not self.forest_capable:
            raise ValueError(
                f"forest recording requires a root-based finish "
                f"({'/'.join(FOREST_METHODS)}), not {self.finish_str!r} — "
                f"paper §3.4")
        kw = {}
        if self.finish.method == "uf_sync":
            kw["compress"] = self.finish.compress
        if kernels not in (None, "auto"):
            kw["kernels"] = kernels
        return make_forest_finish(self.finish.method, **kw)

    def __str__(self) -> str:
        return f"{self.sampling}+{self.finish_str}"


# ---------------------------------------------------------------------------
# Variant-space enumeration (paper §3, Table 1 cross-product).
# ---------------------------------------------------------------------------

def is_compatible(sampling: SamplingSpec, finish_str: str) -> bool:
    """Paper-documented composition rules for sampling × finish.

    * Stergiou's two-array (prev/cur) algorithm assumes the identity
      labeling as its starting point (paper B.2.5); the paper composes it
      with sampling only in a modified form we do not enumerate.
    * Invalid Liu–Tarjan rule mixes never reach this predicate: only the 16
      paper-valid codes (LIU_TARJAN_VARIANTS) are representable/enumerated.
    """
    if sampling.enabled and finish_str == "stergiou":
        return False
    return True


def default_sampling_grid() -> list[SamplingSpec]:
    """The paper's sampling schemes at their Table-1 parameterizations."""
    return (
        [SamplingSpec()]
        + [SamplingSpec("kout", k=2, variant=v) for v in KOUT_VARIANTS]
        + [SamplingSpec("bfs"), SamplingSpec("ldd")]
    )


def default_finish_grid() -> list[str]:
    """Every finish × compression parameterization the paper evaluates."""
    return (
        [f"uf_sync_{c}" for c in COMPRESS_MODES]
        + ["shiloach_vishkin", "label_prop", "stergiou"]
        + [f"liu_tarjan_{code}" for code in sorted(LIU_TARJAN_VARIANTS)]
    )


def enumerate_variants(
    samplings: Optional[Sequence[SamplingSpec]] = None,
    finishes: Optional[Sequence[str]] = None,
) -> list[VariantSpec]:
    """Materialize the sampling × finish × compression cross-product.

    With the default grids this yields 7 sampling configurations × 22 finish
    configurations minus the documented incompatibilities (``is_compatible``)
    = 148 variants — the enumerable slice of the paper's several-hundred
    variant space (Liu–Tarjan rule mixes outside the valid 16 are excluded
    by construction).
    """
    samplings = default_sampling_grid() if samplings is None else samplings
    finishes = default_finish_grid() if finishes is None else finishes
    out = []
    for s in samplings:
        for f in finishes:
            if not is_compatible(s, f):
                continue
            # construct directly from the caller's SamplingSpec (a string
            # round-trip would quietly re-quantize float parameters)
            finish, lt_opts = _parse_finish_part(f)
            out.append(VariantSpec(sampling=s, finish=finish, **lt_opts))
    return out


# ---------------------------------------------------------------------------
# Session front-end: one object for static, forest, and streaming paths.
# ---------------------------------------------------------------------------

SpecLike = Union[str, VariantSpec]
ExecLike = Union[str, ExecutionSpec]


class Stream:
    """Batch-incremental connectivity handle bound to one finish variant and
    one execution placement (paper §3.5 / Algorithm 3).

    Batches are device dispatches with static shapes. Incoming batches are
    bucketed under the ExecutionSpec pad policy (power-of-two by default) so
    a ragged final batch reuses an existing compiled shape instead of
    triggering a fresh jit compile, and are padded with the dump id ``n``.
    Under a distributed placement, insert and query batches are sharded over
    the spec's edge axes (labels replicated or sharded per the placement).
    """

    def __init__(self, n: int, finish_fn, *, backend=None, variant: str = ""):
        self.n = n
        self.variant = variant
        self._backend = make_backend() if backend is None else backend
        self._ops = self._backend.stream_ops(n, finish_fn)
        self.state = self._ops.init()
        self.batches = 0
        self._dispatch_sizes: list[int] = []
        # device-side counters (pad slots point at the dump id n and must
        # not count); accumulated lazily — no per-batch host sync
        self._edges = jnp.int32(0)
        self._edges_dev = jnp.zeros((self._ops.edge_shards,), jnp.int32)
        self._rounds = jnp.int32(0)

    # -- shape bucketing -----------------------------------------------------

    def _pad_batch(self, u, v):
        u = jnp.asarray(u, jnp.int32)
        v = jnp.asarray(v, jnp.int32)
        k = int(u.shape[0])
        size = self._ops.batch_size(k)
        if size != k:
            u = jnp.pad(u, (0, size - k), constant_values=self.n)
            v = jnp.pad(v, (0, size - k), constant_values=self.n)
        return u, v, size

    def _pad_queries(self, qa, qb):
        qa = jnp.asarray(qa, jnp.int32)
        qb = jnp.asarray(qb, jnp.int32)
        k = int(qa.shape[0])
        size = self._ops.batch_size(k)
        if size != k:
            qa = jnp.pad(qa, (0, size - k))
            qb = jnp.pad(qb, (0, size - k))
        return qa, qb, k

    def _account(self, u, size: int, rounds) -> None:
        self.batches += 1
        self._dispatch_sizes.append(size)
        real = u < self.n
        self._edges = self._edges + jnp.sum(real, dtype=jnp.int32)
        # per-shard directed counts: each edge shard mirrors its own chunk
        # locally (both directions stay on the shard), hence the factor 2
        self._edges_dev = self._edges_dev + 2 * jnp.sum(
            real.reshape(self._ops.edge_shards, -1), axis=1, dtype=jnp.int32)
        self._rounds = self._rounds + jnp.asarray(rounds, jnp.int32)

    # -- operations ----------------------------------------------------------

    def insert(self, u, v) -> "Stream":
        """Insert one batch of undirected edges (symmetrized internally)."""
        u, v, size = self._pad_batch(u, v)
        self.state, rounds = self._ops.insert(self.state, u, v)
        self._account(u, size, rounds)
        return self

    def query(self, qa, qb) -> jax.Array:
        """IsConnected for each (qa[i], qb[i]) pair."""
        qa, qb, k = self._pad_queries(qa, qb)
        return self._ops.query(self.state, qa, qb)[:k]

    def process(self, u, v, qa, qb) -> jax.Array:
        """Inserts then queries in one dispatch (paper Algorithm 3)."""
        u, v, size = self._pad_batch(u, v)
        qa, qb, k = self._pad_queries(qa, qb)
        self.state, ans, rounds = self._ops.process(self.state, u, v, qa, qb)
        self._account(u, size, rounds)
        return ans[:k]

    # -- views ---------------------------------------------------------------

    @property
    def edges_inserted(self) -> int:
        """Real (non-padding) edges inserted so far (syncs on read)."""
        return int(self._edges)

    @property
    def labels(self) -> jax.Array:
        """Current compressed labeling over real vertices (n,)."""
        return self._ops.labels(self.state)

    def num_components(self) -> int:
        return int(self._ops.ncomp(self.state))

    @property
    def stats(self) -> driver.ConnectivityStats:
        """Unified ConnectivityStats of the stream so far (syncs on read).

        Field invariants match the connectivity path: batches are
        symmetrized before dispatch, so the finish phase processes directed
        entries — ``edges_finish`` is twice ``edges_inserted``,
        ``edges_per_device`` sums to it, and ``dispatch_sizes`` (padded per
        edge shard, cumulative over batches) sums to
        ``edges_finish_padded``. ``batch_shapes`` is the distinct padded
        batch shapes compiled — under the default pow2 policy its length
        stays logarithmic in the batch-size spread."""
        spec = self._backend.spec
        shards = self._ops.edge_shards
        padded = 2 * sum(self._dispatch_sizes)
        stats = driver.ConnectivityStats(
            variant=self.variant, exec=str(spec), placement=spec.placement,
            devices=self._backend.devices, fused=spec.fused,
            edges_total=self.edges_inserted,
            edges_finish=2 * self.edges_inserted,
            edges_finish_padded=padded,
            edges_per_device=tuple(np.asarray(self._edges_dev).tolist()),
            dispatch_sizes=(padded // shards,) * shards,
            batch_shapes=tuple(sorted(set(self._dispatch_sizes))),
            finish_rounds=int(self._rounds))
        return stats


class DynamicStream:
    """Batch-dynamic connectivity handle: mixed insert/delete/query batches
    (``repro.dynamic``), bound to one forest-capable variant and one
    execution placement.

    The device state extends the stream labeling with the spanning forest
    (recorded during inserts) and a fixed-capacity tombstoned edge log.
    Deletions that miss the forest cost only the tombstone; forest hits
    trigger the bounded replacement search (``search_rounds`` masked hook
    rounds over the surviving log, then a component-local rebuild through
    the finish program if the bound is exhausted). Within one batch the
    linearization is deletes → inserts → queries.

    Batches are padded onto pow2 dispatch shapes like ``Stream``; the three
    size axes (deletes / inserts / queries) bucket independently. Log
    capacity is tracked host-side with a conservative per-shard bound that
    only syncs the true device occupancy when the bound would overflow —
    steady-state updates stay sync-free.
    """

    def __init__(self, n: int, *, backend=None, variant: str = "",
                 compress: str = "full", log: int = 0,
                 search_rounds: int = DEFAULT_SEARCH_ROUNDS):
        self.n = n
        self.variant = variant
        self._backend = (make_backend("single:dynamic") if backend is None
                         else backend)
        self._ops = self._backend.dynamic_ops(
            n, compress=compress, log=log, search_rounds=search_rounds)
        self._exec = dataclasses.replace(self._backend.spec, dynamic=True,
                                         log=log)
        self.state = self._ops.init()
        self.batches = 0
        self._dispatch_sizes: list[int] = []
        self._edges = jnp.int32(0)
        self._deletes = jnp.int32(0)
        self._rounds = jnp.int32(0)
        # conservative per-shard occupancy bound (tombstones never shrink
        # it; a predicted overflow syncs the true per-shard live counts)
        shards = self._ops.edge_shards
        self._cap_local = self._ops.log_cap // shards
        self._bound = np.zeros((shards,), np.int64)

    # -- shape bucketing -----------------------------------------------------

    def _pad(self, u, v, size_fn):
        u = jnp.asarray(u, jnp.int32)
        v = jnp.asarray(v, jnp.int32)
        k = int(u.shape[0])
        size = size_fn(k)
        if size != k:
            u = jnp.pad(u, (0, size - k), constant_values=self.n)
            v = jnp.pad(v, (0, size - k), constant_values=self.n)
        return u, v, k, size

    def _ensure_capacity(self, k: int, size: int) -> None:
        incoming = np.asarray(_per_chunk_counts(k, size,
                                                self._ops.edge_shards))
        if (self._bound + incoming <= self._cap_local).all():
            self._bound += incoming
            return
        # the bound ignores tombstones — sync the true per-shard occupancy
        # once, then re-check (the only host sync on the capacity path)
        self._bound = np.asarray(self._ops.used(self.state), np.int64)
        if (self._bound + incoming > self._cap_local).any():
            raise ValueError(
                f"edge log full: shard occupancy {self._bound.tolist()} + "
                f"batch {incoming.tolist()} exceeds {self._cap_local} "
                f"slots/shard — build the stream with a larger log= "
                f"(total capacity {self._ops.log_cap})")
        self._bound += incoming

    # -- operations ----------------------------------------------------------

    def process(self, du, dv, u, v, qa, qb) -> jax.Array:
        """One mixed batch: delete ``(du, dv)``, insert ``(u, v)``, then
        answer ``(qa, qb)`` — a single device dispatch."""
        du, dv, _, _ = self._pad(du, dv, self._ops.delete_size)
        u, v, k, size = self._pad(u, v, self._ops.batch_size)
        qa, qb, qk, _ = self._pad(qa, qb, self._ops.batch_size)
        self._ensure_capacity(k, size)
        self.state, ans, rounds = self._ops.update(
            self.state, du, dv, u, v, qa, qb)
        self.batches += 1
        self._dispatch_sizes.append(size)
        self._edges = self._edges + jnp.sum(u < self.n, dtype=jnp.int32)
        self._deletes = self._deletes + jnp.sum(du < self.n,
                                                dtype=jnp.int32)
        self._rounds = self._rounds + jnp.asarray(rounds, jnp.int32)
        return ans[:qk]

    def insert(self, u, v) -> "DynamicStream":
        """Insert one batch of undirected edges."""
        empty = np.empty((0,), np.int32)
        self.process(empty, empty, u, v, empty, empty)
        return self

    def delete(self, u, v) -> "DynamicStream":
        """Delete one batch of undirected edges (all logged copies of each
        pair are removed; pairs not present are ignored)."""
        empty = np.empty((0,), np.int32)
        self.process(u, v, empty, empty, empty, empty)
        return self

    def query(self, qa, qb) -> jax.Array:
        """IsConnected for each (qa[i], qb[i]) pair."""
        qa, qb, qk, _ = self._pad(qa, qb, self._ops.batch_size)
        return self._ops.query(self.state, qa, qb)[:qk]

    # -- views ---------------------------------------------------------------

    @property
    def edges_inserted(self) -> int:
        """Real (non-padding) insert entries so far (syncs on read)."""
        return int(self._edges)

    @property
    def edges_deleted(self) -> int:
        """Real (non-padding) delete entries so far (syncs on read)."""
        return int(self._deletes)

    @property
    def labels(self) -> jax.Array:
        return self._ops.labels(self.state)

    def num_components(self) -> int:
        return int(self._ops.ncomp(self.state))

    def log_used(self) -> int:
        """Live (non-tombstoned) edge-log entries on device (syncs)."""
        return int(np.asarray(self._ops.used(self.state)).sum())

    def forest_edges(self) -> np.ndarray:
        """Current spanning-forest edges, (k, 2) host array."""
        fu, fv = self._ops.forest(self.state)
        return _amsf_impl.forest_edges(fu, fv)

    @property
    def stats(self) -> driver.ConnectivityStats:
        """Unified ConnectivityStats of the dynamic stream (syncs on read).
        ``edges_total`` counts inserts net of deletes submitted;
        ``edges_finish`` follows the stream convention (2× directed)."""
        spec = self._exec
        shards = self._ops.edge_shards
        padded = 2 * sum(self._dispatch_sizes)
        return driver.ConnectivityStats(
            variant=self.variant, exec=str(spec), placement=spec.placement,
            devices=self._backend.devices, fused=spec.fused,
            edges_total=self.edges_inserted - self.edges_deleted,
            edges_finish=2 * self.edges_inserted,
            edges_finish_padded=padded,
            dispatch_sizes=(padded // shards,) * shards,
            batch_shapes=tuple(sorted(set(self._dispatch_sizes))),
            finish_rounds=int(self._rounds))


class ConnectIt:
    """One variant × one execution placement, three workloads: static /
    forest / streaming connectivity.

    >>> ci = ConnectIt("kout_hybrid_k2+uf_sync_full", exec="sharded(x)")
    >>> labels = ci.connectivity(g)
    >>> ci.stats.edges_per_device   # finish-phase work per edge shard

    The backend is planned once at construction (mesh resolution, shard_map
    program builds are memoized per (spec, mesh)); ``.connectivity``,
    ``.spanning_forest``, and ``.stream`` all dispatch through it. Pass
    ``mesh=`` to pin an explicit ``jax.sharding.Mesh`` (it must provide the
    spec's axis names); otherwise the spec's axes are laid out over all
    available devices.

    ``kernels=`` selects the KernelPolicy (``auto | pallas | interpret |
    ref``) the session's hot-path primitives dispatch through — a
    convenience that folds into the ExecutionSpec's ``kernels`` field, so
    placement and kernel policy travel together and ``stats.exec`` reports
    what actually ran (see repro.kernels.ops and docs/API.md).

    ``ConnectIt("auto", ...)`` defers the variant choice to the tuned
    selection cache (``repro.tune``): each ``.connectivity(g)`` call
    resolves the winner recorded for ``g``'s graph-family fingerprint
    (falling back to the backend-global winner, then the paper's
    recommended default on a cold cache) — a pure cache lookup, memoized
    per family, so the query path never measures anything. With the
    ``tune`` exec opt the session instead re-measures the shortlist on the
    first graph of each family it sees and persists the winners. The
    non-connectivity surfaces (streams, forests, ingest) bind the
    backend-global resolution at construction.
    """

    def __init__(self, spec: SpecLike = "none+uf_sync_naive",
                 exec: ExecLike = "single", *, mesh=None,
                 compact_pad: Optional[int] = None,
                 kernels: Optional[str] = None):
        auto = isinstance(spec, str) and spec.strip().lower() == "auto"
        if isinstance(spec, str):
            spec = VariantSpec.parse(spec)
        if not isinstance(spec, VariantSpec):
            raise TypeError(f"spec must be a VariantSpec or string, "
                            f"got {type(spec).__name__}")
        exec_spec = as_execution_spec(exec)
        if compact_pad is not None:
            # convenience override: fixed-granularity compaction padding
            if compact_pad < 1:
                raise ValueError(
                    f"compact_pad must be >= 1, got {compact_pad}")
            exec_spec = dataclasses.replace(exec_spec, pad="multiple",
                                            pad_multiple=compact_pad)
        if kernels is not None:
            # convenience override: the KernelPolicy is an ExecutionSpec
            # field (placement and kernel policy travel together), and the
            # knob folds into it so stats.exec reports what actually ran;
            # validation happens in the spec constructor
            exec_spec = dataclasses.replace(exec_spec, kernels=kernels)
        self.spec = spec
        self.exec = exec_spec
        self._backend = make_backend(exec_spec, mesh=mesh)
        self._sampler = spec.sampling.build()
        self._finish = spec.build_finish(kernels=exec_spec.kernels)
        self._stats: Optional[driver.ConnectivityStats] = None
        self._auto = auto
        self._auto_specs: dict = {}      # family fingerprint -> programs
        self._tuned_families: set = set()

    def __repr__(self) -> str:
        if self.exec == ExecutionSpec():
            return f"ConnectIt({str(self.spec)!r})"
        return f"ConnectIt({str(self.spec)!r}, exec={str(self.exec)!r})"

    def _resolve_auto(self, g):
        """Per-graph programs of an ``"auto"`` session: the cached winner
        for ``g``'s family fingerprint, memoized per family so warm calls
        do a dict lookup and reuse the jitted programs (zero tuning work on
        the query path). Under the ``tune`` exec opt, the first graph of
        each family is measured once per session and the winner persisted."""
        from .tune.cache import fingerprint_graph
        from .tune.tuner import resolve_variant, tune_variant
        fam = fingerprint_graph(g)
        if self.exec.tune and fam not in self._tuned_families:
            tune_variant(
                g, family=fam, kernels=self.exec.kernels,
                exec=str(dataclasses.replace(self.exec, tune=False)))
            self._tuned_families.add(fam)
            self._auto_specs.pop(fam, None)
        if fam not in self._auto_specs:
            spec = VariantSpec.parse(resolve_variant(fam))
            self._auto_specs[fam] = (
                spec, spec.sampling.build(),
                spec.build_finish(kernels=self.exec.kernels))
        return self._auto_specs[fam]

    def connectivity(self, g, *, key: Optional[jax.Array] = None,
                     fused: Optional[bool] = None,
                     return_stats: bool = False):
        """Canonical min-vertex-id connectivity labeling of ``g``.

        Dispatches through the planned execution backend; every path fills
        the same ConnectivityStats, available as ``.stats``. ``fused`` (an
        ExecutionSpec knob, overridable per call on the single placement)
        selects the single-dispatch path with no host compaction. The call
        is the host span ``connectit.connectivity`` (docs/API.md,
        Observability).
        """
        with jax.profiler.TraceAnnotation("connectit.connectivity"):
            spec, sampler, finish = (
                (self.spec, self._sampler, self._finish)
                if not self._auto else self._resolve_auto(g))
            labels, stats = self._backend.connectivity(
                g, sampler, finish, key, variant=str(spec), fused=fused)
        self._stats = stats
        if return_stats:
            return labels, stats
        return labels

    def connected_components(self, g, **kw) -> np.ndarray:
        """Convenience: host numpy labels."""
        return np.asarray(self.connectivity(g, **kw))

    def from_chunks(self, source, *, key: Optional[jax.Array] = None,
                    survivor_cap: Optional[int] = None,
                    sample_chunks: int = 1, return_stats: bool = False):
        """Out-of-core connectivity over a ``ChunkedEdgeSource`` — the
        bounded-memory path for graphs too large to materialize (docs/API.md
        §Out-of-core ingest).

        Runs the session's sampling phase on the stream's head, then streams
        every chunk through relabel-and-filter into a bounded survivor
        buffer; labels are bit-identical to ``.connectivity`` on the same
        edges. ``.stats`` reports chunk/spill/survivor accounting alongside
        the usual fields. Ingest is a single-device pipeline regardless of
        placement (the same precedent as ``.spanning_forest`` on distributed
        placements); ``stats.exec`` reports what actually ran."""
        from .graphs.ingest import ingest_chunks, ingest_stats
        result = ingest_chunks(
            source, self._sampler, self._finish, key,
            kernels=self._backend.kernels, survivor_cap=survivor_cap,
            sample_chunks=sample_chunks)
        stats = ingest_stats(result, variant=str(self.spec))
        self._stats = stats
        if return_stats:
            return result.labels, stats
        return result.labels

    def spanning_forest(self, g, *, key: Optional[jax.Array] = None
                        ) -> np.ndarray:
        """Spanning forest edges, (k, 2) host array (paper §3.4).

        Valid only for root-based finish methods (the uf_sync family and
        Shiloach-Vishkin): the forest invariant needs one recorded edge per
        hooked root — the paper's documented restriction for Algorithm 2.
        Distributed placements currently run the forest on the single-device
        driver (edge recording needs cross-shard tie-breaking; see
        docs/API.md).
        """
        if not self.spec.forest_capable:
            raise ValueError(
                f"spanning forest requires a root-based finish "
                f"({'/'.join(FOREST_METHODS)}), not "
                f"{self.spec.finish_str!r} — paper §3.4")
        return self._backend.spanning_forest(
            g, self._sampler, key, compress=self.spec.forest_compress)

    def stream(self, n: int, *, dynamic: Optional[bool] = None,
               log: Optional[int] = None,
               search_rounds: int = DEFAULT_SEARCH_ROUNDS
               ) -> Union[Stream, "DynamicStream"]:
        """Fresh batch-incremental handle over ``n`` vertices (paper §3.5),
        executing under this session's placement.

        With ``dynamic=True`` (or an exec spec carrying the ``dynamic`` opt)
        the handle is a ``DynamicStream``: mixed insert/delete/query batches
        backed by a spanning forest and a tombstoned edge log of capacity
        ``log`` (power of two; default ``log=`` from the exec spec, else the
        next power of two >= 4n). Requires a root-based (forest-capable)
        finish. ``search_rounds`` bounds the device-side replacement search
        before a deletion falls back to a component-local rebuild."""
        dyn = self.exec.dynamic if dynamic is None else bool(dynamic)
        if not dyn:
            if log:
                raise ValueError("log= is a dynamic-stream knob — pass "
                                 "dynamic=True (or use a ':dynamic' exec)")
            return Stream(n, self._finish, backend=self._backend,
                          variant=str(self.spec))
        if not self.spec.forest_capable:
            raise ValueError(
                f"dynamic streams maintain a spanning forest and need a "
                f"root-based finish ({'/'.join(FOREST_METHODS)}), not "
                f"{self.spec.finish_str!r} — paper §3.4")
        cap = self.exec.log if log is None else log
        if cap and cap & (cap - 1):
            raise ValueError(f"log must be a power of two, got {cap}")
        return DynamicStream(n, backend=self._backend,
                             variant=str(self.spec),
                             compress=self.spec.forest_compress,
                             log=cap, search_rounds=search_rounds)

    def serve(self, n: Optional[int] = None, *, tenants=None, config=None,
              dynamic: Optional[bool] = None, log: Optional[int] = None,
              search_rounds: int = DEFAULT_SEARCH_ROUNDS, **knobs):
        """Async serving front-end over a live graph (``repro.serve``).

        Returns a not-yet-started ``repro.serve.Server``: an asyncio
        admission layer (``submit_inserts`` / ``query`` coroutines) that
        coalesces concurrent client traffic into size-bucketed device
        batches under this session's placement and kernel policy, with
        double-buffered snapshot epochs so queries always read a stable
        committed snapshot. Pass ``n`` for one logical graph, or
        ``tenants={"name": n, ...}`` to serve several tenant namespaces
        from one shared device state. ``config`` is a
        ``repro.serve.ServeConfig``; extra ``knobs``
        (``max_batch_edges=...``, ``flush_ms=...``, ...) override its
        fields. See docs/API.md §Serving.

        With ``dynamic=True`` (or a ``:dynamic`` exec spec) the server also
        accepts ``submit_deletes`` — deletions coalesce into the same
        snapshot-commit pipeline (forest-capable finish required; ``log``
        sizes the tombstoned edge log as in ``stream``).

        >>> server = ConnectIt("none+uf_sync_full").serve(1 << 16)
        >>> async with server:
        ...     epoch = await server.submit_inserts(u, v)
        ...     ans, at_epoch = await server.query(qa, qb)
        """
        from .serve import ServeConfig, Server, TenantRegistry
        registry = TenantRegistry.build(n=n, tenants=tenants)
        cfg = config or ServeConfig()
        if knobs:
            cfg = dataclasses.replace(cfg, **knobs)
        dyn = self.exec.dynamic if dynamic is None else bool(dynamic)
        if dyn:
            if not self.spec.forest_capable:
                raise ValueError(
                    f"dynamic serving needs a root-based finish "
                    f"({'/'.join(FOREST_METHODS)}), not "
                    f"{self.spec.finish_str!r} — paper §3.4")
            cap = self.exec.log if log is None else log
            if cap and cap & (cap - 1):
                raise ValueError(f"log must be a power of two, got {cap}")
            ops = self._backend.dynamic_snapshot_ops(
                registry.total, compress=self.spec.forest_compress,
                log=cap, search_rounds=search_rounds, donate=cfg.donate)
        else:
            if log:
                raise ValueError("log= is a dynamic-serving knob — pass "
                                 "dynamic=True (or use a ':dynamic' exec)")
            ops = self._backend.snapshot_ops(registry.total, self._finish,
                                            donate=cfg.donate)
        return Server(ops, registry, config=cfg, variant=str(self.spec),
                      exec_str=str(self.exec), devices=self._backend.devices)

    # -- applications (paper §5): AMSF / exact MSF / SCAN -------------------

    def _app_stats(self, app: AppSpec, g) -> driver.ConnectivityStats:
        stats = self._backend._base_stats(str(self.spec))
        stats.app = str(app)
        stats.edges_total = g.m
        return stats

    def amsf(self, g, weights, spec: "AppSpecLike" = "amsf", *,
             return_stats: bool = False) -> np.ndarray:
        """Approximate minimum spanning forest (paper §5.1) → (k, 2) host
        edge array; total weight is within ``(1 + eps)`` of the exact MSF.

        ``spec`` names the paper variant (``amsf`` = AMSF-NF,
        ``amsf(skip=lmax)`` = AMSF-NF-S, ``amsf(mode=coo)`` = AMSF-COO,
        ``msf`` = exact Borůvka). The per-bucket forest step is this
        session's finish method (root-based only — uf_sync family /
        Shiloach-Vishkin), dispatched under the session's placement and
        kernel policy; the masked bucket sweep is a single device dispatch
        with no per-bucket host sync. Fills ``.stats`` (buckets,
        edges-per-bucket, rounds, dispatch sizes).
        """
        app = as_app_spec(spec)
        if app.app == "scan":
            raise ValueError("scan specs run via .scan(g, sims, spec)")
        stats = self._app_stats(app, g)
        weights = jnp.asarray(weights)
        if app.app == "msf":
            edges, _ = _amsf_impl.boruvka_msf(g, weights)
            # Borůvka is a self-contained single-device program regardless
            # of the session placement — report what actually ran (the
            # SingleBackend per-call-override precedent)
            stats.exec = "single"
            stats.placement = "single"
            stats.devices = 1
            stats.edges_finish = g.m
            stats.edges_finish_padded = g.m_pad
            stats.edges_per_device = (g.m,)
            stats.dispatch_sizes = (g.m_pad,)
        else:
            forest_fn = self.spec.build_forest_finish(
                kernels=self._backend.kernels)
            fu, fv = self._backend.amsf(
                g, weights, app, forest_fn,
                compress=self.spec.forest_compress, stats=stats)
            edges = _amsf_impl.forest_edges(fu, fv)
        self._stats = stats
        if return_stats:
            return edges, stats
        return edges

    def msf(self, g, weights, **kw) -> np.ndarray:
        """Exact MSF (Borůvka — the GBBS-MSF baseline), ``amsf(g, w, "msf")``."""
        return self.amsf(g, weights, "msf", **kw)

    def scan(self, g, sims, spec: "AppSpecLike" = "scan", *,
             return_stats: bool = False):
        """SCAN clustering via parallel GS*-Query (paper §5.2) →
        ``(labels, is_core)`` device arrays.

        ``sims`` is the per-directed-edge structural-similarity index
        (``repro.core.apps.scan.build_index``; offline, like GS*-Index).
        The core-core connectivity runs this session's finish method under
        its placement and kernel policy; non-core border vertices attach to
        the min adjacent core cluster; remaining vertices keep their own id
        (singletons, reported as noise). Fills ``.stats``."""
        app = as_app_spec(spec)
        if app.app != "scan":
            raise ValueError(
                f"scan() takes a scan spec, got {str(app)!r} "
                f"(amsf/msf run via .amsf(g, weights, spec))")
        stats = self._app_stats(app, g)
        labels, is_core = self._backend.scan(
            g, jnp.asarray(sims), app, self._finish, stats)
        self._stats = stats
        if return_stats:
            return labels, is_core, stats
        return labels, is_core

    @property
    def stats(self) -> Optional[driver.ConnectivityStats]:
        """ConnectivityStats of the most recent ``connectivity`` /
        ``amsf`` / ``scan`` call."""
        return self._stats
