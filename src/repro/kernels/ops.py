"""KernelPolicy: pluggable dispatch for the connectivity hot-path kernels.

Every ConnectIt hot-path primitive (``writeMin`` scatter-min, pointer-jump
compression, the fused uf_sync hook+compress round, edge relabel/rewrite)
has two interchangeable implementations — a pure-jnp reference and a Pallas
TPU kernel — with *identical semantics*, selected by a **kernel policy**:

    auto        ref on every backend, TPU included (the default; see
                ``_backend_policy``)
    pallas      force the compiled Pallas path (TPU); Mosaic refuses
                every kernel today, so this fails at lowering
    interpret   run the Pallas kernels under ``interpret=True`` — the
                compiled code path, executable on CPU (CI parity runs)
    ref         force the pure-jnp reference path

Selection precedence (first set wins):

    1. an explicit ``policy=`` argument — ``ConnectIt(spec, kernels=...)``
       and the ``ExecutionSpec.kernels`` field thread through here;
    2. the ``REPRO_KERNELS`` environment variable;
    3. ``auto`` (backend detection).

The policy is resolved at *trace* time: callables memoized per policy (the
``kernels=`` parameter of the finish factories) re-trace per policy, while
programs built with the default resolve the environment once per process —
set ``REPRO_KERNELS`` before building programs, or use the knob.

This layer owns the dispatch contract between core arrays and kernels:

  * **padding** — core label arrays are ``(n + 1,)`` with arbitrary ``n``;
    kernels want lane-aligned, block-divisible lengths. Labels are padded
    with self-labeled slots (fixed points of every primitive), edge arrays
    with dump-slot sentinels; results are sliced back to ``(n + 1,)``.
  * **dump-slot semantics** — negative / masked / out-of-range scatter
    targets are dumped onto a self-labeled slot with a max-sentinel value,
    so the scatter is a no-op regardless of the target buffer's contents.
  * **-1 virtual-minimum fixed points** — the ``-1`` label pinning L_max
    (core/primitives.py) never hooks, wins every min, and stops every
    pointer chain, in both implementations of every op.
"""

from __future__ import annotations

import functools
import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "KERNEL_POLICIES", "ENV_VAR", "KERNEL_CONTRACT_VERSION",
    "default_policy", "resolve_policy", "tuned_block_m",
    "clear_tuned_blocks", "DEFAULT_BLOCK_M",
    "scatter_min", "pointer_jump", "hook_compress", "edge_relabel",
    "edge_rewrite", "embedding_bag", "compact_mask",
]

KERNEL_POLICIES = ("auto", "pallas", "interpret", "ref")
ENV_VAR = "REPRO_KERNELS"

# Version of the dispatch contract this module owns (padding, dump-slot
# semantics, -1 virtual minimum). Bump on any semantic change: the tune
# selection cache records it per entry and invalidates winners measured
# under an older contract (repro.tune.cache).
KERNEL_CONTRACT_VERSION = 1

_LANE = 128  # TPU lane width: 1-D label/edge buffers pad to multiples of it

DEFAULT_BLOCK_M = 8192  # shipped edge-block size; the tuner's fallback

# These sit below the module constants on purpose: importing the graphs
# package re-enters this module through graphs -> core.execution, which
# needs KERNEL_POLICIES already bound for the cycle to resolve from any
# entry point (not just repro.api).
from ..graphs.containers import round_up  # noqa: E402
from .edge_relabel.kernel import edge_relabel as _edge_relabel_pallas  # noqa: E402
from .edge_relabel.kernel import edge_rewrite as _edge_rewrite_pallas  # noqa: E402
from .edge_relabel.ref import edge_relabel_ref, edge_rewrite_ref  # noqa: E402
from .hook_compress.kernel import hook_compress as _hook_compress_pallas  # noqa: E402
from .hook_compress.ref import hook_compress_ref  # noqa: E402
from .pointer_jump.kernel import pointer_jump as _pointer_jump_pallas  # noqa: E402
from .pointer_jump.ref import pointer_jump_ref  # noqa: E402
from .scatter_min.kernel import scatter_min as _scatter_min_pallas  # noqa: E402
from .scatter_min.ref import scatter_min_ref  # noqa: E402


def default_policy() -> str:
    """The process-level policy: ``REPRO_KERNELS`` if set, else ``auto``."""
    env = os.environ.get(ENV_VAR, "").strip().lower()
    if not env:
        return "auto"
    if env not in KERNEL_POLICIES:
        raise ValueError(
            f"bad {ENV_VAR}={env!r}; have {KERNEL_POLICIES}")
    return env


def _backend_policy() -> str:
    """The implementation ``auto`` resolves to: ``ref`` on every backend.

    On a TPU too. The Pallas kernels gather and scatter by data-dependent
    indices inside the kernel and hold the whole label array as one VMEM
    block, and Mosaic lowers none of the five (scatter-min, ``dynamic_slice``
    of a value, and non-2-D gathers are unimplemented). XLA's gather and
    scatter do compile for the chip at n=2^22, m=2^26. The choice is static,
    with no fallback at lowering time: an explicit ``pallas`` policy still
    dispatches the kernels and fails with the lowering error.
    ``tests/test_tpu_compile.py`` compiles every primitive as resolved here
    for a described v5e, and the tuner offers ``pallas`` only where this
    returns it."""
    return "ref"


def resolve_policy(policy: Optional[str] = None) -> str:
    """Resolve an (optional) explicit policy to a concrete implementation:
    ``pallas`` | ``interpret`` | ``ref``."""
    p = (policy or "auto").strip().lower()
    if p == "auto":
        p = default_policy()
    if p == "auto":
        p = _backend_policy()
    if p == "auto":
        # distinct from an unknown-policy spelling: resolution itself failed
        raise ValueError(
            f"kernel policy 'auto' did not resolve to a concrete "
            f"implementation on backend {jax.default_backend()!r} — "
            f"backend detection returned 'auto' (dispatch-layer bug)")
    if p not in KERNEL_POLICIES:
        raise ValueError(f"unknown kernel policy {policy!r}; "
                         f"have {KERNEL_POLICIES}")
    return p


# ---------------------------------------------------------------------------
# Tuned block-size resolution (repro.tune selection cache).
# ---------------------------------------------------------------------------

_TUNED_BLOCKS: dict = {}


def tuned_block_m(primitive: str) -> int:
    """The edge-block size ``primitive`` dispatches with when the caller
    passes none: the tuned winner from the selection cache
    (``repro.tune``), else ``DEFAULT_BLOCK_M``.

    Resolved at trace time and memoized per process (one cache read per
    primitive), so the hot path never touches the filesystem after its
    first trace. ``clear_tuned_blocks`` drops the memo (tests; after an
    in-process tuning run)."""
    if primitive not in _TUNED_BLOCKS:
        try:
            from ..tune.tuner import resolve_block_m
            block = resolve_block_m(primitive, default=DEFAULT_BLOCK_M)
        except Exception:  # any cache trouble degrades to the default
            block = DEFAULT_BLOCK_M
        _TUNED_BLOCKS[primitive] = block
    return _TUNED_BLOCKS[primitive]


def clear_tuned_blocks() -> None:
    """Forget memoized block-size winners (re-read the cache on next use)."""
    _TUNED_BLOCKS.clear()


# ---------------------------------------------------------------------------
# Dispatch-contract helpers: padding to kernel-friendly shapes.
# ---------------------------------------------------------------------------

def _padded_size(size: int, block: int) -> int:
    """Lane-aligned size; block-divisible once it exceeds one block."""
    padded = round_up(max(size, 1), _LANE)
    if padded > block:
        padded = round_up(size, block)
    return padded


def _pad_labels(P: jax.Array, block: int) -> jax.Array:
    """Pad a label array with self-labeled slots (fixed points of every op)."""
    L = P.shape[0]
    Lp = _padded_size(L, block)
    if Lp == L:
        return P
    return jnp.concatenate([P, jnp.arange(L, Lp, dtype=P.dtype)])


def _pad_edges(arrs, fills, block_m: int):
    """Pad parallel edge-indexed arrays to a kernel-divisible length."""
    m = arrs[0].shape[0]
    mp = _padded_size(m, block_m)
    if mp == m:
        return arrs
    return tuple(
        jnp.concatenate([a, jnp.full((mp - m,), fill, a.dtype)])
        for a, fill in zip(arrs, fills))


# ---------------------------------------------------------------------------
# The ops. Each takes core-convention arrays — labels ``(n + 1,)`` with dump
# row ``n`` — applies the dispatch contract, and returns core-shaped results.
# ---------------------------------------------------------------------------

def _scoped(op):
    """Run ``op`` under the device scope of its own name, so that whatever
    implements it (``ref`` or a Pallas kernel) carries that name in the
    compiled program's op metadata and in a profiler trace."""

    @functools.wraps(op)
    def run(*args, **kwargs):
        with jax.named_scope(op.__name__):
            return op(*args, **kwargs)

    return run


@_scoped
def scatter_min(P: jax.Array, idx: jax.Array, vals: jax.Array,
                mask: Optional[jax.Array] = None, *,
                policy: Optional[str] = None,
                block_m: Optional[int] = None) -> jax.Array:
    """``P[idx] = min(P[idx], vals)`` — the paper's writeMin (Appendix A).

    Negative, masked, and out-of-range targets are dumped (no-op scatter of
    the dtype's max sentinel), so ``P``'s dump row and any non-label buffer
    (e.g. the forest edge-id buffer) are safe targets. ``block_m=None``
    resolves through the tune selection cache (``tuned_block_m``)."""
    p = resolve_policy(policy)
    if block_m is None:
        block_m = tuned_block_m("scatter_min")
    n = P.shape[0] - 1
    big = jnp.iinfo(P.dtype).max
    ok = (idx >= 0) & (idx <= n)
    if mask is not None:
        ok = ok & mask
    idx = jnp.where(ok, idx, n)
    vals = jnp.where(ok, vals.astype(P.dtype), big)
    if p == "ref":
        return scatter_min_ref(P, idx, vals)
    Ppad = _pad_labels(P, block_m)
    idx, vals = _pad_edges((idx, vals), (n, big), block_m)
    out = _scatter_min_pallas(Ppad, idx, vals, block_m=block_m,
                              interpret=(p == "interpret"))
    return out[: n + 1]


@_scoped
def pointer_jump(labels: jax.Array, *, k: int = 1,
                 policy: Optional[str] = None, block: Optional[int] = None
                 ) -> jax.Array:
    """``k`` chained shortcut hops through the round-start snapshot.

    ``k=1`` is exactly one ``P ← P[P]`` round; chained hops compose, so
    ``k=3`` in one dispatch equals two successive rounds (FindHalve).
    ``-1`` labels and self-labeled slots are fixed points."""
    p = resolve_policy(policy)
    if block is None:
        block = tuned_block_m("pointer_jump")
    if p == "ref":
        return pointer_jump_ref(labels, k=k)
    L = labels.shape[0]
    Ppad = _pad_labels(labels, block)
    out = _pointer_jump_pallas(Ppad, k=k, block=block,
                               interpret=(p == "interpret"))
    return out[:L]


@_scoped
def hook_compress(P: jax.Array, senders: jax.Array, receivers: jax.Array,
                  *, k: int = 1, mask: Optional[jax.Array] = None,
                  policy: Optional[str] = None,
                  block_m: Optional[int] = None) -> jax.Array:
    """One fused uf_sync round: root-masked min-hook + ``k`` shortcut hops.

    Equivalent to ``write_min(P, P[s], P[r], root-mask)`` followed by
    ``pointer_jump(·, k)`` on the hooked array, in a single dispatch.
    ``mask=False`` edges are rewritten onto the dump row before dispatch
    (a no-op hook under the dump-slot contract), so frontier-compacted
    callers can deactivate satisfied edges without recompacting the list."""
    if mask is not None:
        dump = jnp.asarray(P.shape[0] - 1, senders.dtype)
        senders = jnp.where(mask, senders, dump)
        receivers = jnp.where(mask, receivers, dump)
    p = resolve_policy(policy)
    if block_m is None:
        block_m = tuned_block_m("hook_compress")
    if p == "ref":
        return hook_compress_ref(P, senders, receivers, k=k)
    n = P.shape[0] - 1
    Ppad = _pad_labels(P, block_m)
    dump = Ppad.shape[0] - 1
    s, r = _pad_edges((senders, receivers), (dump, dump), block_m)
    out = _hook_compress_pallas(Ppad, s, r, k=k, block_m=block_m,
                                interpret=(p == "interpret"))
    return out[: n + 1]


@_scoped
def compact_mask(mask: jax.Array, vals: jax.Array, cap: int, *,
                 policy: Optional[str] = None) -> tuple:
    """Stream-compact the ``True`` positions of ``mask`` (and their ``vals``)
    into fixed-capacity ``(cap,)`` buffers — the frontier-exchange primitive
    behind the sharded min-merge (core/distributed.py).

    Returns ``(idx, out)``: ``idx[j]`` is the j-th set position (int32, in
    mask order) and ``out[j]`` its value; unused slots carry ``idx = -1`` and
    the value dtype's max sentinel, so the pair feeds ``scatter_min``
    directly. Entries beyond ``cap`` are dropped — callers gate on the
    mesh-reduced frontier count before taking the compacted path. Every
    kernel policy shares the jnp path (a cumsum + two scatters; the op is
    bandwidth-trivial next to the scatter_min it feeds)."""
    del policy  # uniform signature with the other ops; no kernel pair yet
    m = mask.shape[0]
    big = jnp.iinfo(vals.dtype).max
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    tgt = jnp.where(mask & (pos < cap), pos, cap)  # overflow → dropped slot
    src = jnp.arange(m, dtype=jnp.int32)
    idx = jnp.full((cap + 1,), -1, jnp.int32).at[tgt].set(src)[:cap]
    out = jnp.full((cap + 1,), big, vals.dtype).at[tgt].set(
        jnp.where(mask, vals, big))[:cap]
    return idx, out


@_scoped
def edge_relabel(labels: jax.Array, senders: jax.Array, receivers: jax.Array,
                 *, policy: Optional[str] = None,
                 block_m: Optional[int] = None) -> jax.Array:
    """One relabel round: propose each endpoint's label to the other, merge
    with scatter-min (the inner loop of label-propagation-style finishes and
    the Liu–Tarjan ParentConnect rule)."""
    p = resolve_policy(policy)
    if block_m is None:
        block_m = tuned_block_m("edge_relabel")
    if p == "ref":
        return edge_relabel_ref(labels, senders, receivers)
    L = labels.shape[0]
    Ppad = _pad_labels(labels, block_m)
    dump = Ppad.shape[0] - 1
    s, r = _pad_edges((senders, receivers), (dump, dump), block_m)
    out = _edge_relabel_pallas(Ppad, s, r, block_m=block_m,
                               interpret=(p == "interpret"))
    return out[:L]


@_scoped
def edge_rewrite(labels: jax.Array, senders: jax.Array, receivers: jax.Array,
                 *, policy: Optional[str] = None,
                 block_m: Optional[int] = None):
    """Rewrite edge endpoints to their parents (Liu–Tarjan alter step, the
    streaming batch relabel): ``e ← P[e]`` with ``-1`` fixed points."""
    p = resolve_policy(policy)
    if block_m is None:
        block_m = tuned_block_m("edge_rewrite")
    if p == "ref":
        return edge_rewrite_ref(labels, senders, receivers)
    m = senders.shape[0]
    Ppad = _pad_labels(labels, block_m)
    dump = Ppad.shape[0] - 1
    s, r = _pad_edges((senders, receivers), (dump, dump), block_m)
    s2, r2 = _edge_rewrite_pallas(Ppad, s, r, block_m=block_m,
                                  interpret=(p == "interpret"))
    return s2[:m], r2[:m]


def embedding_bag(table: jax.Array, idx: jax.Array, *, mode: str = "sum",
                  block_b: int = 1024, policy: Optional[str] = None
                  ) -> jax.Array:
    """Deprecated: the ML-era kernel pair moved to
    ``repro.kernels.legacy.embedding_bag`` (its last consumer, the seed
    model stack, lives in ``repro.legacy``). Import from there directly."""
    warnings.warn(
        "ops.embedding_bag is deprecated — the kernel pair moved to "
        "repro.kernels.legacy.embedding_bag (no connectivity consumer)",
        DeprecationWarning, stacklevel=2)
    from .legacy.embedding_bag.kernel import embedding_bag as _pallas
    from .legacy.embedding_bag.ref import embedding_bag_ref as _ref
    p = resolve_policy(policy)
    if p == "ref":
        return _ref(table, idx, mode=mode)
    return _pallas(table, idx, mode=mode, block_b=block_b,
                   interpret=(p == "interpret"))
