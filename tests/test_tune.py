"""Autotuning subsystem (``repro.tune``): TuneSpec grammar, selection-cache
durability (schema/contract invalidation, atomic writes, env override),
deterministic winner selection under a fake timer, auto-resolution
precedence (explicit spec > cached winner > paper default), and the
query-path guarantees of ``ConnectIt("auto", ...)`` — warm-cache
bit-identity with the explicit winner and zero compilations after warmup.
"""

import json
import logging
import os

import jax
import numpy as np
import pytest

from repro.api import ConnectIt, ExecutionSpec, VariantSpec
from repro.graphs import generators
from repro.kernels import ops
from repro.tune import (
    SelectionCache,
    TuneSpec,
    cache_path,
    default_cache,
    fingerprint,
    fingerprint_graph,
    make_key,
    reset_default_cache,
    resolve_block_m,
    resolve_variant,
    time_fn,
    tune_block_m,
    tune_variant,
)
from repro.tune.cache import SCHEMA_VERSION
from repro.tune.tuner import PAPER_DEFAULT_VARIANT


@pytest.fixture()
def tmp_cache(tmp_path, monkeypatch):
    """A fresh on-disk cache, installed as the process default."""
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    reset_default_cache()
    ops.clear_tuned_blocks()
    yield SelectionCache(path)
    reset_default_cache()
    ops.clear_tuned_blocks()


# ---------------------------------------------------------------------------
# TuneSpec grammar.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("text", [
    "tune", "tune(grid=full)", "tune(trials=5)", "tune(warmup=0)",
    "tune(grid=full,trials=7,warmup=2)", "tune(trials=1,warmup=3)",
])
def test_tune_spec_roundtrip(text):
    spec = TuneSpec.parse(text)
    assert TuneSpec.parse(str(spec)) == spec


def test_tune_spec_canonical_string():
    assert str(TuneSpec()) == "tune"
    assert str(TuneSpec(grid="full")) == "tune(grid=full)"
    assert str(TuneSpec(trials=5, warmup=2)) == "tune(trials=5,warmup=2)"


@pytest.mark.parametrize("text", [
    "tune(grid=medium)", "tune(trials=0)", "tune(warmup=-1)",
    "tune(block=8)", "tune(grid)", "tunes", "tune(trials=two)",
])
def test_tune_spec_rejects(text):
    with pytest.raises(ValueError):
        TuneSpec.parse(text)


def test_tune_spec_grids():
    fast, full = TuneSpec(), TuneSpec(grid="full")
    assert PAPER_DEFAULT_VARIANT in fast.variant_candidates()
    assert len(full.variant_candidates()) > len(fast.variant_candidates())
    assert all(b & (b - 1) == 0 for b in full.block_m_candidates())
    assert set(fast.block_m_candidates()) <= set(full.block_m_candidates())


# ---------------------------------------------------------------------------
# Selection cache: round-trip and durability.
# ---------------------------------------------------------------------------

def test_cache_roundtrip(tmp_cache):
    key = make_key("variant", "n10-mid-lo")
    assert tmp_cache.get(key) is None
    tmp_cache.put(key, "none+uf_sync_full", time_s=0.5, n=1024)
    fresh = SelectionCache(tmp_cache.path)
    entry = fresh.get(key)
    assert entry["winner"] == "none+uf_sync_full"
    assert entry["time_s"] == 0.5 and entry["n"] == 1024
    assert fresh.winner(key) == "none+uf_sync_full"
    fresh.discard(key)
    assert SelectionCache(tmp_cache.path).get(key) is None


def test_cache_schema_version_invalidation(tmp_cache):
    key = make_key("variant")
    tmp_cache.put(key, "none+uf_sync_full")
    data = json.load(open(tmp_cache.path))
    assert data["schema"] == SCHEMA_VERSION
    data["schema"] = SCHEMA_VERSION + 1
    json.dump(data, open(tmp_cache.path, "w"))
    # wrong schema: discarded wholesale, resolution falls back to defaults
    assert SelectionCache(tmp_cache.path).winner(key) is None


def test_cache_contract_invalidation(tmp_cache):
    key = make_key("block_m:scatter_min")
    tmp_cache.put(key, 4096)
    assert SelectionCache(tmp_cache.path).winner(key) == 4096
    # a kernel-contract bump drops winners measured under the old contract
    bumped = SelectionCache(tmp_cache.path,
                            contract=ops.KERNEL_CONTRACT_VERSION + 1)
    assert bumped.winner(key) is None


def test_cache_corrupt_file_degrades_to_empty(tmp_cache):
    with open(tmp_cache.path, "w") as f:
        f.write("{not json")
    cache = SelectionCache(tmp_cache.path)
    assert len(cache) == 0
    # and stays writable: the corrupt file is replaced atomically
    cache.put(make_key("variant"), "none+uf_sync_full")
    assert SelectionCache(tmp_cache.path).winner(make_key("variant"))


def test_cache_atomic_write_crash_safety(tmp_cache, monkeypatch):
    key = make_key("variant", "n10-mid-lo")
    tmp_cache.put(key, "none+uf_sync_full")
    before = open(tmp_cache.path).read()

    def boom(src, dst):
        raise OSError("simulated crash mid-replace")

    monkeypatch.setattr(os, "replace", boom)
    with pytest.raises(OSError):
        SelectionCache(tmp_cache.path).put(key, "none+uf_sync_naive")
    monkeypatch.undo()
    # the previous file is untouched and no temp files leak
    assert open(tmp_cache.path).read() == before
    assert SelectionCache(tmp_cache.path).winner(key) == "none+uf_sync_full"
    leftovers = [f for f in os.listdir(os.path.dirname(tmp_cache.path))
                 if f.endswith(".tmp")]
    assert leftovers == []


def test_cache_env_override(tmp_path, monkeypatch):
    env_path = str(tmp_path / "env.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", env_path)
    reset_default_cache()
    assert cache_path() == env_path
    assert default_cache().path == env_path
    # an explicit path argument wins over the environment
    assert cache_path(str(tmp_path / "explicit.json")).endswith(
        "explicit.json")
    monkeypatch.delenv("REPRO_TUNE_CACHE")
    reset_default_cache()
    assert cache_path().endswith(os.path.join(".cache", "repro",
                                              "tune.json"))
    reset_default_cache()


# ---------------------------------------------------------------------------
# Fingerprints.
# ---------------------------------------------------------------------------

def test_fingerprint_buckets():
    assert fingerprint(1024, 2048) == "n10-sparse-any"
    assert fingerprint(1024, 8192, 2.0) == "n10-mid-lo"
    assert fingerprint(1024, 1 << 15, 50.0) == "n10-dense-hi"


def test_fingerprint_graph_is_stable():
    g = generators.random_graph(256, 1024, seed=0)
    fam = fingerprint_graph(g)
    assert fam == fingerprint_graph(g)
    assert fam.startswith("n8-")


# ---------------------------------------------------------------------------
# Measurement harness: deterministic winners under a fake timer.
# ---------------------------------------------------------------------------

class FakeClock:
    """Injectable timer: consecutive reads are spaced by a scripted delta
    sequence, so each timed call costs exactly the next delta."""

    def __init__(self, deltas):
        self.deltas = list(deltas)
        self.now = 0.0
        self.reading = False

    def __call__(self):
        if self.reading:  # closing read of a sample: advance by one delta
            self.now += self.deltas.pop(0)
        self.reading = not self.reading
        return self.now


def test_time_fn_median_and_validation():
    clock = FakeClock([1.0, 5.0, 2.0])
    t = time_fn(lambda: jax.numpy.zeros(()), trials=3, warmup=0, timer=clock)
    assert t == 2.0  # median, not mean
    with pytest.raises(ValueError):
        time_fn(lambda: None, trials=0)
    with pytest.raises(ValueError):
        time_fn(lambda: None, warmup=-1)


def test_tune_block_m_deterministic_winner(tmp_cache):
    spec = TuneSpec(trials=1, warmup=0)
    ladder = spec.block_m_candidates()
    # script the middle block as the unique winner
    deltas = {ladder[0]: 5.0, ladder[1]: 1.0, ladder[2]: 3.0}
    clock = FakeClock([deltas[b] for b in ladder])
    rows = tune_block_m(spec, cache=tmp_cache, n=256,
                        primitives=("scatter_min",), policy="ref",
                        timer=clock)
    winners = [r["block_m"] for r in rows if r["winner"]]
    assert winners == [ladder[1]]
    assert tmp_cache.winner(make_key("block_m:scatter_min")) == ladder[1]
    # candidates table persisted alongside the winner
    entry = tmp_cache.get(make_key("block_m:scatter_min"))
    assert set(entry["candidates"]) == {str(b) for b in ladder}


def test_tune_block_m_tie_breaks_to_smaller_block(tmp_cache):
    spec = TuneSpec(trials=1, warmup=0)
    clock = FakeClock([1.0] * len(spec.block_m_candidates()))
    tune_block_m(spec, cache=tmp_cache, n=256,
                 primitives=("pointer_jump",), policy="ref", timer=clock)
    assert (tmp_cache.winner(make_key("block_m:pointer_jump"))
            == min(spec.block_m_candidates()))


def test_tune_variant_tie_breaks_to_candidate_order(tmp_cache):
    g = generators.random_graph(64, 256, seed=0)
    candidates = ("none+uf_sync_full", "none+uf_sync_naive")
    clock = FakeClock([1.0] * len(candidates))
    winner = tune_variant(g, TuneSpec(trials=1, warmup=0), cache=tmp_cache,
                          kernels="ref", candidates=candidates, timer=clock)
    assert winner == candidates[0]
    fam = fingerprint_graph(g)
    assert tmp_cache.winner(make_key("variant", fam)) == winner


# ---------------------------------------------------------------------------
# Auto resolution: precedence and block_m wiring.
# ---------------------------------------------------------------------------

def test_resolve_variant_precedence(tmp_cache):
    fam = "n8-mid-lo"
    # cold cache: the paper default, never an error
    assert resolve_variant(fam, cache=tmp_cache) == PAPER_DEFAULT_VARIANT
    # backend-global winner beats the default
    tmp_cache.put(make_key("variant", "*"), "none+uf_sync_full")
    assert resolve_variant(fam, cache=tmp_cache) == "none+uf_sync_full"
    # family winner beats the global winner
    tmp_cache.put(make_key("variant", fam), "none+shiloach_vishkin")
    assert resolve_variant(fam, cache=tmp_cache) == "none+shiloach_vishkin"
    # a corrupt winner is skipped, not raised
    tmp_cache.put(make_key("variant", fam), "not+a+variant")
    assert resolve_variant(fam, cache=tmp_cache) == "none+uf_sync_full"


def test_resolve_block_m_validates_winner(tmp_cache):
    key = make_key("block_m:scatter_min")
    assert resolve_block_m("scatter_min", cache=tmp_cache) == \
        ops.DEFAULT_BLOCK_M
    tmp_cache.put(key, 4096)
    assert resolve_block_m("scatter_min", cache=tmp_cache) == 4096
    # non-pow2 / tiny / non-numeric winners fall back to the default
    for bad in (999, 64, "huge"):
        tmp_cache.put(key, bad)
        assert resolve_block_m("scatter_min", cache=tmp_cache) == \
            ops.DEFAULT_BLOCK_M


def test_ops_tuned_block_m_resolution(tmp_cache):
    tmp_cache.put(make_key("block_m:scatter_min"), 4096)
    ops.clear_tuned_blocks()
    assert ops.tuned_block_m("scatter_min") == 4096
    assert ops.tuned_block_m("pointer_jump") == ops.DEFAULT_BLOCK_M
    # memoized per process: a later cache write needs an explicit clear
    tmp_cache.put(make_key("block_m:scatter_min"), 16384)
    reset_default_cache()
    assert ops.tuned_block_m("scatter_min") == 4096
    ops.clear_tuned_blocks()
    assert ops.tuned_block_m("scatter_min") == 16384


def test_ops_dispatch_uses_tuned_block(tmp_cache):
    """The primitives resolve block_m through the cache and produce the same
    results as an explicit block argument."""
    import jax.numpy as jnp
    tmp_cache.put(make_key("block_m:scatter_min"), 256)
    ops.clear_tuned_blocks()
    P = jnp.arange(65, dtype=jnp.int32)
    s = jnp.zeros(16, dtype=jnp.int32)
    vals = jnp.full((16,), 3, jnp.int32)
    out_tuned = ops.scatter_min(P, s, vals, policy="interpret")
    out_explicit = ops.scatter_min(P, s, vals, policy="interpret",
                                   block_m=256)
    np.testing.assert_array_equal(np.asarray(out_tuned),
                                  np.asarray(out_explicit))


# ---------------------------------------------------------------------------
# ConnectIt("auto"): precedence, warm-path identity, no tuning on queries.
# ---------------------------------------------------------------------------

def test_variant_spec_parse_auto(tmp_cache):
    assert str(VariantSpec.parse("auto")) == PAPER_DEFAULT_VARIANT
    tmp_cache.put(make_key("variant", "*"), "none+uf_sync_full")
    # the process-default cache holds a memoized view; writes through
    # another instance surface after a reload (one file read, not per-query)
    default_cache().reload()
    assert str(VariantSpec.parse("auto")) == "none+uf_sync_full"


def test_explicit_spec_beats_cache(tmp_cache):
    tmp_cache.put(make_key("variant", "*"), "none+shiloach_vishkin")
    ci = ConnectIt("none+uf_sync_naive", kernels="ref")
    g = generators.random_graph(64, 256, seed=0)
    ci.connectivity(g)
    assert ci.stats.variant == "none+uf_sync_naive"


def test_auto_cold_cache_falls_back_to_paper_default(tmp_cache):
    g = generators.random_graph(64, 256, seed=0)
    ci = ConnectIt("auto", kernels="ref")
    labels = ci.connectivity(g)
    assert ci.stats.variant == PAPER_DEFAULT_VARIANT
    ref = ConnectIt(PAPER_DEFAULT_VARIANT, kernels="ref").connectivity(g)
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(ref))


def test_auto_warm_cache_matches_explicit_winner(tmp_cache):
    g = generators.random_graph(128, 512, seed=1)
    fam = fingerprint_graph(g)
    winner = "none+uf_sync_full"
    tmp_cache.put(make_key("variant", fam), winner)
    ci = ConnectIt("auto", kernels="ref")
    labels = ci.connectivity(g)
    assert ci.stats.variant == winner
    explicit = ConnectIt(winner, kernels="ref").connectivity(g)
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(explicit))


def test_auto_warm_path_no_recompilation(tmp_cache):
    """After warmup, auto connectivity does zero tuning and zero compilation
    work on the query path (the no-recompile acceptance gate)."""
    g = generators.random_graph(128, 512, seed=2)
    tmp_cache.put(make_key("variant", fingerprint_graph(g)),
                  "none+uf_sync_full")
    ci = ConnectIt("auto", kernels="ref")
    ci.connectivity(g)
    ci.connectivity(g)  # warm: family memoized, jit caches populated

    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logger = logging.getLogger("jax")
    old_level = logger.level
    jax.config.update("jax_log_compiles", True)
    logger.addHandler(handler)
    logger.setLevel(logging.DEBUG)
    try:
        warm = ci.connectivity(g)
    finally:
        jax.config.update("jax_log_compiles", False)
        logger.removeHandler(handler)
        logger.setLevel(old_level)
    compiles = [r.getMessage() for r in records
                if "compil" in r.getMessage().lower()]
    assert compiles == []
    assert ci.stats.variant == "none+uf_sync_full"
    np.testing.assert_array_equal(
        np.asarray(warm),
        np.asarray(ConnectIt("none+uf_sync_full",
                             kernels="ref").connectivity(g)))


def test_exec_tune_opt_roundtrip():
    spec = ExecutionSpec.parse("single:tune")
    assert spec.tune
    assert str(spec) == "single:tune"
    spec = ExecutionSpec.parse("sharded(x):tune,kernels=ref")
    assert ExecutionSpec.parse(str(spec)) == spec
    assert not ExecutionSpec().tune


def test_exec_tune_forces_retune(tmp_cache):
    """``single:tune`` re-measures once per family per session and persists
    the winner; later graphs of the family are pure lookups."""
    g = generators.random_graph(128, 512, seed=3)
    fam = fingerprint_graph(g)
    # a pre-seeded winner would normally be trusted verbatim...
    tmp_cache.put(make_key("variant", fam), "none+uf_sync_naive")
    ci = ConnectIt("auto", exec="single:tune", kernels="ref")
    ci.connectivity(g)
    # ...but the tune opt re-measured the shortlist and rewrote the entry
    entry = default_cache().reload().get(make_key("variant", fam))
    assert "candidates" in entry and len(entry["candidates"]) > 1
    assert ci.stats.variant == entry["winner"]
    assert fam in ci._tuned_families
    # second call: session memo, no second sweep (the entry is untouched)
    stamp = entry["tuned_at"]
    ci.connectivity(g)
    assert default_cache().reload().get(
        make_key("variant", fam))["tuned_at"] == stamp


# ---------------------------------------------------------------------------
# Dispatch sanitization (satellite: distinct error classes).
# ---------------------------------------------------------------------------

def test_unknown_policy_error_is_distinct(monkeypatch):
    monkeypatch.delenv(ops.ENV_VAR, raising=False)
    with pytest.raises(ValueError, match="unknown kernel policy"):
        ops.resolve_policy("vectorized")
    # unresolved auto is a dispatch-layer bug, reported as such — not as an
    # unknown spelling
    monkeypatch.setattr(ops, "_backend_policy", lambda: "auto")
    with pytest.raises(ValueError, match="did not resolve"):
        ops.resolve_policy("auto")


@pytest.mark.parametrize("auto_impl,swept", [("ref", "interpret"),
                                              ("pallas", "pallas")])
def test_block_sweep_policy_follows_auto(monkeypatch, auto_impl, swept):
    """The tuner sweeps blocks on the compiled kernels only where ``auto``
    dispatches them, so it never offers a kernel the chip cannot lower."""
    from repro.tune.tuner import compiled_policy
    monkeypatch.setattr(ops, "_backend_policy", lambda: auto_impl)
    assert compiled_policy() == swept


def test_embedding_bag_shim_deprecated():
    import jax.numpy as jnp
    table = jnp.ones((8, 4), jnp.float32)
    idx = jnp.zeros((2, 3), jnp.int32)
    with pytest.warns(DeprecationWarning, match="legacy"):
        out = ops.embedding_bag(table, idx, policy="ref")
    from repro.kernels.legacy.embedding_bag.ref import embedding_bag_ref
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(embedding_bag_ref(table, idx)))
