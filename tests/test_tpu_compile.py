"""Compile the chip's connectivity programs for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a topology it is given,
so what it refuses here (an op Mosaic cannot lower, a program that does not
fit) fails these tests instead of a chip run. Sizes are the one-chip smoke
size, n = 2^22 labels and m = 2^26 edges.

The topology is described only inside the module fixture: one process at a
time may load the TPU library, and every pytest-xdist worker imports this
file. Keep every test that needs the topology in this file.
"""

import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.api import VariantSpec
from repro.core import driver
from repro.core.execution import ShardedBackend, as_execution_spec
from repro.graphs.containers import round_up
from repro.kernels import ops
from repro.tune.harness import PRIMITIVES

N = 1 << 22
M = 1 << 26
VARIANT = "kout_hybrid_k2+uf_sync_full"

# each primitive called as the connectivity paths call it, policy left to
# ``auto``: (labels, senders, receivers) -> result
CALLS = {
    "scatter_min": lambda P_, s, r: ops.scatter_min(P_, s, r),
    "pointer_jump": lambda P_, s, r: ops.pointer_jump(P_, k=3),
    "hook_compress": lambda P_, s, r: ops.hook_compress(P_, s, r, k=1),
    "edge_relabel": lambda P_, s, r: ops.edge_relabel(P_, s, r),
    "edge_rewrite": lambda P_, s, r: ops.edge_rewrite(P_, s, r),
}


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_for_tpu(topo):
    """Compile with ``auto`` resolving as it would with no override, and the
    persistent compile cache off (entries written for a described chip
    cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv(ops.ENV_VAR, raising=False)
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def single_finish(compile_for_tpu):
    """The default variant's static finish program on one chip."""
    finish = VariantSpec.parse(VARIANT).build_finish()
    return finish, driver._finish_phase.lower(
        *_single_args(compile_for_tpu), finish_fn=finish).compile()


def _single_args(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    sds = jax.ShapeDtypeStruct
    return (sds((N + 1,), jnp.int32, sharding=one_chip),
            sds((M,), jnp.int32, sharding=one_chip),
            sds((M,), jnp.int32, sharding=one_chip))


def test_tpu_auto_resolution_is_what_these_tests_compile(monkeypatch):
    """``auto`` on a TPU names only the implementation the compile cases
    below lower (they run on this host, so they compile its resolution)."""
    monkeypatch.delenv(ops.ENV_VAR, raising=False)
    here = ops.resolve_policy(None)
    monkeypatch.setattr(ops, "jax", types.SimpleNamespace(
        default_backend=lambda: "tpu"))
    assert ops._backend_policy() == here


@pytest.mark.parametrize("name", PRIMITIVES)
def test_primitive_compiles_for_v5e(compile_for_tpu, name):
    compiled = jax.jit(CALLS[name]).lower(
        *_single_args(compile_for_tpu)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= (N + 1) * 4
    assert mem.temp_size_in_bytes < 4 << 30  # well inside 16 GB of HBM


def test_default_variant_finish_compiles_for_v5e(single_finish):
    mem = single_finish[1].memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30


def test_sharded_finish_compiles_on_2x2_mesh(compile_for_tpu, single_finish):
    finish, single = single_finish
    mesh = Mesh(np.asarray(compile_for_tpu.devices).reshape(-1), ("x",))
    backend = ShardedBackend(as_execution_spec("sharded(x)"), mesh)
    shard = NamedSharding(mesh, P("x"))
    sds = jax.ShapeDtypeStruct
    labels = sds((round_up(N + 1, mesh.size),), jnp.int32, sharding=shard)
    edges = sds((M,), jnp.int32, sharding=shard)
    compiled = backend.finish_program(finish).lower(
        labels, edges, edges).compile()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < single.memory_analysis().argument_size_in_bytes
    assert per_device <= (labels.shape[0] + 2 * M) * 4 // mesh.size + 4096
