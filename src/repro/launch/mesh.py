"""Production mesh construction (MULTI-POD DRY-RUN step 1).

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state. Runtime notes for real
clusters (not exercisable on one CPU host):

  * straggler mitigation: per-step collective timeouts + replica-group
    shrink are a runtime/plugin concern (e.g. borg/tpu runtime restarts);
    the framework side is the elastic re-mesh restore path in
    ``repro.checkpoint`` (checkpoints are mesh-shape independent).
  * elastic scaling: any mesh whose axis product divides the checkpoint's
    logical shapes restores cleanly; the launcher re-lowers on the new mesh.
"""

from __future__ import annotations

import jax


def make_mesh_compat(shape, axes):
    """``jax.make_mesh`` with every axis in ``Auto`` sharding mode."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_smoke_mesh():
    """1-device mesh with the production axis names (CPU tests)."""
    return make_mesh_compat((1, 1), ("data", "model"))


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def all_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)


# TPU v5e hardware constants used by the roofline analysis (§Roofline).
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
