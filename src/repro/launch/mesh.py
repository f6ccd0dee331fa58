"""Production meshes.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device initialization.  The single-pod mesh is
16x16 = 256 v5e chips (data, model); the multi-pod mesh adds a leading ``pod``
axis (2 pods = 512 chips).  In the DySTop mapping the ``pod`` axis doubles as
the decentralized-FL worker axis (each pod holds one DFL replica).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes.  The engines place GSPMD sharding
    constraints and let the partitioner route gathers and scatters; JAX
    0.9's default Explicit axes would instead demand an ``out_sharding`` on
    every gather or scatter into a sharded operand."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke runs (shardings become no-ops)."""
    return make_mesh((1, 1), ("data", "model"))


FLEET_AXIS = "fleet"


def make_fleet_mesh(mesh_shards: int):
    """1-D mesh over the DFL fleet (worker) axis for the sharded engines.

    The resident ``(N, P)`` / ``(N, S)`` fleet buffers partition their row
    axis over this mesh (``sharding.rules.FleetSharding``), one contiguous
    block of workers per device — the N-scaling axis of the ROADMAP, distinct
    from the intra-model (data, model) axes of ``make_production_mesh``
    (there each DFL worker is a whole pod; here each device holds a SLICE of
    the fleet).  On hardware the devices are chips; on the CI box the mesh is
    emulated with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    Raises if the process has fewer devices than requested shards.
    """
    if mesh_shards < 1:
        raise ValueError(f"mesh_shards must be >= 1, got {mesh_shards}")
    n_dev = len(jax.devices())
    if mesh_shards > n_dev:
        raise ValueError(
            f"mesh_shards={mesh_shards} but only {n_dev} device(s) visible; "
            f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{mesh_shards} (before jax initializes) to emulate the mesh")
    return make_mesh((mesh_shards,), (FLEET_AXIS,))


# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
CHIPS_PER_POD = 256
