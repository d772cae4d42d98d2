"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module does not touch jax device state.  The dry-run launcher
sets XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax
import; everything else sees the real single CPU device.

Target hardware: TPU v5e, 256 chips/pod, 2 pods.
  single-pod mesh: (16, 16)      axes ("data", "model")
  multi-pod mesh:  (2, 16, 16)   axes ("pod", "data", "model")
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

# v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link (~unidirectional)


def _auto(n: int):
    """Auto axes: the sharding rules here place arrays with
    ``with_sharding_constraint`` and ``shard_map``, which need Auto axes
    (``jax.make_mesh`` defaults to Explicit ones)."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh() -> Mesh:
    """Single-device mesh for CPU smoke tests (data=1, model=1)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1], axis_types=_auto(2))


def make_fleet_mesh(num_devices: int | None = None) -> Mesh:
    """1-D mesh over the fleet row axis of the scan-superstep launch.

    The surveillance-fleet workload shards along ONE axis — the folded
    (query, edge) row axis of the fused triage slab (rows are mutually
    independent, so the kernel runs shard-local with no collectives; see
    ``repro.distributed.sharding.fleet_specs``).  On CPU this is
    exercised with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    (set by the sharded CI leg); defaults to every visible device."""
    devices = jax.devices()
    n = len(devices) if num_devices is None else num_devices
    return jax.make_mesh((n,), ("fleet",), devices=devices[:n],
                         axis_types=_auto(1))


def chips(mesh: Mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
