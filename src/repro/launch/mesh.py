"""Production device meshes.

Target hardware: TPU v5e pods — 256 chips (16x16) per pod; the multi-pod
configuration is 2 pods = 512 chips with a leading 'pod' axis.  Defined as
functions (never module-level constants) so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax

__all__ = [
    "make_production_mesh", "make_test_mesh", "make_group_mesh",
    "PEAK_FLOPS", "HBM_BW", "ICI_BW",
]

# TPU v5e hardware constants (per chip) for the roofline analysis
PEAK_FLOPS = 197e12   # bf16 FLOP/s
HBM_BW = 819e9        # bytes/s
ICI_BW = 50e9         # bytes/s per link


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(4, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (requires
    --xla_force_host_platform_device_count)."""
    return _make_mesh(shape, axes)


def make_group_mesh(n_processes: int = 1, axes=("data", "model")):
    """Mesh over an elastic process group's devices.

    After ``jax.distributed.initialize`` (the elastic runtime's
    ``jax_distributed=True`` path) ``jax.devices()`` spans every process in
    the group; the leading axis covers the processes (one data shard per
    worker) and the trailing axis each process's local device fan-out
    (``RuntimeConfig.host_devices`` on CPU).  With ``n_processes=1`` this
    degenerates to a local mesh over the host's devices."""
    devices = jax.devices()
    if n_processes < 1 or len(devices) % n_processes:
        raise ValueError(
            f"{len(devices)} devices do not split over {n_processes} processes"
        )
    return _make_mesh((n_processes, len(devices) // n_processes), axes)
