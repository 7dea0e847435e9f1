"""Production mesh builders.

Functions (never module-level constants) so importing this module never
touches jax device state.  The dry-run entry point (dryrun.py) sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE importing jax;
everything here just consumes whatever devices exist.
"""
from __future__ import annotations

from typing import Tuple

from ..compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) data x model = 256 chips.
    Multi-pod: (2, 16, 16) pod x data x model = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh over host devices (tests / elastic drills)."""
    return make_mesh(shape, axes)


def mesh_devices(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n


def make_cell_mesh(n_shards: "int | None" = None):
    """1-D mesh for ISLA cell-axis sharding (``route="mesh"``): the
    stacked (store, group, block) cell axis of a ``MeshDeviceStack``
    splits over its single ``"cells"`` axis by block runs.  ``n_shards``
    defaults to every visible device (on a forced host-device-count
    runtime that is the ``--xla_force_host_platform_device_count``
    value)."""
    import jax
    if n_shards is None:
        n_shards = jax.device_count()
    return make_mesh((int(n_shards),), ("cells",))
