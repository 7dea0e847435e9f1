"""One spelling for the JAX mesh surface the repo touches (jax 0.9).

Exports
  AxisType            — ``jax.sharding.AxisType``
  shard_map           — ``jax.shard_map``
  make_mesh           — ``jax.make_mesh`` with every axis ``AxisType.Auto``
  make_abstract_mesh  — device-free ``AbstractMesh``, axes ``Auto``
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AbstractMesh, AxisType

shard_map = jax.shard_map


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` over the visible devices, every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_abstract_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Device-free mesh for sharding-rule checks, every axis Auto."""
    return AbstractMesh(tuple(shape), tuple(axes),
                        axis_types=(AxisType.Auto,) * len(axes))
