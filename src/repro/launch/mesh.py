"""Mesh builders: the one place this repo makes a ``jax.sharding.Mesh``.

Functions, not module-level state, so importing this module never touches
jax device initialization.  Every axis is ``AxisType.Auto``: the sharding
rules (``distributed/sharding.py``) place arrays with constraints and let
the partitioner propagate the rest.  ``jax.make_mesh`` defaults to Explicit
axes since JAX 0.7, under which sharding becomes part of every array's type
and ops such as the embedding gather raise ``ShardingTypeError``.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None) -> Mesh:
    """``shape`` over ``axes`` (Auto axis types) on ``devices`` (default:
    all of this process's devices)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1) -> Mesh:
    """Whatever this host has: (n/model, model) data x model mesh."""
    n = len(jax.devices())
    data = max(n // model, 1)
    return make_mesh((data, model), ("data", "model"))


def mesh_chip_count(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
