"""Mesh construction pinned to the sharding mode this repo is written for.

``jax.make_mesh`` gives ``Explicit`` axes by default, under which
``res.T[i]`` on a sharded result raises ``ShardingTypeError``. Every mesh
the code and its multi-device tests build is an ``Auto`` mesh, made here.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axis types on every axis."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
