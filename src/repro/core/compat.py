"""Mesh and shard_map helpers over the installed JAX API.

Every mesh or shard_map construction in the repo goes through these
three wrappers, so they fix one convention in one place: meshes use
``AxisType.Auto`` axes, and shard_map runs unchecked
(``check_vma=False``).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def device_mesh(devices, axes):
    """``jax.sharding.Mesh`` over an explicit device array, Auto axes."""
    return jax.sharding.Mesh(np.asarray(devices), axes,
                             axis_types=(AxisType.Auto,) * len(axes))


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None):
    """Unchecked ``jax.shard_map``; ``axis_names`` (when given) is the
    set of manual axes."""
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kw)
