"""The collectives and mesh substrate: one import point for the SPMD surface.

Every repro module takes these primitives from here, so the codebase
tracks one point of contact with JAX's sharding API (jax 0.9):

* :func:`make_mesh`   — a device mesh whose axes are all ``Auto`` (the
  sharding-propagation mode every rule table here is written for)
* :func:`shard_map`   — the per-device SPMD mapper
* :func:`axis_size`   — static size of one or more mesh axes, usable inside
  a ``shard_map`` body
* :func:`axis_index`  — flattened (row-major) device index over mesh axes
* :func:`ppermute`    — neighbour permutation (the RINGI hop)
* :func:`all_gather` / :func:`psum_scatter` — the XLA-native comparison
  points for the §Perf flat-vs-hierarchical ablations
* :func:`mesh_axis_size` — axis size read off a concrete ``Mesh`` (outside
  any traced context)
* :func:`halo_block_spec` — an element-offset (overlapping halo) Pallas
  ``BlockSpec``
"""
from __future__ import annotations

import math
from typing import Callable, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

Axis = str | Sequence[str]


def _axis_tuple(axis_names: Axis) -> tuple[str, ...]:
    if isinstance(axis_names, str):
        return (axis_names,)
    return tuple(axis_names)


def make_mesh(shape: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> Mesh:
    """A mesh of ``shape`` over ``devices`` (default: the first
    ``prod(shape)`` of ``jax.devices()``), every axis ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to Explicit axes, under which the rule-table
    ``with_sharding_constraint`` calls and reshapes of sharded values are
    refused; the whole repo shards by propagation, so its meshes are Auto.
    """
    shape, names = tuple(int(s) for s in shape), tuple(axis_names)
    auto = (AxisType.Auto,) * len(names)
    if devices is None:
        return jax.make_mesh(shape, names, axis_types=auto)
    arr = np.asarray(devices, dtype=object)[: math.prod(shape)]
    return Mesh(arr.reshape(shape), names, axis_types=auto)


def shard_map(f: Callable, *, mesh: Mesh, in_specs, out_specs, **kwargs):
    """``jax.shard_map`` with the ``mesh``/``in_specs``/``out_specs``
    keywords this repo uses, under ``jax.jit``: called eagerly, a bare
    shard_map runs its body one primitive at a time, compiling each; jitted
    it compiles once (and inlines into an enclosing jit)."""
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, **kwargs))


def axis_size(axis_names: Axis) -> int:
    """Size of (the product of) mesh axes, inside a ``shard_map`` body."""
    return jax.lax.axis_size(_axis_tuple(axis_names))


def axis_index(axis_names: Axis) -> jax.Array:
    """Flattened row-major index over ``axis_names`` (first axis major)."""
    names = _axis_tuple(axis_names)
    idx = jax.lax.axis_index(names[0])
    for a in names[1:]:
        idx = idx * axis_size(a) + jax.lax.axis_index(a)
    return idx


def ppermute(x: jax.Array, axis_names: Axis,
             perm: Sequence[tuple[int, int]]) -> jax.Array:
    """Source->dest permutation over the flattened ``axis_names`` ring."""
    return jax.lax.ppermute(x, _axis_tuple(axis_names), perm=perm)


def psum(x, axis_names: Axis):
    return jax.lax.psum(x, _axis_tuple(axis_names))


def pmax(x, axis_names: Axis):
    return jax.lax.pmax(x, _axis_tuple(axis_names))


def all_gather(x: jax.Array, axis_names: Axis, *, axis: int = 0,
               tiled: bool = True) -> jax.Array:
    """XLA-native all-gather (the flat baseline the RINGI version races)."""
    return jax.lax.all_gather(x, _axis_tuple(axis_names), axis=axis,
                              tiled=tiled)


def psum_scatter(x: jax.Array, axis_names: Axis, *, scatter_dimension: int = 0,
                 tiled: bool = True) -> jax.Array:
    """XLA-native reduce-scatter comparison point."""
    return jax.lax.psum_scatter(x, _axis_tuple(axis_names),
                                scatter_dimension=scatter_dimension,
                                tiled=tiled)


def mesh_axis_size(mesh: Mesh, axis_names: Axis) -> int:
    """Static axis size read off a concrete mesh (outside traced code)."""
    return math.prod(mesh.shape[a] for a in _axis_tuple(axis_names))


def halo_block_spec(block_shape: Sequence[int], index_map: Callable):
    """Pallas ``BlockSpec`` whose ``index_map`` returns *element* offsets
    (``pl.Element`` per dimension): overlapping halo windows for stencil
    reads."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec(tuple(pl.Element(b) for b in block_shape), index_map)
