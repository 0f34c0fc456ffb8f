"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count is locked at first backend init — the dry-run
sets XLA_FLAGS before any import for exactly this reason).
"""

from __future__ import annotations

import math

import numpy as np

import jax
from jax.sharding import AxisType, Mesh

from repro.dist.sharding import MeshAxes


def make_mesh(shape, axes, devices=None) -> Mesh:
    """The one mesh constructor: every axis ``Auto``.

    The sharding rules (``repro.dist``) place values with
    ``with_sharding_constraint`` hints that GSPMD may propagate around.
    ``jax.make_mesh`` defaults to ``Explicit`` axes, on which those hints
    become asserts and gathers demand an ``out_sharding``; so no caller
    builds a mesh with it directly.  ``devices`` pins an explicit device
    list (len == prod(shape)) in row-major order.
    """
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    devs = np.asarray(devices, dtype=object).reshape(tuple(shape))
    return Mesh(devs, tuple(axes), axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_axes(*, multi_pod: bool = False) -> MeshAxes:
    return MeshAxes(pod="pod" if multi_pod else None)


def make_debug_mesh(shape=(2, 2), axes=("data", "model"), devices=None):
    """Small mesh for unit tests (requires enough local devices).

    ``devices`` pins an explicit device list (len == prod(shape)) — the
    building block for carving one host's pool into disjoint replica slices.
    """
    return make_mesh(shape, axes, devices)


def slice_device_pool(shapes, axes=("data", "model"), devices=None, *,
                      allow_remainder: bool = True,
                      return_remainder: bool = False):
    """Partition a device pool into disjoint mesh slices, one per shape.

    The heterogeneous-fleet constructor: ``shapes=[(1, 1), (2, 1), (2, 2)]``
    carves 7 of the pool's devices into three replicas of mixed size (the
    paper's non-uniform PEs).  Slices never share devices; a pool too small
    for the requested shapes raises with the exact shortfall.

    Shapes that don't tile the pool leave devices over; those are no longer
    dropped silently: ``return_remainder=True`` returns ``(meshes,
    remainder)`` so the caller can re-carve the spare devices on a later
    resize event, and ``allow_remainder=False`` raises when any device would
    go unused (the strict fleet-spec contract).
    """
    pool = list(jax.devices()) if devices is None else list(devices)
    need = sum(math.prod(s) for s in shapes)
    if need > len(pool):
        raise ValueError(
            f"device pool oversubscribed: shapes {list(shapes)} need {need} "
            f"devices but the pool has only {len(pool)} ({need - len(pool)} "
            f"short) — drop a slice, shrink a shape, or grow the pool")
    meshes, off = [], 0
    for shape in shapes:
        n = math.prod(shape)
        meshes.append(make_debug_mesh(tuple(shape), axes, pool[off:off + n]))
        off += n
    remainder = pool[off:]
    if remainder and not allow_remainder:
        raise ValueError(
            f"shapes {list(shapes)} use {off} of {len(pool)} devices, "
            f"leaving {len(remainder)} unused — pass allow_remainder=True "
            f"to keep the spares (return_remainder=True hands them back "
            f"for re-carving)")
    if return_remainder:
        return meshes, remainder
    return meshes
