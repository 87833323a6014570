"""Mesh construction: the one place this repo builds a ``jax.sharding.Mesh``.

All mesh construction is behind functions (never module-level) so importing
this module never touches jax device state — the dry-run must set XLA_FLAGS
*before* any jax initialization.

Every mesh gets ``AxisType.Auto`` axes: the runtime is written for GSPMD
propagation plus ``shard_map`` over ``model``, not for explicit-sharding
types (``jax.make_mesh``'s default), under which slicing a ``model``-sharded
pool to a length the axis does not divide is a type error.

Axis semantics:
  pod    outer data-parallel axis across pods (DCN); hierarchical all-reduce
  data   data parallel + FSDP weight sharding inside a pod (ICI)
  model  tensor/expert/sequence parallel — and the RoundPipe worker-pool axis
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType

_HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count"


def request_host_devices(n: int) -> None:
    """Ask the CPU backend for ``n`` virtual devices by appending to the
    user's ``XLA_FLAGS`` (never overwriting it; a count already given there
    wins).  Takes effect only before jax initializes its backends, and
    changes nothing on an accelerator."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _HOST_DEVICES_FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_HOST_DEVICES_FLAG}={n}".strip()


def make_mesh(shape, axes, *, devices=None):
    """Mesh of ``shape`` over ``axes`` with Auto axis types.  ``devices``
    (default: all of this process's devices) picks a subset, e.g. the
    survivors after an elastic re-plan."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """The batch-sharding axes present in this mesh (pod first)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh, name) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]
