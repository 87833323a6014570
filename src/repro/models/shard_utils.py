"""Mesh-aware optional sharding constraints usable from model code.

Model functions run in three contexts: unsharded smoke tests (no mesh), GSPMD
jit under a mesh, and shard_map bodies.  ``maybe_constrain`` applies a
PartitionSpec constraint only when a mesh context exists and every named axis
divides the corresponding dim — otherwise it is the identity, so model code
can annotate its preferred layouts unconditionally.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, PartitionSpec as P


def current_mesh():
    from jax._src import mesh as mesh_lib
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        mesh = mesh_lib.thread_resources.env.physical_mesh  # `with mesh:` form
    if mesh is None or mesh.empty:
        return None
    return mesh


def maybe_constrain(x, spec: P):
    mesh = current_mesh()
    if mesh is None or not mesh.shape_tuple:
        return x
    sizes = dict(mesh.shape_tuple)
    # inside shard_map, manual axes cannot appear in sharding constraints
    auto = {name for name, kind in zip(mesh.axis_names, mesh.axis_types)
            if kind == AxisType.Auto}
    fixed = []
    for dim, ax in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        names = (ax,) if isinstance(ax, str) else tuple(ax or ())
        names = tuple(n for n in names if n in auto)
        ax = names[0] if len(names) == 1 else (names or None)
        tot = 1
        for n in names:
            tot *= sizes.get(n, 1)
        fixed.append(ax if names and all(n in sizes for n in names)
                     and dim % tot == 0 and tot > 1 else None)
    if all(f is None for f in fixed):
        return x
    return jax.lax.with_sharding_constraint(x, P(*fixed))
