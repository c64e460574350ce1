"""Mesh context + logical-axis sharding constraints.

Models annotate arrays with LOGICAL axes ("batch", "model", "expert"); the
translation to the PHYSICAL mesh happens here so the same model code runs on
the production (data, model) / (pod, data, model) meshes, the 1x1 host mesh
of the tests, and with no mesh at all (plain CPU smoke paths, where
:func:`constrain` is an identity).

Logical -> physical:

  batch   -> the product of the DP axes present in the mesh ("pod", "data")
  model   -> "model"   (TP / SP)
  expert  -> "model"   (EP rides the same 16-way axis, mesh.py docstring)

Logical axes without a translation entry fall through to themselves and
shard over a physical axis of that name when the mesh has one.

Axes absent from the mesh are dropped to ``None`` — a smaller mesh silently
replicates instead of erroring, which is what lets the dry-run lower the same
program on single- and multi-pod meshes.  (The ``mesh:<profile>:<n>``
serving engines do not use these constraints: Pallas kernels cannot be
partitioned by the compiler, so ``repro.engine`` shard_maps them.)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_LOGICAL_TO_PHYSICAL = {
    "batch": ("pod", "data"),
    "model": ("model",),
    "expert": ("model",),
}

_state = threading.local()


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Activate ``mesh`` for :func:`current_mesh` / :func:`constrain`."""
    stack = getattr(_state, "stack", None)
    if stack is None:
        stack = _state.stack = []
    stack.append(mesh)
    try:
        yield mesh
    finally:
        stack.pop()


def current_mesh() -> Optional[Mesh]:
    """The innermost active mesh, or None outside any ``use_mesh``."""
    stack = getattr(_state, "stack", None)
    return stack[-1] if stack else None


def physical_spec(logical, mesh: Mesh) -> P:
    """Translate a tuple of logical axes (or None) into a PartitionSpec."""
    names = set(mesh.axis_names)
    entries = []
    for ax in logical:
        if ax is None:
            entries.append(None)
            continue
        phys = [a for a in _LOGICAL_TO_PHYSICAL.get(ax, (ax,)) if a in names]
        if not phys:
            entries.append(None)
        elif len(phys) == 1:
            entries.append(phys[0])
        else:
            entries.append(tuple(phys))
    return P(*entries)


def constrain(x: jax.Array, *logical) -> jax.Array:
    """with_sharding_constraint on logical axes; identity without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = physical_spec(logical, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
