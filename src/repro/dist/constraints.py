"""Activation sharding constraints (mesh-aware, no-op without a mesh).

``constrain(x, *axes)`` tags intermediate activations with the mesh axes
they should live on.  Axis entries are mesh axis names, ``None``
(replicated), or the alias ``"dp"`` which expands to every data-parallel
axis the active mesh has (``("pod", "data")`` on the multi-pod mesh,
``("data",)`` on a single pod).  Entries that name axes absent from the
mesh, or whose axis-size product does not divide the tensor dimension,
are dropped (degrade to replication) instead of failing — this is what
keeps the tags GQA-safe and lets the same model code run on any mesh.

Outside a mesh scope the functions return their inputs untouched (exact
no-ops, not identity-with-copy), so single-device tests see bit-identical
arrays.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax._src.mesh import thread_resources

DATA_AXES = ("pod", "data")     # data-parallel axes, outermost first


def active_mesh():
    """The ambient mesh, or None outside any mesh scope: the mesh of
    ``with jax.set_mesh(m):`` (as the abstract mesh, which is what a
    traced function sees), else that of a ``with m:`` block (which only
    the physical-mesh context reports).  Inside a ``shard_map`` body the
    program is per device, so there is nothing to constrain: None."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        manual = jax.sharding.AxisType.Manual in mesh.axis_types
        return None if manual else mesh
    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def axis_sizes(mesh) -> dict:
    """Mesh axis-name -> size mapping (works for Mesh and AbstractMesh)."""
    return dict(mesh.shape)


def axes_size(mesh, axes) -> int:
    """Product of the named axes' sizes (single name or tuple)."""
    sizes = axis_sizes(mesh)
    names = axes if isinstance(axes, tuple) else (axes,)
    n = 1
    for name in names:
        n *= sizes[name]
    return n


def data_axes(mesh, *, pods: bool = True) -> Tuple[str, ...]:
    names = DATA_AXES if pods else ("data",)
    return tuple(a for a in mesh.axis_names if a in names)


def divisible_data_axes(mesh, dim: int, *, pods: bool = True) -> Tuple[str, ...]:
    """The data axes usable for ``dim``: outermost axes are dropped until
    their size product divides it (the single degradation policy shared
    by activation tags, batch specs, and FSDP)."""
    axes = data_axes(mesh, pods=pods)
    while axes and dim % axes_size(mesh, axes) != 0:
        axes = axes[1:]
    return axes


def _resolve_entry(mesh, dim: int, entry):
    """Resolve one spec entry against the mesh; None if it can't apply."""
    if entry is None:
        return None
    if entry == "dp":
        axes = divisible_data_axes(mesh, dim)
        if not axes:
            return None
        return axes if len(axes) > 1 else axes[0]
    names = entry if isinstance(entry, tuple) else (entry,)
    sizes = axis_sizes(mesh)
    if any(n not in sizes for n in names):
        return None
    if dim % axes_size(mesh, names) != 0:
        return None
    return entry


def resolve_spec(mesh, shape: Sequence[int], axes) -> jax.sharding.PartitionSpec:
    """Build a full-rank PartitionSpec for ``shape`` from the axis tags."""
    entries = []
    for i, dim in enumerate(shape):
        entry = axes[i] if i < len(axes) else None
        entries.append(_resolve_entry(mesh, dim, entry))
    return jax.sharding.PartitionSpec(*entries)


def constrain(x, *axes):
    """Tag ``x`` with mesh axes; exact no-op when no mesh is active."""
    mesh = active_mesh()
    if mesh is None:
        return x
    spec = resolve_spec(mesh, x.shape, axes)
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def constrain_qkv(q, k, v, *, batch_axis: Optional[str] = "dp"):
    """One consistent tensor-parallel scheme across q/k/v projections.

    q: (B, S, H, hd); k/v: (B, S, Hkv, hd).  Heads are sharded on the
    "model" axis; with GQA the kv-head count may not divide the model
    axis, in which case k/v (and only k/v) degrade to replicated heads —
    the flash-attention contraction then broadcasts kv per model shard,
    which is exactly the memory/compute layout a GQA TP scheme wants.
    """
    mesh = active_mesh()
    if mesh is None:
        return q, k, v
    q = constrain(q, batch_axis, None, "model", None)
    k = constrain(k, batch_axis, None, "model", None)
    v = constrain(v, batch_axis, None, "model", None)
    return q, k, v
