"""Logical-axis -> mesh-axis sharding rules with divisibility fallbacks.

Every parameter carries a tuple of logical dim names (built by the model
inits). The rules engine walks an ordered candidate list and assigns each
mesh axis to at most one tensor dim, skipping non-divisible dims — that is
what absorbs the awkward arch geometries (mixtral E=8 on a 16-way model axis
falls through to d_ff TP; hymba's 25 heads fall through to row-parallel
embed; whisper's 20 heads likewise).

ZeRO: optimizer-state leaves additionally shard their largest still-
replicated dim over the data axes (pod×data on the multi-pod mesh).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "TP_RULES",
    "auto_axes",
    "dp_axes",
    "spec_for",
    "sharding_for",
    "with_zero",
    "wire_spec",
    "mesh_axis_sizes",
]

# Ordered tensor-parallel candidates: (logical axis, mesh axis).
TP_RULES: Tuple[Tuple[str, str], ...] = (
    ("experts", "model"),
    ("heads", "model"),
    ("kv_heads", "model"),
    ("mlp", "model"),
    ("vocab", "model"),
    ("state", "model"),
    ("embed", "model"),  # last resort: row-parallel (contracting-dim shard)
)

# Logical axes that must never be sharded (scan/layer dims, tiny dims).
NEVER_SHARD = ("layers", "head_dim", "gates")


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` builds ``Explicit`` axes, and
    ``with_sharding_constraint`` refuses specs over them.  The program
    shards by constraint (GSPMD propagation), so each place that turns a
    caller's mesh into a constraint goes through here: same devices, same
    axis names, so the constrained layout is the one the caller named.
    """
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names, axis_types=(AxisType.Auto,) * mesh.devices.ndim)


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Data-parallel mesh axes, outermost first (('pod','data') multi-pod)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh: Mesh) -> int:
    sizes = mesh_axis_sizes(mesh)
    n = 1
    for a in dp_axes(mesh):
        n *= sizes[a]
    return n


def spec_for(
    shape: Tuple[int, ...],
    axes: Tuple[str, ...],
    mesh: Mesh,
    rules: Sequence[Tuple[str, str]] = TP_RULES,
) -> P:
    """Tensor-parallel PartitionSpec for a parameter."""
    assert len(shape) == len(axes), (shape, axes)
    sizes = mesh_axis_sizes(mesh)
    assignment: Dict[int, str] = {}
    used_mesh = set()
    for logical, mesh_axis in rules:
        if mesh_axis in used_mesh or mesh_axis not in sizes:
            continue
        for dim, name in enumerate(axes):
            if name != logical or dim in assignment or name in NEVER_SHARD:
                continue
            if shape[dim] % sizes[mesh_axis] == 0:
                assignment[dim] = mesh_axis
                used_mesh.add(mesh_axis)
                break
    return P(*(assignment.get(d) for d in range(len(shape))))


def with_zero(shape: Tuple[int, ...], spec: P, mesh: Mesh, axes=None) -> P:
    """Add the data axes over the largest still-unsharded divisible dim
    (ZeRO state sharding). Dims named in NEVER_SHARD (e.g. the scan 'layers'
    dim) are skipped when ``axes`` is given."""
    dps = dp_axes(mesh)
    if not dps:
        return spec
    n_dp = dp_size(mesh)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    order = sorted(range(len(shape)), key=lambda d: -shape[d])
    for d in order:
        if axes is not None and d < len(axes) and axes[d] in NEVER_SHARD:
            continue
        if entries[d] is None and shape[d] % n_dp == 0 and shape[d] > 0:
            entries[d] = dps if len(dps) > 1 else dps[0]
            return P(*entries)
    return P(*entries)


def wire_spec(shape: Tuple[int, ...], axes: Tuple[str, ...], mesh: Mesh) -> P:
    """ZeRO wire layout for a gradient-shaped tensor moving through the
    collective.  Also used by ``repro.comms`` for quantized transport: packed
    int4 codes keep the parameter's ndim (nibble packing halves only the last
    dim), so the same logical axes resolve their layout — the divisibility
    fallbacks in ``spec_for``/``with_zero`` absorb the halved dim exactly the
    way they absorb awkward arch geometries."""
    return with_zero(shape, spec_for(shape, axes, mesh), mesh, axes=axes)


def sharding_for(
    shape: Tuple[int, ...],
    axes: Tuple[str, ...],
    mesh: Mesh,
    zero: bool = False,
) -> NamedSharding:
    spec = spec_for(shape, axes, mesh)
    if zero:
        spec = with_zero(shape, spec, mesh)
    return NamedSharding(auto_axes(mesh), spec)
