"""Named-axis device mesh construction.

The reference expresses multi-worker layout with placement groups +
`TPU-v4-8-head`-style resources (python/ray/_private/accelerators/tpu.py:75)
and leaves intra-model parallelism to whatever the user wraps (SURVEY.md
§2.3: only DP exists natively). Here the mesh IS the first-class object:
every parallelism strategy (dp/fsdp/pp/tp/sp/ep) is a named axis of one
`jax.sharding.Mesh`, XLA inserts the collectives, and ICI/DCN placement
falls out of device order (`mesh_utils.create_device_mesh` optimizes
axis-to-torus assignment on real TPU slices).
"""
from __future__ import annotations

from dataclasses import dataclass, field, fields
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical axis order: data-like axes outermost (cross-slice / DCN friendly),
# model axes innermost (ICI-bandwidth hungry: tp/sp want nearest neighbors).
MESH_AXES: Tuple[str, ...] = ("dp", "fsdp", "pp", "sp", "ep", "tp")


def solve_axis_sizes(vals: Dict[str, int], total: int,
                     unit: str) -> Dict[str, int]:
    """Solve the named-axis size map against `total` items: at most one
    axis may be -1 ("fill with the remainder"), the rest must be
    positive and their product must divide (fill) or equal (no fill)
    `total`. Shared by the ICI solve (MeshConfig.sizes, unit="device")
    and the DCN solve (HybridMeshConfig.dcn_sizes, unit="slice")."""
    vals = dict(vals)
    fill = [k for k, v in vals.items() if v == -1]
    if len(fill) > 1:
        raise ValueError(f"only one axis may be -1, got {fill}")
    fixed = 1
    for k, v in vals.items():
        if v != -1:
            if v <= 0:
                raise ValueError(f"axis {k} must be positive or -1, got {v}")
            fixed *= v
    if fill:
        if total % fixed != 0:
            raise ValueError(
                f"{total} {unit}s not divisible by fixed axes "
                f"product {fixed}")
        vals[fill[0]] = total // fixed
    elif fixed != total:
        raise ValueError(
            f"mesh axes product {fixed} != {unit} count {total}")
    return vals


@dataclass(frozen=True)
class MeshConfig:
    """Sizes for each named axis; -1 on exactly one axis means "fill with
    the remaining devices" (like torch DeviceMesh / GSPMD conventions)."""

    dp: int = -1
    fsdp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1
    tp: int = 1

    def sizes(self, n_devices: int) -> Dict[str, int]:
        # fields(MeshConfig), not fields(self): subclasses (HybridMeshConfig)
        # add DCN axes that must not leak into the ICI size solve.
        vals = {f.name: getattr(self, f.name) for f in fields(MeshConfig)}
        solved = solve_axis_sizes(vals, n_devices, "device")
        return {k: solved[k] for k in MESH_AXES}

    def build(self, devices: Optional[Sequence[Any]] = None) -> Mesh:
        return make_mesh(self, devices)


def make_mesh(config: Optional[MeshConfig] = None,
              devices: Optional[Sequence[Any]] = None,
              **axis_sizes: int) -> Mesh:
    """Build a `jax.sharding.Mesh` with canonical named axes.

    make_mesh(MeshConfig(dp=2, tp=4))  or  make_mesh(dp=2, tp=4).
    On TPU hardware, device order is topology-optimized so the innermost
    axes land on ICI nearest-neighbor rings.
    """
    if config is None:
        config = MeshConfig(**axis_sizes) if axis_sizes else MeshConfig()
    elif axis_sizes:
        raise ValueError("pass either a MeshConfig or axis kwargs, not both")
    if devices is None:
        devices = jax.devices()
    sizes = config.sizes(len(devices))
    shape = tuple(sizes[a] for a in MESH_AXES)
    return Mesh(ici_device_mesh(shape, devices), MESH_AXES)


def ici_device_mesh(shape: Tuple[int, ...],
                    devices: Sequence[Any]) -> np.ndarray:
    """Topology-optimized device array for one ICI domain (a slice, or the
    whole device set when there is only one). Falls back to a plain
    row-major reshape where mesh_utils has no assignment (virtual CPU
    devices, odd shapes) — shared by make_mesh and the multislice
    per-slice builder."""
    try:
        return mesh_utils.create_device_mesh(
            shape, devices=np.asarray(devices, dtype=object).ravel())
    except (ValueError, AssertionError, NotImplementedError):
        return np.asarray(devices, dtype=object).reshape(shape)


def validate_axis_names(mesh: Any, specs: Any, what: str = "spec") -> None:
    """Raise a clear ValueError when a PartitionSpec (or pytree of specs)
    names an axis the mesh does not have — instead of the opaque deep-XLA
    failure a bad name produces otherwise. Works for Mesh and
    AbstractMesh alike (anything with .axis_names)."""
    names = tuple(getattr(mesh, "axis_names", ()) or ())
    if not names:
        return
    known = set(names)
    for spec in jax.tree.leaves(specs,
                                is_leaf=lambda s: isinstance(s, P)):
        if not isinstance(spec, P):
            continue
        for entry in tuple(spec):
            axes = entry if isinstance(entry, (tuple, list)) else (entry,)
            for ax in axes:
                if ax is not None and ax not in known:
                    raise ValueError(
                        f"unknown mesh axis {ax!r} in {what} {spec}: "
                        f"this mesh has axes {names} (canonical "
                        f"MESH_AXES = {MESH_AXES})")


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True):
    """`jax.shard_map` with the spec axis names validated against the
    mesh up front (clear ValueError, not a deep-XLA error)."""
    validate_axis_names(mesh, in_specs, "shard_map in_specs")
    validate_axis_names(mesh, out_specs, "shard_map out_specs")
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    """Shorthand: named_sharding(mesh, 'dp', None) ==
    NamedSharding(mesh, PartitionSpec('dp', None)). Axis names are
    validated against the mesh up front."""
    pspec = P(*spec)
    validate_axis_names(mesh, pspec, "named_sharding spec")
    return NamedSharding(mesh, pspec)


def host_local_array_to_global(mesh: Mesh, spec: P, host_arrays):
    """Assemble per-host shards into a global jax.Array (multi-host path;
    analog of the reference relying on torch DDP to scatter). Single-host:
    jax.device_put with the target sharding."""
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(host_arrays, sharding)
    return jax.make_array_from_process_local_data(sharding, host_arrays)
