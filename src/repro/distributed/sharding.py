"""Sharding rules: logical axis names -> mesh axes.

Logical names used throughout the model zoo:
    "fsdp"   — parameter shards (ZeRO-3 style) over the intra-pod data axis;
               gathered at use, grads reduce-scattered. NOT sharded over the
               pod axis: cross-pod links are the slow tier, so pods keep full
               FSDP replicas and all-reduce grads across pods only.
    "model"  — tensor/expert parallel axis.
    "dp"     — batch: all data axes, including the pod axis.
    "sp"     — sequence-parallel shards of saved activations (model axis).
    "tile"   — per-tile render work (serving). Resolves to the `model` mesh
               axis so frame x tile sharding composes on one mesh: frames
               split over "data", each frame's tiles over "model".
    None     — replicated.
"""
from __future__ import annotations

import contextlib

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape, axis_names) -> Mesh:
    """`jax.make_mesh` with every axis `Auto`-typed: shardings propagate
    through the compiler as they did before explicit axis types became the
    default, so unannotated scatters such as `x.at[...].set` stay legal."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def dp_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def resolve(logical, mesh: Mesh, fsdp_over_pod: bool = False) -> P:
    """Map a tuple of logical axis names to a PartitionSpec for `mesh`.

    fsdp_over_pod: ZeRO-3 across pods too (param/grad/moment shards span the
    pod axis). Default keeps FSDP intra-pod (pods hold replicas; only the
    gradient all-reduce crosses the slow inter-pod links) — the half-TB
    arctic config flips this on to fit v5e HBM."""
    out = []
    for name in logical:
        if name is None:
            out.append(None)
        elif name == "fsdp":
            if fsdp_over_pod and "pod" in mesh.axis_names:
                out.append(("pod", "data"))
            else:
                out.append("data")
        elif name == "model" or name == "sp" or name == "tile":
            out.append("model")
        elif name == "dp":
            out.append(dp_axes(mesh))
        else:
            raise ValueError(f"unknown logical axis {name!r}")
    return P(*out)


def named(mesh: Mesh, logical, fsdp_over_pod: bool = False) -> NamedSharding:
    return NamedSharding(mesh, resolve(logical, mesh, fsdp_over_pod))


def constrain(x, mesh: Mesh, *logical):
    """with_sharding_constraint using logical names (no-op without mesh)."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, named(mesh, logical))


# --- active mesh -----------------------------------------------------------
#
# Renderer internals (core/renderer.py) are mesh-agnostic: a tile-sharded
# RenderPlan discovers the mesh at trace time through this stack instead of
# carrying a (unhashable) Mesh in the plan. The serving engine pushes its
# mesh around every jitted call; tests and benchmarks use `use_mesh(...)`
# directly.

_ACTIVE_MESHES: list[Mesh] = []


def active_mesh() -> Mesh | None:
    """The innermost mesh pushed by `use_mesh`, or None."""
    return _ACTIVE_MESHES[-1] if _ACTIVE_MESHES else None


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    """Make `mesh` discoverable via `active_mesh()` for the duration.

    A None mesh is a no-op context so callers can write
    `with use_mesh(self.mesh):` unconditionally.
    """
    if mesh is None:
        yield
        return
    _ACTIVE_MESHES.append(mesh)
    try:
        yield
    finally:
        _ACTIVE_MESHES.pop()
