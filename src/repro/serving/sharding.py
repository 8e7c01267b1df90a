"""Frame-axis device sharding for serving batches.

A render batch is a batched `Camera` pytree with a leading frame axis; the
engine shards that axis over the mesh's data axes (`"pod"` + `"data"`, per
`distributed.sharding.dp_axes`) and replicates the scene, so one
`render_batch` call fans frames out across every local device. On the 1-chip
local mesh this is an explicit (trivial) placement; on a real slice the same
code splits the batch.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import dp_axes, make_mesh, resolve


def data_parallel_size(mesh: Mesh) -> int:
    """Number of ways the frame axis splits on `mesh`."""
    return math.prod(mesh.shape[a] for a in dp_axes(mesh))


def tile_parallel_size(mesh: Mesh) -> int:
    """Number of ways the tile axis splits on `mesh` (the `model` axis)."""
    return mesh.shape.get("model", 1)


def frame_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """NamedSharding splitting axis 0 over the data axes, rest replicated."""
    return NamedSharding(mesh, P(dp_axes(mesh), *([None] * (ndim - 1))))


def tile_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """NamedSharding splitting axis 0 (tiles) over the `tile` logical axis."""
    spec = resolve(("tile",) + (None,) * (ndim - 1), mesh)
    return NamedSharding(mesh, spec)


def tile_mesh(tile_shards: int, frame_shards: int = 1) -> Mesh:
    """A (data=frame_shards, model=tile_shards) mesh over local devices.

    Picks a subset of devices when fewer than all are needed; raises if the
    host doesn't expose enough (force more with
    XLA_FLAGS=--xla_force_host_platform_device_count=N).
    """
    need = tile_shards * frame_shards
    avail = jax.device_count()
    if need > avail:
        raise ValueError(
            f"tile_mesh needs {need} devices "
            f"({frame_shards} frame x {tile_shards} tile) but only {avail} "
            "are visible; set XLA_FLAGS="
            "--xla_force_host_platform_device_count=N")
    return make_mesh((frame_shards, tile_shards), ("data", "model"))


def shard_frames(batch, mesh: Mesh):
    """Place every array leaf of a frame-batched pytree with its leading axis
    sharded over the mesh's data axes.

    A frame axis that doesn't divide the data-parallel size is padded up to
    the next multiple (repeating the last frame) and then sharded — callers
    already slice results back to the true frame count, and the engine's
    power-of-two buckets make padding the exception, not the rule. The old
    behaviour of silently *replicating* such a batch hid the fact that no
    frame parallelism happened at all.
    """
    n_dp = data_parallel_size(mesh)

    def place(x):
        if x.ndim == 0:
            return replicate(x, mesh)
        pad = (-x.shape[0]) % n_dp
        if pad:
            x = jnp.concatenate(
                [x, jnp.repeat(x[-1:], pad, axis=0)], axis=0)
        return jax.device_put(x, frame_sharding(mesh, x.ndim))

    return jax.tree.map(place, batch)


def replicate(tree, mesh: Mesh):
    """Replicate a pytree (e.g. the scene) across the whole mesh."""
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), tree)
