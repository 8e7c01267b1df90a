"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the pod axis is pure
data parallelism (gradient all-reduce crosses the slow inter-pod links
exactly once per step; params/optimizer FSDP stays intra-pod).

Defined as functions so importing this module never touches jax device
state (dryrun must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

from repro.distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names (smoke tests)."""
    return make_mesh((1, 1), ("data", "model"))
