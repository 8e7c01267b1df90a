"""Two-stage hierarchical Gaussian testing (paper §IV-B, Fig. 6).

Stage 1 — sub-tile (8×8) AABB test in the preprocessing core: cheap, culls
~30% of the CTU workload.
Stage 2 — Mini-Tile CAT in the CTU, only on Gaussians that passed Stage 1,
producing fine-grained (mini-tile × Gaussian) masks.

Two dataflows implement the same hierarchy:

* `stream_hierarchical_test` (the pipeline default) — the paper's Fig. 6
  queue dataflow: Stage 1 produces per-tile survivor *streams* (compacted
  depth-ordered `(T, K)` lists) and the CTU tests only entries of those
  streams, emitting per-entry `(T, K, regions_per_tile)` masks. Memory is
  O(T·K·16) and CAT FLOPs are spent on survivors only.
* `hierarchical_test` (the dense parity oracle, `dataflow="dense"`) —
  materializes the full (num_regions, N) boolean masks at every level;
  O(regions × N) memory, kept because it is trivially auditable and every
  stream quantity must match it entry-for-entry.

Both return the workload counters the performance model consumes (CTU
tests, VRU work, duplicate Gaussian instances per level) — the quantities
behind Fig. 4, Fig. 8 and Fig. 9 — and the stream counters are asserted
equal to the dense ones whenever no tile list overflows.

Under `OverflowPolicy.SPILL` the stream CTU runs once per compacted pass
(`stream_entry_test` is pass-agnostic: it tests whatever (T, K) list it is
handed). Per-pass counters in `ADDITIVE_COUNTER_KEYS` are sums over list
entries, so summing them across passes reproduces the dense totals exactly;
the remaining keys are scene-level and identical in every pass.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.gaussians import Projected, classify_spiky
from repro.core.culling import TileGrid, aabb_mask, intersection_mask
from repro.core.cat import (SamplingMode, minitile_cat_mask, entry_cat_mask,
                            leader_pixel_count)
from repro.core.precision import PrecisionScheme, FULL_FP32


class HierarchyOut(NamedTuple):
    tile_mask: jax.Array        # (num_tiles, N) — any mini-tile in tile hit
    minitile_mask: jax.Array    # (num_minitiles, N) — final fine-grained mask
    subtile_mask: jax.Array     # (num_subtiles, N) — stage-1 result
    counters: dict              # python dict of scalar jax counters


def hierarchical_test(proj: Projected, grid: TileGrid,
                      mode: SamplingMode = SamplingMode.SMOOTH_FOCUSED,
                      prec: PrecisionScheme = FULL_FP32,
                      spiky_threshold: float = 3.0,
                      cat_mask=None) -> HierarchyOut:
    """Stage-1 sub-tile AABB -> Stage-2 Mini-Tile CAT.

    cat_mask: optional precomputed (num_minitiles, N) CAT mask (e.g. from the
    Pallas PRTU kernel); computed with the pure-jnp path when None.
    """
    # Stage 1: sub-tile AABB (preprocessing core).
    sub_mask = aabb_mask(proj, grid.subtile_origins(), grid.subtile)  # (S, N)

    # Stage 2: Mini-Tile CAT gated by the containing sub-tile's Stage-1 bit.
    if cat_mask is None:
        cat = minitile_cat_mask(proj, grid, mode, prec, spiky_threshold)
    else:
        cat = cat_mask                                                 # (M, N)
    sub_of_mini = grid.subtile_of_minitile()                           # (M,)
    gate = sub_mask[sub_of_mini]                                       # (M, N)
    mini_mask = cat & gate

    # Tile-level mask = OR over the tile's mini-tiles (drives list compaction).
    tile_of_mini = grid.tile_of_region(grid.minitile)                  # (M,)
    tile_mask = jax.ops.segment_sum(
        mini_mask.astype(jnp.int32), tile_of_mini,
        num_segments=grid.num_tiles) > 0                               # (T, N)

    # ---- workload counters -------------------------------------------------
    n_frustum = jnp.sum(proj.in_frustum)
    # CTU workload: (sub-tile, Gaussian) pairs that reach Stage 2. Each pair
    # tests all mini-tiles of the sub-tile (PRs per Fig. 3b).
    ctu_pairs = jnp.sum(sub_mask)
    # Without Stage 1 the CTU would test every (sub-tile, frustum-Gaussian)
    # pair whose *tile-level AABB* intersects (the paper's no-hierarchy ref).
    tile_aabb = aabb_mask(proj, grid.tile_origins(), grid.tile)
    sub_per_tile = grid.subtiles_per_tile
    ctu_pairs_no_stage1 = jnp.sum(tile_aabb) * sub_per_tile

    spiky = classify_spiky(proj.axis_ratio, spiky_threshold)
    if mode == SamplingMode.UNIFORM_DENSE:
        prs_per_minitile = jnp.full(proj.depth.shape, 1.0)
    elif mode == SamplingMode.UNIFORM_SPARSE:
        prs_per_minitile = jnp.full(proj.depth.shape, 0.5)
    elif mode == SamplingMode.SMOOTH_FOCUSED:
        prs_per_minitile = jnp.where(spiky, 0.5, 1.0)
    else:  # SPIKY_FOCUSED
        prs_per_minitile = jnp.where(spiky, 1.0, 0.5)
    mpsub = grid.minitiles_per_subtile
    ctu_prs = jnp.sum(sub_mask * prs_per_minitile[None, :]) * mpsub

    counters = dict(
        n_gaussians=jnp.asarray(proj.depth.shape[0], jnp.float32),
        n_frustum=n_frustum.astype(jnp.float32),
        ctu_pairs=ctu_pairs.astype(jnp.float32),
        ctu_pairs_no_stage1=ctu_pairs_no_stage1.astype(jnp.float32),
        ctu_prs=ctu_prs.astype(jnp.float32),
        leader_tests_per_pair=leader_pixel_count(proj, grid, mode,
                                                 spiky_threshold),
        dup_tile=jnp.sum(tile_aabb).astype(jnp.float32),
        dup_subtile=jnp.sum(sub_mask).astype(jnp.float32),
        dup_minitile=jnp.sum(mini_mask).astype(jnp.float32),
        # VRU workload: (mini-tile, Gaussian) pairs forwarded to FIFOs; each
        # drives 16 pixel-blend ops.
        vru_pairs=jnp.sum(mini_mask).astype(jnp.float32),
        vru_pairs_tile_aabb=(jnp.sum(tile_aabb)
                             * grid.minitiles_per_tile).astype(jnp.float32),
    )
    return HierarchyOut(tile_mask=tile_mask, minitile_mask=mini_mask,
                        subtile_mask=sub_mask, counters=counters)


# ---------------------------------------------------------------------------
# Survivor-stream dataflow (paper Fig. 6: the CTU tests only queued entries)
# ---------------------------------------------------------------------------


# Counter keys that are sums over stream list entries: additive across
# spill passes (pass entries are disjoint), and equal to the dense-mask
# totals once every survivor is listed. Everything else the hierarchy
# reports (n_gaussians, n_frustum, leader_tests_per_pair) is scene-level —
# identical per pass, merged by taking any one pass's value.
ADDITIVE_COUNTER_KEYS = frozenset({
    "ctu_pairs", "ctu_pairs_no_stage1", "ctu_prs",
    "dup_tile", "dup_subtile", "dup_minitile",
    "vru_pairs", "vru_pairs_tile_aabb",
})


class StreamHierarchyOut(NamedTuple):
    lists: jax.Array            # (T, K) int32 depth-ordered Gaussian ids
    valid: jax.Array            # (T, K) bool — slot occupied
    entry_sub_mask: jax.Array   # (T, K, subtiles_per_tile) — Stage-1 result
    #                             per entry (which of the tile's sub-tiles
    #                             the entry's AABB hits)
    entry_mini_mask: jax.Array  # (T, K, minitiles_per_tile) — final CAT mask
    #                             per entry, Stage-1 gated
    overflow: jax.Array         # () bool: some tile exceeded k_max
    counters: dict              # same keys/values as HierarchyOut.counters


def entry_subtile_mask(proj: Projected, grid: TileGrid,
                       lists: jax.Array, valid: jax.Array,
                       tile_origins: Optional[jax.Array] = None) -> jax.Array:
    """(T, K, subtiles_per_tile) bool: Stage-1 sub-tile AABB evaluated only
    on compacted entries. Equals the dense `aabb_mask` over sub-tiles
    gathered at (tile's sub-tiles, lists[t, k]) for every valid entry.

    tile_origins: optional (T, 2) int origins of the tiles the rows of
    `lists` belong to — defaults to the full grid. Passing a row subset
    (with matching `lists`/`valid` rows) evaluates only those tiles, which
    is how the tile-sharded and shard-recovery paths run this per block.
    """
    t_origins = (grid.tile_origins() if tile_origins is None
                 else tile_origins)                      # (T, 2) int
    local = grid.subtile_local_origins()                 # (Sp, 2) int
    x0 = (t_origins[:, 0:1] + local[None, :, 0])[:, None, :]   # (T, 1, Sp)
    y0 = (t_origins[:, 1:2] + local[None, :, 1])[:, None, :]
    x1 = x0 + grid.subtile
    y1 = y0 + grid.subtile

    idx = lists.clip(0)
    mx = proj.mean2d[idx][..., 0][:, :, None]            # (T, K, 1)
    my = proj.mean2d[idx][..., 1][:, :, None]
    r = proj.radius[idx][:, :, None]
    hit = ((mx + r) > x0) & ((mx - r) < x1) \
        & ((my + r) > y0) & ((my - r) < y1)
    live = (valid & proj.in_frustum[idx])[:, :, None]
    return hit & live


def stream_hierarchical_test(
        proj: Projected, grid: TileGrid,
        mode: SamplingMode = SamplingMode.SMOOTH_FOCUSED,
        prec: PrecisionScheme = FULL_FP32,
        spiky_threshold: float = 3.0, *, k_max: int,
        order: Optional[jax.Array] = None,
        cat_fn: Optional[Callable] = None) -> StreamHierarchyOut:
    """Stage-1 AABB -> compact survivor streams -> entry-indexed CAT.

    The stream-first realization of `hierarchical_test`: per-tile
    depth-ordered lists are built from the Stage-1 tile-level AABB (the
    union of a tile's sub-tile AABBs *is* its tile AABB, since the sub-tiles
    partition the tile), then Stage-1 sub-tile bits and the Mini-Tile CAT
    are evaluated per list entry (`stream_entry_test`, which the staged
    `renderer.RenderPlan` also calls directly as its CTU stage). Nothing of
    shape (num_subtiles, N) or (num_minitiles, N) is ever materialized.

    order: optional precomputed `raster.depth_order(proj)`.
    cat_fn: optional callable (proj, grid, lists, valid) -> (T, K, Mt) bool
    entry CAT mask (e.g. the Pallas entry-PRTU kernel); defaults to the
    pure-jnp `cat.entry_cat_mask`.
    """
    from repro.core import raster  # late import: raster is mask-agnostic

    if order is None:
        order = raster.depth_order(proj)
    # Stage-1 AABB fused into the chunked compaction: the transient (T, N)
    # mask only ever materializes one tile block at a time.
    lists, valid, overflow = raster.compact_aabb_tile_lists(proj, grid,
                                                            order, k_max)
    return stream_entry_test(proj, grid, lists[0], valid[0], overflow, mode,
                             prec, spiky_threshold, cat_fn=cat_fn)


def stream_entry_counters(proj: Projected, grid: TileGrid,
                          lists: jax.Array, valid: jax.Array,
                          sub_hits: jax.Array, mini_hits: jax.Array,
                          mode: SamplingMode = SamplingMode.SMOOTH_FOCUSED,
                          spiky_threshold: float = 3.0) -> dict:
    """The stream CTU's workload counters from per-entry hit counts.

    sub_hits/mini_hits: (T, K) int — per list entry, the number of sub-tile
    (Stage-1) and mini-tile (CAT) hits. `stream_entry_test` computes them by
    reducing the full per-entry masks; the tile-sharded render path computes
    them per shard and gathers the int rows (exactly), then calls this with
    the full arrays — so both paths evaluate the very same expressions on
    the very same values and the counters stay bit-identical.
    """
    idx = lists.clip(0)
    n_frustum = jnp.sum(proj.in_frustum)
    n_listed = jnp.sum(valid)
    ctu_pairs = jnp.sum(sub_hits)

    # PRs per hit mini-tile, doubled to stay integer: a float32 sum past
    # 2^24 rounds in whatever order the compiler reduces, which differs
    # between the tile-sharded and the single-device program.
    spiky = classify_spiky(proj.axis_ratio, spiky_threshold)
    if mode == SamplingMode.UNIFORM_DENSE:
        prs2_per_minitile = jnp.full(proj.depth.shape, 2)
    elif mode == SamplingMode.UNIFORM_SPARSE:
        prs2_per_minitile = jnp.full(proj.depth.shape, 1)
    elif mode == SamplingMode.SMOOTH_FOCUSED:
        prs2_per_minitile = jnp.where(spiky, 1, 2)
    else:  # SPIKY_FOCUSED
        prs2_per_minitile = jnp.where(spiky, 2, 1)
    mpsub = grid.minitiles_per_subtile
    ctu_prs2 = jnp.sum(sub_hits * prs2_per_minitile[idx]) * mpsub

    return dict(
        n_gaussians=jnp.asarray(proj.depth.shape[0], jnp.float32),
        n_frustum=n_frustum.astype(jnp.float32),
        ctu_pairs=ctu_pairs.astype(jnp.float32),
        # Without Stage 1 the CTU tests every sub-tile of every stream entry.
        ctu_pairs_no_stage1=(n_listed
                             * grid.subtiles_per_tile).astype(jnp.float32),
        ctu_prs=ctu_prs2.astype(jnp.float32) / 2,
        leader_tests_per_pair=leader_pixel_count(proj, grid, mode,
                                                 spiky_threshold),
        dup_tile=n_listed.astype(jnp.float32),
        dup_subtile=ctu_pairs.astype(jnp.float32),
        dup_minitile=jnp.sum(mini_hits).astype(jnp.float32),
        vru_pairs=jnp.sum(mini_hits).astype(jnp.float32),
        vru_pairs_tile_aabb=(n_listed
                             * grid.minitiles_per_tile).astype(jnp.float32),
    )


def stream_entry_test(
        proj: Projected, grid: TileGrid,
        lists: jax.Array, valid: jax.Array, overflow: jax.Array,
        mode: SamplingMode = SamplingMode.SMOOTH_FOCUSED,
        prec: PrecisionScheme = FULL_FP32,
        spiky_threshold: float = 3.0, *,
        cat_fn: Optional[Callable] = None) -> StreamHierarchyOut:
    """The CTU stage proper: per-entry hierarchy masks on a compacted stream.

    Takes the already-built survivor streams (from `raster.compact_tile_lists`
    over the Stage-1 tile mask) and evaluates Stage-1 sub-tile bits and the
    Mini-Tile CAT per list entry.

    Counters carry the same keys and — absent overflow — the same values as
    the dense path: every dense mask sum is re-expressed as a sum over
    stream entries (a dense sub-tile/mini-tile hit implies a tile-level AABB
    hit, so each hit pair owns exactly one list entry).
    """
    entry_sub = entry_subtile_mask(proj, grid, lists, valid)  # (T, K, Sp)
    if cat_fn is None:
        cat = entry_cat_mask(proj, grid, lists, valid, mode, prec,
                             spiky_threshold)
    else:
        cat = cat_fn(proj, grid, lists, valid)                # (T, K, Mt)
    sub_of_mini = grid.subtile_of_minitile_local()            # (Mt,)
    gate = entry_sub[:, :, sub_of_mini]                       # (T, K, Mt)
    entry_mini = cat & gate & valid[:, :, None]

    # ---- workload counters (stream-derived, dense-equal) -------------------
    sub_hits = jnp.sum(entry_sub, axis=-1)                    # (T, K) int
    mini_hits = jnp.sum(entry_mini, axis=-1)                  # (T, K) int
    counters = stream_entry_counters(proj, grid, lists, valid, sub_hits,
                                     mini_hits, mode, spiky_threshold)
    return StreamHierarchyOut(lists=lists, valid=valid,
                              entry_sub_mask=entry_sub,
                              entry_mini_mask=entry_mini,
                              overflow=overflow, counters=counters)


def baseline_masks(proj: Projected, grid: TileGrid, method: str):
    """Masks for the non-CAT baselines.

    method 'aabb'  — vanilla 3DGS: tile-level AABB, every pixel blends the
                     whole tile list.
    method 'obb'   — GSCore: sub-tile level OBB; pixels blend their sub-tile's
                     list (emulated as a mini-tile mask constant per sub-tile).
    Returns (tile_mask (T,N), minitile_mask or None, counters dict).
    """
    if method == "aabb":
        tile_mask = intersection_mask(proj, grid, "aabb", "tile")
        counters = dict(
            dup_tile=jnp.sum(tile_mask).astype(jnp.float32),
            vru_pairs=(jnp.sum(tile_mask)
                       * grid.minitiles_per_tile).astype(jnp.float32),
        )
        return tile_mask, None, counters
    if method == "obb":
        sub = intersection_mask(proj, grid, "obb", "subtile")   # (S, N)
        sub_of_mini = grid.subtile_of_minitile()
        mini = sub[sub_of_mini]                                  # (M, N)
        tile_of_mini = grid.tile_of_region(grid.minitile)
        tile_mask = jax.ops.segment_sum(
            mini.astype(jnp.int32), tile_of_mini,
            num_segments=grid.num_tiles) > 0
        counters = dict(
            dup_subtile=jnp.sum(sub).astype(jnp.float32),
            vru_pairs=jnp.sum(mini).astype(jnp.float32),
        )
        return tile_mask, mini, counters
    raise ValueError(method)
