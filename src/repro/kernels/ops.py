"""Jit'd wrappers routing the render pipeline through the Pallas kernels.

This is what the staged `core.renderer.RenderPlan` dispatches to for its
"pallas" backends: `TestConfig(backend="pallas")` routes the CTU stage
through the PRTU kernels (`entry_cat_mask_pallas` on the stream dataflow,
`cat_mask_pallas`/`hierarchical_test_pallas` on the dense oracle), and
`RasterConfig(fused=True)` routes the blend stage through
`render_tiles_fused`.

Two blend routes exist on top of the shared operand gather
(`gather_tile_features`): `blend_tiles_pallas` is the full-sweep kernel and
`render_tiles_fused` is the contribution-aware kernel with true in-kernel
early termination; the latter also converts the kernel's measured work
counters into the pipeline's `RenderOut` + counters-dict convention.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.core.gaussians import Projected, classify_spiky
from repro.core.culling import TileGrid
from repro.core.cat import SamplingMode
from repro.core.precision import PrecisionScheme
from repro.core import hierarchy as H
from repro.core import raster
from repro.kernels import prtu, render as krender
from repro.kernels import ref as kref


def cat_mask_pallas(proj: Projected, grid: TileGrid, mode: SamplingMode,
                    prec: PrecisionScheme, spiky_threshold: float = 3.0
                    ) -> jax.Array:
    """(num_minitiles, N) bool CAT mask via the PRTU kernel."""
    origins = grid.minitile_origins().astype(jnp.float32)
    m = float(grid.minitile - 1)
    p_top = origins + jnp.asarray([0.5, 0.5])
    p_bot = origins + jnp.asarray([m + 0.5, m + 0.5])
    lhs = jnp.log(255.0 * jnp.maximum(proj.opacity, 1e-12))
    lhs = jnp.where(proj.in_frustum, lhs, -jnp.inf)   # culled never pass
    spiky = classify_spiky(proj.axis_ratio, spiky_threshold)
    mask = prtu.prtu_cat_mask(
        p_top, p_bot, proj.mean2d, proj.conic, lhs, spiky,
        mode=mode.value, coord_prec=prec.coord, delta_prec=prec.delta,
        mul_prec=prec.mul, acc_prec=prec.acc, slack=prec.slack)
    return mask != 0


def hierarchical_test_pallas(proj: Projected, grid: TileGrid,
                             mode: SamplingMode, prec: PrecisionScheme,
                             spiky_threshold: float = 3.0) -> H.HierarchyOut:
    cat = cat_mask_pallas(proj, grid, mode, prec, spiky_threshold)
    return H.hierarchical_test(proj, grid, mode, prec, spiky_threshold,
                               cat_mask=cat)


def entry_cat_mask_pallas(proj: Projected, grid: TileGrid, lists, valid,
                          mode: SamplingMode, prec: PrecisionScheme,
                          spiky_threshold: float = 3.0,
                          tile_origins=None) -> jax.Array:
    """(T, K, Mt) bool entry CAT mask via the entry-stream PRTU kernel.

    Drop-in for `core.cat.entry_cat_mask`: per-entry features are gathered
    at the compacted lists (invalid/padded entries get lhs = -inf so the
    kernel rejects them), and the kernel grid runs over entries only —
    the Pallas realization of the paper's queue-fed CTU.

    tile_origins: optional (T, 2) int origins of the tiles the rows of
    `lists` belong to (defaults to the full grid) — the kernel already
    takes origins as an explicit operand, so a row subset shards trivially.
    """
    local = grid.minitile_local_origins().astype(jnp.float32)  # (Mt, 2)
    m = float(grid.minitile - 1)
    p_top_l = local + jnp.asarray([0.5, 0.5])
    p_bot_l = local + jnp.asarray([m + 0.5, m + 0.5])
    if tile_origins is None:
        tile_origins = grid.tile_origins()

    # Gather each feature from its own (N,) vector: every (T, K) result is
    # lane-dense, where gathering (N, 2)/(N, 3) rows would build (T, K, 2)
    # arrays that a TPU pads 64-fold.
    idx = lists.clip(0)
    lhs = jnp.log(255.0 * jnp.maximum(proj.opacity, 1e-12))[idx]
    lhs = jnp.where(valid & proj.in_frustum[idx], lhs, -jnp.inf)
    spiky = classify_spiky(proj.axis_ratio, spiky_threshold)[idx]
    feat = prtu.feature_rows(
        proj.mean2d[:, 0][idx], proj.mean2d[:, 1][idx], proj.conic[:, 0][idx],
        proj.conic[:, 1][idx], proj.conic[:, 2][idx], lhs, spiky)
    mask = prtu.prtu_entry_cat_mask(
        p_top_l, p_bot_l, tile_origins, feat,
        mode=mode.value, coord_prec=prec.coord, delta_prec=prec.delta,
        mul_prec=prec.mul, acc_prec=prec.acc, slack=prec.slack)
    return mask != 0


def entry_cat_fn(mode: SamplingMode, prec: PrecisionScheme,
                 spiky_threshold: float = 3.0):
    """The `cat_fn` closure that routes an entry CAT evaluation through the
    Pallas entry-PRTU kernel — the single place the kernel routing lives.
    `core.renderer.RenderPlan.ctu` passes this to
    `hierarchy.stream_entry_test` when `TestConfig.backend == "pallas"`;
    the tile-sharded path calls it with per-shard rows + `tile_origins`."""
    return lambda p, g, ls, v, tile_origins=None: entry_cat_mask_pallas(
        p, g, ls, v, mode, prec, spiky_threshold, tile_origins=tile_origins)


def stream_hierarchical_test_pallas(proj: Projected, grid: TileGrid,
                                    mode: SamplingMode,
                                    prec: PrecisionScheme,
                                    spiky_threshold: float = 3.0, *,
                                    k_max: int, order=None) \
        -> H.StreamHierarchyOut:
    """`core.hierarchy.stream_hierarchical_test` with the entry CAT routed
    through the Pallas entry-PRTU kernel."""
    return H.stream_hierarchical_test(
        proj, grid, mode, prec, spiky_threshold, k_max=k_max, order=order,
        cat_fn=entry_cat_fn(mode, prec, spiky_threshold))


def gather_tile_features(proj: Projected, grid: TileGrid, lists, valid,
                         entry_mask=None, tile_origins=None):
    """Build the kernel operand blocks from compacted per-tile lists.

    entry_mask: optional (T, K, Mt) per-entry CAT mask
    (`StreamHierarchyOut.entry_mini_mask`; dense masks convert via
    `raster.entry_mask_from_dense`). tile_origins: optional (T, 2) int
    origins of the tiles the rows of `lists` belong to (defaults to the
    full grid; row subsets feed the tile-sharded/recovery blends). Returns
    (pix (T,P,2), feat (T,K,8), colors (T,K,3), valid_i8 (T,K),
    allow (T,K,Mt))."""
    t_origins = (grid.tile_origins() if tile_origins is None
                 else tile_origins).astype(jnp.float32)   # (T, 2)
    poffs = raster._pixel_offsets(grid.tile)              # (P, 2)
    pix = t_origins[:, None, :] + poffs[None, :, :]       # (T, P, 2)

    # One (N,) gather per feature (see `entry_cat_mask_pallas`).
    idx = lists.clip(0)
    feat = jnp.stack(
        [proj.mean2d[:, 0][idx], proj.mean2d[:, 1][idx],
         proj.conic[:, 0][idx], proj.conic[:, 1][idx], proj.conic[:, 2][idx],
         proj.opacity[idx], jnp.zeros(lists.shape, jnp.float32),
         jnp.zeros(lists.shape, jnp.float32)], axis=-1)   # (T, K, 8)
    colors = jnp.stack([proj.color[:, c][idx] for c in range(3)], axis=-1)

    if entry_mask is None:
        allow = jnp.ones(lists.shape + (grid.minitiles_per_tile,), jnp.int8)
    else:
        allow = entry_mask.astype(jnp.int8)
    valid_i8 = valid.astype(jnp.int8)
    return pix, feat, colors, valid_i8, allow


def blend_tiles_pallas(proj, grid, lists, valid, entry_mask=None):
    ops = gather_tile_features(proj, grid, lists, valid, entry_mask)
    return krender.blend_tiles(*ops)


def blend_tiles_reference(proj, grid, lists, valid, entry_mask=None):
    ops = gather_tile_features(proj, grid, lists, valid, entry_mask)
    return kref.blend_tiles_ref(*ops)


def blend_tiles_fused_pallas(proj, grid, lists, valid, entry_mask=None,
                             init=None, tile_origins=None
                             ) -> krender.FusedBlendOut:
    ops = gather_tile_features(proj, grid, lists, valid, entry_mask,
                               tile_origins=tile_origins)
    return krender.blend_tiles_fused(*ops, init=init)


def render_tiles_fused(proj, grid, lists, valid, entry_mask=None,
                       background: float = 0.0,
                       overflow: jax.Array | bool = False):
    """Fused-kernel drop-in for `core.raster.render_tiles` (single pass).

    See `render_tiles_fused_passes` for the counters contract and the
    multi-pass (SPILL) form this wraps.
    """
    return render_tiles_fused_passes(proj, grid,
                                     [(lists, valid, entry_mask)],
                                     background, overflow)


def render_tiles_fused_passes(proj, grid, passes,
                              background: float = 0.0,
                              overflow: jax.Array | bool = False, *,
                              span_cb=None):
    """Fused-kernel blend over one or more compacted spill passes.

    passes: sequence of (lists (T, K), valid, entry_mask) — consecutive
    segments of each tile's depth-ordered survivor list
    (`OverflowPolicy.SPILL`). The kernel's VMEM carry (transmittance, RGB,
    work counters) is threaded between the calls via the `init` operand, so
    the chain blends exactly like one kernel call over the concatenation
    whenever K is a multiple of the kernel's K block (and within < T_EPS
    otherwise). Early termination spans passes: a pass whose tiles have all
    saturated executes zero live K blocks.

    Returns (RenderOut, counters dict). The RenderOut fields come from the
    kernel's own measurements (processed/blended/entry_alive, with
    entry_alive concatenating the passes along K), and the dict adds the
    sweep-level counters only the fused kernel can report:

      kblocks_processed  — K blocks the kernel actually executed (summed
                           over tiles and passes; termination + adaptive
                           trip count)
      kblocks_total      — K blocks a full sweep would execute
      swept_per_pixel    — Gaussian list slots each pixel lane swept,
                           averaged over tiles (the unfused path always
                           sweeps the padded k_max of every pass)

    `alpha` is derived as 1 - transmittance — the identity sum(T_excl·a) =
    1 - prod(1-a) holds telescopically inside the kernel too, so it equals
    the blended accumulation exactly up to the terminated tail (< T_EPS).

    span_cb: optional `span_cb(pass_index)` returning a context manager —
    the renderer passes the active tracer's `blend[pass=i]` span so the
    fused pass loop shows up in the host-side span tree (obs is never
    imported here; a None default keeps the kernel layer standalone).
    """
    state = None
    alive_parts = []
    kproc = jnp.zeros((), jnp.float32)
    kblocks_total = 0
    for i, (lists, valid, entry_mask) in enumerate(passes):
        with (span_cb(i) if span_cb is not None
              else contextlib.nullcontext()):
            fb = blend_tiles_fused_pallas(proj, grid, lists, valid,
                                          entry_mask, init=state)
            state = (fb.trans, fb.rgb, fb.processed, fb.blended)
        alive_parts.append(fb.entry_alive)
        kproc = kproc + jnp.sum(fb.kblocks_processed).astype(jnp.float32)
        kblocks_total += fb.kblocks_total
    entry_alive = (alive_parts[0] if len(alive_parts) == 1
                   else jnp.concatenate(alive_parts, axis=1))
    return finalize_fused_passes(grid, state, background, overflow,
                                 entry_alive, kproc, kblocks_total)


def finalize_fused_passes(grid, state, background, overflow, entry_alive,
                          kproc, kblocks_total):
    """Assemble (RenderOut, counters) from the fused kernel's carried state.

    state: the (trans, rgb, processed, blended) tile-major carry after the
    last pass; kproc: summed kblocks_processed (float scalar);
    kblocks_total: static per-tile K-block count summed over passes. Split
    out of `render_tiles_fused_passes` so the tile-sharded render path can
    gather per-shard state rows and finalize with the identical arithmetic.
    """
    trans, rgb, processed, blended = state
    acc = 1.0 - trans
    rgb = rgb + background * trans[:, :, None]
    out = raster.RenderOut(
        image=raster.untile(grid, rgb),
        alpha=raster.untile(grid, acc),
        processed_per_pixel=raster.untile(grid, processed),
        blended_per_pixel=raster.untile(grid, blended),
        overflow=jnp.asarray(overflow),
        entry_alive=entry_alive,
    )
    counters = dict(
        kblocks_processed=kproc,
        kblocks_total=jnp.asarray(float(grid.num_tiles * kblocks_total),
                                  jnp.float32),
        swept_per_pixel=kproc * krender.K_BLK / grid.num_tiles,
    )
    return out, counters
