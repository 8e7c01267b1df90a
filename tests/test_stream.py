"""Survivor-stream dataflow vs the dense oracle.

The stream pipeline (`RenderConfig(dataflow="stream")`, the default) must be
indistinguishable from the dense one wherever both can run: identical tile
lists, entry-identical CAT masks, bit-identical images, and equal workload
counters. Plus the point of the refactor: a scene size the dense path cannot
comfortably touch (512²/64k) renders on the stream path with a fraction of
the CAT-stage memory.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gaussians import random_scene, project
from repro.core.camera import default_camera
from repro.core.culling import TileGrid
from repro.core.cat import SamplingMode, minitile_cat_mask, entry_cat_mask
from repro.core.hierarchy import (hierarchical_test,
                                  stream_hierarchical_test)
from repro.core.pipeline import (render_with_stats, RenderConfig,
                                 cat_mask_elems)
from repro.core.precision import FULL_FP32, MIXED
from repro.core import raster
from repro.kernels import ops as kops


# ---------------------------------------------------------------------------
# Property: stream CAT masks == dense CAT masks gathered at compacted indices
# ---------------------------------------------------------------------------


def check_entry_cat_equals_dense_gathered(mode, prec, seed, n):
    """For every valid entry (t, k): entry_cat[t, k, m] must equal the dense
    CAT mask at (global minitile id of (t, m), lists[t, k]) — the stream
    path evaluates the same arithmetic on the survivors only. Shared body
    of the hypothesis property (test_stream_properties.py) and the seeded
    sweep below."""
    scene = random_scene(jax.random.PRNGKey(seed), n)
    cam = default_camera(64, 64)
    grid = TileGrid(64, 64)
    proj = project(scene, cam)

    h = stream_hierarchical_test(proj, grid, mode, prec, k_max=n)
    assert not bool(h.overflow)
    stream_cat = entry_cat_mask(proj, grid, h.lists, h.valid, mode, prec)

    dense_cat = minitile_cat_mask(proj, grid, mode, prec)    # (M, N)
    gathered = raster.entry_mask_from_dense(grid, dense_cat, h.lists)
    # Stream CAT carries the valid gate (padded entries test gaussian 0);
    # compare inside the valid region only, where it must be exact.
    v = np.asarray(h.valid)[:, :, None]
    np.testing.assert_array_equal(np.asarray(stream_cat) & v,
                                  np.asarray(gathered) & v)


@pytest.mark.parametrize("prec", [FULL_FP32, MIXED], ids=["fp32", "mixed"])
@pytest.mark.parametrize("mode", list(SamplingMode))
@pytest.mark.parametrize("seed,n", [(0, 123), (7, 400)])
def test_entry_cat_equals_dense_cat_gathered(mode, prec, seed, n):
    check_entry_cat_equals_dense_gathered(mode, prec, seed, n)


def test_entry_subtile_equals_dense_stage1_gathered(proj64, grid64):
    from repro.core.culling import aabb_mask
    h = stream_hierarchical_test(proj64, grid64, k_max=800)
    sub_dense = aabb_mask(proj64, grid64.subtile_origins(), grid64.subtile)
    sids = grid64.global_subtile_ids()                       # (T, Sp)
    idx = np.asarray(h.lists).clip(0)
    gathered = np.asarray(sub_dense)[np.asarray(sids)[:, None, :],
                                     idx[:, :, None]]
    v = np.asarray(h.valid)[:, :, None]
    np.testing.assert_array_equal(np.asarray(h.entry_sub_mask),
                                  gathered & v)
    # Stage-2 gating invariant, stream form: a mini-tile bit implies its
    # containing sub-tile's Stage-1 bit.
    gate = np.asarray(h.entry_sub_mask)[
        :, :, np.asarray(grid64.subtile_of_minitile_local())]
    assert (gate | ~np.asarray(h.entry_mini_mask)).all()


def test_stream_lists_equal_dense_stage1_lists(proj64, grid64):
    """The tile-level AABB equals the OR of the tile's sub-tile AABBs (the
    sub-tiles partition the tile), so both dataflows build identical
    depth-ordered survivor streams."""
    h_d = hierarchical_test(proj64, grid64)
    sub_of_tile = grid64.tile_of_region(grid64.subtile)
    stage1_tile = jax.ops.segment_sum(
        h_d.subtile_mask.astype(jnp.int32), sub_of_tile,
        num_segments=grid64.num_tiles) > 0
    order = raster.depth_order(proj64)
    lists_d, valid_d, _ = raster.compact_tile_lists(stage1_tile, order, 800)
    h_s = stream_hierarchical_test(proj64, grid64, k_max=800, order=order)
    np.testing.assert_array_equal(np.asarray(h_s.lists), np.asarray(lists_d))
    np.testing.assert_array_equal(np.asarray(h_s.valid), np.asarray(valid_d))


# ---------------------------------------------------------------------------
# End-to-end parity: images and counters, wall + random scenes
# ---------------------------------------------------------------------------

# Workload counters that must be equal ENTRY-FOR-ENTRY across dataflows
# (excludes cat_mask_bytes, which is the quantity that differs by design).
PARITY_KEYS = (
    "n_frustum", "ctu_pairs", "ctu_pairs_no_stage1", "ctu_prs",
    "leader_tests_per_pair", "dup_tile", "dup_subtile", "dup_minitile",
    "vru_pairs", "vru_pairs_tile_aabb", "processed_per_pixel",
    "blended_per_pixel", "swept_per_pixel", "ctu_pairs_eff", "ctu_prs_eff",
    "vru_pairs_eff", "ctu_stream_len",
)


@pytest.mark.parametrize("scene_fixture", ["small_scene", "wall_scene"])
@pytest.mark.parametrize("fused", [False, True], ids=["jnp", "fused"])
def test_stream_matches_dense_pipeline(request, scene_fixture, fused, cam64):
    scene = request.getfixturevalue(scene_fixture)
    cfg = RenderConfig(height=64, width=64, method="cat", k_max=4096,
                       precision=MIXED, fused=fused)
    out_s, c_s = render_with_stats(scene, cam64, cfg)
    out_d, c_d = render_with_stats(
        scene, cam64, dataclasses.replace(cfg, dataflow="dense"))
    assert not bool(out_s.overflow)
    # Identical lists + identical per-entry masks => bit-identical blending.
    np.testing.assert_array_equal(np.asarray(out_s.image),
                                  np.asarray(out_d.image))
    np.testing.assert_array_equal(np.asarray(out_s.entry_alive),
                                  np.asarray(out_d.entry_alive))
    for key in PARITY_KEYS:
        assert float(c_s[key]) == float(c_d[key]), key


def test_stream_pallas_pipeline_matches_jnp_stream(small_scene, cam64):
    """use_pallas on the stream path (entry-gridded PRTU kernel) matches the
    pure-jnp stream path."""
    cfg = RenderConfig(height=64, width=64, method="cat", k_max=1024,
                       precision=FULL_FP32)
    out_j, c_j = render_with_stats(small_scene, cam64, cfg)
    out_p, c_p = render_with_stats(
        small_scene, cam64, dataclasses.replace(cfg, use_pallas=True))
    np.testing.assert_array_equal(np.asarray(out_j.image),
                                  np.asarray(out_p.image))
    for key in PARITY_KEYS:
        assert float(c_j[key]) == float(c_p[key]), key


@pytest.mark.parametrize("mode", list(SamplingMode))
def test_entry_prtu_kernel_matches_jnp(mode, proj64, grid64):
    h = stream_hierarchical_test(proj64, grid64, mode, k_max=800)
    for prec in (FULL_FP32, MIXED):
        mk = kops.entry_cat_mask_pallas(proj64, grid64, h.lists, h.valid,
                                        mode, prec)
        mr = entry_cat_mask(proj64, grid64, h.lists, h.valid, mode, prec)
        v = np.asarray(h.valid)[:, :, None]
        mismatch = float(np.mean((np.asarray(mk) & v) != (np.asarray(mr) & v)))
        if prec is FULL_FP32:
            assert mismatch == 0.0
        else:
            # reduced precision: quantization casts may fuse differently
            # between kernel and jnp programs — bound exact-tie flips.
            assert mismatch < 5e-4


def test_stream_render_differentiable(small_scene, cam64):
    """Gradients flow through the stream path (entry-indexed gathers +
    tile-chunked lax.map blending) — the training story survives the
    refactor."""
    cfg = RenderConfig(height=64, width=64, method="cat", k_max=800,
                       precision=FULL_FP32)

    def loss(scene):
        out, _ = render_with_stats(scene, cam64, cfg)
        return jnp.mean(out.image ** 2)

    g = jax.grad(loss)(small_scene)
    flat = jax.tree.leaves(g)
    assert all(bool(jnp.isfinite(x).all()) for x in flat)
    assert float(jnp.abs(g.colors).max()) > 0.0


# ---------------------------------------------------------------------------
# Scale: the regime the dense path cannot comfortably enter
# ---------------------------------------------------------------------------


def test_stream_renders_where_dense_mask_would_not_fit():
    """512²/64k-Gaussian frame on the stream path. The dense CAT stage would
    materialize > 1 GB of masks here ((S+M)·N bools) — an order of magnitude
    over the stream footprint — so only the stream dataflow runs it."""
    n, res, k_max = 65536, 512, 1536
    scene = random_scene(jax.random.PRNGKey(11), n,
                         scale_range=(-3.3, -2.7), stretch=3.0,
                         opacity_range=(-1.0, 3.0))
    cam = default_camera(res, res)
    cfg = RenderConfig(height=res, width=res, method="cat", k_max=k_max,
                       precision=MIXED)
    grid = cfg.grid()

    dense_bytes = cat_mask_elems(grid, n, k_max, "dense")
    stream_bytes = cat_mask_elems(grid, n, k_max, "stream")
    assert dense_bytes > 1 << 30          # the wall the refactor removes
    assert dense_bytes > 8 * stream_bytes

    out, counters = render_with_stats(scene, cam, cfg)
    assert not bool(out.overflow)
    img = np.asarray(out.image)
    assert img.shape == (res, res, 3)
    assert np.isfinite(img).all()
    assert img.max() > 0.01               # actually rendered something
    assert float(counters["cat_mask_bytes"]) == float(stream_bytes)
    assert float(counters["vru_pairs"]) > 0


@pytest.mark.parametrize("mode", list(SamplingMode))
def test_stream_ctu_prs_exact_past_float32_integers(mode, proj64, grid64):
    """`ctu_prs` is an exact integer sum: past 2^24 a float32 sum would
    round in whatever order the compiler reduces, and the tile-sharded and
    single-device programs reduce in different orders."""
    from repro.core.hierarchy import stream_entry_counters
    from repro.core.gaussians import classify_spiky
    rng = np.random.default_rng(0)
    shape = (4096, 2048)
    lists = jnp.asarray(rng.integers(0, proj64.depth.shape[0], shape),
                        jnp.int32)
    sub_hits = jnp.asarray(rng.integers(0, 17, shape), jnp.int32)
    valid = jnp.ones(shape, bool)
    got = jax.jit(stream_entry_counters, static_argnums=(1, 6))(
        proj64, grid64, lists, valid, sub_hits, sub_hits, mode)["ctu_prs"]
    spiky = np.asarray(classify_spiky(proj64.axis_ratio))[np.asarray(lists)]
    prs2 = {SamplingMode.UNIFORM_DENSE: np.full(shape, 2),
            SamplingMode.UNIFORM_SPARSE: np.full(shape, 1),
            SamplingMode.SMOOTH_FOCUSED: np.where(spiky, 1, 2),
            SamplingMode.SPIKY_FOCUSED: np.where(spiky, 2, 1)}[mode]
    total2 = int(np.sum(np.asarray(sub_hits, np.int64) * prs2)) \
        * grid64.minitiles_per_subtile
    assert total2 > 2 * 2**24       # ctu_prs past float32's exact integers
    assert float(got) == float(np.float32(total2) / 2)
