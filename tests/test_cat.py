"""Mini-Tile CAT correctness: Alg. 1 equivalence, mode semantics, hierarchy
invariants, precision behavior."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.cat import (pr_gaussian_weight, minitile_cat_mask,
                            exact_minitile_mask, SamplingMode)
from repro.core.precision import FULL_FP32, FULL_FP8, MIXED
from repro.core.hierarchy import hierarchical_test
from repro.core.culling import aabb_mask


@settings(deadline=None, max_examples=100)
@given(st.floats(-30, 30), st.floats(-30, 30), st.floats(0.05, 2),
       st.floats(0.05, 2), st.floats(-0.5, 0.5),
       st.floats(0, 8), st.floats(0, 8))
def test_alg1_matches_direct_quadratic(mx, my, cxx, cyy, cxy_f, w, h):
    """Alg. 1's term-shared E equals the direct quadratic form at all 4
    corners of the PR (fp32)."""
    cxy = cxy_f * (cxx * cyy) ** 0.5      # keep conic PSD
    mu = jnp.asarray([mx, my])
    conic = jnp.asarray([cxx, cxy, cyy])
    p_top = jnp.asarray([1.5, 2.5])
    p_bot = jnp.asarray([1.5 + w, 2.5 + h])
    E = np.asarray(pr_gaussian_weight(mu, conic, p_top, p_bot, FULL_FP32))
    corners = [p_top,
               jnp.asarray([p_bot[0], p_top[1]]),
               jnp.asarray([p_top[0], p_bot[1]]),
               p_bot]
    for i, p in enumerate(corners):
        d = np.asarray(p - mu)
        direct = 0.5 * (cxx * d[0] ** 2 + cyy * d[1] ** 2) + cxy * d[0] * d[1]
        np.testing.assert_allclose(E[i], direct, rtol=1e-5, atol=1e-5)


def test_dense_superset_of_sparse(proj64, grid64):
    dense = minitile_cat_mask(proj64, grid64, SamplingMode.UNIFORM_DENSE,
                              FULL_FP32)
    sparse = minitile_cat_mask(proj64, grid64, SamplingMode.UNIFORM_SPARSE,
                               FULL_FP32)
    assert bool(jnp.all(dense | ~sparse))   # sparse => dense


def test_adaptive_between_dense_and_sparse(proj64, grid64):
    dense = minitile_cat_mask(proj64, grid64, SamplingMode.UNIFORM_DENSE,
                              FULL_FP32)
    sparse = minitile_cat_mask(proj64, grid64, SamplingMode.UNIFORM_SPARSE,
                               FULL_FP32)
    for mode in (SamplingMode.SMOOTH_FOCUSED, SamplingMode.SPIKY_FOCUSED):
        adap = minitile_cat_mask(proj64, grid64, mode, FULL_FP32)
        assert int(sparse.sum()) <= int(adap.sum()) <= int(dense.sum())


def test_cat_false_negative_rate_bounded(proj64, grid64):
    """Dense fp32 CAT misses few truly-contributing (minitile, gaussian)
    pairs (only interior-only contributors can be missed)."""
    cat = minitile_cat_mask(proj64, grid64, SamplingMode.UNIFORM_DENSE,
                            FULL_FP32)
    oracle = exact_minitile_mask(proj64, grid64)
    missed = jnp.sum(oracle & ~cat)
    total = jnp.maximum(jnp.sum(oracle), 1)
    assert float(missed / total) < 0.12


def test_slack_only_adds_positives(proj64, grid64):
    """MIXED's conservative slack may only add (never remove) passes
    relative to the same scheme without slack."""
    import dataclasses
    mixed_noslack = dataclasses.replace(MIXED, slack=0.0)
    with_slack = minitile_cat_mask(proj64, grid64,
                                   SamplingMode.UNIFORM_DENSE, MIXED)
    without = minitile_cat_mask(proj64, grid64, SamplingMode.UNIFORM_DENSE,
                                mixed_noslack)
    assert bool(jnp.all(with_slack | ~without))


def test_mixed_close_to_fp32_fp8_not(proj64, grid64):
    ref = minitile_cat_mask(proj64, grid64, SamplingMode.UNIFORM_DENSE,
                            FULL_FP32)
    mixed = minitile_cat_mask(proj64, grid64, SamplingMode.UNIFORM_DENSE,
                              MIXED)
    fp8 = minitile_cat_mask(proj64, grid64, SamplingMode.UNIFORM_DENSE,
                            FULL_FP8)
    # false negatives vs fp32 (the quality-relevant direction)
    fn_mixed = float(jnp.sum(ref & ~mixed) / jnp.maximum(jnp.sum(ref), 1))
    fn_fp8 = float(jnp.sum(ref & ~fp8) / jnp.maximum(jnp.sum(ref), 1))
    assert fn_mixed < 0.01
    assert fn_fp8 > fn_mixed


def test_hierarchy_gating(proj64, grid64):
    """Stage-2 mask must be a subset of its sub-tile's Stage-1 mask, and the
    tile mask the OR of its mini-tiles."""
    h = hierarchical_test(proj64, grid64, SamplingMode.UNIFORM_DENSE,
                          FULL_FP32)
    sub_of_mini = grid64.subtile_of_minitile()
    gate = h.subtile_mask[sub_of_mini]
    assert bool(jnp.all(gate | ~h.minitile_mask))
    tile_of_mini = grid64.tile_of_region(grid64.minitile)
    recon = jax.ops.segment_sum(h.minitile_mask.astype(jnp.int32),
                                tile_of_mini,
                                num_segments=grid64.num_tiles) > 0
    np.testing.assert_array_equal(np.asarray(recon), np.asarray(h.tile_mask))


def test_subtile_aabb_nearly_superset_of_exact(proj64, grid64):
    """Stage-1 AABB is the conservative test up to the 3-sigma bbox
    approximation inherited from vanilla 3DGS: a Gaussian with opacity near
    1 contributes (alpha >= 1/255) out to 3.33 sigma, slightly past the
    bbox. The miss rate must stay well under 1%."""
    sub = aabb_mask(proj64, grid64.subtile_origins(), grid64.subtile)
    oracle = exact_minitile_mask(proj64, grid64)
    sub_of_mini = grid64.subtile_of_minitile()
    missed = jnp.sum(oracle & ~sub[sub_of_mini])
    total = jnp.maximum(jnp.sum(oracle), 1)
    assert float(missed / total) < 0.005


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("kind", ["fp16", "fp8"])
def test_quantize_equals_cast_round_trip(kind, jit):
    """`quantize` is bit-identical to the fp16 / float8_e4m3fn cast round
    trip (as ml_dtypes computes it on the host), across magnitudes,
    subnormals, ties, overflow and arbitrary bit patterns."""
    import ml_dtypes
    from repro.core.precision import quantize
    rng = np.random.default_rng(0)
    x = np.concatenate(
        [rng.standard_normal(20000).astype(np.float32) * s
         for s in (1e-9, 1e-4, 1e-2, 1, 300, 7e4)]
        + [rng.integers(0, 2**32, 50000, dtype=np.uint64)
           .astype(np.uint32).view(np.float32),
           np.array([0., -0., 448, 464, -464, 470, 480, 65504, 65520, 2**-6,
                     2**-9, 2**-10, 1.5 * 2**-10, 2**-14, 2**-24, 2**-25,
                     np.inf, -np.inf, np.nan], np.float32)])
    dt = {"fp16": np.float16, "fp8": ml_dtypes.float8_e4m3fn}[kind]
    with np.errstate(over="ignore", invalid="ignore"):
        ref = x.astype(dt).astype(np.float32)
    f = (lambda v: quantize(v, kind))
    got = np.asarray((jax.jit(f) if jit else f)(jnp.asarray(x)))
    same = ((got.view(np.uint32) == ref.view(np.uint32))
            | (np.isnan(got) & np.isnan(ref)))
    assert same.all(), x[~same][:8]
