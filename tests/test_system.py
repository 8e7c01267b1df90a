"""End-to-end behaviour tests: the paper's full pipeline (train a scene,
prune, render with FLICKER) and training/serving drivers."""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core.gaussians import random_scene, project
from repro.core.camera import default_camera
from repro.core.culling import TileGrid
from repro.core.pipeline import render_with_stats, RenderConfig, psnr
from repro.core.training import fit, TrainConfig
from repro.core.pruning import contribution_scores, prune
from repro.core.clustering import (kmeans_clusters, cluster_frustum_cull,
                                   memory_traffic_model)
from repro.core.cat import SamplingMode
from repro.core.precision import MIXED, FULL_FP32


SIZE = 32


def _cfg(**kw):
    base = dict(height=SIZE, width=SIZE, method="aabb",
                precision=FULL_FP32, k_max=300)
    base.update(kw)
    return RenderConfig(**base)


def test_end_to_end_train_prune_flicker_render():
    """The paper's §V-A pipeline in miniature."""
    key = jax.random.PRNGKey(0)
    cam = default_camera(SIZE, SIZE)
    # target: render of a hidden scene
    hidden = random_scene(key, 150, scale_range=(-2.5, -1.8),
                          opacity_range=(0.0, 2.0))
    target = render_with_stats(hidden, cam, _cfg())[0].image

    scene0 = random_scene(jax.random.fold_in(key, 1), 250,
                          scale_range=(-2.5, -1.8),
                          opacity_range=(-1.0, 1.0))
    scene, losses = fit(scene0, cam, target, _cfg(), TrainConfig(),
                        steps=60)
    assert float(losses[-1]) < float(losses[0])
    base_psnr = float(psnr(render_with_stats(scene, cam, _cfg())[0].image,
                           target))
    assert base_psnr > 15.0

    grid = TileGrid(SIZE, SIZE)
    scores = contribution_scores(scene, [cam], grid, k_max=250)
    pscene, kept = prune(scene, scores, keep_frac=0.7)
    assert pscene.n == int(250 * 0.7)

    out, counters = render_with_stats(
        pscene, cam, _cfg(method="cat", mode=SamplingMode.SMOOTH_FOCUSED,
                          precision=MIXED))
    ours_psnr = float(psnr(out.image, target))
    # contribution-aware render loses little vs the pruned baseline
    prun_psnr = float(psnr(render_with_stats(pscene, cam, _cfg())[0].image,
                           target))
    assert ours_psnr > prun_psnr - 1.5


def test_clustering_reduces_traffic():
    scene = random_scene(jax.random.PRNGKey(2), 400)
    # narrow-FOV camera so a large part of the scene leaves the frustum —
    # cluster-level culling only pays off when clusters are actually culled
    # (with everything visible it adds C cluster-record reads).
    cam = default_camera(SIZE, SIZE, fov_deg=22.0)
    cl = kmeans_clusters(scene, 64)
    assert int(cl.counts.sum()) == 400
    vis = cluster_frustum_cull(cl, cam)
    proj = project(scene, cam)
    grid = TileGrid(SIZE, SIZE)
    from repro.core.culling import aabb_mask
    inter = jnp.any(aabb_mask(proj, grid.tile_origins(), grid.tile), axis=0)
    t = memory_traffic_model(cl, vis, inter)
    assert int(jnp.sum(vis)) < 64          # something actually culled
    assert float(t["bytes_cluster"]) <= float(t["bytes_no_cluster"])
    # conservative culling: every in-frustum gaussian's cluster is visible
    assert bool(jnp.all(vis[cl.assign] | ~proj.in_frustum))


def test_train_driver_cli(tmp_path):
    from repro.launch.train import main
    rc = main(["--arch", "mamba2-780m", "--reduced", "--steps", "4",
               "--batch", "2", "--seq", "32",
               "--ckpt-dir", str(tmp_path / "ck"), "--save-every", "2"])
    assert rc == 0
    # restart picks up the checkpoint
    rc = main(["--arch", "mamba2-780m", "--reduced", "--steps", "6",
               "--batch", "2", "--seq", "32",
               "--ckpt-dir", str(tmp_path / "ck"), "--save-every", "2"])
    assert rc == 0


def test_train_driver_with_compression(tmp_path):
    from repro.launch.train import main
    rc = main(["--arch", "qwen1.5-0.5b", "--reduced", "--steps", "3",
               "--batch", "2", "--seq", "32", "--compress", "int8",
               "--ckpt-dir", str(tmp_path / "ck2"), "--save-every", "100"])
    assert rc == 0


@pytest.fixture
def cache_dir(monkeypatch, tmp_path):
    """Point the persistent compilation cache (the serve driver turns it
    on) at a temporary directory, and restore this process's setting
    afterwards so no later test compiles into it."""
    from jax.experimental.compilation_cache import compilation_cache
    path = tmp_path / "jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(path))
    was = jax.config.jax_compilation_cache_dir
    yield path
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_compile_cache_lands_in_env_dir(cache_dir):
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch.compile_cache import enable_compile_cache
    assert enable_compile_cache() == str(cache_dir)
    compilation_cache.reset_cache()
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.ones(7))
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was)
    assert any(cache_dir.iterdir())


def test_compile_cache_defaults_to_checkout(cache_dir, monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    checkout = Path(__file__).resolve().parents[1]
    assert path == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_serve_driver_render(cache_dir):
    from repro.launch.serve import main
    rc = main(["--mode", "render", "--frames", "2", "--res", "32",
               "--gaussians", "200"])
    assert rc == 0


def test_serve_driver_lm(cache_dir):
    from repro.launch.serve import main
    rc = main(["--mode", "lm", "--arch", "zamba2-1.2b", "--reduced",
               "--batch", "1", "--prefill", "32", "--decode", "3"])
    assert rc == 0
