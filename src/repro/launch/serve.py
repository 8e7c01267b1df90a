"""Serving driver.

--mode render : the paper's workload at request level — a mixed multi-scene
                stream (≥2 scenes, ≥2 resolutions, varying batch sizes)
                micro-batched through `repro.serving.RenderEngine`; frames
                shard over the mesh's data axes, buckets keep the jit cache
                small, telemetry reports latency percentiles + modeled
                accelerator FPS.
--mode lm     : prefill + decode loop for any --arch (reduced config on CPU).

    PYTHONPATH=src python -m repro.launch.serve --mode render --frames 16
    PYTHONPATH=src python -m repro.launch.serve --mode lm \
        --arch qwen1.5-0.5b --reduced --prefill 64 --decode 16
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh


def serve_render(args) -> int:
    from repro.core import (orbit_camera, Renderer, TestConfig, SamplingMode,
                            MIXED)
    from repro.serving import (RenderEngine, MicroBatcher,
                               register_demo_scenes)

    renderer = Renderer(test=TestConfig(
        method="cat", mode=SamplingMode.SMOOTH_FOCUSED, precision=MIXED,
        backend="pallas" if args.pallas else "jnp"))
    engine = RenderEngine(renderer, mesh=make_local_mesh(),
                          max_batch=args.max_batch)
    # Probe-driven per-scene k_max over both served resolutions (the
    # engine's OverflowPolicy.WARN flags any off-probe pose that still
    # overflows, and telemetry counts it in overflow_frames).
    probes = [orbit_camera(t, r, r)
              for r in (args.res, max(args.res // 2, 16))
              for t in (0.0, 1.6, 3.2, 4.8)]
    register_demo_scenes(engine, args.gaussians, probe_cameras=probes)
    batcher = MicroBatcher(engine)

    # Mixed workload with request locality (real traffic clusters on hot
    # scenes): the scene flips every 4 requests and the resolution every
    # 4*len(scenes), so all scene x resolution combinations occur over the
    # run while consecutive requests still form multi-frame batches. Wave
    # sizes vary so several batch buckets are exercised.
    scenes = engine.scene_names()
    resolutions = (args.res, max(args.res // 2, 16))
    wave_sizes = [1, 2, 4, args.max_batch]
    futures, submitted, w = [], 0, 0
    while submitted < args.frames:
        wave = min(wave_sizes[w % len(wave_sizes)], args.frames - submitted)
        for i in range(wave):
            j = submitted + i
            res = resolutions[(j // (4 * len(scenes)))
                              % len(resolutions)]
            futures.append(batcher.submit(
                scenes[(j // 4) % len(scenes)],
                orbit_camera(2 * np.pi * j / args.frames, res, res)))
        submitted += wave
        t0 = time.perf_counter()
        served = batcher.flush()
        w += 1
        print(f"wave {w}: {served} requests in "
              f"{(time.perf_counter() - t0)*1e3:7.1f} ms "
              f"({engine.compile_count} compiles so far)", flush=True)

    for f in futures:
        f.result(timeout=0)   # all resolved by flush; raises on failure
    print(engine.telemetry.format_snapshot())
    print(f"jit cache: {engine.compile_count} executables for "
          f"{len(scenes)} scenes x {len(resolutions)} resolutions x "
          f"waves {wave_sizes}")
    return 0


def serve_lm(args) -> int:
    from repro.configs import get_arch, reduced as reduce_cfg
    from repro.models.model import Model

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model = Model(cfg)
    mesh = make_local_mesh()

    with mesh:
        params = model.init(jax.random.PRNGKey(0))
        b, s = args.batch, args.prefill
        if cfg.family == "encdec":
            batch = dict(
                enc_embeds=jnp.zeros((b, s, cfg.d_model), jnp.bfloat16),
                tokens=jnp.ones((b, s), jnp.int32))
        elif cfg.embeds_input:
            batch = dict(embeds=jnp.zeros((b, s, cfg.d_model), jnp.bfloat16))
        else:
            batch = dict(tokens=jnp.ones((b, s), jnp.int32))

        t0 = time.perf_counter()
        logits, _ = jax.block_until_ready(
            jax.jit(lambda p, bt: model.prefill(p, bt, mesh))(params, batch))
        print(f"prefill ({b}x{s}): {time.perf_counter()-t0:.2f}s "
              f"logits {logits.shape}")

        # Decode with freshly initialized caches sized prefill+decode.
        caches = model.init_caches(b, s + args.decode)
        step = jax.jit(lambda p, c, t: model.decode_step(p, c, t, mesh))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        lat = []
        for i in range(args.decode):
            t0 = time.perf_counter()
            logits_i, caches = jax.block_until_ready(step(params, caches, tok))
            lat.append(time.perf_counter() - t0)
            tok = jnp.argmax(logits_i, -1).astype(jnp.int32)[:, None]
        lat = np.array(lat[1:]) if len(lat) > 1 else np.array(lat)
        print(f"decoded {args.decode} tokens; median {np.median(lat)*1e3:.1f}"
              f" ms/token; last tokens {np.asarray(tok[:, 0])[:4]}")
    return 0


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="render", choices=["render", "lm"])
    # render
    ap.add_argument("--frames", type=int, default=16)
    ap.add_argument("--res", type=int, default=128)
    ap.add_argument("--gaussians", type=int, default=4000)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--pallas", action="store_true")
    # lm
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prefill", type=int, default=64)
    ap.add_argument("--decode", type=int, default=8)
    args = ap.parse_args(argv)
    return serve_render(args) if args.mode == "render" else serve_lm(args)


if __name__ == "__main__":
    raise SystemExit(main())
