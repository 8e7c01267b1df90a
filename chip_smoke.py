"""Serve Full-HD frames on a TPU through the Mosaic-compiled Pallas path.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the 4-way tile-sharded path only

One chip: builds the hd1080 serving deployment (1920x1088, 512k Gaussians,
SPILL overflow policy) with the CTU as the Pallas PRTU kernel and the blend
as the fused Pallas kernel, submits FRAMES requests through `Scheduler`,
and resolves every future. It renders the same requests through the plain
jnp plan as the reference and checks that the integer counters are equal,
that the images agree, and that the served program holds Mosaic kernels
(`tpu_custom_call`), i.e. nothing was interpreted. Images agree when every
channel is within the fused kernel's bound (2 x T_EPS, as in the repo's
fused-vs-jnp parity tests) except marginal-entry flips: the kernel's `exp`
(Mosaic) and XLA's differ in the last bit, which can move an alpha across
the ALPHA_MIN cut; as in `test_pallas_pipeline_matches_jnp_pipeline`, such
channels must stay under 1% of the frame and under 0.05.

--four-chips: one hd1080 request through a 4-way tile-sharded engine
(`RenderEngine(shard_tiles=4)`), compared with the one-device render of the
same request: images, entry_alive and every counter bit-identical.

Lines starting with "info:" are informational, not benchmark numbers. The
last line is {"ok": true, "device": {...}} and is printed only when every
check passed. Without a TPU, or when any phase fails, the script exits
nonzero and prints no such line. Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FRAMES = 4          # requests of the one-chip run: two batches of two
TILE_SHARDS = 4


def info(msg: str):
    print(f"info: {msg}", flush=True)


def check(ok: bool, what: str):
    if not ok:
        raise AssertionError(what)
    info(f"check passed: {what}")


def served_counters(frame) -> dict:
    return {k: float(v) for k, v in frame.counters.items()}


def one_chip() -> None:
    import jax
    import numpy as np

    from repro import kernels
    from repro.core import stack_cameras
    from repro.core.raster import T_EPS
    from repro.serving import RenderRequest, Scheduler
    from repro.serving.workloads import (HD1080_HEIGHT, HD1080_WIDTH,
                                         hd1080_cameras, hd1080_engine)

    check(not kernels.interpret_mode(), "Pallas kernels compile via Mosaic")
    t0 = time.perf_counter()
    engine, name = hd1080_engine(fused=True, backend="pallas")
    info(f"fused/Pallas engine built, scene registered and probed in "
         f"{time.perf_counter() - t0:.1f} s")
    sched = Scheduler(engine)
    chunk = sched.chunk_for(HD1080_HEIGHT, HD1080_WIDTH)
    # The scene's two probe poses, twice: the probed survivor bound covers
    # them, so no spill retry recompiles mid-run.
    cameras = hd1080_cameras(2) * (FRAMES // 2)
    futures = [sched.submit(name, cam) for cam in cameras]
    walls = []
    while sched.pending:
        t = time.perf_counter()
        sched.step()
        walls.append(time.perf_counter() - t)
    results = [f.result(timeout=0) for f in futures]   # raises on failure
    info(f"{FRAMES} frames in {len(walls)} batches of {chunk}; batch walls "
         f"(s, the first includes compilation): {walls}")
    stats = jax.devices()[0].memory_stats() or {}
    info(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")

    ref_engine, _ = hd1080_engine(fused=False, backend="jnp")
    requests = [RenderRequest(scene=name, camera=c) for c in cameras]
    refs, ref_walls = [], []
    for i in range(0, FRAMES, chunk):
        t = time.perf_counter()
        refs += ref_engine.render_batch(requests[i:i + chunk])
        ref_walls.append(time.perf_counter() - t)
    info(f"jnp reference batch walls (s, the first includes compilation): "
         f"{ref_walls}")

    worst = 0.0
    for i, (res, ref) in enumerate(zip(results, refs)):
        img = np.asarray(res.image)
        check(img.shape == (HD1080_HEIGHT, HD1080_WIDTH, 3)
              and bool(np.isfinite(img).all()),
              f"frame {i}: finite {img.shape} image")
        got, want = served_counters(res.frame), served_counters(ref)
        info(f"frame {i} counters (served / jnp): " + ", ".join(
            f"{k}={got.get(k)!r}/{want.get(k)!r}"
            for k in ("vru_pairs", "spill_passes", "processed_per_pixel",
                      "blended_per_pixel", "kblocks_processed")))
        for key in ("vru_pairs", "spill_passes"):
            check(got[key] == want[key],
                  f"frame {i}: {key} {got[key]!r} == jnp {want[key]!r}")
        diff = np.abs(img - np.asarray(ref.image))
        beyond = float(np.mean(diff > 2 * T_EPS))
        worst = max(worst, float(diff.max()))
        check(beyond < 1e-2 and float(diff.max()) < 0.05,
              f"frame {i}: image matches jnp (max |diff| "
              f"{float(diff.max())!r}; share of channels beyond 2*T_EPS "
              f"{beyond!r})")
    check(engine.spill_retries == ref_engine.spill_retries,
          f"spill_retries {engine.spill_retries} == jnp "
          f"{ref_engine.spill_retries}")
    info(f"worst image |diff| vs jnp: {worst!r}")

    plan = engine.plan_for(name, HD1080_HEIGHT, HD1080_WIDTH)
    cams = stack_cameras(cameras[:chunk])
    t = time.perf_counter()
    text = jax.jit(
        lambda scene, cams: plan.render_batch_with_stats(scene, cams)) \
        .lower(engine.scene(name), cams).compile().as_text()
    info(f"served program re-lowered in {time.perf_counter() - t:.1f} s")
    stats = jax.devices()[0].memory_stats() or {}
    info(f"peak_bytes_in_use after all phases: "
         f"{stats.get('peak_bytes_in_use')}")
    check(text.count("tpu_custom_call") > 0,
          f"served program holds {text.count('tpu_custom_call')} "
          "tpu_custom_call kernels")


def four_chips() -> None:
    import jax
    import numpy as np

    from repro.core import stack_cameras
    from repro.distributed import sharding as dshard
    from repro.serving import RenderRequest
    from repro.serving.sharding import shard_frames
    from repro.serving.workloads import (HD1080_HEIGHT, HD1080_WIDTH,
                                         hd1080_cameras, hd1080_engine)

    check(jax.device_count() >= TILE_SHARDS,
          f"{jax.device_count()} devices >= {TILE_SHARDS}")
    camera = hd1080_cameras(1)[0]
    renders = {}
    for shards in (1, TILE_SHARDS):
        t0 = time.perf_counter()
        engine, name = hd1080_engine(fused=True, backend="pallas",
                                     shard_tiles=shards)
        frame, = engine.render_batch([RenderRequest(scene=name,
                                                    camera=camera)])
        # entry_alive is not part of a served frame: render the engine's
        # own plan on its own mesh once more to read it.
        plan = engine.plan_for(name, HD1080_HEIGHT, HD1080_WIDTH)
        cams = stack_cameras([camera])
        if engine.mesh is not None:
            cams = shard_frames(cams, engine.mesh)
        with dshard.use_mesh(engine.mesh):
            out, _ = jax.jit(
                lambda scene, cams: plan.render_batch_with_stats(scene, cams)
            )(engine.scene(name), cams)
            alive = np.asarray(out.entry_alive[0])
        renders[shards] = (frame, alive)
        info(f"tile_shards={shards}: built and rendered in "
             f"{time.perf_counter() - t0:.1f} s")
    (ref, ref_alive), (got, got_alive) = renders[1], renders[TILE_SHARDS]
    for field in ("image", "alpha"):
        check(np.array_equal(np.asarray(getattr(ref, field)),
                             np.asarray(getattr(got, field))),
              f"sharded {field} bit-identical")
    check(np.array_equal(ref_alive, got_alive),
          "sharded entry_alive bit-identical")
    bad = [k for k in ref.counters
           if not np.array_equal(np.asarray(ref.counters[k]),
                                 np.asarray(got.counters[k]))]
    check(not bad, f"every shared counter bit-identical (mismatch: {bad})")
    c = served_counters(got)
    info(f"shard_entries_max={c['shard_entries_max']!r} "
         f"shard_entries_min={c['shard_entries_min']!r} "
         f"tile_shards={c['tile_shards']!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way tile-sharded hd1080 path and "
                         "its one-device comparison")
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's backend is {backend!r}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    info(f"compile cache: {enable_compile_cache()}")
    dev = jax.devices()[0]
    info(f"device: {dev.device_kind} x{jax.device_count()} "
         f"(jax {jax.__version__})")
    t0 = time.perf_counter()
    four_chips() if args.four_chips else one_chip()
    info(f"all phases done in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
