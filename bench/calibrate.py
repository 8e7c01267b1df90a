"""Readings behind a cell's limits: sound runs, the control and the faults.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--out f]

On the chip, in one process: the cell's engine is built and compiled once;
then for every seed the scene is drawn, the first batch of the cell's
traffic is served through the scheduler exactly as a run's window serves
it, and the compared numbers (`bench.check`) are read for

  sound            the served frames against the float32 reference;
  control          the reference computed in bfloat16 against the float32
                   reference, at each position of the same frames;
  answer_altered   the served frames with one 16x16 tile of the first frame
                   brightened by a quarter;
  half_batch       the served frames with the first half of the batch
                   handed to the second half's requests

(these three on the first --control-seeds seeds only)

and, with --program-controls, the program itself at lower matmul
precisions (JAX's "default", one bfloat16 pass, and "high", three) on the
first --control-seeds seeds: its own lower-precision path, read as `sound`
is, under a `program_precision` tag.

One JSON line per seed goes to standard output (and to --out). A cell's
limit lies above the largest sound reading and below the smallest of the
others (PERF.md gives the readings of each limit).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from bench import check, harness, loadgen, reference, scenes  # noqa: E402


def readings(cfg: dict, mix: dict, engine, sched, seed: int,
             controls: bool = True) -> dict:
    import jax.numpy as jnp
    core, _, _ = harness.program()
    scene = scenes.make_scene(cfg["scene"], cfg["gaussians"], seed)
    engine.register_scene(cfg["name"], core.GaussianScene(**scene),
                          k_max=cfg["engine"]["k_max"])
    poses = loadgen.Poses(cfg["views"], mix, seed, cfg["width"],
                          cfg["height"])
    cams = [poses.camera()
            for _ in range(sched.chunk_for(cfg["height"], cfg["width"]))]
    t0 = time.perf_counter()
    futs = [sched.submit(cfg["name"], harness.to_camera(c)) for c in cams]
    while sched.pending:
        sched.step()
    served = [f.result() for f in futs]
    images = [np.asarray(r.image) for r in served]
    counters = [{k: float(v) for k, v in r.counters.items()}
                for r in served]
    t1 = time.perf_counter()
    k_pass = cfg["engine"]["k_pass"]
    refs = [reference.render(scene, c, k_pass=k_pass) for c in cams]
    t2 = time.perf_counter()

    def numbers(imgs, ctrs):
        return check.worst([check.frame_numbers(i, c, r)
                            for i, c, r in zip(imgs, ctrs, refs)])

    base = dict(seed=seed, sound=numbers(images, counters),
                served_counters=counters,
                reference_counters=[r["counters"] for r in refs],
                batch_s=t1 - t0, reference_s=t2 - t1)
    if not controls:
        return base
    lows = [reference.render(scene, c, k_pass=k_pass, dtype=jnp.bfloat16)
            for c in cams]

    altered = [images[0].copy()] + images[1:]
    altered[0][:16, :16] += 0.25
    half = max(len(images) // 2, 1)
    return dict(
        base,
        control=numbers([lo["image"] for lo in lows],
                        [lo["counters"] for lo in lows]),
        answer_altered=numbers(altered, counters),
        half_batch=numbers(images[:half] + [images[i % half] for i in
                                            range(len(images) - half)],
                           counters[:half] + [counters[i % half] for i in
                                              range(len(images) - half)]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out")
    ap.add_argument("--program-controls", default="",
                    help="comma-separated matmul precisions to run the "
                         "program at as controls, e.g. default,high")
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    harness.pin_hash_seed(time.perf_counter())
    harness.enable_compile_cache()
    spec = harness.load_spec()
    cell = harness.workload(spec, args.workload)
    cfg = harness.config(spec, cell["config"])
    mix = harness.traffic(cell["traffic"])
    harness.program()
    import jax
    if jax.default_backend() != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    stated = cfg["engine"]["matmul_precision"]
    runs = [(stated, seeds)] + [
        (p, seeds[:args.control_seeds])
        for p in args.program_controls.split(",") if p]
    out = open(args.out, "a") if args.out else None
    for precision, run_seeds in runs:
        jax.config.update("jax_default_matmul_precision",
                          None if precision == "default" else precision)
        engine, sched = harness.build_engine(cfg)
        for i, seed in enumerate(run_seeds):
            line = json.dumps(dict(
                readings(cfg, mix, engine, sched, seed,
                         controls=(precision == stated
                                   and i < args.control_seeds)),
                program_precision=precision))
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
        del engine, sched
    return 0


if __name__ == "__main__":
    sys.exit(main())
