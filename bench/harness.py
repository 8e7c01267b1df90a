"""One run of one cell: set-up, the measured window, the check, the line.

Everything that belongs to one configuration, traffic mix, metric or
cell is found by its name in `BENCHMARK.json`:

  configuration  the JSON file the configuration's entry names
                 (`bench/configs/<config>.json`);
  traffic mix    `bench/traffic/<traffic>.json`, read by `bench.loadgen`;
  metric         `bench/metrics/<metric>.py`, whose `read(run)` returns the
                 number or None where the run has nothing to read;
  cell limits    `bench/checks/<cell>.json`: the limit of each number
                 `bench.check` compares, with the readings behind it.

`execute` does the run and returns the result line as a dict; `bench/run.py`
checks for the chips first and prints it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Callable

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(spec: dict, name: str) -> dict:
    return _named(spec["workloads"], name, "workload")


def config(spec: dict, name: str, root: Path = ROOT) -> dict:
    with open(root / _named(spec["configs"], name, "config")["file"]) as f:
        return json.load(f)


def traffic(name: str, root: Path = ROOT) -> dict:
    with open(root / "bench" / "traffic" / f"{name}.json") as f:
        return json.load(f)


def limits(cell: str, root: Path = ROOT) -> dict:
    with open(root / "bench" / "checks" / f"{cell}.json") as f:
        return json.load(f)["limits"]


def reader(metric: str, root: Path = ROOT) -> Callable:
    """The `read` function of `bench/metrics/<metric>.py`."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell: str, traced: bool) -> list:
    """The metric entries a run of `cell` reports: per-layer ones when
    traced, end-to-end ones otherwise; an entry with a `workloads` key
    only in the cells it lists."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: str
    setup_s: float
    window: object                 # loadgen.Window
    compiles_in_window: int
    trace: object = None           # tracing.Trace of the window, if traced

    @property
    def served(self) -> list:
        """Results of the requests completed inside the window."""
        return [r.result() for r in self.window.requests
                if r.t_done <= self.window.t_close
                and r.result() is not None]


def program():
    """The system under test, imported from the checkout's `src`."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro import core
    from repro.serving import Scheduler
    from repro.serving.engine import RenderEngine
    return core, Scheduler, RenderEngine


def build_engine(cfg: dict):
    """The configuration's engine, as `hd1080_engine` builds the program's
    own Full-HD deployment: a SPILL plan of `k_pass`-slot passes, the CTU
    and blend backends and precision the configuration states."""
    core, Scheduler, RenderEngine = program()
    e = cfg["engine"]
    plan = core.RenderPlan(
        test=core.TestConfig(method="cat", backend=e["ctu"],
                             mode=core.SamplingMode(e["sampling"]),
                             precision=getattr(core, e["precision"])),
        stream=core.StreamConfig(k_max=e["k_pass"],
                                 overflow=core.OverflowPolicy.SPILL,
                                 max_spill_passes=e["k_max"] // e["k_pass"]))
    engine = RenderEngine(plan, fused=e["fused"], max_batch=e["max_batch"],
                          incremental=e["incremental"])
    return engine, Scheduler(engine)


def to_camera(cam: dict):
    core, _, _ = program()
    return core.Camera(R_wc=cam["R_wc"], t_wc=cam["t_wc"], fx=cam["fx"],
                       fy=cam["fy"], cx=cam["cx"], cy=cam["cy"],
                       width=cam["width"], height=cam["height"],
                       near=cam["near"])


def warm(engine, name: str, cameras: list) -> str:
    """Compile (or load from the persistent cache) the one executable the
    window uses, without running it: the engine's jitted batch program for
    this scene, resolution and batch (`RenderEngine._render_fn`, the jit
    cache the engine serves from), lowered and compiled ahead of time.
    Returns the compiled program's HLO text."""
    core, _, _ = program()
    cams = core.stack_cameras(cameras)
    scene = engine.scene(name)
    plan = engine.plan_for(name, cams.height, cams.width)
    fn, _ = engine._render_fn(scene.means.shape[0], plan, len(cameras))
    t0 = time.perf_counter()
    lowered = fn.lower(scene, cams)
    t1 = time.perf_counter()
    text = lowered.compile().as_text()
    print(f"info: traced and lowered in {t1 - t0:.3f} s, compiled (or "
          f"loaded) in {time.perf_counter() - t1:.3f} s", file=sys.stderr)
    return text


HASH_SEED = "0"


def pin_hash_seed(t_start: float) -> float:
    """Re-execute this process with PYTHONHASHSEED fixed, unless it is, and
    return the time the first image of the process started.

    The program iterates a set of counter names while it traces
    (`core/renderer.py` over `hierarchy.ADDITIVE_COUNTER_KEYS`), so the
    order of the ops it emits follows Python's string hashing, which is
    salted anew in every process. The compiled program's cache key then
    differs in every run and the persistent cache never serves it. With
    the salt fixed, every run lowers the same program. Call it before
    anything imports JAX: `execv` replaces the process, so no child is
    left and the chip is not held twice. The start time crosses the exec
    in the environment (`time.perf_counter` is the system's monotonic
    clock, the same in both images)."""
    if os.environ.get("PYTHONHASHSEED") != HASH_SEED:
        os.environ["PYTHONHASHSEED"] = HASH_SEED
        os.environ["BENCH_T_START"] = repr(t_start)
        os.execv(sys.executable, [sys.executable] + sys.argv)
    return float(os.environ.pop("BENCH_T_START", t_start))


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, or where JAX_COMPILATION_CACHE_DIR says. Call it before
    anything compiles: JAX decides once per process whether it caches."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def execute(cell: dict, cfg: dict, mix: dict, lims: dict, metrics: list,
            seed: int, seconds: float, trace: bool, t_start: float,
            log: Callable = lambda msg: print(msg, file=sys.stderr),
            root: Path = ROOT) -> dict:
    """One run of `cell`: returns the result line as a dict."""
    import jax
    from bench import check, loadgen, reference, scenes

    core, _, _ = program()
    # The configuration states float32 arithmetic: on a TPU, JAX runs a
    # float32 matmul in one bfloat16 pass unless told otherwise.
    jax.config.update("jax_default_matmul_precision",
                      cfg["engine"]["matmul_precision"])
    width, height = cfg["width"], cfg["height"]
    log(f"info: set-up {time.perf_counter() - t_start:.3f} s: "
        f"{jax.device_count()} {jax.default_backend()} device(s)")
    engine, sched = build_engine(cfg)
    scene_arrays = scenes.make_scene(cfg["scene"], cfg["gaussians"], seed)
    engine.register_scene(cfg["name"], core.GaussianScene(**scene_arrays),
                          k_max=cfg["engine"]["k_max"])
    jax.block_until_ready(scene_arrays)
    log(f"info: set-up {time.perf_counter() - t_start:.3f} s: scene made")
    chunk = sched.chunk_for(height, width)
    poses = loadgen.Poses(cfg["views"], mix, seed, width, height)
    hlo = warm(engine, cfg["name"], [to_camera(scenes.orbit_pose(
        cfg["views"], 0.0, 0.0, width, height))] * chunk)
    log(f"info: set-up {time.perf_counter() - t_start:.3f} s: batch of "
        f"{chunk} compiled")
    compiles0 = engine.compile_count

    def submit(cam):
        return sched.submit(cfg["name"], to_camera(cam))

    if trace:
        TRACE_DIR.mkdir(exist_ok=True)
        for f in TRACE_DIR.rglob("*.xplane.pb"):
            f.unlink()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # annotations only, no calls
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
        span = jax.profiler.TraceAnnotation
    else:
        span = None
    setup_s = time.perf_counter() - t_start
    with (span("bench.window") if trace else contextlib.nullcontext()):
        window = loadgen.drive(mix, poses, submit, sched.step,
                               lambda: sched.pending, seconds, chunk,
                               span=span)
    if trace:
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    run = Run(cell=cell["name"], setup_s=setup_s, window=window,
              compiles_in_window=engine.compile_count - compiles0)
    dev = jax.devices()[0]
    line = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": jax.device_count(),
                       "memory_peak_bytes": int(
                           stats.get("peak_bytes_in_use", 0))}}
    if trace:
        from bench import tracing
        run.trace = tracing.Trace.load(str(TRACE_DIR),
                                       tracing.kernel_map(hlo))
        line["device"].update(busy_s=run.trace.busy_s,
                              window_s=run.trace.window_s)
        line["breakdown"] = {"device_ops": run.trace.top_ops(10),
                             "idle_gaps": run.trace.idle_gaps(10)}
    ends = sorted({r.t_done - window.t_open for r in window.requests})
    log(f"info: window {window.seconds!r} s, {len(window.requests)} "
        f"requests, dispatches ending at {ends!r} s")
    for m in metrics:
        v = reader(m["name"], root)(run)
        if v is not None:
            line["metrics"][m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}

    # The check: a sample of the window's served frames, drawn from the
    # seed, against the reference, once the program's state is freed.
    in_window = [r for r in window.requests if r.t_submit <= window.t_close]
    served = [r for r in in_window if r.result() is not None]
    line["attempted"] = len(in_window)
    line["failed"] = len(in_window) - len(served)
    rng = np.random.default_rng([int(seed), 2])
    pick = sorted(rng.choice(len(served), min(len(served),
                                              cfg["check_frames"]),
                             replace=False))
    sample = [(served[i].camera, np.asarray(served[i].result().image),
               {k: float(v) for k, v in served[i].result().counters.items()})
              for i in pick]
    del engine, sched, run, window, in_window, served
    gc.collect()
    per_frame = [check.frame_numbers(image, counters, reference.render(
        scene_arrays, cam, k_pass=cfg["engine"]["k_pass"]))
        for cam, image, counters in sample]
    ok, rows = (check.verdict(check.worst(per_frame), lims) if per_frame
                else (False, []))
    line["correct"] = ok and line["failed"] == 0
    line["check"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in rows}
    for name, v, lim in rows:
        log(f"check {name} {v!r} limit {lim!r}")
    return line
