"""Shared serving workloads: the two-scene demo registry used by the serve
CLI, the example, and the serving tests, plus the Full-HD (1920×1088 /
512k-Gaussian) workload the 1080p scaling benchmark serves — one definition
each so they cannot diverge. Scene knobs mirror `benchmarks/common.py`'s
synthetic stand-ins for the paper's captures (screen-space sigma ~2-3 px,
~40% spiky); the HD scene uses the compact-footprint regime of
`benchmarks/scaling.py` (many small Gaussians — the production shape)."""
from __future__ import annotations

import dataclasses
import hashlib
import math
import time
from typing import Optional, Sequence

import jax
import numpy as np

from repro.core import OverflowPolicy, RenderPlan, StreamConfig, \
    TestConfig, orbit_camera, random_scene
from repro.serving.engine import RenderEngine

DEMO_SCENE_KW = dict(scale_range=(-2.9, -2.4), stretch=4.0,
                     opacity_range=(-1.0, 3.0))

# Compact screen footprints so survivor lists grow with density, not blob
# size — same knobs as benchmarks/scaling.py's scenes.
HD_SCENE_KW = dict(scale_range=(-3.3, -2.7), stretch=3.0,
                   opacity_range=(-1.0, 3.0))

# Full HD, tile-aligned: 1080 rows round up to 1088 (multiples of the
# 16-px tile), matching how real rasterizers pad 1080p framebuffers.
HD1080_WIDTH, HD1080_HEIGHT = 1920, 1088
HD1080_GAUSSIANS = 1 << 19        # 512k — the paper-scale scene size


def register_demo_scenes(engine: RenderEngine, n_gaussians: int, *,
                         sizes: Optional[dict] = None,
                         k_max: Optional[int] = None,
                         probe_cameras=None) -> list[str]:
    """Register the standard mixed workload: 'train' at `n_gaussians`,
    'truck' at 3/4 of it (override both via `sizes={name: n}`). Returns the
    registered scene names.

    probe_cameras: forwarded to `RenderEngine.register_scene` — when given
    (and k_max is None) each scene's k_max is measured from its Stage-1
    survivor histogram over the probe set instead of defaulting to the
    scene bucket size."""
    if sizes is None:
        sizes = {"train": n_gaussians,
                 "truck": max(n_gaussians * 3 // 4, 16)}
    for seed, (name, n) in enumerate(sizes.items()):
        engine.register_scene(
            name, random_scene(jax.random.PRNGKey(seed), n, **DEMO_SCENE_KW),
            k_max=k_max, probe_cameras=probe_cameras)
    return list(sizes)


def max_batch_for(height: int, width: int,
                  pixel_budget: int = 1 << 22) -> int:
    """Batching policy for large frames: the biggest power-of-two batch
    whose total pixel count stays within `pixel_budget` (default 4M px —
    two Full-HD frames). Small frames batch wide for SIMD width; a
    1920×1088 frame lands at 2 and anything larger serves frame-at-a-time,
    because past the budget the vmapped blend's working set scales with the
    batch while the per-frame latency bound does not.
    """
    frames = max(1, pixel_budget // (height * width))
    # 64 is the engine's default max_batch — batching wider than that buys
    # no SIMD width on any frame size, it only fattens tail latency.
    return min(1 << (frames.bit_length() - 1), 64)


def trajectory_cameras(n_frames: int, *, width: int = 128, height: int = 128,
                       step: float = 2 * math.pi / 64,
                       jump_frames=(), jump_offset: float = 2.0,
                       start: float = 0.0, radius: float = 4.0,
                       center=(0.0, 0.0, 4.0), fov_deg: float = 60.0) -> list:
    """A client-like camera trajectory: a smooth orbit (azimuth advances by
    `step` per frame) with jump-cuts injected at `jump_frames` — at each
    such frame the azimuth additionally skips ahead by `jump_offset`
    radians, the camera-path analogue of a scene cut. This is the workload
    the frame-coherent serving mode (`RenderEngine(incremental=True)`) is
    measured on: the smooth segments reuse almost every tile's survivor
    stream, the cuts force (and must be charged as) full recompactions.
    Deterministic, so benchmark counters diff exactly run-to-run."""
    jumps = set(jump_frames)
    cams, theta = [], start
    for i in range(n_frames):
        if i in jumps and i > 0:
            theta += jump_offset
        cams.append(orbit_camera(theta, width, height, radius=radius,
                                 center=center, fov_deg=fov_deg))
        theta += step
    return cams


# ---------------------------------------------------------------------------
# Open-loop traffic for the deadline scheduler (benchmarks/serve_slo.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request of an open-loop trace.

    `t` is the arrival time at **unit rate** (mean inter-arrival 1.0);
    `replay_open_loop` divides by the offered rate, so one trace replays
    at any load without changing its request sequence."""
    t: float
    scene: str
    width: int
    height: int
    tier: str                      # "interactive" | "batch"
    deadline_s: Optional[float]    # latency budget (None = no deadline)
    session: Optional[str]


def open_loop_trace(n_requests: int, *, seed: int = 0,
                    scenes: Sequence[str] = ("train", "truck"),
                    resolutions: Sequence[tuple[int, int]] = ((32, 32),),
                    interactive_frac: float = 0.75,
                    interactive_deadline_s: Optional[float] = None,
                    batch_deadline_s: Optional[float] = None,
                    n_sessions: int = 0,
                    theta_step: float = 2 * math.pi / 64) -> list[Arrival]:
    """A deterministic seeded open-loop arrival process: Poisson arrivals
    (exponential inter-arrival times at unit rate) over a mixed
    scene x resolution x tier x session request population.

    Same seed -> byte-identical trace (`np.random.default_rng` streams are
    versioned and the requirements pin numpy), which is what lets
    `BENCH_slo.json` commit the trace fingerprint and diff it exactly.
    Sessioned requests (when `n_sessions` > 0) walk a smooth per-session
    orbit so an incremental engine sees coherent streams; sessionless ones
    get an independent random pose each.
    """
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0, size=n_requests)
    t = np.cumsum(gaps) - gaps[0]          # first arrival at t=0
    session_theta = {f"s{i}": 0.0 for i in range(n_sessions)}
    trace = []
    for i in range(n_requests):
        scene = scenes[int(rng.integers(len(scenes)))]
        height, width = resolutions[int(rng.integers(len(resolutions)))]
        interactive = bool(rng.random() < interactive_frac)
        session = None
        if n_sessions and interactive and rng.random() < 0.5:
            session = f"s{int(rng.integers(n_sessions))}"
            theta = session_theta[session]
            session_theta[session] = theta + theta_step
        else:
            theta = float(rng.uniform(0.0, 2 * math.pi))
        trace.append(Arrival(
            t=float(t[i]), scene=scene, width=width, height=height,
            tier="interactive" if interactive else "batch",
            deadline_s=(interactive_deadline_s if interactive
                        else batch_deadline_s),
            session=session))
    return trace


def trace_fingerprint(trace: Sequence[Arrival]) -> str:
    """Hex digest of the trace's categorical sequence (scene, resolution,
    tier, session per arrival) — rate- and deadline-independent, so the
    committed artifact can gate trace determinism exactly while latency
    knobs stay machine-calibrated."""
    h = hashlib.sha256()
    for a in trace:
        h.update(f"{a.scene}|{a.width}x{a.height}|{a.tier}|"
                 f"{a.session}\n".encode())
    return h.hexdigest()[:16]


def replay_open_loop(scheduler, trace: Sequence[Arrival], *,
                     rate_rps: float) -> list[tuple[Arrival, object]]:
    """Replay a trace open-loop at `rate_rps` requests/sec: arrivals are
    submitted at their scheduled wall-clock times **regardless of
    completions** (the definition of open loop — a slow server builds a
    queue instead of slowing the clients), with `scheduler.step()`
    dispatching continuously between arrivals, then the pending set is
    drained. Returns [(arrival, future)] in arrival order; rejected
    arrivals carry a future whose exception is `AdmissionRejected`.

    Cameras are constructed for the whole trace *before* the clock
    starts: building a Camera touches jax (milliseconds per pose), and
    doing it inline would stall dispatch for hundreds of ms during
    arrival bursts — client-side work billed to the server's latency."""
    from repro.serving.scheduler import Tier
    tiers = {"interactive": Tier.INTERACTIVE, "batch": Tier.BATCH}
    cameras = [orbit_camera(_arrival_theta(a), a.width, a.height)
               for a in trace]
    out = []
    t0 = time.perf_counter()
    for a, camera in zip(trace, cameras):
        due = t0 + a.t / rate_rps
        while True:
            now = time.perf_counter()
            if now >= due:
                break
            if scheduler.pending:
                scheduler.step()       # dispatch while the clock runs
            else:
                time.sleep(min(due - now, 5e-4))
        out.append((a, scheduler.submit(
            a.scene, camera,
            deadline_s=a.deadline_s, tier=tiers[a.tier],
            session=a.session)))
    scheduler.flush()
    return out


def _arrival_theta(a: Arrival) -> float:
    """Deterministic pose angle for an arrival (hash of its identity) —
    keeps replay free of hidden RNG state so two replays of one trace
    submit identical cameras."""
    h = hashlib.sha256(
        f"{a.t}|{a.scene}|{a.session}".encode()).digest()
    return int.from_bytes(h[:4], "big") / 2**32 * 2 * math.pi


def hd1080_cameras(n: int, *, width: int = HD1080_WIDTH,
                   height: int = HD1080_HEIGHT) -> list:
    """n orbit poses at the Full-HD resolution."""
    return [orbit_camera(2 * math.pi * i / max(n, 1), width, height)
            for i in range(n)]


def register_hd1080_scene(engine: RenderEngine,
                          n_gaussians: int = HD1080_GAUSSIANS, *,
                          name: str = "hd1080",
                          n_probes: int = 2) -> str:
    """Register the Full-HD workload scene: `n_gaussians` compact-footprint
    Gaussians, k_max measured from `n_probes` orbit probes at 1920×1088.
    Returns the scene name."""
    scene = random_scene(jax.random.PRNGKey(1080), n_gaussians,
                         **HD_SCENE_KW)
    engine.register_scene(name, scene,
                          probe_cameras=hd1080_cameras(n_probes))
    return name


def hd1080_engine(n_gaussians: int = HD1080_GAUSSIANS, *,
                  k_max_pass: int = 512,
                  max_spill_passes: int = 8,
                  fused: Optional[bool] = None,
                  backend: str = "jnp",
                  **engine_kw) -> tuple[RenderEngine, str]:
    """The 1080p serving configuration in one call: a SPILL-policy engine
    (per-pass chunk `k_max_pass`, pass bucket derived per scene at render
    time) with the frame-size-aware batching policy, and the 512k-Gaussian
    HD scene registered under 'hd1080'. Returns (engine, scene_name).
    `backend` picks the CTU ("jnp" or the "pallas" PRTU kernel); other
    keywords (e.g. `shard_tiles`) go to `RenderEngine`.

    SPILL is what makes this workload servable: Full-HD survivor lists
    exceed any memory-comfortable single k_max, so overflow entries render
    in extra bounded passes instead of being clamped (or forcing a
    capacity-sized k_max). `max_spill_passes` here is only the *base plan*
    default; the engine re-derives the real pass bucket from the scene's
    measured survivor bound.
    """
    base = RenderPlan(
        test=TestConfig(backend=backend),
        stream=StreamConfig(k_max=k_max_pass, overflow=OverflowPolicy.SPILL,
                            max_spill_passes=max_spill_passes))
    engine = RenderEngine(
        base, fused=fused,
        max_batch=max_batch_for(HD1080_HEIGHT, HD1080_WIDTH), **engine_kw)
    name = register_hd1080_scene(engine, n_gaussians)
    return engine, name
