"""Composable staged render API: structured configs, `RenderPlan`, `Renderer`.

The paper's pipeline is explicitly staged (Fig. 6):

    Preprocess -> Stage 1 -> Compact -> CTU -> Blend

and this module makes those stage boundaries *the API*. Instead of one flat
config of orthogonal booleans routed through if-chains, the design space is
four structured sub-configs — one per resource the stages consume — plus a
dataflow selector:

    GridConfig    image tiling hierarchy (height/width/tile/subtile/minitile)
    TestConfig    hierarchical-test stage: method (aabb|obb|cat), leader-pixel
                  sampling mode, CTU precision scheme, spiky threshold, and
                  the stage backend ("jnp" | "pallas" — the PRTU CTU kernel)
    StreamConfig  survivor-stream resources: k_max (per-tile compacted list
                  capacity, the paper's FIFO-depth knob) and the
                  OverflowPolicy applied when a tile list exceeds it
    RasterConfig  blend stage: background color and the raster backend
                  (fused=True routes through the fused contribution-aware
                  Pallas kernel with true in-kernel early termination)

`RenderPlan` assembles them into an executable plan of stage callables with
dataclass I/O contracts:

    preprocess(scene, camera)      -> ProjectedScene
    stage1_compact(ProjectedScene) -> tuple[TileStream, ...]  (1 per pass)
    ctu(ProjectedScene, TileStream)-> StreamHierarchyOut      (per pass)
    blend(ProjectedScene, ...)     -> RenderOut (+ blend counters)

Under `OverflowPolicy.SPILL` the plan runs `StreamConfig.max_spill_passes`
compacted passes: stage1_compact emits one TileStream per pass, the CTU
tests each pass's entries, and the blend folds the passes through a carried
`raster.BlendState` — overflow entries render (bit-identical to the dense
oracle) instead of being clamped, with per-pass memory at the k_max size.

The plan is a frozen dataclass of frozen sub-configs: hashable and
value-equal, so it doubles as the jit-cache key in `serving.RenderEngine`.
`Renderer` is the user-facing facade over a plan.

The legacy flat `core.pipeline.RenderConfig` and its module-level
`render`/`render_with_stats`/`render_batch_with_stats` entry points remain as
deprecation shims that build the equivalent plan (`RenderConfig.to_plan`),
bit-matching this API on every image and workload counter.
"""
from __future__ import annotations

import dataclasses
import enum
import math
import warnings
from typing import Optional, TYPE_CHECKING

if TYPE_CHECKING:
    from repro.lod.build import LODScene
    from repro.lod.config import LODConfig

import jax
import jax.numpy as jnp

from repro.core.gaussians import GaussianScene, Projected, project, \
    classify_spiky
from repro.obs import trace as obs_trace
from repro.core.culling import TileGrid, aabb_mask
from repro.core.cat import SamplingMode
from repro.core import hierarchy as H
from repro.core import raster
from repro.core.precision import PrecisionScheme, MIXED

BACKENDS = ("jnp", "pallas")


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1)."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


# ---------------------------------------------------------------------------
# Structured per-stage configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GridConfig:
    """Image tiling hierarchy (the Preprocess/Stage-1 spatial layout)."""
    height: int = 128
    width: int = 128
    tile: int = 16
    subtile: int = 8
    minitile: int = 4

    def make(self) -> TileGrid:
        return TileGrid(self.height, self.width, self.tile, self.subtile,
                        self.minitile)

    def with_resolution(self, height: int, width: int) -> "GridConfig":
        return dataclasses.replace(self, height=height, width=width)


@dataclasses.dataclass(frozen=True)
class TestConfig:
    """Hierarchical-test stage (Stage-1 AABB + Mini-Tile CAT in the CTU)."""
    __test__ = False          # "Test" prefix: keep pytest collection away
    method: str = "cat"                       # aabb | obb | cat
    mode: SamplingMode = SamplingMode.SMOOTH_FOCUSED
    precision: PrecisionScheme = MIXED
    spiky_threshold: float = 3.0
    backend: str = "jnp"                      # jnp | pallas (PRTU kernel)

    def __post_init__(self):
        if self.method not in ("aabb", "obb", "cat"):
            raise ValueError(f"unknown method {self.method!r} "
                             "(expected 'aabb', 'obb' or 'cat')")
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown test backend {self.backend!r} "
                             f"(expected one of {BACKENDS})")


class OverflowPolicy(enum.Enum):
    """What to do when a tile's Stage-1 survivor list exceeds `k_max`.

    CLAMP/WARN/RAISE drop entries past k_max in-graph (jit-compiled code
    cannot branch on a traced overflow bit); WARN and RAISE are enforced
    wherever the overflow flag becomes concrete: in eager `Renderer` calls
    and, for serving traffic, per frame in
    `serving.RenderEngine.render_batch` (which also counts `overflow_frames`
    in telemetry).

    SPILL renders the overflow entries instead of dropping them: Stage-1
    compaction emits up to `StreamConfig.max_spill_passes` per-tile lists of
    k_max entries each (pass p holds survivors p*k_max..(p+1)*k_max-1), the
    CTU tests each pass's entries, and the blend folds the passes
    front-to-back through a carried `raster.BlendState` — bit-identical to
    a single pass over the concatenated lists, hence to the dense oracle.
    Per-pass working memory stays at the k_max size (that is the point: the
    cap becomes a bounded-memory streaming knob, not a correctness hazard).
    The overflow flag then only fires when the *total* capacity
    (max_spill_passes * k_max) is exceeded, which warns like WARN.
    """
    CLAMP = "clamp"
    WARN = "warn"
    RAISE = "raise"
    SPILL = "spill"


class StreamOverflowWarning(RuntimeWarning):
    """A frame's Stage-1 tile list overflowed k_max and was clamped."""


class StreamOverflowError(RuntimeError):
    """A frame's Stage-1 tile list overflowed k_max under OverflowPolicy.RAISE."""


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Survivor-stream resources (Compact stage).

    k_max is the per-tile list capacity *per pass*; under
    `OverflowPolicy.SPILL` up to `max_spill_passes` passes run, so the
    total per-tile capacity is k_max * max_spill_passes (other policies
    always run exactly one pass and ignore `max_spill_passes`). Passes are
    static shapes: a spill plan always executes its configured pass count
    in-graph — empty trailing passes blend nothing — which is what lets
    the serving engine key its jit cache on the (bucketed) pass count.
    """
    k_max: int = 1024                         # per-tile list capacity / pass
    overflow: OverflowPolicy = OverflowPolicy.CLAMP
    max_spill_passes: int = 4                 # total passes under SPILL

    def __post_init__(self):
        if not isinstance(self.overflow, OverflowPolicy):
            object.__setattr__(self, "overflow",
                               OverflowPolicy(self.overflow))
        if self.max_spill_passes < 1:
            raise ValueError(
                f"max_spill_passes must be >= 1, got {self.max_spill_passes}")


@dataclasses.dataclass(frozen=True)
class RasterConfig:
    """Blend stage (VRU array)."""
    background: float = 0.0
    fused: bool = False                       # fused contribution-aware kernel

    @property
    def backend(self) -> str:
        """The blend backend: the fused path is the Pallas raster kernel."""
        return "pallas" if self.fused else "jnp"


@dataclasses.dataclass(frozen=True)
class ShardConfig:
    """Tile-axis device sharding of the post-Stage-1 pipeline.

    With tile_shards > 1 the plan partitions the per-tile survivor streams
    (`TileStream` rows, every spill pass) into `tile_shards` contiguous
    blocks over the mesh axis the logical `axis` resolves to
    (`distributed.sharding.resolve`; "tile" -> the `model` mesh axis) and
    runs CTU + blend per shard under `shard_map`, gathering exactly once at
    `raster.untile` — the multi-PRTU parallel datapath of the paper, mapped
    onto devices. Tiles are independent after compaction, so the sharded
    render is bit-identical to the single-device path on images,
    `entry_alive` and every additive counter.

    Requirements: the stream dataflow with the CAT method, a tile count
    divisible by tile_shards, an active mesh (`distributed.sharding.use_mesh`
    or `serving.RenderEngine(shard_tiles=...)`) whose resolved axis has size
    tile_shards, and execution under `jax.jit`. Part of the plan hash, so
    the serving jit cache keys on it like every other stage config.
    """
    tile_shards: int = 1
    axis: str = "tile"

    def __post_init__(self):
        if self.tile_shards < 1:
            raise ValueError(
                f"tile_shards must be >= 1, got {self.tile_shards}")


# ---------------------------------------------------------------------------
# Stage I/O contracts
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ProjectedScene:
    """Preprocess-stage output: screen-space Gaussians + the tile grid."""
    proj: Projected
    grid: TileGrid


@dataclasses.dataclass(frozen=True)
class TileStream:
    """One compacted pass of per-tile depth-ordered survivor streams.

    `stage1_compact` emits a tuple of these — one per spill pass (length 1
    unless the plan's overflow policy is SPILL). Pass `index` holds
    survivors index*k_max..(index+1)*k_max-1 of each tile's depth-ordered
    list; `overflow` is the *global* flag (total capacity exceeded),
    identical in every pass of a frame.

    `dense` carries the full-mask `HierarchyOut` on the dense parity
    dataflow (the oracle computes every mask up front); `baseline_mini` and
    `counters` carry the non-CAT baselines' mini-tile mask / workload
    counters. All three are None on the stream dataflow, where nothing of
    shape (regions, N) survives past compaction; on multi-pass plans they
    are shared (the same arrays) across the passes.
    """
    lists: jax.Array                          # (T, K) int32 gaussian ids
    valid: jax.Array                          # (T, K) bool
    overflow: jax.Array                       # () bool
    dense: Optional[H.HierarchyOut] = None
    baseline_mini: Optional[jax.Array] = None
    counters: Optional[dict] = None
    index: int = 0                            # spill pass index (0-based)


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """Introspection record for one plan stage."""
    name: str
    backend: str
    description: str


# ---------------------------------------------------------------------------
# RenderPlan: the assembled stage pipeline
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RenderPlan:
    """An executable, hashable composition of the render stages.

    dataflow selects how the hierarchy materializes between Stage 1 and the
    CTU: "stream" (default — compact first, CTU on survivors only,
    O(T·k_max·16) masks) or "dense" (the O(regions×N) parity oracle).
    Plans are value-equal frozen dataclasses, so a plan is directly usable
    as a jit-cache key (`serving.RenderEngine` does exactly that).

    lod (default None) attaches the optional camera-dependent LOD stage
    (`repro.lod.LODConfig`): `render_lod_with_stats` selects clusters and
    gathers a pow2-bucketed sub-scene before Stage-1. With lod=None every
    other entry point is bit-identical to a plan without the field — the
    LOD stage only exists on the `render_lod_with_stats` path.
    """
    grid: GridConfig = GridConfig()
    test: TestConfig = TestConfig()
    stream: StreamConfig = StreamConfig()
    raster: RasterConfig = RasterConfig()
    dataflow: str = "stream"                  # stream | dense
    shard: ShardConfig = ShardConfig()
    lod: Optional["LODConfig"] = None

    def __post_init__(self):
        if self.dataflow not in ("stream", "dense"):
            raise ValueError(f"unknown dataflow {self.dataflow!r} "
                             "(expected 'stream' or 'dense')")
        if self.shard.tile_shards > 1:
            if self.dataflow != "stream" or self.test.method != "cat":
                raise ValueError(
                    "tile sharding requires the stream dataflow with the "
                    f"'cat' method (got dataflow={self.dataflow!r}, "
                    f"method={self.test.method!r}) — the dense oracle and "
                    "the baselines materialize (regions, N) masks that the "
                    "per-tile partitioning cannot split")

    # -- stage callables ----------------------------------------------------

    def preprocess(self, scene: GaussianScene, camera) -> ProjectedScene:
        """Projection + 3σ screen-space footprints (preprocessing core)."""
        return ProjectedScene(proj=project(scene, camera),
                              grid=self.grid.make())

    @property
    def n_passes(self) -> int:
        """Static spill pass count: max_spill_passes under SPILL, else 1."""
        return (self.stream.max_spill_passes
                if self.stream.overflow is OverflowPolicy.SPILL else 1)

    def stage1_compact(self, ps: ProjectedScene) -> tuple[TileStream, ...]:
        """Stage-1 test + depth sort + per-tile list compaction.

        Returns one `TileStream` per spill pass (a 1-tuple unless the
        overflow policy is SPILL): pass p holds survivors
        p*k_max..(p+1)*k_max-1 of each tile's depth-ordered list, so the
        concatenation of the passes equals a single k_max*n_passes
        compaction.

        stream: tile-level AABB only (== OR of the tile's sub-tile AABBs),
        fused into the chunked compaction so the transient (T, N) mask
        materializes one tile block at a time.
        dense:  the full dense hierarchy runs here (the oracle needs every
        mask anyway) and the tile lists derive from its sub-tile bits.
        baselines: `hierarchy.baseline_masks` for the method.
        """
        proj, grid = ps.proj, ps.grid
        k_max = self.stream.k_max
        n_passes = self.n_passes

        def as_streams(lists, valid, overflow, **shared):
            return tuple(
                TileStream(lists[p], valid[p], overflow, index=p, **shared)
                for p in range(n_passes))

        if self.test.method != "cat":
            tile_mask, mini_mask, counters = H.baseline_masks(
                proj, grid, self.test.method)
            order = raster.depth_order(proj)
            lists, valid, overflow = raster.compact_tile_lists_passes(
                tile_mask, order, k_max, n_passes)
            return as_streams(lists, valid, overflow,
                              baseline_mini=mini_mask, counters=counters)
        if self.dataflow == "dense":
            if self.test.backend == "pallas":
                from repro.kernels import ops as kops
                hout = kops.hierarchical_test_pallas(
                    proj, grid, self.test.mode, self.test.precision,
                    self.test.spiky_threshold)
            else:
                hout = H.hierarchical_test(
                    proj, grid, self.test.mode, self.test.precision,
                    self.test.spiky_threshold)
            # The CTU's input stream: Stage-1 survivors per tile.
            sub_of_tile = grid.tile_of_region(grid.subtile)          # (S,)
            stage1_tile = jax.ops.segment_sum(
                hout.subtile_mask.astype(jnp.int32), sub_of_tile,
                num_segments=grid.num_tiles) > 0                     # (T, N)
            order = raster.depth_order(proj)
            lists, valid, overflow = raster.compact_tile_lists_passes(
                stage1_tile, order, k_max, n_passes)
            return as_streams(lists, valid, overflow, dense=hout)
        # stream
        order = raster.depth_order(proj)
        lists, valid, overflow = raster.compact_aabb_tile_lists(
            proj, grid, order, k_max, n_passes)
        return as_streams(lists, valid, overflow)

    def ctu(self, ps: ProjectedScene, ts: TileStream) -> H.StreamHierarchyOut:
        """Per-entry hierarchical testing (the queue-fed CTU of Fig. 6).

        stream: Stage-1 sub-tile bits + Mini-Tile CAT evaluated on list
        entries only (`hierarchy.stream_entry_test`; the Pallas backend
        routes the CAT through the entry-gridded PRTU kernel).
        dense/baselines: the masks already exist — gather them at the
        compacted entries (`raster.entry_mask_from_dense`).
        """
        proj, grid = ps.proj, ps.grid
        if self.test.method != "cat":
            entry = (None if ts.baseline_mini is None else
                     raster.entry_mask_from_dense(grid, ts.baseline_mini,
                                                  ts.lists))
            return H.StreamHierarchyOut(
                lists=ts.lists, valid=ts.valid, entry_sub_mask=None,
                entry_mini_mask=entry, overflow=ts.overflow,
                counters=ts.counters)
        if self.dataflow == "dense":
            entry = raster.entry_mask_from_dense(grid, ts.dense.minitile_mask,
                                                 ts.lists)
            return H.StreamHierarchyOut(
                lists=ts.lists, valid=ts.valid, entry_sub_mask=None,
                entry_mini_mask=entry, overflow=ts.overflow,
                counters=ts.dense.counters)
        if self.test.backend == "pallas":
            from repro.kernels import ops as kops
            cat_fn = kops.entry_cat_fn(self.test.mode, self.test.precision,
                                       self.test.spiky_threshold)
        else:
            cat_fn = None
        return H.stream_entry_test(
            proj, grid, ts.lists, ts.valid, ts.overflow, self.test.mode,
            self.test.precision, self.test.spiky_threshold, cat_fn=cat_fn)

    def blend(self, ps: ProjectedScene, hout: H.StreamHierarchyOut):
        """Blend stage, single pass: (RenderOut, blend counters dict).

        fused=False: the pure-jnp differentiable rasterizer (early
        termination modeled by counters); fused=True: the Pallas kernel with
        true in-kernel termination and kernel-measured counters. Multi-pass
        (SPILL) plans blend through `_blend_passes`, which folds each pass
        into the carried blend state; this method is the 1-pass view of it.
        """
        out, counters, _ = self._blend_passes(ps, [hout])
        return out, counters

    def _blend_passes(self, ps: ProjectedScene, houts, tracer=None):
        """Blend the spill passes front-to-back from one carried state.

        Returns (RenderOut, blend counters dict, per-pass entry_alive list).
        The RenderOut's entry_alive concatenates the passes along K, so it
        lines up entry-for-entry with a single dense pass of the same total
        capacity.

        Each pass's fold is bracketed by a host-side `blend` span (see
        `repro.obs.trace`): the unfused path runs the same
        init -> `raster.blend_pass` per pass -> `raster.finalize_blend`
        sequence `raster.render_tiles` composes, so the per-pass spans cost
        nothing and the output stays bit-identical.
        """
        if tracer is None:
            tracer = obs_trace.current()
        proj, grid = ps.proj, ps.grid
        live = tracer.enabled and not obs_trace.is_traced(proj)
        counters: dict = {}
        if self.raster.fused:
            from repro.kernels import ops as kops
            out, fused_counters = kops.render_tiles_fused_passes(
                proj, grid,
                [(h.lists, h.valid, h.entry_mini_mask) for h in houts],
                self.raster.background, houts[0].overflow,
                span_cb=lambda i: tracer.span(
                    "blend", {"pass": i, "backend": "pallas"}))
            counters.update(fused_counters)
            k = houts[0].lists.shape[1]
            alive_parts = [out.entry_alive[:, i * k:(i + 1) * k]
                           for i in range(len(houts))]
        else:
            state = raster.init_blend_state(grid.num_tiles, grid.tile ** 2)
            alive_parts = []
            prev_proc = prev_blend = 0.0
            for i, h in enumerate(houts):
                with tracer.span("blend",
                                 {"pass": i, "backend": "jnp"}) as sp:
                    state, alive = raster.blend_pass(
                        proj, grid, h.lists, h.valid, h.entry_mini_mask,
                        state)
                    tracer.block((state, alive))
                    if live:
                        proc = float(jnp.sum(state.processed))
                        blend = float(jnp.sum(state.blended))
                        sp.set(processed_delta=proc - prev_proc,
                               blended_delta=blend - prev_blend,
                               entries_alive=float(jnp.sum(alive)))
                        prev_proc, prev_blend = proc, blend
                alive_parts.append(alive)
            entry_alive = (alive_parts[0] if len(alive_parts) == 1
                           else jnp.concatenate(alive_parts, axis=1))
            out = raster.finalize_blend(grid, state, self.raster.background,
                                        houts[0].overflow, entry_alive)
            # The unfused sweep always walks every padded list slot.
            counters["swept_per_pixel"] = jnp.asarray(
                float(sum(h.lists.shape[1] for h in houts)), jnp.float32)
        counters["processed_per_pixel"] = jnp.mean(out.processed_per_pixel)
        counters["blended_per_pixel"] = jnp.mean(out.blended_per_pixel)
        return out, counters, alive_parts

    def _merge_hout_counters(self, houts) -> dict:
        """Fold per-pass CTU counters into frame totals.

        Stream-dataflow CAT counters are per-entry sums — additive across
        passes (`hierarchy.ADDITIVE_COUNTER_KEYS`). Dense-oracle and
        baseline counters are full-mask sums, identical in every pass, so
        pass 0's dict already is the total.
        """
        counters = dict(houts[0].counters)
        if self.dataflow == "stream" and self.test.method == "cat":
            for h in houts[1:]:
                for key in H.ADDITIVE_COUNTER_KEYS:
                    counters[key] = counters[key] + h.counters[key]
        return counters

    # -- composition --------------------------------------------------------

    def render_with_stats(self, scene: GaussianScene, camera):
        """Run the full plan: returns (RenderOut, counters dict).

        Under SPILL this is the multi-pass loop of the staged API: one CTU
        evaluation and one blend fold per compacted pass, sharing a single
        carried `raster.BlendState` — so overflow entries render instead of
        being clamped, while per-pass mask memory stays at the k_max size.

        Every call emits a host-side span tree on the active tracer
        (`repro.obs.trace`, NoopTracer by default = zero cost):

            render
            ├── preprocess
            ├── stage1_compact
            ├── ctu   [pass=i]   (x n_passes, with that pass's CTU counters)
            ├── blend [pass=i]   (x n_passes, with processed/blended deltas)
            └── finalize

        Span walls are `jax.block_until_ready`-bounded on eager (concrete)
        renders; under jit/vmap tracing the spans carry `traced=True` and
        measure trace time (the compile side of the compile-vs-execute
        split — see docs/observability.md). `plan_first_call` on the root
        marks the first render this tracer saw for this exact plan.
        """
        tracer = obs_trace.current()
        with tracer.span("render") as root:
            live = tracer.enabled and not obs_trace.is_traced(
                (scene, camera))
            if tracer.enabled:
                root.set(dataflow=self.dataflow, method=self.test.method,
                         k_max=self.stream.k_max, n_passes=self.n_passes,
                         overflow_policy=self.stream.overflow.value,
                         fused=self.raster.fused,
                         tile_shards=self.shard.tile_shards,
                         height=self.grid.height, width=self.grid.width,
                         plan_first_call=tracer.mark_first(self),
                         traced=not live)
            with tracer.span("preprocess") as sp:
                ps = self.preprocess(scene, camera)
                tracer.block(ps)
                if tracer.enabled:
                    sp.set(n_gaussians=int(ps.proj.depth.shape[0]),
                           tiles=int(ps.grid.num_tiles))
            with tracer.span("stage1_compact") as sp:
                streams = self.stage1_compact(ps)
                tracer.block(streams)
                if live:
                    sp.set(survivors_per_pass=[
                        float(jnp.sum(ts.valid)) for ts in streams],
                        overflow=bool(streams[0].overflow))
            out, counters = self._render_streams(ps, streams, tracer,
                                                 root=root)
        return out, counters

    def _render_streams(self, ps: ProjectedScene, streams, tracer,
                        root=None):
        """The shared post-Stage-1 tail: CTU per pass, counter merge, blend
        fold, finalize. `render_with_stats` runs it after `stage1_compact`;
        `core.coherence`'s incremental programs run it after rebuilding the
        streams from a `FrameCache` — one body, so the two paths cannot
        diverge. With `ShardConfig.tile_shards > 1` the tail runs
        tile-sharded over the active mesh (`_render_streams_sharded`,
        bit-identical output). Returns (RenderOut, counters dict)."""
        if self.shard.tile_shards > 1:
            return self._render_streams_sharded(ps, streams, tracer,
                                                root=root)
        live = tracer.enabled and not obs_trace.is_traced(ps.proj)
        houts = []
        for ts in streams:
            with tracer.span("ctu", {"pass": ts.index}) as sp:
                hout = self.ctu(ps, ts)
                tracer.block(hout)
                if live and hout.counters:
                    sp.set(**{k: float(v)
                              for k, v in hout.counters.items()
                              if jnp.ndim(v) == 0})
            houts.append(hout)
        counters = self._merge_hout_counters(houts)
        if self.test.method == "cat":
            counters["cat_mask_bytes"] = jnp.asarray(
                float(cat_mask_elems(ps.grid, ps.proj.depth.shape[0],
                                     self.stream.k_max, self.dataflow)),
                jnp.float32)
        out, blend_counters, alive_parts = self._blend_passes(
            ps, houts, tracer)
        with tracer.span("finalize") as sp:
            counters.update(blend_counters)
            if self.test.method == "cat":
                eff: dict = {}
                for ts, hout, alive in zip(streams, houts, alive_parts):
                    for key, v in self._effective_counters(
                            ps, ts, hout, alive).items():
                        eff[key] = v if key not in eff else eff[key] + v
                counters.update(eff)
            # How many passes actually carried entries (>= 1 even on an
            # empty frame, so the counter always reads as a pass count).
            counters["spill_passes"] = jnp.maximum(
                sum(jnp.any(h.valid) for h in houts),
                1).astype(jnp.float32)
            tracer.block((out, counters))
            if live:
                sp.set(spill_passes=float(counters["spill_passes"]),
                       overflow=bool(out.overflow))
                if root is not None:
                    root.set(**{k: float(counters[k]) for k in
                                ("processed_per_pixel", "blended_per_pixel",
                                 "vru_pairs", "spill_passes")
                                if k in counters and
                                jnp.ndim(counters[k]) == 0})
            enforce_overflow_policy(out.overflow, self.stream.overflow,
                                    k_max=self.stream.k_max,
                                    n_passes=self.n_passes)
        return out, counters

    # -- tile-row primitives (single-shard body = single-device row subset) --

    def _ctu_tile_rows(self, proj: Projected, grid, lists, valid,
                       tile_origins):
        """CTU on a block of tile rows: per-entry CAT mask + hit counts.

        The per-shard body of the tile-sharded CTU and the row kernel of
        `render_tile_subset` — the same math `hierarchy.stream_entry_test`
        runs on the full grid, restricted to the rows whose origins are
        given. Returns (entry_mini (B, K, Mt) bool, sub_hits (B, K) int32,
        mini_hits (B, K) int32).
        """
        entry_sub = H.entry_subtile_mask(proj, grid, lists, valid,
                                         tile_origins=tile_origins)
        if self.test.backend == "pallas":
            from repro.kernels import ops as kops
            cat = kops.entry_cat_mask_pallas(
                proj, grid, lists, valid, self.test.mode,
                self.test.precision, self.test.spiky_threshold,
                tile_origins=tile_origins)
        else:
            from repro.core.cat import entry_cat_mask
            cat = entry_cat_mask(proj, grid, lists, valid, self.test.mode,
                                 self.test.precision,
                                 self.test.spiky_threshold,
                                 tile_origins=tile_origins)
        gate = entry_sub[:, :, grid.subtile_of_minitile_local()]
        entry_mini = cat & gate & valid[:, :, None]
        sub_hits = jnp.sum(entry_sub, axis=-1).astype(jnp.int32)
        mini_hits = jnp.sum(entry_mini, axis=-1).astype(jnp.int32)
        return entry_mini, sub_hits, mini_hits

    def _blend_tile_rows(self, proj: Projected, grid, pass_rows,
                         tile_origins):
        """Blend fold over the spill passes on a block of tile rows.

        pass_rows: [(lists, valid, entry_mini), ...] per pass, rows matching
        `tile_origins`. Returns (state, alive_parts, kblock_rows):
        state is the fused (trans, rgb, processed, blended) carry or the
        unfused `raster.BlendState`; alive_parts is the per-pass (B, K)
        entry_alive list; kblock_rows the per-pass (B,) kblocks_processed
        list on the fused path (None unfused). Tiles blend independently,
        so these rows equal the same rows of the full-grid fold exactly.
        """
        if self.raster.fused:
            from repro.kernels import ops as kops
            state, alive, kproc = None, [], []
            for lists, valid, mini in pass_rows:
                fb = kops.blend_tiles_fused_pallas(
                    proj, grid, lists, valid, mini, init=state,
                    tile_origins=tile_origins)
                state = (fb.trans, fb.rgb, fb.processed, fb.blended)
                alive.append(fb.entry_alive)
                kproc.append(fb.kblocks_processed)
            return state, alive, kproc
        state = raster.init_blend_state(tile_origins.shape[0],
                                        grid.tile ** 2)
        alive = []
        for lists, valid, mini in pass_rows:
            state, a = raster.blend_pass(proj, grid, lists, valid, mini,
                                         state, tile_origins=tile_origins)
            alive.append(a)
        return state, alive, None

    def _render_streams_sharded(self, ps: ProjectedScene, streams, tracer,
                                root=None):
        """Tile-sharded post-Stage-1 tail: shard_map over the tile axis.

        The per-tile survivor streams of every spill pass are partitioned
        into `shard.tile_shards` contiguous row blocks over the mesh axis
        the logical shard axis resolves to; each shard runs CTU -> blend on
        its rows (the shard x pass grid), emitting its blend-state rows,
        entry_alive rows and integer per-entry hit counts. One gather (a
        replicate constraint — integers and per-tile floats move exactly)
        then feeds the identical finalize arithmetic the single-device path
        runs at `raster.untile`, and the counters are evaluated by the very
        same expressions on the gathered hit counts
        (`hierarchy.stream_entry_counters`) — which is why the sharded
        render is bit-identical on images, entry_alive and every additive
        counter.

        The shard_map is manual over every mesh axis: Mosaic-compiled
        kernels (the Pallas CTU and blend) cannot be partitioned over an
        auto axis. Frame x tile composition comes from
        `render_batch_with_stats`, whose vmap maps the frame axis onto the
        mesh's data axes (`spmd_axis_name`), so a frame batch sharded over
        "data" keeps its placement while tiles split over "model". Runs
        must be under `jax.jit` (the serving engine always is).
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import sharding as dshard

        proj, grid = ps.proj, ps.grid
        s = self.shard.tile_shards
        mesh = dshard.active_mesh()
        if mesh is None:
            raise RuntimeError(
                f"RenderPlan has shard.tile_shards={s} but no active mesh; "
                "wrap the jitted render in "
                "distributed.sharding.use_mesh(mesh) (serving.RenderEngine "
                "does this when constructed with shard_tiles)")
        axes = dshard.resolve((self.shard.axis,), mesh)[0]
        axes_tuple = (axes,) if isinstance(axes, str) else tuple(axes)
        axis_size = math.prod(mesh.shape[a] for a in axes_tuple)
        if axis_size != s:
            raise ValueError(
                f"shard.tile_shards={s} but the mesh's "
                f"{self.shard.axis!r} axis ({axes_tuple} on mesh "
                f"{dict(mesh.shape)}) has size {axis_size}")
        if grid.num_tiles % s != 0:
            raise ValueError(
                f"num_tiles={grid.num_tiles} is not divisible by "
                f"tile_shards={s}")
        if not isinstance(proj.depth, jax.core.Tracer):
            raise RuntimeError(
                "tile-sharded rendering must run under jax.jit (wrap the "
                "render in jax.jit, or use serving.RenderEngine which "
                "always jits)")

        n_passes = len(streams)
        k = streams[0].lists.shape[1]
        lists_all = jnp.stack([ts.lists for ts in streams])   # (n_p, T, K)
        valid_all = jnp.stack([ts.valid for ts in streams])
        t_origins = grid.tile_origins()                       # (T, 2) int
        tile_spec, pass_spec = P(axes), P(None, axes)

        def body(proj_s, t_orig, lists_s, valid_s):
            pass_rows, subs, minis = [], [], []
            for p in range(n_passes):
                with tracer.span("ctu", {"pass": p, "sharded": True,
                                         "tile_shards": s}):
                    mini, sub_h, mini_h = self._ctu_tile_rows(
                        proj_s, grid, lists_s[p], valid_s[p], t_orig)
                pass_rows.append((lists_s[p], valid_s[p], mini))
                subs.append(sub_h)
                minis.append(mini_h)
            with tracer.span("blend", {"sharded": True, "tile_shards": s,
                                       "backend": self.raster.backend}):
                state, alive, kproc = self._blend_tile_rows(
                    proj_s, grid, pass_rows, t_orig)
            out = dict(state=tuple(state), alive=jnp.stack(alive),
                       sub_hits=jnp.stack(subs),
                       mini_hits=jnp.stack(minis))
            if kproc is not None:
                out["kproc"] = jnp.stack(kproc)
            return out

        out_specs = dict(state=tile_spec, alive=pass_spec,
                         sub_hits=pass_spec, mini_hits=pass_spec)
        if self.raster.fused:
            out_specs["kproc"] = pass_spec
        shard_out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), tile_spec, pass_spec, pass_spec),
            out_specs=out_specs, check_vma=False)(
                proj, t_origins, lists_all, valid_all)

        # The single gather: replicate the per-shard rows (ints and
        # independent per-tile floats — exact), then finalize and count on
        # the full arrays with the same expressions as the unsharded path.
        rep = NamedSharding(mesh, P())
        shard_out = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, rep), shard_out)
        sub_hits, mini_hits = shard_out["sub_hits"], shard_out["mini_hits"]
        alive_parts = [shard_out["alive"][p] for p in range(n_passes)]
        entry_alive = (alive_parts[0] if n_passes == 1
                       else jnp.concatenate(alive_parts, axis=1))

        pass_counters = [
            H.stream_entry_counters(proj, grid, streams[p].lists,
                                    streams[p].valid, sub_hits[p],
                                    mini_hits[p], self.test.mode,
                                    self.test.spiky_threshold)
            for p in range(n_passes)]
        counters = dict(pass_counters[0])
        for c in pass_counters[1:]:
            for key in H.ADDITIVE_COUNTER_KEYS:
                counters[key] = counters[key] + c[key]
        counters["cat_mask_bytes"] = jnp.asarray(
            float(cat_mask_elems(grid, proj.depth.shape[0],
                                 self.stream.k_max, self.dataflow)),
            jnp.float32)

        if self.raster.fused:
            from repro.kernels import ops as kops
            from repro.kernels import render as krender
            kproc = jnp.sum(shard_out["kproc"]).astype(jnp.float32)
            kb_total = n_passes * (-(-k // krender.K_BLK))
            out, blend_counters = kops.finalize_fused_passes(
                grid, shard_out["state"], self.raster.background,
                streams[0].overflow, entry_alive, kproc, kb_total)
        else:
            state = raster.BlendState(*shard_out["state"])
            out = raster.finalize_blend(grid, state, self.raster.background,
                                        streams[0].overflow, entry_alive)
            blend_counters = {"swept_per_pixel": jnp.asarray(
                float(n_passes * k), jnp.float32)}
        blend_counters["processed_per_pixel"] = jnp.mean(
            out.processed_per_pixel)
        blend_counters["blended_per_pixel"] = jnp.mean(
            out.blended_per_pixel)

        with tracer.span("finalize") as sp:
            counters.update(blend_counters)
            eff: dict = {}
            for p in range(n_passes):
                for key, v in self._effective_counters_from_hits(
                        proj, streams[p].lists, sub_hits[p], mini_hits[p],
                        alive_parts[p]).items():
                    eff[key] = v if key not in eff else eff[key] + v
            counters.update(eff)
            counters["spill_passes"] = jnp.maximum(
                sum(jnp.any(ts.valid) for ts in streams),
                1).astype(jnp.float32)
            # Shard-occupancy accounting: how evenly the survivor entries
            # split over the shards (contiguous tile blocks). max == min is
            # a perfectly balanced frame; the serving telemetry turns these
            # into per-shard occupancy gauges.
            per_shard = jnp.sum(
                valid_all.reshape(n_passes, s, grid.num_tiles // s, k),
                axis=(0, 2, 3))
            counters["tile_shards"] = jnp.asarray(float(s), jnp.float32)
            counters["shard_entries_max"] = jnp.max(per_shard).astype(
                jnp.float32)
            counters["shard_entries_min"] = jnp.min(per_shard).astype(
                jnp.float32)
            if tracer.enabled:
                sp.set(tile_shards=s, sharded=True)
            tracer.block((out, counters))
            enforce_overflow_policy(out.overflow, self.stream.overflow,
                                    k_max=self.stream.k_max,
                                    n_passes=self.n_passes)
        return out, counters

    def render_tile_subset(self, scene: GaussianScene, camera, tile_ids):
        """Single-device re-render of a subset of tiles (by row index).

        The shard-recovery path: when a tile shard is lost mid-frame, the
        survivors re-run exactly the lost rows — preprocess and Stage-1 are
        recomputed (they were never sharded), then CTU + blend on the
        selected rows only. Tiles are independent, so each returned row
        equals the same row of the full render bit-for-bit, which is what
        lets `distributed.fault.render_with_shard_recovery` splice them
        into the healthy frame under a parity gate.

        tile_ids: (B,) int tile indices. Returns a dict of per-tile rows —
        image (B, P, 3), alpha (B, P), processed (B, P), blended (B, P)
        (floats, post-background/finalize), entry_alive (B, n_passes*K).
        """
        if self.dataflow != "stream" or self.test.method != "cat":
            raise ValueError(
                "render_tile_subset requires the stream dataflow with the "
                "'cat' method (the row-wise CTU has no dense/baseline form)")
        ps = self.preprocess(scene, camera)
        streams = self.stage1_compact(ps)
        proj, grid = ps.proj, ps.grid
        tile_ids = jnp.asarray(tile_ids, jnp.int32)
        t_orig = grid.tile_origins()[tile_ids]
        pass_rows = []
        for ts in streams:
            lists, valid = ts.lists[tile_ids], ts.valid[tile_ids]
            mini, _, _ = self._ctu_tile_rows(proj, grid, lists, valid,
                                             t_orig)
            pass_rows.append((lists, valid, mini))
        state, alive, _ = self._blend_tile_rows(proj, grid, pass_rows,
                                                t_orig)
        entry_alive = (alive[0] if len(alive) == 1
                       else jnp.concatenate(alive, axis=1))
        bg = self.raster.background
        if self.raster.fused:
            trans, rgb, processed, blended = state
            acc = 1.0 - trans
            rgb = rgb + bg * trans[:, :, None]
        else:
            rgb = state.rgb + bg * (1.0 - state.acc)[..., None]
            acc = state.acc
            processed = state.processed.astype(jnp.float32)
            blended = state.blended.astype(jnp.float32)
        return dict(image=rgb, alpha=acc, processed=processed,
                    blended=blended, entry_alive=entry_alive)

    def render(self, scene: GaussianScene, camera) -> raster.RenderOut:
        out, _ = self.render_with_stats(scene, camera)
        return out

    def render_incremental(self, scene: GaussianScene, camera, cache=None,
                           cfg=None, **kw):
        """Frame-coherent render: reuse the previous frame's per-tile
        survivor streams for every tile whose Stage-1 candidate set is
        provably unchanged, recompacting only the rest (bit-identical to
        `render_with_stats` under jit — see `core.coherence`).

        cache: the `coherence.FrameCache` returned by the previous call
        (None = cold start, a full recompaction that seeds one).
        cfg: a `coherence.CoherenceConfig` (fallback thresholds).
        Returns (RenderOut, counters, FrameCache).
        """
        from repro.core import coherence
        return coherence.render_incremental(self, scene, camera, cache=cache,
                                            cfg=cfg, **kw)

    def render_batch_with_stats(self, scene: GaussianScene, cameras):
        """Render a batch of camera poses of one scene in one vmapped call.

        cameras: a batched `core.camera.Camera` pytree (leading frame axis on
        every array leaf — build it with `core.camera.stack_cameras`); its
        static height/width must match the plan's grid. Frames are
        independent, so the result equals `render_with_stats` per camera;
        batching buys SIMD width and compile reuse. Returns (RenderOut with a
        leading frame axis, counters dict of (B,) arrays).
        """
        if (cameras.height, cameras.width) != (self.grid.height,
                                               self.grid.width):
            raise ValueError(
                f"camera resolution {(cameras.height, cameras.width)} != "
                f"plan grid {(self.grid.height, self.grid.width)}")
        spmd_axes = None
        if self.shard.tile_shards > 1:
            # The tile shard_map is manual over the data axes too: map the
            # frame axis onto them here (see `_render_streams_sharded`).
            from repro.distributed import sharding as dshard
            mesh = dshard.active_mesh()
            if mesh is not None:
                spmd_axes = dshard.dp_axes(mesh) or None
        out, counters = jax.vmap(
            lambda cam: self.render_with_stats(scene, cam),
            spmd_axis_name=spmd_axes)(cameras)
        enforce_overflow_policy(jnp.any(out.overflow), self.stream.overflow,
                                k_max=self.stream.k_max,
                                n_passes=self.n_passes)
        return out, counters

    def render_lod_with_stats(self, lod_scene: "LODScene", camera):
        """Camera-dependent LOD render: select clusters, gather the compact
        sub-scene, run the normal plan on it (a `stage0_lod` span in front
        of the usual tree).

        Requires `plan.lod` (an `repro.lod.LODConfig`) and a `LODScene`
        from `repro.lod.build_lod`. The selection bucket — the static
        gather capacity — comes from `lod.selection_bucket` when pinned
        (the serving engine pins it per batch so it keys the jit cache;
        pinning is mandatory under jit/vmap, where the selected count is
        abstract) and is otherwise derived host-side from the selected
        member count. Returns (RenderOut, counters) like
        `render_with_stats` plus the selection counters
        lod_clusters_total / lod_clusters_selected /
        lod_gaussians_selected / lod_selection_ratio / lod_bucket.
        """
        cfg = self.lod
        if cfg is None:
            raise ValueError("render_lod_with_stats needs a plan with "
                             "lod=LODConfig(...) (this plan has lod=None)")
        from repro.lod.select import (gather_subscene, select_clusters,
                                      selected_members, selection_bucket_for)
        tracer = obs_trace.current()
        live = not obs_trace.is_traced((lod_scene, camera))
        with tracer.span("stage0_lod") as sp:
            sel = select_clusters(lod_scene, camera, cfg)
            n_sel = selected_members(lod_scene, sel)
            if cfg.selection_bucket is not None:
                bucket = cfg.selection_bucket
            elif not live:
                raise ValueError(
                    "render_lod_with_stats under jit/vmap needs a pinned "
                    "LODConfig.selection_bucket — the gather capacity is a "
                    "static shape and cannot come from a traced count")
            else:
                bucket = selection_bucket_for(int(n_sel), cfg,
                                              lod_scene.n_padded)
            sub, _ = gather_subscene(lod_scene, sel, bucket)
            tracer.block(sub)
            if tracer.enabled:
                sp.set(clusters_total=lod_scene.n_clusters, bucket=bucket,
                       traced=not live)
                if live:
                    sp.set(clusters_selected=int(jnp.sum(sel)),
                           gaussians_selected=int(n_sel))
        out, counters = self.render_with_stats(sub, camera)
        counters = dict(counters)
        counters["lod_clusters_total"] = jnp.asarray(
            float(lod_scene.n_clusters), jnp.float32)
        counters["lod_clusters_selected"] = jnp.sum(sel).astype(jnp.float32)
        counters["lod_gaussians_selected"] = n_sel.astype(jnp.float32)
        counters["lod_selection_ratio"] = (
            n_sel.astype(jnp.float32) / float(max(lod_scene.n_real, 1)))
        counters["lod_bucket"] = jnp.asarray(float(bucket), jnp.float32)
        return out, counters

    # -- introspection ------------------------------------------------------

    def stages(self) -> tuple[StageSpec, ...]:
        """The plan's stage sequence (name, backend, one-line description)."""
        test_be = self.test.backend if self.test.method == "cat" else "jnp"
        ctu_desc = {
            "cat": f"mini-tile CAT on {self.dataflow} entries",
            "obb": "sub-tile OBB gathered at entries",
            "aabb": "no fine test (whole tile list blends)",
        }[self.test.method]
        passes = (f" x {self.n_passes} spill passes"
                  if self.n_passes > 1 else "")
        return (
            StageSpec("preprocess", "jnp", "projection + 3σ footprints"),
            StageSpec("stage1_compact", "jnp",
                      f"Stage-1 {self.test.method} + depth sort + "
                      f"k_max={self.stream.k_max} compaction{passes} "
                      f"({self.stream.overflow.value} on overflow)"),
            StageSpec("ctu", test_be, ctu_desc),
            StageSpec("blend", self.raster.backend,
                      "fused in-kernel early termination" if self.raster.fused
                      else "pure-jnp differentiable sweep"),
        )

    # -- effective (termination-aware) counters -----------------------------

    def _prs_per_subtile(self, proj: Projected) -> jax.Array:
        """(N,) int PRs the CTU evaluates per hit sub-tile: 4 dense / 2
        sparse per Fig. 3(b), adaptive modes pick per Gaussian. Integer so
        the counter sums are exact in any reduction order."""
        spiky = classify_spiky(proj.axis_ratio, self.test.spiky_threshold)
        if self.test.mode == SamplingMode.UNIFORM_DENSE:
            return jnp.full(spiky.shape, 4)
        if self.test.mode == SamplingMode.UNIFORM_SPARSE:
            return jnp.full(spiky.shape, 2)
        if self.test.mode == SamplingMode.SMOOTH_FOCUSED:
            return jnp.where(spiky, 2, 4)
        return jnp.where(spiky, 4, 2)

    def _effective_counters_from_hits(self, proj: Projected, lists,
                                      sub_hits, mini_hits,
                                      entry_alive) -> dict:
        """Stream-dataflow effective counters from per-entry hit counts.

        The (T, K) int hit counts are all the termination-aware accounting
        needs; `_effective_counters` reduces the full per-entry masks down
        to them, and the tile-sharded path gathers them from the shards —
        one expression set, so the two paths stay bit-identical.
        """
        idx = lists.clip(0)                                  # (T, K)
        live = entry_alive                                   # (T, K)
        prs = self._prs_per_subtile(proj)[idx]               # (T, K)
        return dict(
            ctu_pairs_eff=jnp.sum(sub_hits * live).astype(jnp.float32),
            ctu_prs_eff=jnp.sum(sub_hits * prs * live).astype(jnp.float32),
            vru_pairs_eff=jnp.sum(mini_hits * live).astype(jnp.float32),
            ctu_stream_len=jnp.sum(entry_alive).astype(jnp.float32),
        )

    def _effective_counters(self, ps: ProjectedScene, ts: TileStream,
                            hout: H.StreamHierarchyOut, entry_alive) -> dict:
        """Termination-aware CTU/VRU workload (paper Fig. 6 semantics).

        For each list entry processed before its tile terminated, the CTU
        evaluated one PR batch per hit sub-tile (4 PRs dense, 2 sparse) and
        the VRUs blended one mini-tile per CAT-passing mini-tile. On the
        stream dataflow the per-entry masks already are those quantities; on
        the dense oracle they are gathered per tile from the full masks.
        """
        proj, grid = ps.proj, ps.grid
        idx = hout.lists.clip(0)                                 # (T, K)
        live = entry_alive                                       # (T, K)
        prs_per_sub = self._prs_per_subtile(proj)

        if self.dataflow == "stream":
            sub_hits = jnp.sum(hout.entry_sub_mask, axis=-1)     # (T, K)
            mini_hits = jnp.sum(hout.entry_mini_mask, axis=-1)   # (T, K)
            return self._effective_counters_from_hits(
                proj, hout.lists, sub_hits, mini_hits, entry_alive)

        # Dense oracle: per-tile grouped masks (T, subtiles_per_tile, N) etc.
        dense = ts.dense
        sub_of_tile = grid.tile_of_region(grid.subtile)
        mini_of_tile = grid.tile_of_region(grid.minitile)
        s_sort = jnp.argsort(sub_of_tile)
        m_sort = jnp.argsort(mini_of_tile)
        sub_by_tile = dense.subtile_mask[s_sort].reshape(
            grid.num_tiles, grid.subtiles_per_tile, -1)
        mini_by_tile = dense.minitile_mask[m_sort].reshape(
            grid.num_tiles, grid.minitiles_per_tile, -1)

        def per_tile(sub_t, mini_t, id_row, live_row):
            sub_hits = jnp.sum(sub_t[:, id_row], axis=0)         # (K,)
            mini_hits = jnp.sum(mini_t[:, id_row], axis=0)       # (K,)
            return (jnp.sum(sub_hits * live_row),
                    jnp.sum(mini_hits * live_row))

        def per_tile_prs(sub_t, id_row, live_row):
            sub_hits = jnp.sum(sub_t[:, id_row], axis=0)
            return jnp.sum(sub_hits * prs_per_sub[id_row] * live_row)

        sub_eff, mini_eff = jax.vmap(per_tile)(sub_by_tile, mini_by_tile,
                                               idx, live)
        prs_eff = jax.vmap(per_tile_prs)(sub_by_tile, idx, live)
        return dict(
            ctu_pairs_eff=jnp.sum(sub_eff).astype(jnp.float32),
            ctu_prs_eff=jnp.sum(prs_eff).astype(jnp.float32),
            vru_pairs_eff=jnp.sum(mini_eff).astype(jnp.float32),
            ctu_stream_len=jnp.sum(entry_alive).astype(jnp.float32),
        )


# ---------------------------------------------------------------------------
# Renderer facade
# ---------------------------------------------------------------------------


class Renderer:
    """User-facing facade over a `RenderPlan`.

        r = Renderer(test=TestConfig(method="cat", backend="pallas"),
                     stream=StreamConfig(k_max=2048,
                                         overflow=OverflowPolicy.WARN),
                     raster=RasterConfig(fused=True))
        out, counters = r.render_with_stats(scene, camera)

    Omitted sub-configs take their defaults (the FLICKER configuration:
    CAT method, SMOOTH_FOCUSED leaders, MIXED precision, stream dataflow).
    """

    def __init__(self, grid: Optional[GridConfig] = None,
                 test: Optional[TestConfig] = None,
                 stream: Optional[StreamConfig] = None,
                 raster: Optional[RasterConfig] = None,
                 dataflow: str = "stream",
                 shard: Optional[ShardConfig] = None,
                 lod: Optional["LODConfig"] = None):
        self.plan = RenderPlan(
            grid=grid if grid is not None else GridConfig(),
            test=test if test is not None else TestConfig(),
            stream=stream if stream is not None else StreamConfig(),
            raster=raster if raster is not None else RasterConfig(),
            dataflow=dataflow,
            shard=shard if shard is not None else ShardConfig(),
            lod=lod)

    @classmethod
    def from_plan(cls, plan: RenderPlan) -> "Renderer":
        r = cls.__new__(cls)
        r.plan = plan
        return r

    @classmethod
    def from_config(cls, cfg) -> "Renderer":
        """Bridge from the legacy flat `pipeline.RenderConfig` (no warning —
        this is the supported migration path)."""
        return cls.from_plan(cfg.to_plan())

    def replace(self, **kw) -> "Renderer":
        """New Renderer with plan fields replaced (grid/test/stream/raster/
        dataflow/shard/lod)."""
        return Renderer.from_plan(dataclasses.replace(self.plan, **kw))

    def render(self, scene: GaussianScene, camera) -> raster.RenderOut:
        return self.plan.render(scene, camera)

    def render_with_stats(self, scene: GaussianScene, camera):
        return self.plan.render_with_stats(scene, camera)

    def render_incremental(self, scene: GaussianScene, camera, cache=None,
                           cfg=None, **kw):
        return self.plan.render_incremental(scene, camera, cache=cache,
                                            cfg=cfg, **kw)

    def render_batch_with_stats(self, scene: GaussianScene, cameras):
        return self.plan.render_batch_with_stats(scene, cameras)

    def render_lod_with_stats(self, lod_scene: "LODScene", camera):
        return self.plan.render_lod_with_stats(lod_scene, camera)

    def __repr__(self):
        return f"Renderer({self.plan!r})"


def as_plan(obj) -> RenderPlan:
    """Normalize Renderer | RenderPlan | legacy RenderConfig to a plan."""
    if isinstance(obj, RenderPlan):
        return obj
    if isinstance(obj, Renderer):
        return obj.plan
    if hasattr(obj, "to_plan"):               # legacy pipeline.RenderConfig
        return obj.to_plan()
    raise TypeError(f"cannot build a RenderPlan from {type(obj).__name__}")


# ---------------------------------------------------------------------------
# Overflow policy enforcement (host-side)
# ---------------------------------------------------------------------------


def enforce_overflow_policy(overflow, policy: OverflowPolicy, *,
                            k_max: int, n_passes: int = 1,
                            context: str = "") -> bool:
    """Apply an OverflowPolicy to a concrete overflow flag.

    No-ops under tracing (jit/vmap cannot branch on the flag — the in-graph
    behavior is always clamping); callers holding concrete results (eager
    renders, the serving engine after device sync) get the warn/raise
    behavior. Returns True iff overflow was observed (and not raised).

    Under SPILL the flag means the total spill capacity (k_max * n_passes)
    was exhausted and the remainder clamped — never silent: it warns with
    the spill-specific remedy (more passes), while the serving engine
    additionally retries with a doubled pass bucket before any frame is
    allowed to report it.
    """
    if policy is OverflowPolicy.CLAMP or isinstance(overflow, jax.core.Tracer):
        return False
    if not bool(overflow):
        return False
    suffix = " — " + context if context else ""
    if policy is OverflowPolicy.SPILL:
        warnings.warn(
            f"Stage-1 tile list overflowed the spill capacity "
            f"k_max={k_max} x {n_passes} passes; entries past it were "
            f"dropped (clamped){suffix}. Raise StreamConfig.max_spill_passes "
            f"(or k_max) to cover the longest survivor list.",
            StreamOverflowWarning, stacklevel=2)
        return True
    msg = (f"Stage-1 tile list overflowed k_max={k_max}; entries past the "
           f"capacity were dropped (clamped){suffix}. "
           f"Raise StreamConfig.k_max or register the scene with "
           f"probe_cameras to measure a sufficient bound.")
    if policy is OverflowPolicy.RAISE:
        raise StreamOverflowError(msg)
    warnings.warn(msg, StreamOverflowWarning, stacklevel=2)
    return True


# ---------------------------------------------------------------------------
# Probe-driven k_max (the paper's FIFO-depth knob, measured)
# ---------------------------------------------------------------------------


def measure_k_max(scene: GaussianScene, cameras, *,
                  grid: GridConfig = GridConfig(),
                  cap: Optional[int] = None) -> int:
    """k_max from the Stage-1 survivor histogram over a camera probe set.

    For each probe camera, projects the scene and takes the per-tile
    Stage-1 survivor counts (the histogram the Compact stage fills its
    per-tile lists from); the bound is the longest list seen over the whole
    probe set, rounded up to the next power of two so nearby probe sets land
    on the same value and the serving jit cache stays small. `cap` (e.g. the
    scene's padded Gaussian count) bounds the result from above.

    Each camera carries its own resolution; `grid` supplies the tile shape.
    The per-probe (T, N) Stage-1 mask is counted one tile block at a time
    (same chunking as the compaction), so probing stays feasible at
    1080p/512k-Gaussian scale where the full mask would be gigabytes.
    """
    from repro.core.raster import COMPACT_CHUNK_ELEMS
    from repro.core.culling import tile_divisor_chunk, map_tile_chunks

    cameras = list(cameras)
    if not cameras:
        raise ValueError("measure_k_max needs at least one probe camera "
                         "(an empty probe set would measure k_max=1 and "
                         "clamp every tile list)")
    longest = 1
    for cam in cameras:
        g = grid.with_resolution(cam.height, cam.width).make()
        proj = project(scene, cam)
        t, n = g.num_tiles, proj.depth.shape[0]
        counts = map_tile_chunks(
            lambda ob: jnp.sum(aabb_mask(proj, ob, g.tile), axis=1),
            (g.tile_origins(),), t,
            tile_divisor_chunk(t, n, COMPACT_CHUNK_ELEMS))
        longest = max(longest, int(jnp.max(counts)))
    k = next_pow2(longest)
    return min(k, cap) if cap is not None else k


# ---------------------------------------------------------------------------
# Static accounting + batch helpers
# ---------------------------------------------------------------------------


def cat_mask_elems(grid: TileGrid, n: int, k_max: int, dataflow: str) -> int:
    """Boolean elements the CAT stage materializes *per pass* (the Stage-1 +
    CAT mask footprint, 1 byte/element): dense = (S + M)·N, stream =
    T·K·(Sp + Mt). Static per config — the stream/dense ratio is the memory
    win `benchmarks/scaling.py` tracks. SPILL plans hold one pass's masks
    at this size in the CTU working set regardless of the survivor count;
    that boundedness is exactly what the policy buys."""
    if dataflow == "dense":
        return (grid.num_subtiles + grid.num_minitiles) * n
    if dataflow == "stream":
        return grid.num_tiles * k_max * (grid.subtiles_per_tile
                                         + grid.minitiles_per_tile)
    raise ValueError(dataflow)


def frame_counters(counters: dict, i: int) -> dict:
    """Slice frame `i`'s scalars out of a batched counters dict."""
    return {k: v[i] for k, v in counters.items()}
