"""Pallas tile alpha-blend kernels — the VRU array on TPU.

One grid step blends a (P pixels × K_BLK Gaussians) block of a tile's
compacted, depth-sorted list. The sequential transmittance dependency runs
along the K grid axis: per-pixel transmittance T and the RGB accumulator
live in VMEM scratch and persist across the K-axis grid iterations (TPU
"arbitrary" dimension semantics; exact in interpret mode). This is the
TPU-idiomatic version of the VRU pipeline: front-to-back order is preserved
at block granularity, and all pixel lanes blend the same Gaussian in
lockstep — which is precisely why the CAT compaction upstream matters (no
masked-out lanes).

Two kernels share that skeleton:

`blend_tiles` (`_blend_kernel`) — the full sweep: every K block of every
tile is blended; contribution skipping only shows up in the per-pixel CAT
`allow` mask.

`blend_tiles_fused` (`_fused_blend_kernel`) — the contribution-aware hot
path. It folds the paper's two in-loop skipping decisions into the kernel:

  * true tile-level early termination: once every pixel lane of the tile
    has transmittance T < T_EPS, the remaining K blocks of the tile are
    skipped entirely (`pl.when` on the carried VMEM transmittance) — the
    VRU-array behavior of "the rendering of the current tile can terminate
    early" rather than a counter model of it;
  * per-tile adaptive trip count: a scalar-prefetched (T,) bound (number of
    occupied K blocks per compacted list) keeps short tiles from sweeping
    the longest tile's padding.

The fused kernel also *measures* its own work instead of having the
perf model re-derive it: per-pixel processed/blended counts, per-entry
`entry_alive` flags (which drive the CTU accounting upstream), and the
per-tile count of K blocks actually executed all come back as outputs.

Inputs are pre-gathered per-tile feature blocks (the analogue of the feature
FIFOs in Fig. 6):
    pix    (T, P, 2)   pixel centers
    feat   (T, K, 8)   = [mean_x, mean_y, cxx, cxy, cyy, opacity, 0, 0]
    colors (T, K, 3)
    valid  (T, K)      int8 (list slot occupied)
    allow  (T, K, Mt)  int8 per-ENTRY CAT mask over the tile's Mt mini-tiles
                       (the survivor-stream representation — 16× smaller
                       than a per-pixel mask; `StreamHierarchyOut
                       .entry_mini_mask`)
The wrappers hand them to the kernels lane-dense, long axis last
(`_kernel_operands`; docs/kernels.md "Kernel-side layout").
The kernels expand the per-entry mask to pixel lanes in VMEM with a one-hot
(P, Mt) pixel→mini-tile matmul (static per grid; matmul rather than gather
so the expansion lowers to the MXU instead of an unsupported dynamic
gather). Output: (T, P, 3) blended RGB + (T, P) final transmittance (+ the
measured work counters for the fused kernel; see `FusedBlendOut`).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.gaussians import ALPHA_MIN
from repro.core.raster import T_EPS  # transmittance floor: all pixel lanes
#                                      below => tile terminated; shared with
#                                      the jnp rasterizer's modeled counters
from repro import kernels

ALPHA_MAX = 0.99

K_BLK = 128

# Per-tile pixel state, (T, STATE_ROWS, P): one row per quantity so the
# pixel axis is the lane axis. Rows: transmittance, r, g, b, processed,
# blended, K blocks executed (fused kernel only), unused.
STATE_ROWS = 8


def _prefix_product(x):
    """Inclusive product scan along the lane axis of a (P, K) block.

    Log-step doubling over static shifts (Hillis-Steele): Mosaic has no
    cumprod lowering, and this stays a handful of VPU multiplies."""
    s = 1
    while s < x.shape[1]:
        x = x * jnp.concatenate([jnp.ones_like(x[:, :s]), x[:, :-s]], axis=1)
        s *= 2
    return x


def _blend_block(pix_ref, feat_ref, col_ref, allow_ref, mtmap_ref, t_in):
    """Blend one (P pixels × K_BLK entries) block front to back.

    Blocks arrive lane-dense: pix (1, 2, P), feat (1, 8, K) rows [mean_x,
    mean_y, cxx, cxy, cyy, opacity, valid, 0], col (1, 3, K), allow
    (1, Mt, K) i8; mtmap (P, Mt) is the one-hot pixel→mini-tile map.
    t_in: (P, 1) transmittance carried into the block. Returns (lane (P, K)
    bool, a (P, K) alpha, t_excl (P, K) transmittance entering each entry,
    rgb (P, 3) the block's colour contribution, t_out (P, 1))."""
    pix = pix_ref[0].T                     # (P, 2)
    px = pix[:, 0:1]                       # (P, 1)
    py = pix[:, 1:2]
    feat = feat_ref[0]                     # (8, K)
    mx, my, cxx, cxy, cyy, op, valid = (feat[j:j + 1, :] for j in range(7))

    dx = px - mx                           # (P, K)
    dy = py - my
    e = 0.5 * (cxx * dx * dx + cyy * dy * dy) + cxy * dx * dy
    a = jnp.minimum(op * jnp.exp(-e), ALPHA_MAX)
    # Per-entry mask -> pixel lanes: each mtmap row has exactly one 1, so
    # the matmul reproduces the gather exactly (values stay 0/1) and lowers
    # to the MXU instead of an unsupported dynamic gather.
    allow = jnp.dot(mtmap_ref[...], allow_ref[0].astype(jnp.float32)) > 0.5
    lane = (valid != 0) & allow
    a = jnp.where(lane & (a >= ALPHA_MIN), a, 0.0)

    # Sequential front-to-back blend within the block via a prefix product.
    cum = _prefix_product(1.0 - a)
    t_excl = t_in * jnp.concatenate(
        [jnp.ones_like(cum[:, :1]), cum[:, :-1]], axis=1)
    rgb = jax.lax.dot_general(             # (P, K) x (3, K) -> (P, 3)
        t_excl * a, col_ref[0], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)
    return lane, a, t_excl, rgb, t_in * cum[:, -1:]


def _store_state(st_ref, t_scr, acc_scr):
    """Write the carried (P, 1) transmittance and (P, 3) colour columns to
    rows 0-3 of the (1, STATE_ROWS, P) state block."""
    st_ref[0, 0:1, :] = t_scr[...].T
    st_ref[0, 1:4, :] = acc_scr[...].T


def _blend_kernel(pix_ref, feat_ref, col_ref, allow_ref, mtmap_ref, st_ref,
                  t_scr, acc_scr, *, n_kblocks: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        t_scr[...] = jnp.ones_like(t_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    _, _, _, rgb, t_out = _blend_block(pix_ref, feat_ref, col_ref, allow_ref,
                                       mtmap_ref, t_scr[...])
    acc_scr[...] += rgb
    t_scr[...] = t_out

    @pl.when(k == n_kblocks - 1)
    def _out():
        st_ref[...] = jnp.zeros_like(st_ref)
        _store_state(st_ref, t_scr, acc_scr)


def pixel_minitile_index(p: int, mt: int) -> jnp.ndarray:
    """(P,) tile-local mini-tile index of each tile pixel (row-major).

    Shape-only derivation of `raster._minitile_index_in_tile` for kernel
    wrappers/oracles that see operands but no TileGrid: tile = √P and
    minitile = tile/√Mt — both perfect squares by TileGrid's invariants."""
    tile = int(round(p ** 0.5))
    mtx = int(round(mt ** 0.5))
    m = tile // mtx
    dy, dx = jnp.meshgrid(jnp.arange(tile), jnp.arange(tile), indexing="ij")
    return ((dy // m) * mtx + (dx // m)).reshape(-1)


def _pixel_minitile_onehot(p: int, mt: int) -> jnp.ndarray:
    """(P, Mt) f32 one-hot form of `pixel_minitile_index` (kernel operand)."""
    mt_in_tile = pixel_minitile_index(p, mt)
    return (mt_in_tile[:, None] == jnp.arange(mt)[None, :]).astype(
        jnp.float32)


def _kernel_operands(pix, feat, colors, valid, allow):
    """Pad K to a K_BLK multiple and lay the operands out lane-dense.

    Every operand keeps its long axis (pixels or list entries) last and its
    short one (coordinates, features, channels, mini-tiles) second to last:
    the TPU tiles the last two dims of an array by (8, 128), so a (T, K, 8)
    layout would pad each 8-wide row to 128 lanes in HBM (16×; 4 GB per
    Full-HD frame and spill pass). `valid` rides in feature row 6."""
    t, k = valid.shape
    kp = -(-k // K_BLK) * K_BLK

    def rows(x):                            # (T, K, C) -> (T, C, Kp)
        return jnp.pad(jnp.swapaxes(x, 1, 2), ((0, 0), (0, 0), (0, kp - k)))

    feat_rows = jnp.stack(
        [feat[..., j].astype(jnp.float32) for j in range(6)]
        + [valid.astype(jnp.float32), jnp.zeros((t, k), jnp.float32)],
        axis=-1)
    return (jnp.swapaxes(pix.astype(jnp.float32), 1, 2),
            rows(feat_rows),
            rows(colors.astype(jnp.float32)),
            rows(allow.astype(jnp.int8))), kp


def _operand_specs(p: int, mt: int):
    """BlockSpecs of (pix, feat, colors, allow, mtmap) for grid step
    (tile i, K block j)."""
    return [
        pl.BlockSpec((1, 2, p), lambda i, j, *_: (i, 0, 0)),
        pl.BlockSpec((1, 8, K_BLK), lambda i, j, *_: (i, 0, j)),
        pl.BlockSpec((1, 3, K_BLK), lambda i, j, *_: (i, 0, j)),
        pl.BlockSpec((1, mt, K_BLK), lambda i, j, *_: (i, 0, j)),
        pl.BlockSpec((p, mt), lambda i, j, *_: (0, 0)),
    ]


def _state_spec(p: int):
    return pl.BlockSpec((1, STATE_ROWS, p), lambda i, j, *_: (i, 0, 0))


def blend_tiles(pix: jax.Array, feat: jax.Array, colors: jax.Array,
                valid: jax.Array, allow: jax.Array):
    """pix: (T, P, 2); feat: (T, K, 8); colors: (T, K, 3); valid: (T, K) i8;
    allow: (T, K, Mt) i8 per-entry mask over the tile's mini-tiles.
    Returns (rgb (T, P, 3), transmittance (T, P))."""
    t, p, _ = pix.shape
    mt = allow.shape[2]
    ops, kp = _kernel_operands(pix, feat, colors, valid, allow)
    n_kblocks = kp // K_BLK

    kernel = functools.partial(_blend_kernel, n_kblocks=n_kblocks)
    state = pl.pallas_call(
        kernel,
        grid=(t, n_kblocks),
        in_specs=_operand_specs(p, mt),
        out_specs=_state_spec(p),
        out_shape=jax.ShapeDtypeStruct((t, STATE_ROWS, p), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((p, 1), jnp.float32),
            pltpu.VMEM((p, 3), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=kernels.interpret_mode(),
    )(*ops, _pixel_minitile_onehot(p, mt))
    return jnp.swapaxes(state[:, 1:4], 1, 2), state[:, 0]


# ---------------------------------------------------------------------------
# Fused contribution-aware kernel (early termination + adaptive trip count)
# ---------------------------------------------------------------------------


class FusedBlendOut(NamedTuple):
    rgb: jax.Array                # (T, P, 3) blended color
    trans: jax.Array              # (T, P) transmittance at termination
    processed: jax.Array          # (T, P) f32 Gaussians touched while alive
    blended: jax.Array            # (T, P) f32 Gaussians actually blended
    entry_alive: jax.Array        # (T, K) bool list entry seen pre-termination
    kblocks_processed: jax.Array  # (T,) i32 K blocks the kernel executed
    kblocks_total: int            # static: K blocks a full sweep would run


def _fused_blend_kernel(kb_ref, pix_ref, feat_ref, col_ref, allow_ref,
                        mtmap_ref, st0_ref, st_ref, alive_ref, t_scr,
                        acc_scr, pcnt_scr, bcnt_scr, kp_scr, *,
                        n_kblocks: int):
    i = pl.program_id(0)
    k = pl.program_id(1)

    # Scratch starts from the carried pass state (all-ones transmittance /
    # zero accumulators on the first pass) — the cross-call analogue of the
    # cross-K-block carry the scratch already implements, which is what
    # makes a spill pass resume exactly where the previous one stopped.
    # State rows arrive as (1, P) and live in scratch as (P, 1) columns.
    @pl.when(k == 0)
    def _init():
        t_scr[...] = st0_ref[0, 0:1, :].T
        acc_scr[...] = st0_ref[0, 1:4, :].T
        pcnt_scr[...] = st0_ref[0, 4:5, :].T
        bcnt_scr[...] = st0_ref[0, 5:6, :].T
        kp_scr[0] = 0

    # Skipped blocks (terminated tile or past the tile's occupied bound)
    # report no live entries; the active branch overwrites this.
    alive_ref[0] = jnp.zeros_like(alive_ref[0])

    # The fused decision: run this block only while (a) the compacted list
    # still has entries here and (b) some pixel lane is above the
    # transmittance floor. Both guards skip the block's whole dataflow.
    active = (k < kb_ref[i]) & (jnp.max(t_scr[...]) >= T_EPS)

    @pl.when(active)
    def _blend():
        lane, a, t_excl, rgb, t_out = _blend_block(
            pix_ref, feat_ref, col_ref, allow_ref, mtmap_ref, t_scr[...])
        acc_scr[...] += rgb
        t_scr[...] = t_out

        # Measured work — same accounting as core.raster.render_tiles, but
        # produced by the kernel that did the work.
        alive_px = t_excl >= T_EPS         # (P, K)
        pcnt_scr[...] += jnp.sum((lane & alive_px).astype(jnp.float32),
                                 axis=1, keepdims=True)
        bcnt_scr[...] += jnp.sum(((a > 0) & alive_px).astype(jnp.float32),
                                 axis=1, keepdims=True)
        alive_ref[0] = (jnp.any(alive_px, axis=0, keepdims=True)
                        & (feat_ref[0, 6:7, :] != 0)).astype(jnp.int8)
        kp_scr[0] += 1

    @pl.when(k == n_kblocks - 1)
    def _out():
        _store_state(st_ref, t_scr, acc_scr)
        st_ref[0, 4:5, :] = pcnt_scr[...].T
        st_ref[0, 5:6, :] = bcnt_scr[...].T
        st_ref[0, 6:7, :] = jnp.full((1, st_ref.shape[2]),
                                     kp_scr[0].astype(jnp.float32))
        st_ref[0, 7:8, :] = jnp.zeros((1, st_ref.shape[2]), jnp.float32)


def blend_tiles_fused(pix: jax.Array, feat: jax.Array, colors: jax.Array,
                      valid: jax.Array, allow: jax.Array,
                      kblock_bound: Optional[jax.Array] = None,
                      init: Optional[tuple] = None) -> FusedBlendOut:
    """Contribution-aware blend with in-kernel early termination.

    Same operands as `blend_tiles`. `kblock_bound` is the optional (T,) i32
    count of occupied K blocks per tile (computed from `valid` when None);
    it is scalar-prefetched so the grid's K loop for tile t runs at most
    `kblock_bound[t]` live iterations, and the transmittance guard cuts even
    those short once the tile saturates. Image/transmittance match the full
    sweep to < T_EPS per channel (every skipped contribution has weight
    T·a < T_EPS); the work counters match `core.raster.render_tiles`'s
    accounting exactly.

    init: optional carried state (trans (T,P), rgb (T,P,3), processed (T,P),
    blended (T,P)) from a previous spill pass — the kernel's VMEM carries
    resume from it, so chaining calls over consecutive list segments equals
    one call over the concatenation whenever the segment lengths are
    multiples of K_BLK (the kernel's op sequence is per-K-block either way).
    """
    t, p, _ = pix.shape
    k = feat.shape[1]
    mt = allow.shape[2]
    ops, kp = _kernel_operands(pix, feat, colors, valid, allow)
    n_kblocks = kp // K_BLK

    if kblock_bound is None:
        # Compacted lists put valid entries first, so the occupied-block
        # count is ceil(popcount / K_BLK).
        nvalid = jnp.sum((valid != 0).astype(jnp.int32), axis=1)
        kblock_bound = -(-nvalid // K_BLK)
    kblock_bound = kblock_bound.astype(jnp.int32)

    if init is None:
        state0 = jnp.zeros((t, STATE_ROWS, p), jnp.float32).at[:, 0].set(1.0)
    else:
        # A fully-terminated or fully-empty spill pass still runs its
        # guarded grid (the scalar bound already skips dead blocks).
        t0, acc0, p0, b0 = (x.astype(jnp.float32) for x in init)
        state0 = jnp.concatenate(
            [t0[:, None], jnp.swapaxes(acc0, 1, 2), p0[:, None],
             b0[:, None], jnp.zeros((t, STATE_ROWS - 6, p), jnp.float32)],
            axis=1)

    kernel = functools.partial(_fused_blend_kernel, n_kblocks=n_kblocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t, n_kblocks),
        in_specs=_operand_specs(p, mt) + [_state_spec(p)],
        out_specs=[
            _state_spec(p),
            pl.BlockSpec((1, 1, K_BLK), lambda i, j, kb: (i, 0, j)),
        ],
        scratch_shapes=[
            pltpu.VMEM((p, 1), jnp.float32),    # transmittance carry
            pltpu.VMEM((p, 3), jnp.float32),    # rgb accumulator
            pltpu.VMEM((p, 1), jnp.float32),    # processed counter
            pltpu.VMEM((p, 1), jnp.float32),    # blended counter
            pltpu.SMEM((1,), jnp.int32),        # executed-block counter
        ],
    )
    state, alive = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((t, STATE_ROWS, p), jnp.float32),
            jax.ShapeDtypeStruct((t, 1, kp), jnp.int8),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=kernels.interpret_mode(),
    )(kblock_bound, *ops, _pixel_minitile_onehot(p, mt), state0)
    return FusedBlendOut(
        rgb=jnp.swapaxes(state[:, 1:4], 1, 2), trans=state[:, 0],
        processed=state[:, 4], blended=state[:, 5],
        entry_alive=(alive[:, 0, :k] != 0),
        kblocks_processed=state[:, 6, 0].astype(jnp.int32),
        kblocks_total=n_kblocks,
    )
