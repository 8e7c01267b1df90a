"""Pallas PRTU kernels — the Mini-Tile CAT engine (paper §IV-C) on TPU.

The ASIC's CTU tests 2 pixel-rectangles (8 leader pixels) per cycle. Two TPU
adaptations of Alg. 1 live here, both forming the four separable terms
s{top,bot}×{x,y} once (line 2–3 sharing) and the four cross terms — the
arithmetic per corner is half of a naive per-leader evaluation, which is
where the paper's ~2× CAT FLOP saving shows up on the VPU as well:

* `prtu_entry_cat_mask` — the survivor-stream kernel (the pipeline
  default): the grid runs over compacted per-tile list *entries* (T tiles ×
  K/KE_BLK entry blocks), and each block tests KE_BLK entries against the
  Mt mini-tiles of their own tile. This is the paper's Fig. 6 dataflow —
  the CTU only ever sees Gaussians sitting in a tile's queue — and its
  output is the per-entry (T, K, Mt) mask the blend kernels consume.
* `prtu_cat_mask` — the dense-oracle kernel: blocks the full (mini-tile ×
  Gaussian) matrix into (M_BLK, G_BLK) VMEM tiles; O(M·G) output, kept for
  the `dataflow="dense"` parity path.

Mixed precision: Δ in fp16, quadratic accumulation in fp8 (float8_e4m3fn),
matching the CTU datapath; the comparison against ln(255·o) is fp32.

Block shapes are multiples of 8/128 to line up with TPU VREG lanes; all
operands use explicit BlockSpecs into VMEM. Outputs are int8 masks (bool
stored as i8 for clean tiling).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.core.precision import quantize as _quant

M_BLK = 128   # mini-tiles per block (sublane-friendly)
G_BLK = 128   # gaussians per block (lane dimension)


def _alg1_hits(ptx, pty, pbx, pby, mu_x, mu_y, cxx, cxy, cyy, lhs, spiky,
               *, mode: str, delta_prec: str, mul_prec: str, acc_prec: str,
               slack: float):
    """Alg. 1 body shared by the dense and the entry-stream PRTU kernels.

    All operands are already broadcast-compatible and coord-quantized; the
    result mask has their broadcast shape. ptx/pty/pbx/pby are the PR's
    main-diagonal leader coordinates, lhs = ln(255·o), spiky is boolean.
    """
    # Alg. 1 line 1: subtract at coord precision, convert to delta precision
    dtx = _quant(ptx - mu_x, delta_prec)
    dty = _quant(pty - mu_y, delta_prec)
    dbx = _quant(pbx - mu_x, delta_prec)
    dby = _quant(pby - mu_y, delta_prec)

    qm = functools.partial(_quant, kind=mul_prec)
    qa = functools.partial(_quant, kind=acc_prec)
    # lines 2-3: shared separable terms
    s_top_x = qm(qm(0.5 * qm(dtx * dtx)) * cxx)
    s_top_y = qm(qm(0.5 * qm(dty * dty)) * cyy)
    s_bot_x = qm(qm(0.5 * qm(dbx * dbx)) * cxx)
    s_bot_y = qm(qm(0.5 * qm(dby * dby)) * cyy)
    # lines 4-5: cross terms
    t0 = qm(qm(dtx * dty) * cxy)
    t1 = qm(qm(dbx * dty) * cxy)
    t2 = qm(qm(dtx * dby) * cxy)
    t3 = qm(qm(dbx * dby) * cxy)
    # lines 6-7: adders at acc precision
    e0 = qa(qa(s_top_x + s_top_y) + t0)
    e1 = qa(qa(s_bot_x + s_top_y) + t1)
    e2 = qa(qa(s_top_x + s_bot_y) + t2)
    e3 = qa(qa(s_bot_x + s_bot_y) + t3)

    k = 1.0 - slack
    hit0 = lhs > e0 * k
    hit1 = lhs > e1 * k
    hit2 = lhs > e2 * k
    hit3 = lhs > e3 * k
    sparse = hit0 | hit3                 # main diagonal only
    anti = hit1 | hit2                   # what the dense test adds

    # Mode selection as boolean algebra (sparse ⊆ dense): Mosaic cannot
    # broadcast a select between bool operands.
    if mode == "uniform_dense":
        return sparse | anti
    if mode == "uniform_sparse":
        return sparse
    if mode == "smooth_focused":
        return sparse | (anti & ~spiky)
    if mode == "spiky_focused":
        return sparse | (anti & spiky)
    raise ValueError(mode)


def _prtu_kernel(ptop_ref, pbot_ref, feat_ref, mask_ref, *, mode: str,
                 coord_prec: str, delta_prec: str, mul_prec: str,
                 acc_prec: str, slack: float):
    """One (M_BLK, G_BLK) block of the CAT test matrix.

    ptop/pbot: (M_BLK, 2) — main-diagonal leader coords of each mini-tile PR.
    feat: (8, G_BLK) per-Gaussian rows [mu_x, mu_y, cxx, cxy, cyy, lhs,
    spiky, 0] with lhs = ln(255·o) (shared term, computed once outside, as
    in the CTU) and spiky as 0/1. mask: (M_BLK, G_BLK) int8 out.
    """
    qc = functools.partial(_quant, kind=coord_prec)
    out = _alg1_hits(
        ptx=qc(ptop_ref[:, 0:1]),                # (M, 1)
        pty=qc(ptop_ref[:, 1:2]),
        pbx=qc(pbot_ref[:, 0:1]),
        pby=qc(pbot_ref[:, 1:2]),
        mu_x=qc(feat_ref[0:1, :]),               # (1, G)
        mu_y=qc(feat_ref[1:2, :]),
        cxx=qc(feat_ref[2:3, :]),
        cxy=qc(feat_ref[3:4, :]),
        cyy=qc(feat_ref[4:5, :]),
        lhs=feat_ref[5:6, :],
        spiky=feat_ref[6:7, :] != 0,
        mode=mode, delta_prec=delta_prec, mul_prec=mul_prec,
        acc_prec=acc_prec, slack=slack)
    mask_ref[...] = out.astype(jnp.int8)


def feature_rows(mu_x, mu_y, cxx, cxy, cyy, lhs, spiky):
    """Stack per-Gaussian (or per-entry) operands as the 8 PRTU feature
    rows [mu_x, mu_y, cxx, cxy, cyy, lhs, spiky, 0] along axis -2: shape
    (..., 8, L). Gaussians (entries) run along the last, lane axis, so the
    operand needs no lane padding on a TPU."""
    vals = [mu_x, mu_y, cxx, cxy, cyy, lhs, spiky]
    vals = [v.astype(jnp.float32) for v in vals]
    return jnp.stack(vals + [jnp.zeros_like(vals[0])], axis=-2)


def prtu_cat_mask(p_top: jax.Array, p_bot: jax.Array, mu: jax.Array,
                  conic: jax.Array, lhs: jax.Array, spiky: jax.Array,
                  *, mode: str = "smooth_focused", coord_prec: str = "fp16",
                  delta_prec: str = "fp8", mul_prec: str = "fp8",
                  acc_prec: str = "fp16", slack: float = 0.0) -> jax.Array:
    """(M, G) int8 CAT mask via the Pallas PRTU kernel.

    Pads M and G up to block multiples; callers slice the result.
    """
    m, g = p_top.shape[0], mu.shape[0]
    mp = -(-m // M_BLK) * M_BLK
    gp = -(-g // G_BLK) * G_BLK

    def pad(x, n):
        return jnp.pad(x, [(0, n - x.shape[0])] + [(0, 0)] * (x.ndim - 1))

    # Padded Gaussians carry lhs = -inf so they never pass.
    lhs_p = jnp.full((gp,), -jnp.inf, jnp.float32).at[:g].set(
        lhs.astype(jnp.float32))
    mu_p, conic_p = pad(mu, gp), pad(conic, gp)
    feat = feature_rows(mu_p[:, 0], mu_p[:, 1], conic_p[:, 0], conic_p[:, 1],
                        conic_p[:, 2], lhs_p, pad(spiky, gp))    # (8, Gp)

    kernel = functools.partial(_prtu_kernel, mode=mode,
                               coord_prec=coord_prec, delta_prec=delta_prec,
                               mul_prec=mul_prec, acc_prec=acc_prec,
                               slack=slack)
    out = pl.pallas_call(
        kernel,
        grid=(mp // M_BLK, gp // G_BLK),
        in_specs=[
            pl.BlockSpec((M_BLK, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((M_BLK, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((8, G_BLK), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((M_BLK, G_BLK), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, gp), jnp.int8),
        # Unlike the blend kernels there is no carried state: every
        # (mini-tile, Gaussian) block is independent, so both grid axes are
        # parallel and Mosaic may reorder/overlap them freely.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=kernels.interpret_mode(),
    )(pad(p_top.astype(jnp.float32), mp), pad(p_bot.astype(jnp.float32), mp),
      feat)
    return out[:m, :g]


# ---------------------------------------------------------------------------
# Entry-stream PRTU kernel (grid over compacted per-tile list entries)
# ---------------------------------------------------------------------------

KE_BLK = 128  # stream entries per block (lane dimension)


def _prtu_entry_kernel(ptop_ref, pbot_ref, orig_ref, feat_ref, mask_ref, *,
                       mode: str, coord_prec: str, delta_prec: str,
                       mul_prec: str, acc_prec: str, slack: float):
    """One (1 tile, KE_BLK entries) block of the survivor-stream CAT test.

    ptop/pbot: (Mt, 2) tile-LOCAL main-diagonal leader coords of the tile's
    mini-tile PRs (shared by every tile); orig: (1, 1, 2) this tile's pixel
    origin. feat: (1, 8, KE) per-entry `feature_rows`, lhs = ln(255·o) with
    -inf on invalid/padded entries.
    mask: (1, Mt, KE) int8 out — mini-tile m of this tile vs entry k.
    """
    qc = functools.partial(_quant, kind=coord_prec)
    orig = orig_ref[0]                           # (1, 2)
    ox = orig[:, 0:1]                            # (1, 1)
    oy = orig[:, 1:2]
    feat = feat_ref[0]                           # (8, KE)
    out = _alg1_hits(
        ptx=qc(ox + ptop_ref[:, 0:1]),           # (Mt, 1)
        pty=qc(oy + ptop_ref[:, 1:2]),
        pbx=qc(ox + pbot_ref[:, 0:1]),
        pby=qc(oy + pbot_ref[:, 1:2]),
        mu_x=qc(feat[0:1, :]),                   # (1, KE)
        mu_y=qc(feat[1:2, :]),
        cxx=qc(feat[2:3, :]),
        cxy=qc(feat[3:4, :]),
        cyy=qc(feat[4:5, :]),
        lhs=feat[5:6, :],
        spiky=feat[6:7, :] != 0,
        mode=mode, delta_prec=delta_prec, mul_prec=mul_prec,
        acc_prec=acc_prec, slack=slack)
    mask_ref[0] = out.astype(jnp.int8)           # (Mt, KE)


def prtu_entry_cat_mask(p_top_local: jax.Array, p_bot_local: jax.Array,
                        tile_origins: jax.Array, feat: jax.Array,
                        *, mode: str = "smooth_focused",
                        coord_prec: str = "fp16", delta_prec: str = "fp8",
                        mul_prec: str = "fp8", acc_prec: str = "fp16",
                        slack: float = 0.0) -> jax.Array:
    """(T, K, Mt) int8 CAT mask over compacted list entries.

    p_top_local/p_bot_local: (Mt, 2) tile-local leader coords; tile_origins:
    (T, 2); feat: (T, 8, K) per-entry `feature_rows` gathered at the
    compacted lists. Invalid entries must carry lhs = -inf (they then never
    pass). K is padded to a KE_BLK multiple internally; callers get the
    unpadded slice back.
    """
    t, _, k = feat.shape
    mt = p_top_local.shape[0]
    kpad = -(-k // KE_BLK) * KE_BLK
    # Padded entries: lhs = -inf (row 5), so they never pass.
    pad_rows = jnp.zeros((8, 1), jnp.float32).at[5].set(-jnp.inf)
    feat = jnp.concatenate(
        [feat.astype(jnp.float32),
         jnp.broadcast_to(pad_rows, (t, 8, kpad - k))], axis=2)

    kernel = functools.partial(_prtu_entry_kernel, mode=mode,
                               coord_prec=coord_prec, delta_prec=delta_prec,
                               mul_prec=mul_prec, acc_prec=acc_prec,
                               slack=slack)
    out = pl.pallas_call(
        kernel,
        grid=(t, kpad // KE_BLK),
        in_specs=[
            pl.BlockSpec((mt, 2), lambda i, j: (0, 0)),
            pl.BlockSpec((mt, 2), lambda i, j: (0, 0)),
            # Per-tile origin as (T, 1, 2): the blocked tile axis leads.
            pl.BlockSpec((1, 1, 2), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 8, KE_BLK), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, mt, KE_BLK), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((t, mt, kpad), jnp.int8),
        # Every (tile, entry-block) is independent — no carried state, both
        # grid axes parallel, same as the dense PRTU kernel.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=kernels.interpret_mode(),
    )(p_top_local.astype(jnp.float32), p_bot_local.astype(jnp.float32),
      tile_origins.astype(jnp.float32).reshape(t, 1, 2), feat)
    return jnp.swapaxes(out[:, :, :k], 1, 2)
