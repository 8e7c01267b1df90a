"""Pure-jnp oracles for the Pallas kernels (shape-for-shape identical)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.precision import PrecisionScheme
from repro.core.cat import pr_gaussian_weight
from repro.core.gaussians import ALPHA_MIN
from repro.core.raster import T_EPS
from repro.kernels.render import K_BLK, pixel_minitile_index

ALPHA_MAX = 0.99


def _allow_pixels(allow, p: int):
    """(T, K, Mt) i8 per-entry mask -> (T, P, K) bool per-pixel lanes.

    Oracle-side counterpart of the kernels' in-VMEM one-hot expansion
    (in `render._blend_block`), sharing its pixel→mini-tile derivation."""
    mt_in_tile = pixel_minitile_index(p, allow.shape[2])       # (P,)
    return allow[:, :, mt_in_tile].swapaxes(1, 2) != 0         # (T, P, K)


def prtu_cat_mask_ref(p_top, p_bot, mu, conic, lhs, spiky, *,
                      mode: str = "smooth_focused", coord_prec: str = "fp16",
                      delta_prec: str = "fp8", mul_prec: str = "fp8",
                      acc_prec: str = "fp16", slack: float = 0.0) -> jax.Array:
    """(M, G) int8 — oracle for kernels.prtu.prtu_cat_mask."""
    prec = PrecisionScheme(coord_prec, delta_prec, mul_prec, acc_prec,
                           slack=slack)
    E = pr_gaussian_weight(mu[None, :, :], conic[None, :, :],
                           p_top[:, None, :], p_bot[:, None, :], prec)
    hit = lhs[None, :, None] > E * (1.0 - prec.slack)  # (M, G, 4)
    dense = jnp.any(hit, axis=-1)
    sparse = hit[..., 0] | hit[..., 3]
    if mode == "uniform_dense":
        out = dense
    elif mode == "uniform_sparse":
        out = sparse
    elif mode == "smooth_focused":
        out = jnp.where(spiky[None, :] != 0, sparse, dense)
    elif mode == "spiky_focused":
        out = jnp.where(spiky[None, :] != 0, dense, sparse)
    else:
        raise ValueError(mode)
    return out.astype(jnp.int8)


def blend_tiles_fused_ref(pix, feat, colors, valid, allow,
                          k_blk: int = K_BLK, t_eps: float = T_EPS):
    """Oracle for kernels.render.blend_tiles_fused's measured counters.

    Computes the full (no-termination) sweep, then derives what the fused
    kernel must report: per-pixel processed/blended counts and per-entry
    alive flags under the T >= t_eps rule, and the number of K blocks the
    kernel executes — block j of tile t runs iff j is within the tile's
    occupied-block bound and some pixel is still above t_eps entering it.
    (The kernel's carried transmittance equals the full cumulative product
    at every block it executes, and a skipped tile stays dead, so deriving
    liveness from the full product is exact.)

    Returns (rgb, trans, processed, blended, entry_alive, kblocks_processed,
    kblocks_total) shaped like `FusedBlendOut` — rgb/trans are the *full*
    sweep, which the fused kernel matches to < t_eps.
    """
    px = pix[..., 0][:, :, None]                      # (T, P, 1)
    py = pix[..., 1][:, :, None]
    mx = feat[..., 0][:, None, :]                     # (T, 1, K)
    my = feat[..., 1][:, None, :]
    cxx = feat[..., 2][:, None, :]
    cxy = feat[..., 3][:, None, :]
    cyy = feat[..., 4][:, None, :]
    op = feat[..., 5][:, None, :]
    dx = px - mx
    dy = py - my
    e = 0.5 * (cxx * dx * dx + cyy * dy * dy) + cxy * dx * dy
    a = jnp.minimum(op * jnp.exp(-e), ALPHA_MAX)      # (T, P, K)
    lane = (valid[:, None, :] != 0) & _allow_pixels(allow, pix.shape[1])
    a = jnp.where(lane & (a >= ALPHA_MIN), a, 0.0)
    tcum = jnp.cumprod(1.0 - a, axis=-1)
    t_excl = jnp.concatenate([jnp.ones_like(tcum[..., :1]),
                              tcum[..., :-1]], axis=-1)
    w = t_excl * a
    rgb = jnp.einsum("tpk,tkc->tpc", w, colors)
    trans = tcum[..., -1]

    alive = t_excl >= t_eps                           # (T, P, K)
    processed = jnp.sum((lane & alive).astype(jnp.float32), axis=-1)
    blended = jnp.sum(((a > 0) & alive).astype(jnp.float32), axis=-1)
    entry_alive = jnp.any(alive, axis=1) & (valid != 0)   # (T, K)

    k = valid.shape[1]
    n_blocks = -(-k // k_blk)
    nvalid = jnp.sum((valid != 0).astype(jnp.int32), axis=1)
    kb_bound = -(-nvalid // k_blk)                    # (T,)
    starts = jnp.arange(n_blocks) * k_blk
    # t_excl at each block's first entry; starts < k always (n_blocks from k).
    t_enter = t_excl[:, :, starts]                    # (T, P, n_blocks)
    tile_alive = jnp.any(t_enter >= t_eps, axis=1)    # (T, n_blocks)
    runs = tile_alive & (jnp.arange(n_blocks)[None, :] < kb_bound[:, None])
    kblocks_processed = jnp.sum(runs.astype(jnp.int32), axis=1)
    return (rgb, trans, processed, blended, entry_alive, kblocks_processed,
            n_blocks)


def blend_tiles_ref(pix, feat, colors, valid, allow):
    """Oracle for kernels.render.blend_tiles. Same signature/outputs."""
    px = pix[..., 0][:, :, None]                      # (T, P, 1)
    py = pix[..., 1][:, :, None]
    mx = feat[..., 0][:, None, :]                     # (T, 1, K)
    my = feat[..., 1][:, None, :]
    cxx = feat[..., 2][:, None, :]
    cxy = feat[..., 3][:, None, :]
    cyy = feat[..., 4][:, None, :]
    op = feat[..., 5][:, None, :]
    dx = px - mx
    dy = py - my
    e = 0.5 * (cxx * dx * dx + cyy * dy * dy) + cxy * dx * dy
    a = jnp.minimum(op * jnp.exp(-e), ALPHA_MAX)      # (T, P, K)
    ok = ((valid[:, None, :] != 0)
          & _allow_pixels(allow, pix.shape[1]) & (a >= ALPHA_MIN))
    a = jnp.where(ok, a, 0.0)
    tcum = jnp.cumprod(1.0 - a, axis=-1)
    t_excl = jnp.concatenate([jnp.ones_like(tcum[..., :1]),
                              tcum[..., :-1]], axis=-1)
    w = t_excl * a                                    # (T, P, K)
    rgb = jnp.einsum("tpk,tkc->tpc", w, colors)
    trans = tcum[..., -1]
    return rgb, trans
