"""The comparison that decides a run's `correct`.

Each served frame of the sample is compared with the plain reference
(`bench.reference`) rendered from the same scene and camera. Six numbers
are compared, each the worst over the sampled frames:

  image_mae        mean absolute difference over every channel of the frame;
  tile_mae_max     the same mean over the channels of one 16x16 tile, for
                   the frame's worst tile: a fault in one tile's list, CTU
                   or blend shows here where the frame's mean hides it;
  image_off_share  share of channels that differ by more than one 8-bit
                   level (1/255);
  stage1_gap       largest relative gap of the Stage-1 counts: Gaussians in
                   the frustum, tile-list entries, spill passes;
  ctu_gap          largest relative gap of the CTU counts: sub-tile hits
                   and mini-tile hits (VRU pairs);
  blend_gap        largest relative gap of the blend counts: entries each
                   pixel processed and blended while not yet opaque.

A relative gap is |served - reference| / max(|reference|, 1). Each number
has its own limit, kept per cell in `bench/checks/<cell>.json` with the
readings it was set from.
"""
from __future__ import annotations

import numpy as np

GROUPS = {
    "stage1_gap": ("n_frustum", "dup_tile", "spill_passes"),
    "ctu_gap": ("ctu_pairs", "vru_pairs"),
    "blend_gap": ("processed_per_pixel", "blended_per_pixel"),
}
NUMBERS = ("image_mae", "tile_mae_max", "image_off_share") + tuple(GROUPS)
TILE = 16


def tile_mae_max(diff) -> float:
    """Largest mean of `diff` (H, W, C) over one 16x16 tile, the unit the
    Stage-1 lists, the CTU and the blend work in."""
    h, w, c = diff.shape
    tiles = diff.reshape(h // TILE, TILE, w // TILE, TILE, c)
    return float(tiles.mean(axis=(1, 3, 4)).max())


def frame_numbers(image, counters: dict, ref: dict) -> dict:
    """The compared numbers of one frame against its reference render."""
    diff = np.abs(np.asarray(image, np.float32) - ref["image"])
    out = dict(image_mae=float(diff.mean()), tile_mae_max=tile_mae_max(diff),
               image_off_share=float(np.mean(diff > 1.0 / 255.0)))
    for name, keys in GROUPS.items():
        out[name] = max(
            abs(float(counters[k]) - ref["counters"][k])
            / max(abs(ref["counters"][k]), 1.0) for k in keys)
    return out


def worst(per_frame: list) -> dict:
    """Each number's worst (largest) value over frames."""
    return {k: max(f[k] for f in per_frame) for k in NUMBERS}


def verdict(numbers: dict, limits: dict) -> tuple[bool, list]:
    """(every number within its limit, [(name, number, limit), ...])."""
    rows = [(k, numbers[k], limits[k]) for k in NUMBERS]
    return all(v <= lim for _, v, lim in rows), rows
