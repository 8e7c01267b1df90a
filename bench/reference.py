"""Plain float32 reference of the served render path.

A straightforward implementation of what a served frame shows, written from
the algorithm's definition and sharing no code with the program:

1. projection (EWA splatting): camera transform, 2-D covariance with the
   0.3 px low-pass, conic, 3-sigma radius, frustum and opacity cull;
2. Stage 1: every in-frustum Gaussian is binned into the 16-px tiles its
   axis-aligned 3-sigma square overlaps, and each tile's list is ordered
   front to back (depth, then Gaussian index);
3. the contribution test (CTU): per list entry, the 8-px sub-tile AABB gate
   and the mini-tile leader-pixel test of the paper's Alg. 1 in its MIXED
   precision (coordinates fp16, deltas fp8, products and sums fp16, 15 %
   slack), smooth Gaussians on four corners and spiky ones on the diagonal;
4. the blend: a strict front-to-back fold, alpha = min(0.99, o exp(-E)),
   alpha < 1/255 skipped, no early termination, background 0.

It also counts the work the program reports per frame: Gaussians in the
frustum, list entries, sub-tile and mini-tile hits, spill passes, and the
entries each pixel touched (processed) and blended while its transmittance
was at least 1e-4.

Matrix products are written out as sums of elementwise products, so no
matmul precision setting applies. `dtype` switches the arithmetic of
steps 1, 2 and 4 to another float type (the control runs it in bfloat16);
the CTU keeps its own fixed-format rounding.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ALPHA_MIN = 1.0 / 255.0
ALPHA_MAX = 0.99
T_EPS = 1e-4
TILE, SUBTILE, MINITILE = 16, 8, 4
SPIKY_RATIO = 3.0
SLACK = 0.15
# (mantissa bits, smallest normal exponent, largest finite, overflow value)
FORMATS = {"fp16": (10, -14, 65504.0, math.inf),
           "fp8": (3, -6, 448.0, math.nan)}     # float8_e4m3fn


def quantize(x, kind: str):
    """Round float32 `x` to the nearest value of an fp16 or fp8 (e4m3fn)
    grid, ties to even, as float32: the grid spacing at x's binade (or the
    subnormal spacing), and the format's overflow value past its largest
    finite number."""
    mbits, emin, fmax, over = FORMATS[kind]
    _, e = jnp.frexp(x)                       # x = m * 2**e, 0.5 <= |m| < 1
    step = jnp.ldexp(jnp.float32(1.0), jnp.maximum(e - 1, emin) - mbits)
    y = jnp.round(x / step) * step
    y = jnp.where(jnp.abs(y) > fmax, jnp.where(x < 0, -over, over), y)
    return jnp.where(jnp.isfinite(x), y, x)


def project(scene: dict, cam: dict, dtype=jnp.float32) -> dict:
    """Per-Gaussian image-plane features of one camera (arrays of (N,))."""
    f = lambda x: jnp.asarray(x).astype(dtype)
    means, log_scales, quats = f(scene["means"]), f(scene["log_scales"]), \
        f(scene["quats"])
    R, t = f(cam["R_wc"]), f(cam["t_wc"])
    fx, fy, cx, cy = f(cam["fx"]), f(cam["fy"]), f(cam["cx"]), f(cam["cy"])
    width, height, near = cam["width"], cam["height"], cam["near"]

    pc = [sum(R[i, j] * means[:, j] for j in range(3)) + t[i]
          for i in range(3)]                              # camera space
    z = pc[2]
    zs = jnp.maximum(z, near)
    xn, yn = pc[0] / zs, pc[1] / zs
    px, py = xn * fx + cx, yn * fy + cy
    limx = 1.3 * (width / (2.0 * fx))
    limy = 1.3 * (height / (2.0 * fy))
    tx = jnp.clip(xn, -limx, limx) * zs
    ty = jnp.clip(yn, -limy, limy) * zs
    # Jacobian rows of the perspective projection, times the rotation.
    j0 = (fx / zs, jnp.zeros_like(zs), -fx * tx / (zs * zs))
    j1 = (jnp.zeros_like(zs), fy / zs, -fy * ty / (zs * zs))
    jw = [[sum(jr[k] * R[k, c] for k in range(3)) for c in range(3)]
          for jr in (j0, j1)]

    q = quats / (jnp.sqrt(jnp.sum(quats * quats, axis=1, keepdims=True))
                 + 1e-12)
    w, x, y, zq = q[:, 0], q[:, 1], q[:, 2], q[:, 3]
    rot = [[1 - 2 * (y * y + zq * zq), 2 * (x * y - w * zq),
            2 * (x * zq + w * y)],
           [2 * (x * y + w * zq), 1 - 2 * (x * x + zq * zq),
            2 * (y * zq - w * x)],
           [2 * (x * zq - w * y), 2 * (y * zq + w * x),
            1 - 2 * (x * x + y * y)]]
    s = jnp.exp(log_scales)
    m = [[rot[i][k] * s[:, k] for k in range(3)] for i in range(3)]  # R S
    sigma = [[sum(m[i][k] * m[j][k] for k in range(3)) for j in range(3)]
             for i in range(3)]
    js = [[sum(jw[a][k] * sigma[k][c] for k in range(3)) for c in range(3)]
          for a in range(2)]
    cov = [[sum(js[a][k] * jw[b][k] for k in range(3)) for b in range(2)]
           for a in range(2)]
    sxx, sxy, syy = cov[0][0] + 0.3, cov[0][1], cov[1][1] + 0.3

    det = sxx * syy - sxy * sxy
    inv = 1.0 / jnp.maximum(det, 1e-12)
    half_tr = (sxx + syy) / 2.0
    disc = jnp.sqrt(jnp.maximum(half_tr * half_tr - det, 0.0))
    major = jnp.sqrt(jnp.maximum(half_tr + disc, 1e-12))
    minor = jnp.sqrt(jnp.maximum(half_tr - disc, 1e-12))
    radius = jnp.ceil(3.0 * major)
    opacity = jax.nn.sigmoid(f(scene["opacity_logits"]))
    in_frustum = ((z > near)
                  & (px + radius > 0) & (px - radius < width)
                  & (py + radius > 0) & (py - radius < height)
                  & (opacity >= ALPHA_MIN))
    return dict(mx=px, my=py, ca=syy * inv, cb=-sxy * inv, cc=sxx * inv,
                depth=z, radius=radius, opacity=opacity,
                color=f(scene["colors"]),
                spiky=major / jnp.maximum(minor, 1e-12) >= SPIKY_RATIO,
                in_frustum=in_frustum)


def _tile_ranges(p: dict, tiles_x: int, tiles_y: int):
    """First tile and extent of each Gaussian's 3-sigma square on the tile
    grid, and its tile count (0 out of the frustum): tile tx is hit iff
    mx + r > 16 tx and mx - r < 16 tx + 16."""
    def rng(c, n):
        lo = jnp.clip(jnp.floor((c - p["radius"]) / TILE), 0, n - 1)
        hi = jnp.clip(jnp.ceil((c + p["radius"]) / TILE) - 1, -1, n - 1)
        return lo.astype(jnp.int32), (hi - lo + 1).astype(jnp.int32)
    x0, w = rng(p["mx"], tiles_x)
    y0, h = rng(p["my"], tiles_y)
    live = p["in_frustum"] & (w > 0) & (h > 0)
    return x0, y0, w, h, jnp.where(live, w * h, 0)


@functools.partial(jax.jit, static_argnames=("tiles_x", "tiles_y"))
def _list_lengths(p, *, tiles_x, tiles_y):
    """(total pairs, (T,) list length of every tile): each Gaussian adds 1
    to every tile of its rectangle, through a 2-D difference array."""
    x0, y0, w, h, count = _tile_ranges(p, tiles_x, tiles_y)
    one = (count > 0).astype(jnp.int32)
    diff = jnp.zeros((tiles_y + 1, tiles_x + 1), jnp.int32)
    diff = diff.at[y0, x0].add(one).at[y0, x0 + w].add(-one)
    diff = diff.at[y0 + h, x0].add(-one).at[y0 + h, x0 + w].add(one)
    grid = jnp.cumsum(jnp.cumsum(diff, axis=0), axis=1)
    return jnp.sum(count), grid[:tiles_y, :tiles_x].reshape(-1)


@functools.partial(jax.jit,
                   static_argnames=("tiles_x", "tiles_y", "cap", "k_cap"))
def _tile_lists(p, *, tiles_x, tiles_y, cap, k_cap):
    """(k_cap, T) Gaussian ids of each tile's list, front to back, and the
    (T,) list lengths. `cap` bounds the number of (tile, Gaussian) pairs."""
    n = p["mx"].shape[0]
    n_tiles = tiles_x * tiles_y
    key = jnp.where(p["in_frustum"], p["depth"], jnp.inf)
    order = jnp.argsort(key, stable=True)            # front to back
    x0, y0, w, _, count = (a[order]
                           for a in _tile_ranges(p, tiles_x, tiles_y))
    start = jnp.cumsum(count) - count
    total = jnp.sum(count)
    rank = jnp.repeat(jnp.arange(n, dtype=jnp.int32), count,
                      total_repeat_length=cap)
    pair = jnp.arange(cap, dtype=jnp.int32)
    j = pair - start[rank]
    tile = (y0[rank] + j // w[rank]) * tiles_x + x0[rank] + j % w[rank]
    tile = jnp.where(pair < total, tile, n_tiles)    # padding sorts last
    tile, gid = jax.lax.sort((tile, order[rank].astype(jnp.int32)),
                             num_keys=1, is_stable=True)
    length = jnp.zeros((n_tiles + 1,), jnp.int32).at[tile].add(1)[:n_tiles]
    first = jnp.cumsum(length) - length
    k = jnp.arange(k_cap, dtype=jnp.int32)[:, None]
    valid = k < length[None, :]
    lists = jnp.where(valid, gid[jnp.minimum(first[None, :] + k, cap - 1)],
                      0)
    return lists, length


def _leader_hits(p: dict, gid, ox, oy):
    """(T, 16) CTU hits of entry `gid` of each tile at its 16 mini-tiles
    (Alg. 1 in MIXED precision), and the (T, 4) sub-tile AABB gate."""
    q16 = functools.partial(quantize, kind="fp16")
    q8 = functools.partial(quantize, kind="fp8")
    g = lambda k: p[k][gid].astype(jnp.float32)[:, None]
    mux, muy = q16(g("mx")), q16(g("my"))
    ca, cb, cc = q16(g("ca")), q16(g("cb")), q16(g("cc"))
    lhs = jnp.log(255.0 * jnp.maximum(g("opacity"), 1e-12))
    loc = jnp.arange(16)
    lx = ((loc % 4) * MINITILE).astype(jnp.float32)[None, :]
    ly = ((loc // 4) * MINITILE).astype(jnp.float32)[None, :]
    d = {}
    for name, off in (("t", 0.5), ("b", MINITILE - 0.5)):
        d[name + "x"] = q8(q16(ox + lx + off) - mux)
        d[name + "y"] = q8(q16(oy + ly + off) - muy)
    sq = lambda v, c: q16(q16(0.5 * q16(v * v)) * c)
    sx = {k: sq(d[k + "x"], ca) for k in "tb"}
    sy = {k: sq(d[k + "y"], cc) for k in "tb"}
    cross = lambda a, b: q16(q16(d[a + "x"] * d[b + "y"]) * cb)
    e = [q16(q16(sx[a] + sy[b]) + cross(a, b))
         for a, b in (("t", "t"), ("b", "t"), ("t", "b"), ("b", "b"))]
    thr = jnp.float32(1.0 - SLACK)
    ok = [lhs > ei * thr for ei in e]
    spiky = p["spiky"][gid][:, None]
    mini = jnp.where(spiky, ok[0] | ok[3], ok[0] | ok[1] | ok[2] | ok[3])

    mx, my, r = (p[k][gid].astype(jnp.float32)[:, None]
                 for k in ("mx", "my", "radius"))
    sub = jnp.arange(4)
    sx0 = ox + ((sub % 2) * SUBTILE).astype(jnp.float32)[None, :]
    sy0 = oy + ((sub // 2) * SUBTILE).astype(jnp.float32)[None, :]
    gate = ((mx + r > sx0) & (mx - r < sx0 + SUBTILE)
            & (my + r > sy0) & (my - r < sy0 + SUBTILE))
    sub_of_mini = (loc // 8) * 2 + (loc % 4) // 2
    return mini & gate[:, sub_of_mini], gate


@functools.partial(jax.jit, static_argnames=("tiles_x", "tiles_y"))
def _blend(p, lists, length, *, tiles_x, tiles_y):
    """Front-to-back fold over the list slots of every tile at once."""
    n_tiles = tiles_x * tiles_y
    dtype = p["mx"].dtype
    tid = jnp.arange(n_tiles)
    ox = ((tid % tiles_x) * TILE).astype(jnp.float32)[:, None]
    oy = ((tid // tiles_x) * TILE).astype(jnp.float32)[:, None]
    pix = jnp.arange(TILE * TILE)
    px = (ox + (pix % TILE).astype(jnp.float32) + 0.5).astype(dtype)
    py = (oy + (pix // TILE).astype(jnp.float32) + 0.5).astype(dtype)
    mini_of_pixel = (pix // TILE // MINITILE) * 4 + (pix % TILE) // MINITILE

    def step(carry, xs):
        trans, rgb, processed, blended, counts = carry
        k, gid = xs
        valid = (k < length)[:, None]
        mini, gate = _leader_hits(p, gid, ox, oy)
        mini, gate = mini & valid, gate & valid
        lane = mini[:, mini_of_pixel]                          # (T, P)
        g = lambda key: p[key][gid][:, None]
        dx, dy = px - g("mx"), py - g("my")
        e = 0.5 * (g("ca") * dx * dx + g("cc") * dy * dy) + g("cb") * dx * dy
        a = jnp.minimum(g("opacity") * jnp.exp(-e), ALPHA_MAX)
        a = jnp.where(lane & (a >= ALPHA_MIN), a, 0.0).astype(dtype)
        alive = trans >= T_EPS
        w = trans * a
        rgb = rgb + w[..., None] * p["color"][gid][:, None, :]
        processed = processed + (lane & alive)
        blended = blended + ((a > 0) & alive)
        trans = trans * (1.0 - a)
        counts = counts + jnp.stack([jnp.sum(valid), jnp.sum(gate),
                                     jnp.sum(mini)]).astype(jnp.int32)
        return (trans, rgb, processed, blended, counts), None

    n_pix = TILE * TILE
    init = (jnp.ones((n_tiles, n_pix), dtype),
            jnp.zeros((n_tiles, n_pix, 3), dtype),
            jnp.zeros((n_tiles, n_pix), jnp.int32),
            jnp.zeros((n_tiles, n_pix), jnp.int32),
            jnp.zeros((3,), jnp.int32))
    k_cap = lists.shape[0]
    (trans, rgb, processed, blended, counts), _ = jax.lax.scan(
        step, init, (jnp.arange(k_cap, dtype=jnp.int32), lists))

    def untile(x):
        x = x.reshape((tiles_y, tiles_x, TILE, TILE) + x.shape[2:])
        x = jnp.moveaxis(x, 2, 1)
        return x.reshape((tiles_y * TILE, tiles_x * TILE) + x.shape[4:])
    return (untile(rgb).astype(jnp.float32), counts,
            jnp.mean(processed.astype(jnp.float32)),
            jnp.mean(blended.astype(jnp.float32)))


def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def render(scene: dict, cam: dict, *, k_pass: int,
           dtype=jnp.float32) -> dict:
    """One frame: the image (H, W, 3) as a NumPy array and the counters.
    `cam` holds the camera's arrays and its width, height and near plane;
    `k_pass` is the spill pass length the pass count is reckoned in."""
    tiles_x, tiles_y = cam["width"] // TILE, cam["height"] // TILE
    arrays = {k: cam[k] for k in ("R_wc", "t_wc", "fx", "fy", "cx", "cy")}
    p = _project(scene, arrays, width=cam["width"], height=cam["height"],
                 near=cam["near"], dtype=dtype)
    total, length = _list_lengths(p, tiles_x=tiles_x, tiles_y=tiles_y)
    total, longest = int(total), int(jnp.max(length))
    lists, length = _tile_lists(p, tiles_x=tiles_x, tiles_y=tiles_y,
                                cap=next_pow2(max(total, 1)),
                                k_cap=next_pow2(max(longest, 1)))
    image, counts, processed, blended = _blend(
        p, lists, length, tiles_x=tiles_x, tiles_y=tiles_y)
    dup_tile, ctu_pairs, vru_pairs = (float(c) for c in np.asarray(counts))
    return dict(
        image=np.asarray(image),
        counters=dict(
            n_frustum=float(jnp.sum(p["in_frustum"])),
            dup_tile=dup_tile, ctu_pairs=ctu_pairs, vru_pairs=vru_pairs,
            spill_passes=float(max(-(-longest // k_pass), 1)),
            processed_per_pixel=float(processed),
            blended_per_pixel=float(blended)))


@functools.partial(jax.jit, static_argnames=("width", "height", "near",
                                             "dtype"))
def _project(scene, arrays, *, width, height, near, dtype):
    return project(scene, dict(arrays, width=width, height=height,
                               near=near), dtype)
