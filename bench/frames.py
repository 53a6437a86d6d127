"""The benchmark's own copy of the paper's §6.2 synthetic frame recipe.

A frame is ``sky + N(0, read_noise) + sum_i A_i * G(sigma_i, x_i, y_i)``:
~3.4 stars per kilopixel², a per-frame star count drawn within +-40%,
power-law amplitudes from 10 to 5000 and PSF sigmas ~ U(1, 2.5) px,
rendered as 15 x 15 stamps onto float32.  The random streams are those of
the program's loader (per-row noise streams ``[77, id, 0, row]``, star
draws ``[77, id, 1]``), so a frame id names the same pixels here and
there, bit for bit: the distributed cell's reference renders the frames
the program's loader rendered.

The program renders stars one by one, rounding to float32 after each
stamp.  Here every stamp is computed at once and the additions are
applied level by level: level ``k`` holds each pixel's ``k``-th stamp in
star order, so the stamps of one level touch distinct pixels and every
pixel sees the same additions, in the same order, with the same
rounding.
"""
from __future__ import annotations

import numpy as np

RECIPE_KEYS = ("density_per_kpx2", "sky", "read_noise", "amp_min",
               "amp_max", "sigma_min", "sigma_max", "count_factor_min",
               "count_factor_max", "stamp")


def star_params(frame_id: int, size: int, recipe: dict):
    """Amplitudes, (row, col) centres and PSF sigmas of one frame."""
    rng = np.random.default_rng(np.random.SeedSequence([77, frame_id, 1]))
    base = max(1, int(recipe["density_per_kpx2"] / 1000.0 * size * size))
    n = max(1, int(base * rng.uniform(recipe["count_factor_min"],
                                      recipe["count_factor_max"])))
    u = rng.random(n)
    lo, hi = recipe["amp_min"], recipe["amp_max"]
    a = lo * (1 - u * (1 - (hi / lo) ** -0.8)) ** (-1 / 0.8)
    xy = rng.random((n, 2)) * size
    sig = rng.uniform(recipe["sigma_min"], recipe["sigma_max"], n)
    return a, xy, sig


def noise(frame_id: int, size: int, recipe: dict) -> np.ndarray:
    """Sky plus Gaussian read noise, one random stream per row."""
    img = np.empty((size, size), np.float32)
    for r in range(size):
        rng = np.random.default_rng(
            np.random.SeedSequence([77, frame_id, 0, r]))
        img[r] = rng.normal(recipe["sky"], recipe["read_noise"], size)
    return img


def render(frame_id: int, size: int, recipe: dict) -> np.ndarray:
    """One ``(size, size)`` float32 frame."""
    img = noise(frame_id, size, recipe)
    a, xy, sig = star_params(frame_id, size, recipe)
    half = int(recipe["stamp"]) // 2
    off = np.arange(-half, half + 1)
    grid = off.astype(np.float32).astype(np.float64)
    iy = xy[:, 0].astype(np.int64)
    ix = xy[:, 1].astype(np.int64)
    dy = xy[:, 0] - iy
    dx = xy[:, 1] - ix
    # The scalar expression of the one-by-one renderer, star by star, so
    # the denominators round exactly as there.
    den = np.array([2.0 * s ** 2 for s in sig])
    ry = (grid[None, :] - dy[:, None]) ** 2
    rx = (grid[None, :] - dx[:, None]) ** 2
    g = a[:, None, None] * np.exp(
        -((ry[:, :, None] + rx[:, None, :]) / den[:, None, None]))
    rows = iy[:, None] + off
    cols = ix[:, None] + off
    inside = (((rows >= 0) & (rows < size))[:, :, None]
              & ((cols >= 0) & (cols < size))[:, None, :])
    pix = (rows[:, :, None] * size + cols[:, None, :])[inside]
    val = g[inside]
    del g, inside
    # Stamps in star order; sort by (pixel, position) and count each
    # pixel's earlier stamps to get the level of every addition.
    m = pix.size
    shift = max(1, int(m - 1).bit_length())
    order = np.sort((pix << shift) | np.arange(m)) & ((1 << shift) - 1)
    ps = pix[order]
    first = np.ones(m, bool)
    first[1:] = ps[1:] != ps[:-1]
    level = np.arange(m) - np.maximum.accumulate(
        np.where(first, np.arange(m), 0))
    by_level = np.argsort(level.astype(np.int16), kind="stable")
    bounds = np.searchsorted(level[by_level], np.arange(level.max() + 2))
    flat = img.reshape(-1)
    for k in range(len(bounds) - 1):
        sel = order[by_level[bounds[k]:bounds[k + 1]]]
        p = pix[sel]
        flat[p] = (flat[p].astype(np.float64) + val[sel]).astype(np.float32)
    return img


def threshold(img: np.ndarray, factor: float, n_sigma: float = 2.0) -> float:
    """Variant-2 threshold: (median + n_sigma * 1.4826 * MAD) * factor."""
    med = float(np.median(img))
    mad = float(np.median(np.abs(img - med)))
    return (med + n_sigma * 1.4826 * mad) * factor
