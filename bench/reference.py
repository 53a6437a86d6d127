"""Plain reference for 0-dimensional superlevel persistence of a frame.

Independent of the program under test: NumPy only.  Pixels are ordered
by the strict total order ``(value, flat index)``; components are born at
local maxima and, when two meet at a pixel, the one with the younger
(lower) maximum dies there (the elder rule), 8-connectivity.  The
essential class of the global maximum dies at the global minimum.

With a Variant-2 threshold ``t`` only pixels ``>= t`` (compared in
float32) take part: components born below ``t`` do not exist, merges
below ``t`` never happen, and every component still alive at ``t`` other
than the essential one dies at ``t`` with death pixel ``-1``.  That is
the untruncated diagram cut at ``t``.

The union-find runs over merge events only.  Every kept pixel first
climbs to its highest 8-neighbour until it reaches a local maximum (its
basin); a pixel whose strictly higher neighbours all lie in one basin
joins that basin's component and merges nothing, so only pixels whose
higher neighbours span two or more basins are visited in descending
order.  ``bench/tests`` holds this equal to a per-pixel union-find.
"""
from __future__ import annotations

import numpy as np

OFFSETS = ((-1, -1), (-1, 0), (-1, 1), (0, -1), (0, 1), (1, -1), (1, 0),
           (1, 1))


def _neighbours(kept: np.ndarray, h: int, w: int) -> np.ndarray:
    """(m, 8) flat indices of each kept pixel's neighbours, -1 off-frame."""
    r, c = np.divmod(kept, w)
    out = np.full((kept.size, 8), -1, np.int64)
    for j, (dr, dc) in enumerate(OFFSETS):
        rr, cc = r + dr, c + dc
        ok = (rr >= 0) & (rr < h) & (cc >= 0) & (cc < w)
        out[ok, j] = rr[ok] * w + cc[ok]
    return out


def diagram(image: np.ndarray, threshold: float | None = None) -> np.ndarray:
    """``(C, 4)`` float64 rows ``[birth, death, p_birth, p_death]``, sorted
    by descending ``(birth, p_birth)``."""
    img = np.asarray(image, np.float32) + np.float32(0)  # -0.0 -> 0.0
    h, w = img.shape
    vals = img.reshape(-1)
    n = vals.size
    if threshold is None:
        kept = np.arange(n)
    else:
        kept = np.flatnonzero(vals >= np.float32(threshold))
    m = kept.size
    # Rank of each kept pixel in the ascending total order; pixels below
    # the threshold rank -1, under every kept pixel.
    rank = np.full(n, -1, np.int64)
    by_order = kept[np.argsort(vals[kept], kind="stable")]
    rank[by_order] = np.arange(m)
    nb = _neighbours(kept, h, w)
    nb_rank = np.where(nb >= 0, rank[np.maximum(nb, 0)], -1)
    own = rank[kept]
    higher = nb_rank > own[:, None]
    # Steepest ascent in rank space, then pointer doubling to the basin.
    best = nb_rank.max(axis=1)
    up = np.where(best > own, best, own)          # rank -> rank
    parent = np.empty(m, np.int64)
    parent[own] = up
    while True:
        nxt = parent[parent]
        if np.array_equal(nxt, parent):
            break
        parent = nxt
    basin = np.where(higher, parent[np.maximum(nb_rank, 0)], -1)
    # Visit pixels whose higher neighbours span two or more basins.
    bmax = basin.max(axis=1)
    bmin = np.where(higher, basin, np.iinfo(np.int64).max).min(axis=1)
    events = np.flatnonzero(higher.any(axis=1) & (bmin != bmax))
    events = events[np.argsort(-own[events], kind="stable")]
    uf = np.arange(m)                               # over ranks of roots
    death_val: dict[int, float] = {}
    death_pix: dict[int, int] = {}

    def find(x: int) -> int:
        root = x
        while uf[root] != root:
            root = uf[root]
        while uf[x] != root:
            uf[x], x = root, uf[x]
        return root

    for e in events:
        comps = {find(int(b)) for b in basin[e] if b >= 0}
        if len(comps) < 2:
            continue
        elder = max(comps)                          # highest rank
        for cp in comps:
            if cp != elder:
                death_val[cp] = float(vals[kept[e]])
                death_pix[cp] = int(kept[e])
                uf[cp] = elder

    roots = np.flatnonzero(parent == np.arange(m))  # ranks of basins
    pix = by_order[roots]
    top = int(roots.max())
    gmin = int(np.argmin(vals))
    births = vals[pix].astype(np.float64)
    t_death = np.nan if threshold is None else float(np.float32(threshold))
    deaths = np.array([death_val.get(int(r), t_death) for r in roots])
    p_death = np.array([death_pix.get(int(r), -1) for r in roots],
                       np.float64)
    ess = roots == top
    deaths[ess] = float(vals[gmin])
    p_death[ess] = gmin
    rows = np.stack([births, deaths, pix.astype(np.float64), p_death], 1)
    return rows[np.lexsort((rows[:, 2], rows[:, 0]))[::-1]]


def summary(rows: np.ndarray) -> dict:
    """The per-frame summary a distributed job reports, from full rows:
    count, the first five births and deaths of the padded diagram (pad
    rows read -inf), and total persistence."""
    birth, death = rows[:, 0], rows[:, 1]
    pad = [-np.inf] * max(0, 5 - len(rows))
    return {"count": int(rows.shape[0]),
            "top_births": birth[:5].tolist() + pad,
            "top_deaths": death[:5].tolist() + pad,
            "persistence_sum": float(np.sum(np.clip(birth - death, 0, None)))}
