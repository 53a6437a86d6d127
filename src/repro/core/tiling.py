"""Tile-decomposed PixHomology: halo-tiled PH with a cross-tile seam merge.

The paper (§5.2) distributes *whole images* across executors, so the largest
analyzable image is bounded by one worker's memory.  Following the spatial
decompositions of Bauer-Kerber-Reininghaus (DIPHA) and Dory, this module
lets one image span a ``(gr, gc)`` grid of halo-padded tiles (and devices)
while staying **bit-identical** to ``pixhomology`` on the whole image:

1. *Per tile* (steps 1-4, embarrassingly parallel, memory ~ tile size):
   steepest-ascent pointers under the global (value, flat index) total
   order — the 1-pixel halo makes every owned pixel's 3x3 window exact;
   pointer-doubling label resolution *frozen at the halo* (each owned pixel
   resolves to an in-tile basin root or to a halo pixel it exits through);
   exact candidate detection and clique-chained saddle edges computed on a
   per-tile total-order key that is order-isomorphic to the global order —
   packed ``(value, global index)`` int64 bit-keys by default (no per-tile
   sort; ``repro.core.packed_keys``), or lexsort-materialized dense ranks
   on the ``merge_keys="rank"`` fallback.

2. *Boundary condensation* (O(boundary), not O(n)): the 1-px ring of every
   tile is collected into a sorted (pixel -> exit pointer) table; pointer
   doubling on that table resolves every cross-tile basin chain in O(log)
   rounds, since a chain can only leave a tile through a ring pixel.

3. *Global seam merge*: per-tile basin roots and saddle-edge lists are
   concatenated into a compact elder-rule instance and reduced by the same
   :func:`repro.core.parallel_merge.boruvka_forest` machinery the
   whole-image Boruvka path uses — O(log C) rounds over basins, not pixels.

Correctness argument (see also ``src/repro/ph/README.md``): the halo makes
pointers, candidates, and edge chains at owned pixels *pixel-for-pixel equal*
to the whole-image computation (comparisons use (value, global index), so
per-tile ranks can substitute for global ranks); the condensed ring table
reaches the same label fixed point as whole-image pointer doubling; and the
elder-rule deaths are a graph invariant of the (basin, saddle-edge) multiset,
which both paths build identically — so diagrams match bit-for-bit,
including ``p_birth``/``p_death`` in global coordinates.

Capacities are two-level: per-tile (``tile_max_features`` roots +
``tile_max_candidates`` saddle candidates per tile) and global
(``max_features`` diagram rows).  Each level reports its own overflow flag
so :meth:`repro.ph.PHEngine.run_tiled` can regrow exactly the undersized
level.

Residency: :func:`tiled_pixhomology` takes a host-resident ``(H, W)`` array
(convenient for tests and small images), but the compute core is
:func:`tiled_pixhomology_stacks`, which takes the halo-padded tile stacks
directly.  :func:`load_tile_stacks` builds those stacks from a **tile
provider** (anything with ``shape`` / ``dtype`` / ``halo_tile(t, grid)``,
e.g. :class:`repro.data.astro.AstroImage`) one tile at a time — each tile
is placed on device as soon as it is generated, so the host never holds
more than one halo-padded tile of the image (the streaming pipeline's
"no host holds a full image" guarantee; Variant-1 ``load_self`` for tiles).
With ``shard_ctx`` the stacks are sharding-constrained on the mesh's data
axes, so all downstream intermediates are tile-resident per device.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packed_keys
from repro.core.grid import (
    fixed_point_iterate,
    higher_neighbor_basins,
    neg_inf as _neg_inf,
)
from repro.core.packed_keys import key_pad, masked_top_k, pack_keys
from repro.core.parallel_merge import boruvka_forest, chain_clique_edges
from repro.core.pixhomology import (
    Diagram,
    exact_candidates,
    keyed_steepest_pointers,
    resolve_labels,
)

_I32_MAX = np.iinfo(np.int32).max


class TiledDiagram(NamedTuple):
    """Whole-image :class:`Diagram` plus the two-level overflow split."""

    diagram: Diagram
    tile_overflow: jnp.ndarray    # () bool: some tile's F_t/K_t undersized
    merge_overflow: jnp.ndarray   # () bool: global diagram capacity undersized
    n_tile_roots: jnp.ndarray     # (T,) int32 roots per tile (capacity sizing)
    n_tile_cands: jnp.ndarray     # (T,) int32 candidates per tile


class TileBoundaryState(NamedTuple):
    """Everything the seam merge needs, per tile — the cacheable artifact.

    Every field is **tile-local**: computed from one halo-padded tile alone
    (:func:`tile_phase_ab`), never from another tile's state or from the
    resolved cross-tile labels.  That locality is the delta-recompute
    contract (``repro.core.delta``): a tile whose halo-padded bytes are
    unchanged has bit-identical state, so a cached row can stand in for a
    recompute.  Consequently saddle-edge endpoints ``e_a``/``e_b`` carry
    *pre-labels* (an in-tile basin root or the halo pixel the ascent chain
    exits through), not final global basin labels — the final resolution
    through the ring table happens once, in :func:`merge_tile_state`.

    All arrays have a leading tile axis ``T`` when stacked; invariants:

    * ``ring_gidx``/``ring_ptr`` (T, R): the tile's 1-px boundary ring and
      its exit pointers — the condensation-table rows.
    * ``e_*`` (T, k, 8): clique-chained saddle candidate edges keyed by the
      saddle pixel (``e_val``/``e_pos``); endpoints are pre-labels.
    * ``root_*`` (T, f): top-``f`` owned basin roots (a root's final label
      is itself, so these are global already); ``rmax_*`` the unfiltered
      per-tile maximum root for the essential class.
    * ``n_roots``/``n_cand`` (T,): exact counts for overflow detection.
    """

    ring_gidx: jnp.ndarray        # (T, R) int32
    ring_ptr: jnp.ndarray         # (T, R) int32
    min_val: jnp.ndarray          # (T,) image dtype
    min_gidx: jnp.ndarray         # (T,) int32
    e_val: jnp.ndarray            # (T, k, 8) image dtype
    e_pos: jnp.ndarray            # (T, k, 8) int32
    e_a: jnp.ndarray              # (T, k, 8) int32 pre-label endpoint
    e_b: jnp.ndarray              # (T, k, 8) int32 pre-label endpoint
    e_ok: jnp.ndarray             # (T, k, 8) bool
    root_val: jnp.ndarray         # (T, f) image dtype
    root_gidx: jnp.ndarray        # (T, f) int32
    root_valid: jnp.ndarray       # (T, f) bool
    rmax_val: jnp.ndarray         # (T,) image dtype
    rmax_gidx: jnp.ndarray        # (T,) int32
    n_roots: jnp.ndarray          # (T,) int32
    n_cand: jnp.ndarray           # (T,) int32


# ---------------------------------------------------------------------------
# Grid selection / validation
# ---------------------------------------------------------------------------

def validate_grid(shape: tuple[int, int], grid: tuple[int, int]) -> None:
    h, w = shape
    gr, gc = grid
    if gr < 1 or gc < 1:
        raise ValueError(f"tile grid must be >= (1, 1), got {grid}")
    if h % gr or w % gc:
        raise ValueError(f"tile grid {grid} does not divide image {shape}; "
                         f"pick divisors (see choose_grid)")


def choose_grid(shape: tuple[int, int], max_tile_pixels: int
                ) -> tuple[int, int]:
    """Smallest dividing (gr, gc) whose tiles hold <= ``max_tile_pixels``.

    Prefers fewer tiles, then square-ish tiles.  Always solvable: (h, w)
    gives 1-pixel tiles.
    """
    h, w = shape

    def divisors(x):
        return [d for d in range(1, x + 1) if x % d == 0]

    best = None
    for gr in divisors(h):
        tr = h // gr
        for gc in divisors(w):
            tc = w // gc
            if tr * tc > max_tile_pixels:
                continue
            key = (gr * gc, abs(tr - tc), gr, gc)
            if best is None or key < best[0]:
                best = (key, (gr, gc))
            break   # larger gc only shrinks tiles further for this gr
    if best is None:   # max_tile_pixels < 1; degenerate, one pixel per tile
        return (h, w)
    return best[1]


def _ring_coords(tr: int, tc: int) -> tuple[np.ndarray, np.ndarray]:
    """Owned coordinates of the tile's 1-px boundary ring (static)."""
    rr, cc = np.mgrid[0:tr, 0:tc]
    mask = (rr == 0) | (rr == tr - 1) | (cc == 0) | (cc == tc - 1)
    return rr[mask], cc[mask]


def _interior_mask(ph: int, pw: int) -> np.ndarray:
    m = np.zeros((ph, pw), bool)
    m[1:-1, 1:-1] = True
    return m


# ---------------------------------------------------------------------------
# Tile extraction
# ---------------------------------------------------------------------------

def split_tiles(arr2d: jnp.ndarray, grid: tuple[int, int], fill
                ) -> jnp.ndarray:
    """(H, W) -> (T, tr+2, tc+2) halo-padded tiles, row-major tile order."""
    h, w = arr2d.shape
    gr, gc = grid
    tr, tc = h // gr, w // gc
    padded = jnp.pad(arr2d, 1, constant_values=fill)
    oi, oj = jnp.meshgrid(jnp.arange(gr) * tr, jnp.arange(gc) * tc,
                          indexing="ij")
    origins = jnp.stack([oi.reshape(-1), oj.reshape(-1)], axis=1)
    return jax.vmap(lambda o: jax.lax.dynamic_slice(
        padded, (o[0], o[1]), (tr + 2, tc + 2)))(origins)


def halo_gidx_tile(shape: tuple[int, int], grid: tuple[int, int],
                   t: int) -> np.ndarray:
    """Global flat-index map of tile ``t``'s halo-padded window, computed
    arithmetically (O(tile), never touching an (H, W) array); out-of-frame
    halo pixels are -1, matching ``split_tiles(gidx2d, grid, -1)``."""
    h, w = shape
    gr, gc = grid
    tr, tc = h // gr, w // gc
    r0, c0 = (t // gc) * tr, (t % gc) * tc
    rows = np.arange(r0 - 1, r0 + tr + 1, dtype=np.int64)[:, None]
    cols = np.arange(c0 - 1, c0 + tc + 1, dtype=np.int64)[None, :]
    gidx = rows * w + cols
    inside = (rows >= 0) & (rows < h) & (cols >= 0) & (cols < w)
    return np.where(inside, gidx, -1).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class StagedTiles:
    """Device-resident halo-padded tile stacks of one image.

    Built by :func:`load_tile_stacks` (tile-provider path, O(tile) host
    residency) and accepted by :func:`tiled_pixhomology_stacks` /
    :meth:`repro.ph.PHEngine.run_tiled` in place of a host-resident image.
    """

    pvals: Any                    # (T, tr+2, tc+2) image dtype
    pgidx: Any                    # (T, tr+2, tc+2) int32 global indices
    shape: tuple[int, int]        # full-image (H, W)
    grid: tuple[int, int]         # (gr, gc)


def load_tile_stacks(provider, grid: tuple[int, int], *,
                     ctx=None, fill=None) -> StagedTiles:
    """Stage a tile provider's halo-padded tiles on device, one at a time.

    ``provider``: ``shape`` / ``dtype`` / ``halo_tile(t, grid, fill=...)``
    (e.g. :class:`repro.data.astro.AstroImage`).  Each tile is converted to
    a device array as soon as it is generated, so peak host residency is a
    single halo-padded tile regardless of the image size.  With ``ctx`` the
    stacks are placed on the mesh's data axes (the same tile placement the
    sharded per-tile phases use).  ``fill`` overrides the halo fill value
    (user-space inert extreme: ``+inf`` when the stacks will be consumed
    under the sublevel filtration; defaults to the superlevel ``-inf``).
    """
    h, w = provider.shape
    grid = tuple(grid)
    validate_grid((h, w), grid)
    n_tiles = grid[0] * grid[1]
    if fill is None:
        fill = _neg_inf(jnp.dtype(provider.dtype)).item()
    pv = [jnp.asarray(provider.halo_tile(t, grid, fill=fill))
          for t in range(n_tiles)]
    pg = [jnp.asarray(halo_gidx_tile((h, w), grid, t))
          for t in range(n_tiles)]
    pvals, pgidx = jnp.stack(pv), jnp.stack(pg)
    if ctx is not None:
        from repro.distributed.sharding import (constrain,
                                                tile_partition_spec)
        tile_p = tile_partition_spec(n_tiles, ctx.mesh, ctx.dp_axes)
        if tuple(tile_p) != ():
            pvals = constrain(pvals, ctx, (tile_p[0], None, None))
            pgidx = constrain(pgidx, ctx, (tile_p[0], None, None))
    return StagedTiles(pvals, pgidx, (h, w), grid)


# ---------------------------------------------------------------------------
# Phase A (per tile): pointers + in-tile label resolution, frozen at halo
# ---------------------------------------------------------------------------

def tile_phase_a(pvals: jnp.ndarray, pgidx: jnp.ndarray):
    """Steps 1-2 on one halo-padded tile — the per-tile instantiation of
    the core stage graph (``pixhomology.phase_a``/``phase_b`` with tiles
    as the locality unit instead of row strips).

    Pointers come from the shared :func:`~repro.core.pixhomology.\
keyed_steepest_pointers` stage keyed by *global* pixel index (per-tile
    order must be isomorphic to the global total order), and the
    halo-frozen resolution is the shared
    :func:`~repro.core.pixhomology.resolve_labels` doubling — exactly the
    in-strip snap the fused phase-A kernel performs, with the tile halo
    playing the strip boundary's role.

    Returns ``(ptr_owned, ring_gidx, ring_ptr, min_val, min_gidx)``:
    per owned pixel the global index of its in-tile basin root *or* of the
    halo pixel its ascent chain exits through; the boundary-ring slice of
    the same map (the tile's contribution to the condensation table); and
    the tile's (value, index)-minimum for the global essential death.
    """
    ph, pw = pvals.shape
    tr, tc = ph - 2, pw - 2
    interior = jnp.asarray(_interior_mask(ph, pw))
    flat = jnp.arange(ph * pw, dtype=jnp.int32).reshape(ph, pw)

    ptr_l = keyed_steepest_pointers(pvals, pgidx)
    m0 = jnp.where(interior, ptr_l, flat).reshape(-1)   # halo frozen to self
    m = resolve_labels(m0)
    resolved_g = pgidx.reshape(-1)[m].reshape(ph, pw)
    ptr_owned = resolved_g[1:-1, 1:-1]

    own_vals = pvals[1:-1, 1:-1]
    own_gidx = pgidx[1:-1, 1:-1]
    rr, cc = _ring_coords(tr, tc)
    ring_gidx = own_gidx[rr, cc]
    ring_ptr = ptr_owned[rr, cc]

    min_val = jnp.min(own_vals)
    min_gidx = jnp.min(jnp.where(own_vals == min_val, own_gidx,
                                 jnp.int32(_I32_MAX)))
    return ptr_owned, ring_gidx, ring_ptr, min_val, min_gidx


# ---------------------------------------------------------------------------
# Boundary condensation: sorted ring table + pointer doubling across tiles
# ---------------------------------------------------------------------------

def _table_follow(sg: jnp.ndarray, sv: jnp.ndarray, q: jnp.ndarray
                  ) -> jnp.ndarray:
    """values[q] where q is in the sorted-key table ``sg``, else q itself."""
    pos = jnp.clip(jnp.searchsorted(sg, q), 0, sg.shape[0] - 1)
    return jnp.where(sg[pos] == q, sv[pos], q)


def resolve_ring_table(ring_gidx: jnp.ndarray, ring_ptr: jnp.ndarray):
    """Condensed cross-tile label resolution.

    ``ring_gidx``/``ring_ptr``: (T, R) per-tile boundary rings.  A basin
    chain can only leave a tile through a halo pixel, which is a ring pixel
    of the neighboring tile — so pointer doubling on this table alone
    resolves every cross-tile chain to its basin root, in O(log) rounds of
    O(boundary) work (the tiled twin of the whole-image compacted
    frontier, ``pixhomology.resolve_labels_frontier``).  Returns
    ``(sg, sl)``: sorted ring pixel ids and their final global basin
    labels.
    """
    rg = ring_gidx.reshape(-1)
    rp = ring_ptr.reshape(-1)
    order = jnp.argsort(rg)
    sg = rg[order]
    sp = rp[order]
    sl, _ = fixed_point_iterate(lambda p: _table_follow(sg, p, p), sp)
    return sg, sl


# ---------------------------------------------------------------------------
# Phase B (per tile): pre-labels, exact candidates, seam/interior edges
# ---------------------------------------------------------------------------

def tile_phase_b(pvals, pgidx, ptr_owned, tv, *,
                 tile_max_candidates: int, tile_max_features: int,
                 truncated: bool, merge_keys: str = "rank"):
    """Steps 3-4 on one tile, **label-independent** (tile-local only).

    Returns per-tile compact pieces of the global merge instance:
    clique-chained saddle edges (endpoints are *pre-labels* — an in-tile
    basin root or the halo pixel the chain exits through, resolved to
    final global labels later by :func:`merge_tile_state`), the
    top-``tile_max_features`` basin roots, the tile's unfiltered maximum
    root (for the essential class), and candidate/root counts for
    overflow detection.

    Pre-labels keep the diagram bit-identical: equal pre-labels imply
    equal final labels, so every whole-image candidate/edge survives;
    distinct pre-labels that resolve to the *same* final label add only
    edges that become self-loops in the seam merge, which
    :func:`repro.core.parallel_merge.boruvka_forest` skips (``ra != rb``)
    — and duplicate real edges share the saddle pixel, hence the exact
    merge key, so the elder-rule outcome is unchanged.  In exchange the
    stage depends on nothing but this tile's halo-padded bytes, which is
    what makes its output cacheable for delta recompute.

    ``merge_keys="packed"`` keys every comparison on the packed
    ``(value, global index)`` int64 bit-key — per-tile packed keys are
    *globally* order-isomorphic by construction, so the two per-tile
    argsorts (the rank lexsort) disappear along with the full-tile
    ``top_k`` sorts (blockwise tournament selection).  ``"rank"`` keeps
    the lexsort-materialized per-tile dense ranks.
    """
    ph, pw = pvals.shape
    tr, tc = ph - 2, pw - 2
    n_loc = ph * pw
    interior = jnp.asarray(_interior_mask(ph, pw))
    fill_v = _neg_inf(pvals.dtype)

    own_vals = pvals[1:-1, 1:-1]
    own_gidx = pgidx[1:-1, 1:-1]

    # Pre-labels: owned pixels carry their in-tile resolution (basin root
    # or exit halo pixel); halo pixels stand for themselves (they are ring
    # pixels of a neighbor, resolved at seam time); out-of-frame fill -1.
    plbl = jnp.where(interior, jnp.pad(ptr_owned, 1, constant_values=-1),
                     jnp.where(pgidx >= 0, pgidx, -1))

    with jax.named_scope("ph.keys"):
        if merge_keys == "packed":
            # Packed (value, global index) keys are order-isomorphic to
            # the global total order on the padded tile directly — no
            # sort.  Halo fill cells (value -inf/int-min, gidx -1) pack
            # low word 0: below every real pixel (for integer dtype-min
            # fills they reach the pad sentinel itself, which is fine —
            # halo cells are excluded by the interior mask, never by key
            # comparison).
            key = pack_keys(pvals.reshape(-1), pgidx.reshape(-1))
        else:
            # Per-tile rank, order-isomorphic to the global (value, index)
            # order (halo fill keys (-inf, -1) sort strictly below every
            # real pixel).
            order = jnp.lexsort((pgidx.reshape(-1), pvals.reshape(-1)))
            key = jnp.zeros(n_loc, jnp.int32).at[order].set(
                jnp.arange(n_loc, dtype=jnp.int32))
    pad = key_pad(key.dtype)

    cand2d = exact_candidates(key.reshape(ph, pw), plbl) & interior
    with jax.named_scope("ph.select"):
        if truncated:
            cand2d &= pvals >= tv
        cand_flat = cand2d.reshape(-1)
        n_cand = jnp.sum(cand_flat, dtype=jnp.int32)
        k = min(tile_max_candidates, tr * tc)
        top_keys, top_loc = masked_top_k(key, cand_flat, k)

    with jax.named_scope("ph.merge"):
        valid = top_keys > pad
        ok, lbl = higher_neighbor_basins(top_loc, top_keys, key,
                                         plbl.reshape(-1), (ph, pw), valid)
        edge_ok, prev_lbl = chain_clique_edges(ok, lbl)          # (k, 8)
        e_val = jnp.broadcast_to(pvals.reshape(-1)[top_loc][:, None],
                                 ok.shape)
        e_pos = jnp.broadcast_to(pgidx.reshape(-1)[top_loc][:, None],
                                 ok.shape)
        e_a = jnp.where(edge_ok, lbl, 0)
        e_b = jnp.where(edge_ok, prev_lbl, 0)

    # Basin roots owned by this tile.  Root-ness is tile-local: ascent
    # chains are strictly increasing in (value, index), so a pixel whose
    # chain leaves the tile can never resolve back to itself —
    # ``ptr_owned == own_gidx`` iff the final global label is the pixel.
    root_mask = ptr_owned == own_gidx
    # Unfiltered per-tile maximum root: the global maximum pixel is always a
    # root, so the reduce over tiles finds the essential class even when a
    # Variant-2 threshold filters the listed roots.
    rmax_val = jnp.max(jnp.where(root_mask, own_vals, fill_v))
    rmax_gidx = jnp.max(jnp.where(root_mask & (own_vals == rmax_val),
                                  own_gidx, jnp.int32(-1)))
    if truncated:
        root_mask &= own_vals >= tv
    n_roots = jnp.sum(root_mask, dtype=jnp.int32)

    f = min(tile_max_features, tr * tc)
    with jax.named_scope("ph.diagram"):
        own_key = key.reshape(ph, pw)[1:-1, 1:-1].reshape(-1)
        top_rk, top_ri = masked_top_k(own_key, root_mask.reshape(-1), f)
        rvalid = top_rk > pad
        root_gidx = jnp.where(rvalid, own_gidx.reshape(-1)[top_ri], -1)
        root_val = jnp.where(rvalid, own_vals.reshape(-1)[top_ri], fill_v)

    return (e_val, e_pos, e_a, e_b, edge_ok,
            root_val, root_gidx.astype(jnp.int32), rvalid,
            rmax_val, rmax_gidx, n_roots, n_cand)


def tile_phase_ab(pvals, pgidx, tv, *,
                  tile_max_candidates: int, tile_max_features: int,
                  truncated: bool, merge_keys: str = "rank"
                  ) -> TileBoundaryState:
    """Phases A+B on one halo-padded tile -> its :class:`TileBoundaryState`.

    This is the complete tile-local computation — a pure function of one
    tile's halo-padded bytes (plus the static capacities/threshold), which
    is exactly the unit the delta layer caches and replays.  The cold
    tiled path vmaps it over all ``T`` tiles; a delta run vmaps the same
    function over only the dirty subset.
    """
    (ptr_owned, ring_gidx, ring_ptr, min_val, min_gidx) = tile_phase_a(
        pvals, pgidx)
    (e_val, e_pos, e_a, e_b, e_ok, root_val, root_gidx, root_valid,
     rmax_val, rmax_gidx, n_roots, n_cand) = tile_phase_b(
        pvals, pgidx, ptr_owned, tv,
        tile_max_candidates=tile_max_candidates,
        tile_max_features=tile_max_features,
        truncated=truncated, merge_keys=merge_keys)
    return TileBoundaryState(ring_gidx, ring_ptr, min_val, min_gidx,
                             e_val, e_pos, e_a, e_b, e_ok,
                             root_val, root_gidx, root_valid,
                             rmax_val, rmax_gidx, n_roots, n_cand)


# ---------------------------------------------------------------------------
# Global seam merge on the compact (basin, saddle-edge) instance
# ---------------------------------------------------------------------------

def _slot_lookup(sorted_key, slot_of, q):
    """(slot, found) of global root ids in the compact root table."""
    pos = jnp.clip(jnp.searchsorted(sorted_key, q), 0,
                   sorted_key.shape[0] - 1)
    found = sorted_key[pos] == q
    return jnp.where(found, slot_of[pos], -1), found


def seam_merge(root_val, root_gidx, root_valid,
               e_val, e_pos, e_a, e_b, e_valid,
               rmax_val, rmax_gidx, gmin_val, gmin_gidx,
               tv, *, truncated: bool, max_features: int, dtype,
               merge_keys: str = "rank", phase_c_impl: str = "fused",
               phase_c_block: int = 1024):
    """Elder-rule reduction of the concatenated per-tile instances.

    Compact vertex set = listed basin roots; edges reference roots by
    global pixel id and are slotted through a sorted lookup table.  The
    reduction itself is :func:`repro.core.parallel_merge.boruvka_forest`.
    ``merge_keys="packed"`` keys vertices and edges on the packed
    ``(value, global index)`` int64 directly — edges sharing a saddle
    pixel are equal-keyed *by construction*, so the two dense-rank
    argsorts of the ``"rank"`` path (vertex lexsort + edge group ranking)
    disappear.  The seam instance is already compact (listed roots, never
    full-image), so ``phase_c_impl="fused"`` here selects only the round
    reduction backend: the blocked phase-C kernel dispatch
    (``repro.kernels.ph_phase_c.ops.best_edge_reduce`` with
    ``phase_c_block`` edges per step) instead of the plain XLA scatter —
    bit-identical either way.  Returns ``(birth, death, p_birth, p_death,
    count, n_unmerged, merge_overflow)``.
    """
    rv = root_val.reshape(-1)
    rg = root_gidx.reshape(-1)
    ok_r = root_valid.reshape(-1)
    nv = rv.shape[0]
    neg_inf = _neg_inf(dtype)

    # Root id -> compact slot (sorted table; invalid slots key to int-max).
    key_g = jnp.where(ok_r, rg, jnp.int32(_I32_MAX))
    order_g = jnp.argsort(key_g)
    sorted_g = key_g[order_g]

    ev = e_val.reshape(-1)
    ep = e_pos.reshape(-1)
    sa, fa = _slot_lookup(sorted_g, order_g, e_a.reshape(-1))
    sb, fb = _slot_lookup(sorted_g, order_g, e_b.reshape(-1))
    alive = e_valid.reshape(-1) & fa & fb   # missing endpoint => tile overflow

    if merge_keys == "packed":
        # Vertex birth / edge saddle keys: packed (value, global index) —
        # order-isomorphic with no sort, equal exactly when the saddle
        # pixel coincides.
        i64_pad = key_pad(jnp.int64)
        v_rank = jnp.where(ok_r, pack_keys(rv, rg), i64_pad)
        e_rank = jnp.where(alive, pack_keys(ev, ep), i64_pad)
    else:
        # Vertex birth keys: rank of (value, global index) among valid
        # roots.
        vorder = jnp.lexsort((rg, rv, ok_r.astype(jnp.int32)))
        vrank_raw = jnp.zeros(nv, jnp.int32).at[vorder].set(
            jnp.arange(nv, dtype=jnp.int32))
        v_rank = jnp.where(ok_r, vrank_raw, key_pad(jnp.int32))

        # Edge saddle keys: dense rank of (value, global index), EQUAL for
        # edges sharing a saddle pixel (the Boruvka tie rule depends on it).
        ne = ev.shape[0]
        akey = alive.astype(jnp.int32)
        eorder = jnp.lexsort((ep, ev, akey))
        s_ak, s_ev, s_ep = akey[eorder], ev[eorder], ep[eorder]
        new_grp = jnp.concatenate([
            jnp.ones((1,), bool),
            (s_ak[1:] != s_ak[:-1]) | (s_ev[1:] != s_ev[:-1])
            | (s_ep[1:] != s_ep[:-1])])
        grp = (jnp.cumsum(new_grp.astype(jnp.int32)) - 1)
        erank_raw = jnp.zeros(ne, jnp.int32).at[eorder].set(grp)
        e_rank = jnp.where(alive, erank_raw, key_pad(jnp.int32))

    if phase_c_impl == "fused":
        from repro.kernels.ph_phase_c import ops as phase_c_ops
        reduce_fn = functools.partial(phase_c_ops.best_edge_reduce,
                                      block_edges=phase_c_block)
    else:
        reduce_fn = None
    n_live = jnp.sum(ok_r, dtype=jnp.int32)
    dval, dpos, _rounds = boruvka_forest(
        v_rank, e_rank, ev.astype(dtype), ep,
        jnp.clip(sa, 0), jnp.clip(sb, 0),
        n_live=n_live, reduce_fn=reduce_fn)

    if truncated:
        # Survivors that never merged above the threshold die at it
        # (p_death stays -1, matching the whole-image semantics).
        undied = ok_r & (dpos < 0)
        dval = jnp.where(undied, jnp.asarray(tv, dtype), dval)

    # Essential class: the globally maximal root dies at the global minimum.
    gmax_val = jnp.max(rmax_val)
    gmax_gidx = jnp.max(jnp.where(rmax_val == gmax_val, rmax_gidx, -1))
    eslot, efound = _slot_lookup(sorted_g, order_g, gmax_gidx[None])
    es = jnp.clip(eslot[0], 0)
    assign = efound[0]
    dval = dval.at[es].set(jnp.where(assign, jnp.asarray(gmin_val, dtype),
                                     dval[es]))
    dpos = dpos.at[es].set(jnp.where(assign, gmin_gidx, dpos[es]))

    # Diagram rows, descending (birth value, birth index); ``v_rank`` is
    # already pad-keyed on invalid slots, and the vertex set is compact
    # (listed roots, never full-image), so one top_k serves both key paths.
    c = jnp.sum(ok_r, dtype=jnp.int32)
    f = max_features
    kk = min(f, nv)
    _, top_slot = jax.lax.top_k(v_rank, kk)
    row_valid = jnp.arange(kk) < c

    birth = jnp.full(f, neg_inf, dtype).at[:kk].set(
        jnp.where(row_valid, rv[top_slot].astype(dtype), neg_inf))
    death = jnp.full(f, neg_inf, dtype).at[:kk].set(
        jnp.where(row_valid, dval[top_slot], neg_inf))
    p_birth = jnp.full(f, -1, jnp.int32).at[:kk].set(
        jnp.where(row_valid, rg[top_slot], -1))
    p_death = jnp.full(f, -1, jnp.int32).at[:kk].set(
        jnp.where(row_valid, dpos[top_slot], -1))

    n_unmerged = jnp.sum(ok_r & (dpos < 0), dtype=jnp.int32)
    merge_overflow = c > f
    return (birth, death, p_birth, p_death, jnp.minimum(c, f), n_unmerged,
            merge_overflow)


@jax.named_scope("ph.seam")
def merge_tile_state(state: TileBoundaryState, tv, *,
                     shape: tuple[int, int], grid: tuple[int, int],
                     max_features: int, tile_max_features: int,
                     tile_max_candidates: int, truncated: bool,
                     merge_keys: str = "rank", phase_c_impl: str = "fused",
                     phase_c_block: int = 1024) -> TiledDiagram:
    """O(boundary) global replay: ring condensation + pre-label resolution
    + elder-rule seam merge over stacked :class:`TileBoundaryState`.

    This is the only stage that mixes tiles, and it never touches pixels —
    its cost scales with rings/roots/edges.  A delta run re-executes *this*
    against a state whose clean rows come from cache: pointer doubling on
    the full ring table re-resolves every cross-tile chain (a dirty tile
    re-routes chains through clean tiles correctly, because clean rows
    store pre-labels, not stale final labels), then ``e_a``/``e_b`` are
    mapped through the table.  A pre-label absent from the table is an
    in-tile *root* (interior roots never appear on a ring), and a root's
    final label is itself — exactly ``_table_follow``'s miss semantics.
    """
    h, w = shape
    gr, gc = grid
    tr, tc = h // gr, w // gc

    sg, sl = resolve_ring_table(state.ring_gidx, state.ring_ptr)

    gmin_val = jnp.min(state.min_val)
    gmin_gidx = jnp.min(jnp.where(state.min_val == gmin_val,
                                  state.min_gidx, jnp.int32(_I32_MAX)))

    e_a = _table_follow(sg, sl, state.e_a)
    e_b = _table_follow(sg, sl, state.e_b)

    f_global = min(max_features, h * w)
    (birth, death, p_birth, p_death, count, n_unmerged,
     merge_overflow) = seam_merge(
        state.root_val, state.root_gidx, state.root_valid,
        state.e_val, state.e_pos, e_a, e_b, state.e_ok,
        state.rmax_val, state.rmax_gidx, gmin_val, gmin_gidx, tv,
        truncated=truncated, max_features=f_global,
        dtype=state.root_val.dtype, merge_keys=merge_keys,
        phase_c_impl=phase_c_impl, phase_c_block=phase_c_block)

    tile_overflow = (
        jnp.any(state.n_cand > min(tile_max_candidates, tr * tc))
        | jnp.any(state.n_roots > min(tile_max_features, tr * tc)))
    diagram = Diagram(birth, death, p_birth, p_death, count, n_unmerged,
                      tile_overflow | merge_overflow,
                      jnp.sum(state.n_cand, dtype=jnp.int32))
    return TiledDiagram(diagram, tile_overflow, merge_overflow,
                        state.n_roots, state.n_cand)


# ---------------------------------------------------------------------------
# Full tiled algorithm
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("grid", "max_features", "tile_max_features",
                     "tile_max_candidates", "shard_ctx", "merge_keys",
                     "phase_c_impl", "phase_c_block", "filtration"))
def _tiled_pixhomology(image: jnp.ndarray, truncate_value=None, *,
                       grid: tuple[int, int],
                       max_features: int = 8192,
                       tile_max_features: int = 2048,
                       tile_max_candidates: int = 8192,
                       shard_ctx=None,
                       merge_keys: str = "rank",
                       phase_c_impl: str = "fused",
                       phase_c_block: int = 1024,
                       filtration: str = "superlevel") -> TiledDiagram:
    """Jitted host-resident-image core of :func:`tiled_pixhomology`."""
    if image.ndim != 2:
        raise ValueError(f"expected 2D image, got shape {image.shape}")
    h, w = image.shape
    validate_grid((h, w), grid)
    gidx2d = jnp.arange(h * w, dtype=jnp.int32).reshape(h, w)
    # Halo fill stays in user space here (the stacks core owns the
    # filtration negation): inert means below everything under superlevel,
    # above everything under sublevel.
    fill = _neg_inf(image.dtype)
    if filtration == "sublevel":
        fill = jnp.negative(fill)
    pvals = split_tiles(image, grid, fill)
    pgidx = split_tiles(gidx2d, grid, jnp.int32(-1))
    return _tiled_pixhomology_stacks(
        pvals, pgidx, truncate_value, shape=(h, w), grid=grid,
        max_features=max_features, tile_max_features=tile_max_features,
        tile_max_candidates=tile_max_candidates, shard_ctx=shard_ctx,
        merge_keys=merge_keys, phase_c_impl=phase_c_impl,
        phase_c_block=phase_c_block, filtration=filtration)


def tiled_pixhomology(image: jnp.ndarray, truncate_value=None, *,
                      merge_keys: str = "packed", **kwargs) -> TiledDiagram:
    """0-dim PH of one 2D image via halo-tiled decomposition (bit-identical
    to ``pixhomology(image, truncate_value, candidate_mode="exact")``).

    ``grid``: (gr, gc) tile grid; must divide the image shape
    (:func:`choose_grid` picks one from a tile-pixel budget).
    ``shard_ctx``: optional :class:`repro.distributed.DistContext` — the
    per-tile phases run under ``shard_map`` with tile rows placed on the
    mesh's data axes (tile count must divide by the dp size); the compact
    condensation/seam stages stay replicated (they are O(boundary), not
    O(pixels)).
    ``merge_keys``: packed int64 ``(value, global index)`` keys (default;
    no per-tile or seam argsorts) or the dense-rank fallback — resolved
    exactly like :func:`repro.core.pixhomology.pixhomology`.

    This is the host-resident-image convenience wrapper; the compute core
    is :func:`tiled_pixhomology_stacks`, fed either by the in-jit
    ``split_tiles`` below or by :func:`load_tile_stacks` (tile-provider
    path with O(tile) host residency).
    """
    packed_keys.check_finite(image, allow_inf=True)
    merge_keys = packed_keys.resolve_merge_keys(merge_keys, image.dtype)
    with packed_keys.key_scope(merge_keys):
        return _tiled_pixhomology(image, truncate_value,
                                  merge_keys=merge_keys, **kwargs)


@functools.partial(
    jax.jit,
    static_argnames=("shape", "grid", "max_features", "tile_max_features",
                     "tile_max_candidates", "shard_ctx", "merge_keys",
                     "phase_c_impl", "phase_c_block", "filtration"))
def _tiled_pixhomology_stacks(pvals: jnp.ndarray, pgidx: jnp.ndarray,
                              truncate_value=None, *,
                              shape: tuple[int, int],
                              grid: tuple[int, int],
                              max_features: int = 8192,
                              tile_max_features: int = 2048,
                              tile_max_candidates: int = 8192,
                              shard_ctx=None,
                              merge_keys: str = "rank",
                              phase_c_impl: str = "fused",
                              phase_c_block: int = 1024,
                              filtration: str = "superlevel"
                              ) -> TiledDiagram:
    """Jitted tile-stack core of :func:`tiled_pixhomology_stacks`."""
    h, w = shape
    validate_grid((h, w), grid)
    gr, gc = grid
    tr, tc = h // gr, w // gc
    n_tiles = gr * gc
    if pvals.shape != (n_tiles, tr + 2, tc + 2):
        raise ValueError(f"tile stack shape {pvals.shape} does not match "
                         f"image {shape} under grid {grid}")
    packed_keys.assert_key_context(merge_keys)
    # Sublevel runs on the exact negation: the stacks (user space, +inf
    # halo fill) and threshold negate here, every internal stage — tile
    # phases, ring condensation, seam merge — stays in superlevel order,
    # and only the output diagram negates back at the bottom.
    pvals = packed_keys.filtration_view(pvals, filtration)
    if truncate_value is not None and filtration == "sublevel":
        truncate_value = jnp.negative(truncate_value)
    truncated = truncate_value is not None
    tv = (jnp.asarray(truncate_value) if truncated
          else _neg_inf(jnp.float32))

    phase_ab = jax.vmap(
        functools.partial(tile_phase_ab,
                          tile_max_candidates=tile_max_candidates,
                          tile_max_features=tile_max_features,
                          truncated=truncated, merge_keys=merge_keys),
        in_axes=(0, 0, None))

    if shard_ctx is not None:
        from jax.sharding import PartitionSpec as P

        from repro.distributed.context import shard_map_compat
        from repro.distributed.sharding import constrain, tile_partition_spec

        tile_p = tile_partition_spec(n_tiles, shard_ctx.mesh,
                                     shard_ctx.dp_axes)
        if tile_p != P():   # dp size divides the tile count: shard phases
            # Pin the tile stacks (the O(n) intermediates) to the tile
            # placement right after the split, so only the (H, W) input and
            # its padded copy are ever full-size per device; everything
            # downstream of here is tile-resident.
            pvals = constrain(pvals, shard_ctx, (tile_p[0], None, None))
            pgidx = constrain(pgidx, shard_ctx, (tile_p[0], None, None))
            def sp(extra):
                return P(*((tile_p[0],) + (None,) * extra))

            phase_ab = shard_map_compat(
                phase_ab, mesh=shard_ctx.mesh,
                in_specs=(sp(2), sp(2), P()),
                out_specs=TileBoundaryState(
                    sp(1), sp(1), sp(0), sp(0),
                    sp(2), sp(2), sp(2), sp(2), sp(2),
                    sp(1), sp(1), sp(1), sp(0), sp(0), sp(0), sp(0)))

    merge = functools.partial(
        merge_tile_state, shape=(h, w), grid=grid,
        max_features=max_features, tile_max_features=tile_max_features,
        tile_max_candidates=tile_max_candidates, truncated=truncated,
        merge_keys=merge_keys, phase_c_impl=phase_c_impl,
        phase_c_block=phase_c_block)
    if shard_ctx is not None and shard_ctx.mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        from repro.distributed.context import shard_map_compat

        # The O(boundary) seam merge runs whole on every device.  Left to
        # XLA's partitioner it would fail on TPU: a Mosaic kernel (the
        # phase-C reduction) cannot be partitioned automatically.
        merge = shard_map_compat(merge, mesh=shard_ctx.mesh,
                                 in_specs=(P(), P()), out_specs=P())
    td = merge(phase_ab(pvals, pgidx, tv), tv)
    if filtration == "sublevel":
        d = td.diagram
        td = td._replace(diagram=d._replace(birth=jnp.negative(d.birth),
                                            death=jnp.negative(d.death)))
    return td


def tiled_pixhomology_stacks(pvals: jnp.ndarray, pgidx: jnp.ndarray,
                             truncate_value=None, *,
                             merge_keys: str = "packed",
                             **kwargs) -> TiledDiagram:
    """Halo-tiled PH on pre-staged tile stacks (the streaming entry point).

    ``pvals``/``pgidx``: (T, tr+2, tc+2) halo-padded value / global-index
    stacks in row-major tile order — exactly what ``split_tiles`` produces
    from a whole image, or :func:`load_tile_stacks` from a tile provider
    without any host ever materializing the image.  Semantics otherwise
    identical to :func:`tiled_pixhomology` (including ``merge_keys``
    resolution and its x64 scope).
    """
    packed_keys.check_finite(pvals, where="tile stacks", allow_inf=True)
    merge_keys = packed_keys.resolve_merge_keys(merge_keys, pvals.dtype)
    with packed_keys.key_scope(merge_keys):
        return _tiled_pixhomology_stacks(pvals, pgidx, truncate_value,
                                         merge_keys=merge_keys, **kwargs)


# ---------------------------------------------------------------------------
# Per-tile cost model (dryrun / capacity planning)
# ---------------------------------------------------------------------------

def per_tile_cost(tile_shape: tuple[int, int], dtype, n_tiles: int,
                  tile_max_features: int = 2048,
                  tile_max_candidates: int = 8192,
                  merge_keys: str = "packed") -> dict:
    """Compile the per-tile phase programs and report their memory footprint.

    This is the dryrun cost model for the tiled plan: everything here scales
    with the *tile* shape (plus the O(boundary) condensation table), never
    with the full image area — the property that lets one image exceed a
    device.
    """
    tr, tc = tile_shape
    merge_keys = packed_keys.resolve_merge_keys(merge_keys, dtype)
    pv = jax.ShapeDtypeStruct((tr + 2, tc + 2), dtype)
    pg = jax.ShapeDtypeStruct((tr + 2, tc + 2), jnp.int32)
    ring = len(_ring_coords(tr, tc)[0])
    table = jax.ShapeDtypeStruct((n_tiles * ring,), jnp.int32)
    ptr = jax.ShapeDtypeStruct((tr, tc), jnp.int32)
    tv = jax.ShapeDtypeStruct((), jnp.float32)

    out: dict = {"tile_shape": [tr, tc], "ring_pixels": ring,
                 "table_entries": n_tiles * ring, "merge_keys": merge_keys}
    del table   # phase B is label-independent now: no condensation input
    for name, fn, args in (
            ("phase_a", jax.jit(tile_phase_a), (pv, pg)),
            ("phase_b",
             jax.jit(functools.partial(
                 tile_phase_b, tile_max_candidates=tile_max_candidates,
                 tile_max_features=tile_max_features, truncated=True,
                 merge_keys=merge_keys)),
             (pv, pg, ptr, tv))):
        with packed_keys.key_scope(merge_keys):
            compiled = fn.lower(*args).compile()
        ma = compiled.memory_analysis()
        out[name] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "peak_bytes_est": int(ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes),
        }
    return out
