"""PixHomology: 0-dimensional persistent homology of 2D images (paper §5.1).

Superlevel-set filtration: components are born at local maxima and die when
they merge into a component with an older (larger) birth (elder rule).  The
essential class of the global maximum dies at the global minimum (paper's
"ultimate death point").

The implementation is an explicit **three-stage graph** (see
``src/repro/ph/DESIGN.md`` §2 for the TPU adaptation rationale); the
whole-image, batched, sharded, and tiled paths all compose the same stages:

* **Phase A — pointers + candidate flags** (:func:`phase_a`).  Each pixel
  gets its steepest-ascent pointer under the strict total order
  ``(value, flat_index)`` plus the strictly-higher 8-neighbor bitmask.
  ``phase_a_impl="fused"`` (default) runs the
  :mod:`repro.kernels.ph_phase_a` kernel — one VMEM pass per
  ``strip_rows``-row strip that also pointer-chases every pixel to its
  furthest in-strip ancestor — on TPU when ``use_pallas`` resolves true,
  and the bit-identical pure-XLA reference elsewhere.
  ``phase_a_impl="pooled"`` is the unfused baseline: three pooled passes
  (``arg-maxpool2d`` via :mod:`repro.kernels.maxpool`) and raw pointers.

* **Phase B — label resolution** (:func:`phase_b`).  The paper iterates
  ``M[x] <- M[M[x]]`` to a fixed point; we pointer-double instead —
  O(log depth) iterations, not the paper's worst case O(n).  On fused
  phase-A output the doubling runs on a **compacted frontier** of
  strip-boundary rows and basin roots (:func:`resolve_labels_frontier`):
  snapped pointers only ever land on roots or the statically-known
  boundary rows, so each doubling round gathers O(n / strip_rows)
  entries instead of all n, plus one final dense gather — phase-B gather
  volume drops from O(n·log depth) to O(frontier·log depth + n)
  (DESIGN.md §Perf PH-3).  Pooled phase A resolves densely
  (:func:`resolve_labels`).

* **Phase C — merge + diagram** (:func:`phase_c`).  Death-point
  candidates (steps 3-4, below) are reduced by the sequential elder-rule
  sweep or the parallel Boruvka forest, the essential class is closed at
  the global minimum, and the fixed-capacity diagram is emitted.  Every
  comparison keys on an order-isomorphic encoding of the strict
  ``(value, flat_index)`` total order, selected by ``merge_keys``:
  ``"packed"`` (default) bit-casts each value to a monotone int64
  ``(key32 << 32) | index`` (:mod:`repro.core.packed_keys`) — **no
  full-image argsort anywhere**, every top-k a capacity-bounded
  blockwise tournament; ``"rank"`` is the argsort-materialized dense
  rank fallback
  (> 32-bit dtypes, or callers without an x64 scope).  Both paths are
  bit-identical (tests/test_merge_keys.py).

Candidate generators (steps 3-4): ``candidate_mode="exact"`` keeps pixels
whose *higher* 8-neighbors span >= 2 distinct basins — provably a superset
of all merge points and a subset of the paper's edge set; on the fused
path the rank comparisons come pre-packed in phase A's bitmask
(:func:`exact_candidates_masked`).  ``candidate_mode="paper"`` is the
paper's literal edge ∧ (local-min ∨ axis-saddle) distillation (kept for
fidelity; the axis saddle test can miss merge points on adversarial
images — documented in DESIGN.md §6).

All shapes are static (jit/vmap/shard_map friendly): diagrams are padded to
``max_features`` rows and candidate selection to ``max_candidates`` entries
(the merge sweep runs only the live ones), with explicit overflow flags so
a driver can detect undersized capacities and re-dispatch (fault-tolerance
hook used by the pipeline).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# NEIGHBOR_OFFSETS is re-exported here for back-compat; it lives in
# repro.core.grid together with the shared neighbor-gather helpers.
from repro.core.grid import (  # noqa: F401
    NEIGHBOR_OFFSETS,
    fixed_point_iterate,
    higher_neighbor_basins,
    neg_inf,
    shift2d,
)
from repro.core import packed_keys
from repro.core.packed_keys import key_pad, key_top, masked_top_k
from repro.kernels.maxpool import ops as pool_ops
from repro.kernels.ph_phase_a import ops as phase_a_ops


class Diagram(NamedTuple):
    """Fixed-capacity persistence diagram (padded, shardable)."""

    birth: jnp.ndarray     # (F,) image dtype, descending; padding = -inf
    death: jnp.ndarray     # (F,) image dtype; -inf for padding/unmerged
    p_birth: jnp.ndarray   # (F,) int32 flat pixel index of the maximum; -1 pad
    p_death: jnp.ndarray   # (F,) int32 flat pixel index of the merge saddle
    count: jnp.ndarray     # () int32 number of valid rows (components found)
    n_unmerged: jnp.ndarray  # () int32 roots that never died (0 when exact)
    overflow: jnp.ndarray  # () bool: capacity exceeded -> retry with bigger F/K
    # () int32 candidates the merge swept, after the Variant-2 mask (may
    # exceed max_candidates: overflow); None on hand-built diagrams.
    n_candidates: jnp.ndarray | None = None


class PhaseA(NamedTuple):
    """Phase-A artifacts (flat): pointers plus candidate pre-flags.

    ``pointers`` are strip-snapped (fused) or raw steepest-ascent (pooled);
    ``hi_mask`` is the strictly-higher 8-neighbor bitmask on the fused
    path and ``None`` on the pooled one (the dense candidate test derives
    the comparisons from ranks instead).
    """

    pointers: jnp.ndarray
    hi_mask: jnp.ndarray | None


# ---------------------------------------------------------------------------
# Total order helpers
# ---------------------------------------------------------------------------

def total_order_rank(values_flat: jnp.ndarray) -> jnp.ndarray:
    """rank[i] = position of pixel i in the ascending (value, index) order."""
    n = values_flat.shape[0]
    perm = jnp.argsort(values_flat, stable=True)  # ties -> ascending index
    return jnp.zeros(n, jnp.int32).at[perm].set(jnp.arange(n, dtype=jnp.int32))


def total_order_keys(values_flat: jnp.ndarray,
                     merge_keys: str) -> jnp.ndarray:
    """Phase-C merge keys: an order-isomorphic encoding of (value, index).

    ``"packed"``: :func:`repro.core.packed_keys.pack_keys` int64 bit-keys,
    O(n) with no sort; ``"rank"``: the dense int32 argsort ranks.  Both
    encodings compare identically under ``>``; phase C never uses any
    other operation on them.
    """
    with jax.named_scope("ph.keys"):
        if merge_keys == "packed":
            return packed_keys.pack_keys(values_flat)
        if merge_keys == "rank":
            return total_order_rank(values_flat)
    raise ValueError(f"unknown merge_keys {merge_keys!r}")


# ---------------------------------------------------------------------------
# Phase A: steepest-ascent pointers (+ in-strip snap / candidate flags)
# ---------------------------------------------------------------------------

def steepest_neighbors(image: jnp.ndarray, *, use_pallas: bool | None = None,
                       interpret: bool = False) -> jnp.ndarray:
    """arg-maxpool2d(I): flat index of each pixel's 3x3 max (paper line 1)."""
    with jax.named_scope("ph.phase_a"):
        _, arg = pool_ops.maxargmaxpool3x3(image, use_pallas=use_pallas,
                                           interpret=interpret)
        return arg.reshape(-1)


def keyed_steepest_pointers(values2d: jnp.ndarray,
                            keys2d: jnp.ndarray) -> jnp.ndarray:
    """Steepest-ascent pointer (local flat id) under the (value, key) total
    order; self included.  Fill cells (key -1, value -inf) never win.

    This is the shared stage the tiled path instantiates with *global*
    pixel indices as keys on a halo-padded tile (per-tile order must be
    isomorphic to the global one), and the generic fallback for any
    stencil whose tie-break key is not the local flat index.
    """
    h, w = values2d.shape
    flat = jnp.arange(h * w, dtype=jnp.int32).reshape(h, w)
    fill_v = neg_inf(values2d.dtype)
    best_v, best_k, best_l = values2d, keys2d, flat
    with jax.named_scope("ph.phase_a"):
        for dr, dc in NEIGHBOR_OFFSETS:
            v = shift2d(values2d, dr, dc, fill_v)
            k = shift2d(keys2d, dr, dc, jnp.int32(-1))
            l = shift2d(flat, dr, dc, jnp.int32(-1))
            better = (v > best_v) | ((v == best_v) & (k > best_k))
            best_v = jnp.where(better, v, best_v)
            best_k = jnp.where(better, k, best_k)
            best_l = jnp.where(better, l, best_l)
    return best_l


def phase_a(image: jnp.ndarray, *, phase_a_impl: str = "fused",
            strip_rows: int = 8, use_pallas: bool | None = None,
            interpret: bool = False) -> PhaseA:
    """Stage A: per-pixel pointers + candidate flags (paper lines 1-2a).

    ``"fused"`` routes through :mod:`repro.kernels.ph_phase_a` (Pallas on
    TPU / its bit-identical XLA reference elsewhere, per ``use_pallas``):
    pointers arrive snapped to in-strip ancestors with the higher-neighbor
    bitmask.  ``"pooled"`` is the unfused baseline: a pooled argmax pass
    and raw pointers (flags derived later from ranks).
    """
    if phase_a_impl == "fused":
        ptr, hi_mask = phase_a_ops.fused_phase_a(
            image, strip_rows=strip_rows, use_pallas=use_pallas,
            interpret=interpret)
        return PhaseA(ptr, hi_mask)
    if phase_a_impl == "pooled":
        return PhaseA(steepest_neighbors(image, use_pallas=use_pallas,
                                         interpret=interpret), None)
    raise ValueError(f"unknown phase_a_impl {phase_a_impl!r}")


# ---------------------------------------------------------------------------
# Phase B: label resolution (dense doubling or compacted frontier)
# ---------------------------------------------------------------------------

def resolve_labels(pointers: jnp.ndarray, *, with_count: bool = False):
    """Pointer-double ``M = M[M]`` to a fixed point (paper lines 2-4).

    Returns labels[i] = flat index of pixel i's basin root, converging in
    O(log(max basin depth)) iterations; each iteration is a single
    whole-array gather (the changed flag rides the carry instead of
    re-gathering in ``cond`` — DESIGN.md §Perf PH-3).
    """
    with jax.named_scope("ph.phase_b"):
        m, count = fixed_point_iterate(lambda q: q[q], pointers)
    return (m, count) if with_count else m


def resolve_labels_frontier(pointers: jnp.ndarray, shape: tuple[int, int],
                            strip_rows: int, *, with_count: bool = False):
    """Label resolution on the compacted strip-boundary frontier.

    ``pointers`` must be strip-snapped (fused phase A): every entry is a
    basin root or a pixel in a statically-known boundary row
    (:func:`repro.kernels.ph_phase_a.boundary_rows`).  Doubling therefore
    runs on the O(n / strip_rows) frontier table alone; one final dense
    gather extends the result to all pixels.  Output is bit-identical to
    :func:`resolve_labels` on the same (or raw) pointers.
    """
    h, w = shape
    b_rows = phase_a_ops.boundary_rows(h, strip_rows)
    row_slot_np = np.full(h, -1, np.int32)
    row_slot_np[b_rows] = np.arange(len(b_rows), dtype=np.int32)

    def follow(table, q):
        rs = row_slot[q // w]
        slot = rs * w + q % w
        return jnp.where(rs >= 0, table[jnp.clip(slot, 0)], q)

    with jax.named_scope("ph.phase_b"):
        row_slot = jnp.asarray(row_slot_np)
        # Built from the (h / strip_rows)-entry row list on the device: as
        # a host constant the O(n / strip_rows) table bloats every compile.
        b_flat = (jnp.asarray(b_rows)[:, None] * jnp.int32(w)
                  + jnp.arange(w, dtype=jnp.int32)[None, :]).reshape(-1)
        p0 = pointers[b_flat]
        table, count = fixed_point_iterate(lambda p: follow(p, p), p0)
        labels = follow(table, pointers)
    return (labels, count) if with_count else labels


def phase_b(pa: PhaseA, shape: tuple[int, int], *,
            phase_a_impl: str = "fused", strip_rows: int = 8) -> jnp.ndarray:
    """Stage B: basin labels from phase-A pointers (paper lines 2-4)."""
    if phase_a_impl == "fused":
        return resolve_labels_frontier(pa.pointers, shape, strip_rows)
    return resolve_labels(pa.pointers)


# ---------------------------------------------------------------------------
# Steps 3-4: candidate death points
# ---------------------------------------------------------------------------

@jax.named_scope("ph.candidates")
def exact_candidates(key2d: jnp.ndarray, labels2d: jnp.ndarray) -> jnp.ndarray:
    """Pixels whose strictly-higher 8-neighbors span >= 2 distinct basins.

    This is exactly the set of pixels at which the union-find sweep can merge
    two components, so it is complete (no lost deaths) and is a strict subset
    of the paper's step-3 edge set (tighter distillation).

    ``key2d`` is any order-isomorphic total-order key image (dense ranks or
    packed int64 keys).  Labels may exceed the local pixel count (the tiled
    path passes *global* labels on a halo-padded tile), so the no-neighbor
    sentinel for ``hi_min`` is int32 max rather than ``key2d.size``.
    """
    no_lbl = jnp.iinfo(jnp.int32).max
    fill = key_pad(key2d.dtype)
    hi_max = jnp.full(key2d.shape, -1, jnp.int32)
    hi_min = jnp.full(key2d.shape, no_lbl, jnp.int32)
    for dr, dc in NEIGHBOR_OFFSETS:
        nkey = shift2d(key2d, dr, dc, fill)
        nlbl = shift2d(labels2d, dr, dc, jnp.int32(-1))
        higher = nkey > key2d  # border fill (dtype min) is never higher
        hi_max = jnp.where(higher, jnp.maximum(hi_max, nlbl), hi_max)
        hi_min = jnp.where(higher, jnp.minimum(hi_min, nlbl), hi_min)
    return (hi_max >= 0) & (hi_max != hi_min)


@jax.named_scope("ph.candidates")
def exact_candidates_masked(hi_mask2d: jnp.ndarray,
                            labels2d: jnp.ndarray) -> jnp.ndarray:
    """:func:`exact_candidates` from phase A's higher-neighbor bitmask.

    Bit j of ``hi_mask2d`` (``NEIGHBOR_OFFSETS`` order) encodes exactly the
    rank comparison ``rank[nb_j] > rank[self]``, so the result is
    bit-identical to the rank-based test without re-deriving ranks —
    the fused path's candidate generator.
    """
    no_lbl = jnp.iinfo(jnp.int32).max
    hi_max = jnp.full(hi_mask2d.shape, -1, jnp.int32)
    hi_min = jnp.full(hi_mask2d.shape, no_lbl, jnp.int32)
    for j, (dr, dc) in enumerate(NEIGHBOR_OFFSETS):
        nlbl = shift2d(labels2d, dr, dc, jnp.int32(-1))
        higher = (hi_mask2d >> j) & 1 == 1
        hi_max = jnp.where(higher, jnp.maximum(hi_max, nlbl), hi_max)
        hi_min = jnp.where(higher, jnp.minimum(hi_min, nlbl), hi_min)
    return (hi_max >= 0) & (hi_max != hi_min)


@jax.named_scope("ph.candidates")
def paper_candidates(key2d: jnp.ndarray, comp2d: jnp.ndarray,
                     *, use_pallas: bool | None = None,
                     interpret: bool = False) -> jnp.ndarray:
    """Paper-literal steps 3-4: component edges, then min/saddle distillation.

    comp2d: re-indexed component image (incremental ids, paper step 2).
    Edge:   maxpool2d(M) != -maxpool2d(-M)           (paper line 6)
    Keep:   local minima or axis saddles of I        (paper "distillation")
    """
    edge = (pool_ops.maxpool3x3(comp2d, use_pallas=use_pallas,
                                interpret=interpret)
            != pool_ops.minpool3x3(comp2d, use_pallas=use_pallas,
                                   interpret=interpret))

    # Neighbor keys with directional fills: for "min along" tests a missing
    # neighbor counts as higher (dtype max); for "max along" as lower
    # (dtype min) — valid keys never reach either sentinel.
    hi, lo = key_top(key2d.dtype), key_pad(key2d.dtype)

    def nb(dr, dc, fill):
        return shift2d(key2d, dr, dc, fill)

    local_min = jnp.ones(key2d.shape, bool)
    for dr, dc in NEIGHBOR_OFFSETS:
        local_min &= nb(dr, dc, hi) > key2d

    axes = [(0, 1), (1, 0), (1, 1), (1, -1)]
    min_along = []
    max_along = []
    for dr, dc in axes:
        min_along.append((nb(dr, dc, hi) > key2d) & (nb(-dr, -dc, hi) > key2d))
        max_along.append((nb(dr, dc, lo) < key2d) & (nb(-dr, -dc, lo) < key2d))
    saddle = jnp.zeros(key2d.shape, bool)
    for a in range(len(axes)):
        for b in range(len(axes)):
            if a != b:
                saddle |= min_along[a] & max_along[b]
    return edge & (local_min | saddle)


@jax.named_scope("ph.candidates")
def reindex_components(key_flat: jnp.ndarray, labels_flat: jnp.ndarray,
                       is_root: jnp.ndarray) -> jnp.ndarray:
    """Paper step 2 re-indexing: component ids 0..C-1 ascending by birth.

    Returns per-pixel component id; id C-1 = component of the global
    maximum.  The root argsort here is inherent to the paper's incremental
    component ids (only ``candidate_mode="paper"`` pays it; the exact mode
    never re-indexes), so it remains on the packed-key path too.
    """
    n = key_flat.shape[0]
    c = jnp.sum(is_root, dtype=jnp.int32)
    root_key = jnp.where(is_root, key_flat, key_pad(key_flat.dtype))
    order = jnp.argsort(root_key)               # non-roots first, roots asc
    slot = jnp.zeros(n, jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
    comp_of_root = slot - (jnp.int32(n) - c)    # roots -> 0..C-1
    return comp_of_root[labels_flat]


# ---------------------------------------------------------------------------
# Phase C: merge sweep + diagram assembly (paper steps 5-6)
# ---------------------------------------------------------------------------

def _find_vec(parent: jnp.ndarray, start: jnp.ndarray) -> jnp.ndarray:
    """Vectorized union-find root lookup (parent is fixed during the search)."""
    p, _ = fixed_point_iterate(lambda q: parent[q], start)
    return p


def _merge_step(shape, i, carry, top_pix, top_keys, image_flat, key_flat,
                labels_flat):
    """One elder-rule merge of the sweep: candidate ``i`` of the top-k."""
    h, w = shape
    n = h * w
    pad = key_pad(key_flat.dtype)
    parent, dval, dpos = carry
    x, xkey = top_pix[i], top_keys[i]
    valid = xkey > pad
    ok, basin = higher_neighbor_basins(x, xkey, key_flat, labels_flat,
                                       (h, w), valid)  # (8,) each

    start = jnp.where(ok, basin, x)      # x is never a root: safe filler
    roots = _find_vec(parent, start)
    root_key = jnp.where(ok, key_flat[roots], pad)
    elder = roots[jnp.argmax(root_key)]

    # Deduplicate equal roots among the 8 slots; younger distinct roots die.
    dup = jnp.zeros(8, bool)
    for j in range(1, 8):
        seen = (roots[:j] == roots[j]) & ok[:j]
        dup = dup.at[j].set(jnp.any(seen))
    die = ok & ~dup & (roots != elder)

    drop = jnp.int32(n)  # scatter target for masked-out lanes
    parent = parent.at[jnp.where(ok, roots, drop)].set(elder, mode="drop")
    parent = parent.at[jnp.where(ok, basin, drop)].set(elder, mode="drop")
    dval = dval.at[jnp.where(die, roots, drop)].set(
        image_flat[x], mode="drop")
    dpos = dpos.at[jnp.where(die, roots, drop)].set(x, mode="drop")
    return parent, dval, dpos


def _sweep_loop(shape, m, arrays, in_axes=None, axis_size=None):
    """Run :func:`_merge_step` for ``i = 0 .. m-1``; ``(dval, dpos)``.

    ``in_axes`` (one entry per array, 0 or None) vectorizes the step over
    a batch of ``axis_size`` sweeps that share the scalar bound ``m``.
    """
    n = shape[0] * shape[1]
    dtype = arrays[2].dtype  # image_flat
    neg_inf = (-jnp.inf if jnp.issubdtype(dtype, jnp.floating)
               else jnp.iinfo(dtype).min)
    carry = (jnp.arange(n, dtype=jnp.int32), jnp.full(n, neg_inf, dtype),
             jnp.full(n, -1, jnp.int32))
    step = functools.partial(_merge_step, shape)
    if in_axes is not None:
        step = jax.vmap(step, in_axes=(None, 0, *in_axes))
        carry = tuple(jnp.broadcast_to(c, (axis_size, n)) for c in carry)

    def body(state):
        i, carry = state
        return i + jnp.int32(1), step(i, carry, *arrays)

    _, (_, dval, dpos) = jax.lax.while_loop(
        lambda state: state[0] < m, body, (jnp.int32(0), carry))
    return dval, dpos


def _merge_sweep(shape, m, *arrays):
    """The elder-rule sweep over the first ``m`` entries of the top-k.

    Under ``vmap`` a while loop with a per-image bound would select every
    carried n-length array on every step; the batching rule instead runs
    one loop to the batch's largest bound with the step vectorized.  An
    image whose own bound is smaller sweeps pad keys there, which its
    ``valid`` mask turns into no-ops.
    """
    @jax.custom_batching.custom_vmap
    def sweep(m, *arrays):
        return _sweep_loop(shape, m, arrays)

    @sweep.def_vmap
    def sweep_batched(axis_size, in_batched, m, *arrays):
        m_all = jnp.max(m) if in_batched[0] else m
        in_axes = tuple(0 if b else None for b in in_batched[1:])
        return _sweep_loop(shape, m_all, arrays, in_axes, axis_size), \
            (True, True)

    return sweep(m, *arrays)


def merge_components(image_flat: jnp.ndarray, key_flat: jnp.ndarray,
                     labels_flat: jnp.ndarray, cand_flat: jnp.ndarray,
                     shape: tuple[int, int], max_candidates: int):
    """Process candidates in descending (value, index) order, union-find merge.

    ``cand_flat`` arrives with any Variant-2 mask applied (paper §5.2.1:
    merges below the threshold never run; :func:`phase_c` truncates the
    survivors at the threshold).

    ``key_flat``: dense int32 ranks or packed int64 keys — the sweep only
    compares them.  On packed keys the top-k selection runs as a
    blockwise tournament (``packed_keys.select_descending``): identical
    retained set and order — including under candidate overflow — but no
    sort spans more than 2k elements.  The rank path keeps the
    full-array ``top_k`` (its ranks already cost a full argsort, so
    there is nothing to save).

    ``max_candidates`` sizes the top-k and nothing else: the sweep stops
    after ``min(n_cand, max_candidates)`` steps, the live candidates,
    which the top-k places first (every later entry carries the pad key
    and would merge nothing).

    Returns (death_val, death_pos, overflow): per-root death records.
    """
    h, w = shape
    n = h * w
    k = min(max_candidates, n)

    with jax.named_scope("ph.select"):
        n_cand = jnp.sum(cand_flat, dtype=jnp.int32)
        top_keys, top_pix = masked_top_k(key_flat, cand_flat, k)  # desc.
        overflow = n_cand > k

    with jax.named_scope("ph.merge"):
        dval, dpos = _merge_sweep(
            (h, w), jnp.minimum(n_cand, jnp.int32(k)), top_pix, top_keys,
            image_flat, key_flat, labels_flat)
    return dval, dpos, overflow


def phase_c(image_flat: jnp.ndarray, key_flat: jnp.ndarray,
            labels_flat: jnp.ndarray, cand_flat: jnp.ndarray,
            shape: tuple[int, int], truncate_value=None, *,
            max_features: int, max_candidates: int,
            merge_impl: str = "scan", phase_c_impl: str = "fused",
            phase_c_block: int = 1024, tournament_width: int = 2,
            use_pallas: bool | None = None,
            interpret: bool = False) -> Diagram:
    """Stage C: elder-rule merge + essential class + diagram (steps 5-6).

    ``merge_impl="scan"`` is the paper-faithful sequential sweep;
    ``"boruvka"`` the parallel merge forest (O(log C) rounds,
    bit-identical — see ``parallel_merge.py``).  ``key_flat`` carries the
    total order in either encoding (ranks / packed); on packed keys the
    diagram's root top-k also runs as a blockwise tournament (extent
    ``tournament_width * k``), so phase C contains no full-image-length
    sort at all.

    ``phase_c_impl`` selects the Boruvka implementation (ignored by the
    scan sweep): ``"xla"`` runs the rounds over all n pixel-vertices;
    ``"fused"`` (the default) compacts to the top-``max_features`` root
    instance first and reduces with the ``repro.kernels.ph_phase_c``
    blocked kernel (``phase_c_block`` edges per VMEM block) — bit-
    identical whenever the roots fit ``max_features`` (under root
    overflow both impls raise the same flag and the engine regrows; see
    ``kernels/ph_phase_c/ops.py``).
    """
    h, w = shape
    n = h * w
    vals = image_flat
    f = min(max_features, n)
    neg_inf = (-jnp.inf if jnp.issubdtype(vals.dtype, jnp.floating)
               else jnp.iinfo(vals.dtype).min)
    with jax.named_scope("ph.diagram"):
        is_root = labels_flat == jnp.arange(n, dtype=jnp.int32)
        gmax = jnp.argmax(key_flat).astype(jnp.int32)
        gmin = jnp.argmin(key_flat).astype(jnp.int32)
        root_mask = is_root if truncate_value is None else \
            is_root & (vals >= truncate_value)
    with jax.named_scope("ph.select"):
        # The candidates every merge sweeps: the Variant-2 mask (paper
        # §5.2.1) applied once here, their count carried out as
        # ``n_candidates``.
        if truncate_value is not None:
            cand_flat = cand_flat & (vals >= truncate_value)
        n_cand = jnp.sum(cand_flat, dtype=jnp.int32)

    if merge_impl == "boruvka" and phase_c_impl == "fused":
        # Compact fused path: merge + diagram read the same top-f root
        # table, so deaths never materialize in the pixel domain at all.
        from repro.kernels.ph_phase_c import ops as phase_c_ops
        (_, root_pix, rvalid, dval_c, dpos_c, overflow_k,
         _rounds) = phase_c_ops.fused_merge(
            vals, key_flat, labels_flat, cand_flat, root_mask, (h, w),
            max_candidates=max_candidates, max_features=max_features,
            phase_c_block=phase_c_block, tournament_width=tournament_width,
            use_pallas=use_pallas, interpret=interpret)
        with jax.named_scope("ph.diagram"):
            if truncate_value is not None:
                undied_c = rvalid & (dpos_c < 0)
                dval_c = jnp.where(
                    undied_c, jnp.asarray(truncate_value, dval_c.dtype),
                    dval_c)
            # Essential class on the compact table: slot 0 is the global
            # maximum's root whenever any root exists (paper fig 3).
            dval_c = dval_c.at[0].set(
                jnp.where(rvalid[0], vals[gmin], dval_c[0]))
            dpos_c = dpos_c.at[0].set(
                jnp.where(rvalid[0], gmin, dpos_c[0]))

            c = jnp.sum(root_mask, dtype=jnp.int32)
            row_valid = jnp.arange(f) < c
            birth = jnp.where(row_valid, vals[root_pix], neg_inf)
            death = jnp.where(row_valid, dval_c, neg_inf)
            p_birth = jnp.where(row_valid, root_pix, -1).astype(jnp.int32)
            p_death = jnp.where(row_valid, dpos_c, -1).astype(jnp.int32)
            n_unmerged = jnp.sum(rvalid & (dpos_c < 0), dtype=jnp.int32)
            overflow = overflow_k | (c > f)
        return Diagram(birth, death, p_birth, p_death,
                       jnp.minimum(c, f), n_unmerged, overflow, n_cand)

    if merge_impl == "scan":
        dval, dpos, overflow_k = merge_components(
            vals, key_flat, labels_flat, cand_flat, (h, w), max_candidates)
    elif merge_impl == "boruvka":
        from repro.core import parallel_merge
        dval, dpos, overflow_k, _rounds = parallel_merge.boruvka_merge(
            vals, key_flat, labels_flat, cand_flat, (h, w), max_candidates,
            n_live=jnp.sum(root_mask, dtype=jnp.int32),
            tournament_width=tournament_width)
    else:
        raise ValueError(f"unknown merge_impl {merge_impl!r}")

    with jax.named_scope("ph.diagram"):
        if truncate_value is not None:
            # Sub-threshold components are background; survivors die at t.
            is_root = root_mask
            undied = is_root & (dpos < 0)
            dval = jnp.where(undied,
                             jnp.asarray(truncate_value, dval.dtype), dval)

        # Essential class: global maximum dies at the global minimum
        # (paper fig 3).
        dval = dval.at[gmax].set(vals[gmin])
        dpos = dpos.at[gmax].set(gmin)

        # Step 6: persistence diagram, descending by birth.
        _, root_pix = masked_top_k(key_flat, is_root, f, tournament_width)
        row_valid = jnp.arange(f) < jnp.sum(is_root, dtype=jnp.int32)

        birth = jnp.where(row_valid, vals[root_pix], neg_inf)
        death = jnp.where(row_valid, dval[root_pix], neg_inf)
        p_birth = jnp.where(row_valid, root_pix, -1).astype(jnp.int32)
        p_death = jnp.where(row_valid, dpos[root_pix], -1).astype(jnp.int32)

        c = jnp.sum(is_root, dtype=jnp.int32)
        n_unmerged = jnp.sum(is_root & (dpos < 0), dtype=jnp.int32)
        overflow = overflow_k | (c > f)
    return Diagram(birth, death, p_birth, p_death,
                   jnp.minimum(c, f), n_unmerged, overflow, n_cand)


# ---------------------------------------------------------------------------
# Full algorithm (paper Algorithm 1): phase_a -> phase_b -> phase_c
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("max_features", "max_candidates", "candidate_mode",
                     "use_pallas", "interpret", "merge_impl", "phase_a_impl",
                     "strip_rows", "merge_keys", "phase_c_impl",
                     "phase_c_block", "tournament_width", "filtration"))
def _pixhomology(image: jnp.ndarray, truncate_value=None, *,
                 max_features: int = 256,
                 max_candidates: int = 4096,
                 candidate_mode: str = "exact",
                 use_pallas: bool | None = None,
                 interpret: bool = False,
                 merge_impl: str = "scan",
                 phase_a_impl: str = "fused",
                 strip_rows: int = 8,
                 merge_keys: str = "rank",
                 phase_c_impl: str = "fused",
                 phase_c_block: int = 1024,
                 tournament_width: int = 2,
                 filtration: str = "superlevel") -> Diagram:
    """Jitted Algorithm-1 core; ``merge_keys`` must arrive fully resolved
    (the public :func:`pixhomology` wrapper resolves it and opens the x64
    scope the packed encoding needs).

    ``filtration="sublevel"`` is an exact boundary negation: the image
    (and Variant-2 threshold, whose ``keep <= t`` semantics negate to the
    internal ``keep >= -t``) flip sign on entry, the unchanged superlevel
    machinery runs, and the diagram's birth/death values flip back on
    exit.  IEEE negation is bit-exact, so the result is bit-identical to
    ``superlevel(-image)`` with the signs flipped — the differential
    oracle in ``tests/test_filtration_distance.py``.
    """
    if image.ndim != 2:
        raise ValueError(f"expected 2D image, got shape {image.shape}")
    packed_keys.assert_key_context(merge_keys)
    image = packed_keys.filtration_view(image, filtration)
    if truncate_value is not None and filtration == "sublevel":
        truncate_value = jnp.negative(truncate_value)
    h, w = image.shape
    vals = image.reshape(-1)
    key = total_order_keys(vals, merge_keys)

    # Stage A: pointers + candidate flags; stage B: basin labels.
    pa = phase_a(image, phase_a_impl=phase_a_impl, strip_rows=strip_rows,
                 use_pallas=use_pallas, interpret=interpret)
    labels = phase_b(pa, (h, w), phase_a_impl=phase_a_impl,
                     strip_rows=strip_rows)

    # Steps 3-4: death-point candidates.
    key2d = key.reshape(h, w)
    if candidate_mode == "exact":
        if pa.hi_mask is not None:
            cand = exact_candidates_masked(pa.hi_mask.reshape(h, w),
                                           labels.reshape(h, w)).reshape(-1)
        else:
            cand = exact_candidates(key2d, labels.reshape(h, w)).reshape(-1)
    elif candidate_mode == "paper":
        is_root = labels == jnp.arange(h * w, dtype=jnp.int32)
        comp2d = reindex_components(key, labels, is_root).reshape(h, w)
        cand = paper_candidates(key2d, comp2d, use_pallas=use_pallas,
                                interpret=interpret).reshape(-1)
    else:
        raise ValueError(f"unknown candidate_mode {candidate_mode!r}")

    # Stage C: merge + essential class + diagram.
    d = phase_c(vals, key, labels, cand, (h, w), truncate_value,
                max_features=max_features, max_candidates=max_candidates,
                merge_impl=merge_impl, phase_c_impl=phase_c_impl,
                phase_c_block=phase_c_block,
                tournament_width=tournament_width,
                use_pallas=use_pallas, interpret=interpret)
    if filtration == "sublevel":
        # Back to user space: births ascend from minima, padding flips to
        # +inf, the essential class dies at the global maximum.
        d = d._replace(birth=jnp.negative(d.birth),
                       death=jnp.negative(d.death))
    return d


def pixhomology(image: jnp.ndarray, truncate_value=None, *,
                merge_keys: str = "packed", **kwargs) -> Diagram:
    """0-dim PH of a 2D image (Algorithm 1), superlevel by default.

    Returns a fixed-capacity :class:`Diagram`, rows sorted by descending
    (birth value, birth index); row 0 is the essential class of the global
    maximum with death at the global minimum.  ``filtration="sublevel"``
    flips the order (floating dtypes only): rows sort ascending by birth,
    padding is ``+inf``, and the essential class of the global minimum
    dies at the global maximum — bit-identical to ``superlevel(-image)``
    with the signs flipped.

    Non-finite pixels are rejected with :func:`packed_keys.check_finite`
    on concrete inputs (NaN admits no filtration order; ±inf collides
    with the pad sentinels) — identically on the packed and rank key
    paths, since the check precedes key resolution.

    ``truncate_value`` (optional, traced): the paper's Variant-2 threshold.
    Components born below it are dropped, merges below it are skipped, and
    surviving non-essential components die at the threshold — the diagram
    truncated at t.  Births/deaths >= t are bit-identical to the untruncated
    run (tests/test_pipeline.py).

    ``phase_a_impl``/``strip_rows``/``merge_keys`` select the stage
    implementations (see the module docstring); every combination is
    bit-identical — only the compiled program changes, which is why they
    are part of the engine's plan key (``PHConfig.stage_signature``).
    ``merge_keys="packed"`` (the default) resolves to ``"rank"`` for
    > 32-bit dtypes or when the int64 scope cannot be opened; the packed
    trace runs under :func:`repro.core.packed_keys.key_scope`, entered
    here when this is the outermost call.
    """
    packed_keys.check_finite(image, allow_inf=True)
    merge_keys = packed_keys.resolve_merge_keys(merge_keys, image.dtype)
    with packed_keys.key_scope(merge_keys):
        return _pixhomology(image, truncate_value, merge_keys=merge_keys,
                            **kwargs)


def batched_pixhomology(images: jnp.ndarray, truncate_values=None, *,
                        merge_keys: str = "packed", **kwargs) -> Diagram:
    """vmap'd PixHomology over a batch (B, H, W) — one executor task each.

    ``truncate_values``: optional (B,) per-image Variant-2 thresholds."""
    packed_keys.check_finite(images, allow_inf=True)
    merge_keys = packed_keys.resolve_merge_keys(merge_keys, images.dtype)
    fn = functools.partial(_pixhomology, merge_keys=merge_keys, **kwargs)
    with packed_keys.key_scope(merge_keys):
        if truncate_values is None:
            return jax.vmap(lambda im: fn(im))(images)
        return jax.vmap(lambda im, t: fn(im, t))(images, truncate_values)


def num_candidates(image: jnp.ndarray,
                   candidate_mode: str = "exact",
                   truncate_value=None, *,
                   use_pallas: bool | None = None,
                   interpret: bool = False,
                   phase_a_impl: str = "fused",
                   strip_rows: int = 8,
                   merge_keys: str = "packed",
                   filtration: str = "superlevel") -> jnp.ndarray:
    """Count death-point candidates (to size ``max_candidates``).

    The stage toggles follow the same semantics as :func:`pixhomology`
    (and must match it for the count to size the same dispatch);
    :meth:`repro.ph.PHEngine.num_candidates` forwards its config
    automatically.  The candidate *set* is key-encoding invariant, but
    ``merge_keys`` still picks how the total order is materialized on the
    branches that need it (packed bit-keys avoid the argsort here too).
    """
    h, w = image.shape
    packed_keys.check_finite(image, allow_inf=True)
    image = packed_keys.filtration_view(image, filtration)
    if truncate_value is not None and filtration == "sublevel":
        truncate_value = jnp.negative(truncate_value)
    merge_keys = packed_keys.resolve_merge_keys(merge_keys, image.dtype)
    with packed_keys.key_scope(merge_keys):
        pa = phase_a(image, phase_a_impl=phase_a_impl, strip_rows=strip_rows,
                     use_pallas=use_pallas, interpret=interpret)
        labels = phase_b(pa, (h, w), phase_a_impl=phase_a_impl,
                         strip_rows=strip_rows)
        # Total-order keys are only materialized on the branches that
        # consume them (this helper runs eagerly, and a rank argsort
        # dominates large images — the fused+exact path needs just the
        # phase-A bitmask).
        if candidate_mode == "exact":
            if pa.hi_mask is not None:
                cand = exact_candidates_masked(pa.hi_mask.reshape(h, w),
                                               labels.reshape(h, w))
            else:
                key = total_order_keys(image.reshape(-1), merge_keys)
                cand = exact_candidates(key.reshape(h, w),
                                        labels.reshape(h, w))
        else:
            key = total_order_keys(image.reshape(-1), merge_keys)
            is_root = labels == jnp.arange(h * w, dtype=jnp.int32)
            comp2d = reindex_components(key, labels, is_root).reshape(h, w)
            cand = paper_candidates(key.reshape(h, w), comp2d,
                                    use_pallas=use_pallas,
                                    interpret=interpret)
        if truncate_value is not None:
            cand = cand & (image >= truncate_value)
        return jnp.sum(cand, dtype=jnp.int32)
