"""Dispatch + whole-image driver for the fused phase-C merge.

Two public entry points:

* :func:`best_edge_reduce` — the per-round segmented reduction, routed
  to the Pallas kernel (TPU, or ``interpret=True`` anywhere) or the
  bit-identical XLA reference; plugged into
  :func:`repro.core.parallel_merge.boruvka_forest` as ``reduce_fn``.

* :func:`fused_merge` — the whole-image fused phase C.  The plain
  Boruvka path (``phase_c_impl="xla"``) runs every round over all n
  pixel-vertices: each round's label resolve, scatter targets, and die
  masks are O(n) even though only the C basin roots can ever merge
  (C ~ 10³-10⁴ at n = 10⁶).  ``fused_merge`` compacts the instance
  first — and it compacts by **cumsum scatter**, not by selection:
  the XLA path's two n-length blockwise-tournament top-k's (candidate
  selection inside ``candidate_edges`` and the diagram's root table)
  each cost more than all of its Boruvka rounds combined on CPU, so
  the fused path gathers candidates and roots to their capacity-sized
  arrays in one O(n) pass each (``_compact_mask``) and sorts only the
  ≤ ``max_features``-length compact root table into diagram order.
  Edge endpoints map to compact slots through an O(f log f) sorted
  lookup table, and the Boruvka forest — with the blocked reduction
  and the merge-budget early exit (``n_live``) — runs entirely on
  (f, E)-sized arrays.  The diagram assembly reads the compact records
  directly, and the compact edge builder carries each saddle's pixel
  id alongside its key, so the rank-key fallback no longer pays the
  full-image inverse-argsort either.

Bit-identity with the XLA path holds whenever the root count fits
``max_features`` (the no-overflow contract): below capacity the
compacted-then-sorted root table equals the ``masked_top_k`` selection
the XLA diagram makes (same set, same descending total order — keys
are unique), every edge endpoint is a root above any truncation
threshold (its birth exceeds the saddle), and elder-rule deaths are a
graph invariant of the (basin, saddle-edge) multiset — the identical
multiset both paths build, merely enumerated in pixel order instead of
key order (the tiled seam merge already relies on this invariance: its
edges arrive in tile order).  Under root overflow
(``c > max_features``) edges touching a dropped root are dropped too,
so pre-regrow rows may differ from the XLA path's; both impls raise
the same ``Diagram.overflow`` and the engine's regrow re-dispatches at
a capacity where they agree again.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.grid import higher_neighbor_basins
from repro.core.packed_keys import key_pad
from repro.core.parallel_merge import boruvka_forest, chain_clique_edges
from repro.kernels import backend
from repro.kernels.ph_phase_c import kernel, ref


def best_edge_reduce(key, ra, rb, nv: int, *, block_edges: int = 1024,
                     use_pallas: bool | None = None,
                     interpret: bool = False):
    """Per-cluster best incident edge, Pallas or XLA backend, chosen by
    :func:`repro.kernels.backend.resolve` (on CPU the fused win comes
    from the compact instance, not from emulating the kernel;
    ``interpret=True`` is CI's parity path).
    """
    impl = backend.resolve("ph_phase_c", use_pallas, interpret)
    if impl == backend.XLA:
        return ref.best_edge_reduce(key, ra, rb, nv)
    return kernel.best_edge_reduce(key, ra, rb, nv,
                                   block_edges=block_edges,
                                   interpret=impl == backend.INTERPRET)


def _compact_mask(key_flat, mask, k: int):
    """Gather the ≤ k masked lanes to a k-slot table in flat-pixel order.

    One cumsum + two O(n) scatters — no selection sort of any width.
    Returns ``(keys, pix)``: dtype-min pad keys and pixel id 0 on empty
    slots; masked lanes beyond the k-th (capacity overflow — the caller
    raises the flag) fall in the drop lane.
    """
    n = key_flat.shape[0]
    slot = jnp.cumsum(mask.astype(jnp.int32)) - 1
    tgt = jnp.where(mask & (slot < k), slot, k)
    keys = jnp.full(k, key_pad(key_flat.dtype), key_flat.dtype)
    keys = keys.at[tgt].set(key_flat, mode="drop")
    pix = jnp.zeros(k, jnp.int32)
    pix = pix.at[tgt].set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    return keys, pix


def _compact_candidate_edges(key_flat, labels_flat, cand_flat, shape,
                             max_candidates: int):
    """Chained basin edges of the compacted candidate set: flat (K*8,)
    ``(key, a, b, saddle_pixel)``.

    The compaction twin of :func:`repro.core.parallel_merge.candidate_edges`
    (same ``higher_neighbor_basins`` + ``chain_clique_edges`` chain, so the
    edge *multiset* is identical); edges come out in candidate-pixel order
    rather than descending key order, which the merge forest is invariant
    to, and each edge carries its saddle pixel directly — no key→pixel
    inverse lookup for either key encoding.
    """
    h, w = shape
    k = min(max_candidates, h * w)
    pad = key_pad(key_flat.dtype)
    with jax.named_scope("ph.select"):
        top_keys, top_pix = _compact_mask(key_flat, cand_flat, k)
    with jax.named_scope("ph.merge"):
        valid = top_keys > pad
        ok, lbl = higher_neighbor_basins(top_pix, top_keys, key_flat,
                                         labels_flat, shape, valid)  # (K, 8)
        edge_ok, prev_lbl = chain_clique_edges(ok, lbl)
        keys = jnp.broadcast_to(top_keys[:, None], ok.shape)
        pixs = jnp.broadcast_to(top_pix[:, None], ok.shape)
        return (jnp.where(edge_ok, keys, pad).reshape(-1),
                jnp.where(edge_ok, lbl, 0).reshape(-1),
                jnp.where(edge_ok, prev_lbl, 0).reshape(-1),
                pixs.reshape(-1))


def _slot_lookup(sorted_pix, order, q):
    """Binary-search ``q`` in the sorted compact-root pixel table.

    Returns ``(slot, found)``: the root's compact slot (0 where absent —
    callers must mask on ``found``).  Same sorted-table pattern as the
    tiled seam's ring lookup.
    """
    j = jnp.searchsorted(sorted_pix, q)
    j = jnp.clip(j, 0, sorted_pix.shape[0] - 1)
    found = sorted_pix[j] == q
    return jnp.where(found, order[j], 0), found


def fused_merge(image_flat, key_flat, labels_flat, cand_flat, root_mask,
                shape, *, max_candidates: int, max_features: int,
                phase_c_block: int = 1024, tournament_width: int = 2,
                use_pallas: bool | None = None, interpret: bool = False):
    """Compact fused phase-C merge over the top-``max_features`` roots.

    ``root_mask``: (n,) bool — the diagram's root set (already filtered
    by any truncation threshold; every candidate edge endpoint is in it
    because a basin's birth exceeds its saddles).  Returns
    ``(root_key, root_pix, rvalid, dval_c, dpos_c, overflow, rounds)``:
    the descending compact root table (== the XLA diagram's own
    ``masked_top_k`` selection), per-slot death value/position in pixel
    coordinates, the candidate-overflow flag, and the Boruvka round
    count.
    """
    n = image_flat.shape[0]
    f = min(max_features, n)
    e_key, e_a, e_b, e_pos = _compact_candidate_edges(
        key_flat, labels_flat, cand_flat, shape, max_candidates)

    # Compact vertex set: cumsum-compact the roots, then sort only the
    # f-length table into the diagram's descending key order (keys are
    # unique, so below capacity this equals the XLA ``masked_top_k``
    # selection exactly; pads sort to the tail).
    with jax.named_scope("ph.diagram"):
        rk_c, rp_c = _compact_mask(key_flat, root_mask, f)
        order_desc = jnp.argsort(rk_c)[::-1].astype(jnp.int32)
        root_key = rk_c[order_desc]
        root_pix = rp_c[order_desc]
        rvalid = root_key > key_pad(root_key.dtype)

    with jax.named_scope("ph.merge"):
        e_val = image_flat[e_pos]
        # pixel id -> compact slot through one O(f log f) sorted table.
        imax = jnp.int32(jnp.iinfo(jnp.int32).max)
        pix_or_max = jnp.where(rvalid, root_pix, imax)
        order = jnp.argsort(pix_or_max).astype(jnp.int32)
        sorted_pix = pix_or_max[order]
        sa, fa = _slot_lookup(sorted_pix, order, e_a)
        sb, fb = _slot_lookup(sorted_pix, order, e_b)
        e_key_c = jnp.where(fa & fb, e_key, key_pad(e_key.dtype))

        c = jnp.sum(root_mask, dtype=jnp.int32)
        reduce_fn = functools.partial(best_edge_reduce,
                                      block_edges=phase_c_block,
                                      use_pallas=use_pallas,
                                      interpret=interpret)
        dval_c, dpos_c, rounds = boruvka_forest(
            root_key, e_key_c, e_val, e_pos, sa, sb,
            n_live=jnp.minimum(c, f), reduce_fn=reduce_fn)

        n_cand = jnp.sum(cand_flat, dtype=jnp.int32)
        overflow = n_cand > min(max_candidates, n)
    return root_key, root_pix, rvalid, dval_c, dpos_c, overflow, rounds
