"""Parallel merge phase: Boruvka rounds instead of the sequential sweep.

The paper's step 5 processes candidates one-by-one in descending order
(inherently sequential; our faithful version is a fixed-length ``lax.scan``
— 16384 sequential steps for a 1k x 1k astro image).  0-dim superlevel
persistence is equivalent to elder-rule pairing on the *maximum spanning
forest* of the saddle graph, which Boruvka builds in O(log C) fully-parallel
rounds:

  round:  every cluster finds its highest incident saddle edge (segment-max
          via scatter-max, two passes for argmax);  every cluster whose best
          edge leads to an older cluster DIES there (death = that saddle);
          union pointers are resolved by pointer doubling.

Correctness: "die" pointers always point to strictly larger birth keys, so
the simultaneous merges form a forest (no cycles) and each dier's death
saddle equals the one the sequential sweep would assign — the output is
bit-identical to the union-find oracle (tests/test_parallel_merge.py).

Edges are generated from the exact candidate set: per candidate pixel, a
chain over its (masked) higher-neighbor basins — a spanning set of the
clique of basins meeting at that pixel, so all merges at a value-v saddle
still happen at value v.

The round machinery is factored as :func:`boruvka_forest`, a generic
elder-rule forest reduction over an abstract (vertex ranks, edge list)
instance.  ``boruvka_merge`` instantiates it with vertices = pixels (the
whole-image path); ``repro.core.tiling`` instantiates it with vertices =
per-tile basin roots and edges = per-tile + boundary-seam edge lists (the
tiled path's global merge), so both paths share one bit-tested reduction.

Depth: the scan is O(K) sequential steps (K live candidates) with O(1)
work; Boruvka is O(log C) rounds of O(E) parallel work — on a
systolic/vector machine depth is what matters (src/repro/ph/DESIGN.md §Perf PH-2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.grid import fixed_point_iterate, higher_neighbor_basins
from repro.core.packed_keys import key_pad, masked_top_k, packed_index


def candidate_edges(key_flat, labels_flat, cand_flat, shape,
                    max_candidates: int, tournament_width: int = 2):
    """Top-K candidates -> chained basin edges (K, 8) flat: [key_x, a, b].

    ``key_flat``: dense ranks or packed int64 keys; on packed keys the
    selection runs as a blockwise tournament
    (``packed_keys.masked_top_k``, block extent
    ``tournament_width * K``) — same retained set and order, no
    full-image sort.
    """
    h, w = shape
    n = h * w
    k = min(max_candidates, n)
    pad = key_pad(key_flat.dtype)
    with jax.named_scope("ph.select"):
        top_keys, top_pix = masked_top_k(key_flat, cand_flat, k,
                                         tournament_width)
    with jax.named_scope("ph.merge"):
        valid = top_keys > pad
        ok, lbl = higher_neighbor_basins(top_pix, top_keys, key_flat,
                                         labels_flat, shape, valid)  # (K, 8)
        edge_ok, prev_lbl = chain_clique_edges(ok, lbl)
        keys = jnp.broadcast_to(top_keys[:, None], ok.shape)
        return (jnp.where(edge_ok, keys, pad).reshape(-1),
                jnp.where(edge_ok, lbl, 0).reshape(-1),
                jnp.where(edge_ok, prev_lbl, 0).reshape(-1))


def chain_clique_edges(ok: jnp.ndarray, lbl: jnp.ndarray):
    """Chain consecutive valid neighbor slots into clique-spanning edges.

    ``ok``/``lbl``: (K, 8) from :func:`~repro.core.grid.higher_neighbor_basins`.
    Edge j connects slot j's basin to the previous valid slot's basin — a
    spanning set of the per-candidate basin clique, in the fixed
    NEIGHBOR_OFFSETS order (shared by the whole-image and tiled builders so
    the edge multiset is identical).  Returns ``(edge_ok, prev_lbl)``.
    """
    def chain(ok_row, lbl_row):
        def step(prev, xs):
            o, l = xs
            a = jnp.where(o, prev, -1)
            prev = jnp.where(o, l, prev)
            return prev, a

        _, prev_lbl = jax.lax.scan(step, jnp.int32(-1), (ok_row, lbl_row))
        return prev_lbl            # (8,) previous valid basin or -1

    prev_lbl = jax.vmap(chain)(ok, lbl)
    edge_ok = ok & (prev_lbl >= 0) & (prev_lbl != lbl)
    return edge_ok, prev_lbl


def best_edge_reduce(key, ra, rb, nv: int):
    """Per-cluster best incident edge: ``(best key, winning edge index)``.

    The segmented reduction at the heart of every Boruvka round, factored
    out so implementations can be swapped (``reduce_fn`` of
    :func:`boruvka_forest`): ``repro.kernels.ph_phase_c`` supplies a
    blocked Pallas twin that accumulates the same scatters block-by-block
    in VMEM.  Both passes are **integer max reductions** — associative,
    commutative, and tie-free on the index pass — so any blocking of the
    edge axis is bit-identical by construction.

    ``key``: (E,) saddle keys, pre-masked to the dtype-min pad sentinel on
    dead lanes (the sentinel is strictly below every live key, so
    ``key > pad`` recovers liveness).  ``ra``/``rb``: (E,) resolved
    endpoint clusters, in ``[0, nv)`` on every lane.  Returns per-vertex
    ``best`` (pad where no live edge) and ``win`` (max winning edge index
    among best-key ties, -1 where none).
    """
    e_pad = key_pad(key.dtype)
    alive = key > e_pad
    # Pass 1: per-cluster best saddle key (scatter-max on both ends).
    best = jnp.full(nv, e_pad, key.dtype)
    best = best.at[jnp.where(alive, ra, nv)].max(key, mode="drop")
    best = best.at[jnp.where(alive, rb, nv)].max(key, mode="drop")
    # Pass 2: per-cluster winning edge index among key ties.
    eidx = jnp.arange(key.shape[0], dtype=jnp.int32)
    hit_a = alive & (key == best[ra])
    hit_b = alive & (key == best[rb])
    win = jnp.full(nv, -1, jnp.int32)
    win = win.at[jnp.where(hit_a, ra, nv)].max(
        jnp.where(hit_a, eidx, -1), mode="drop")
    win = win.at[jnp.where(hit_b, rb, nv)].max(
        jnp.where(hit_b, eidx, -1), mode="drop")
    return best, win


@jax.named_scope("ph.merge")
def boruvka_forest(v_rank, e_rank, e_val, e_pos, e_a, e_b, *,
                   n_live=None, reduce_fn=None):
    """Elder-rule Boruvka forest over an abstract vertex/edge instance.

    ``v_rank``: (V,) birth key per vertex — any order-isomorphic
    assignment under the (birth value, birth index) total order (dense
    int32 ranks or packed int64 keys); dead or padded vertices carry the
    dtype-min pad sentinel and must have no live edges.
    ``e_rank``: (E,) saddle key per edge — order-isomorphic to the
    saddle (value, index) total order, EQUAL for edges sharing a saddle
    pixel; the dtype-min sentinel marks padding.
    ``e_val``/``e_pos``: (E,) death value / position recorded when an edge
    kills a vertex.  ``e_a``/``e_b``: (E,) endpoint vertex ids.

    ``n_live``: optional (traced) upper bound on the number of clusters
    that can ever merge.  A spanning forest performs at most
    ``n_live - 1`` merges, so once that many clusters have died no
    inter-cluster edge can remain and the loop exits **without** paying
    the final verification round the plain any-alive test needs (for a
    fully merged forest — e.g. a single-component image — that round is
    pure overhead).  An over-estimate is always safe; callers pass their
    root/seam-vertex count.

    ``reduce_fn``: drop-in replacement for :func:`best_edge_reduce`
    (same signature) — the fused phase-C kernel's hook.

    Returns ``(dval, dpos, rounds)``: per-vertex death value (init -inf
    of ``e_val.dtype``), death position (init -1), and the number of
    Boruvka rounds executed (int32; BENCH telemetry).  Vertices that
    never meet an older cluster keep the init values.
    """
    nv = v_rank.shape[0]
    e_pad = key_pad(e_rank.dtype)
    neg_inf = (-jnp.inf if jnp.issubdtype(e_val.dtype, jnp.floating)
               else jnp.iinfo(e_val.dtype).min)
    reduce_ = best_edge_reduce if reduce_fn is None else reduce_fn

    parent0 = jnp.arange(nv, dtype=jnp.int32)
    dval0 = jnp.full(nv, neg_inf, e_val.dtype)
    dpos0 = jnp.full(nv, -1, jnp.int32)
    merge_cap = (jnp.asarray(jnp.iinfo(jnp.int32).max, jnp.int32)
                 if n_live is None
                 else jnp.asarray(n_live, jnp.int32) - 1)

    def resolve(p):
        q, _ = fixed_point_iterate(lambda r: r[r], p)
        return q

    def round_body(state):
        parent, dval, dpos, _, merges, rounds = state
        roots = resolve(parent)
        ra = roots[e_a]
        rb = roots[e_b]
        alive = (e_rank > e_pad) & (ra != rb)
        key = jnp.where(alive, e_rank, e_pad)

        best, win = reduce_(key, ra, rb, nv)

        # For each cluster with a best edge: other endpoint + die rule.
        has = win >= 0
        wi = jnp.clip(win, 0)
        wa = roots[e_a[wi]]
        wb = roots[e_b[wi]]
        me = jnp.arange(nv, dtype=jnp.int32)
        other = jnp.where(wa == me, wb, wa)
        die = has & (v_rank[other] > v_rank[me]) & (roots == me)

        parent = jnp.where(die, other, parent)
        dval = jnp.where(die, e_val[wi], dval)
        dpos = jnp.where(die, e_pos[wi], dpos)
        merges = merges + jnp.sum(die, dtype=jnp.int32)
        return parent, dval, dpos, jnp.any(alive), merges, rounds + 1

    def cond(state):
        return state[3] & (state[4] < merge_cap)

    state = (parent0, dval0, dpos0, jnp.asarray(True), jnp.int32(0),
             jnp.int32(0))
    # Seed round + loop until no alive inter-cluster edges remain (or the
    # merge budget proves none can).
    state = jax.lax.while_loop(cond, round_body, state)
    _, dval, dpos, _, _, rounds = state
    return dval, dpos, rounds


def boruvka_merge(image_flat, key_flat, labels_flat, cand_flat, shape,
                  max_candidates: int, *, n_live=None,
                  tournament_width: int = 2, reduce_fn=None):
    """Parallel replacement for ``pixhomology.merge_components``.

    Whole-image instantiation of :func:`boruvka_forest`: vertices are the n
    pixels keyed by the global total order (only basin roots carry live
    edges).  Packed keys carry their pixel index in the low bits, so the
    key -> pixel map is a mask; dense ranks need the inverse permutation
    (one more argsort — the fallback's price).  ``n_live``/``reduce_fn``
    forward to :func:`boruvka_forest`; returns
    ``(dval, dpos, overflow, rounds)``.
    """
    n = image_flat.shape[0]
    e_key, e_a, e_b = candidate_edges(key_flat, labels_flat, cand_flat,
                                      shape, max_candidates,
                                      tournament_width)
    # Map the saddle key back to its pixel id for death values/positions.
    with jax.named_scope("ph.merge"):
        if key_flat.dtype == jnp.int64:
            e_pos = jnp.clip(packed_index(e_key), 0)   # pad keys -> pixel 0
        else:
            perm = jnp.argsort(key_flat, stable=True)  # rank -> pixel id
            e_pos = perm[jnp.clip(e_key, 0)]
        e_val = image_flat[e_pos]

    dval, dpos, rounds = boruvka_forest(key_flat, e_key, e_val, e_pos,
                                        e_a, e_b, n_live=n_live,
                                        reduce_fn=reduce_fn)

    n_cand = jnp.sum(cand_flat, dtype=jnp.int32)
    overflow = n_cand > min(max_candidates, n)
    return dval, dpos, overflow, rounds
