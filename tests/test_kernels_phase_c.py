"""Differential oracle harness for the fused phase-C kernel (PR 7).

Same three-layer structure as ``test_merge_keys.py`` (whose helpers this
file reuses):

1. unit parity of the Pallas blocked reduction against its XLA reference
   (interpret mode off-TPU) — across key dtypes, tie storms, dead lanes,
   all-dead instances, and block sizes that do not divide the edge count;
2. whole-diagram bit-identity of ``phase_c_impl="fused"`` against
   ``"xla"`` and the scan merge across dtypes, plateaus, truncation, and
   tournament widths — including the overflow-flag contract;
3. a cross-path matrix {whole, batched, sharded, tiled} x {fused, xla}
   against the whole-image rank reference, so no path x impl combination
   can silently diverge.

Plus the merge-budget early exit: a fully merged forest must stop
without the final verification round, bit-identically.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from test_merge_keys import (
    _MATRIX_IMG,
    _assert_fields_equal,
    _image,
    _reference_diagram,
    run_path,
)

from repro.core import packed_keys as pk
from repro.core.parallel_merge import boruvka_forest
from repro.core.pixhomology import pixhomology
from repro.kernels.ph_phase_c import kernel
from repro.kernels.ph_phase_c import ops as phase_c_ops
from repro.kernels.ph_phase_c import ref


# ---------------------------------------------------------------------------
# 1. Pallas kernel parity vs the XLA reference (interpret mode off-TPU)
# ---------------------------------------------------------------------------

def _instance(e: int, nv: int, dtype, seed: int, dead_frac: float = 0.3):
    """Random reduction instance: ~keyspace of 10 values (tie storms),
    ~dead_frac pad lanes, endpoints uniform over the vertex set."""
    rng = np.random.default_rng(seed)
    pad = int(pk.key_pad(dtype))
    key = rng.integers(-5, 5, size=e).astype(np.int64)
    key = np.where(rng.random(e) < dead_frac, pad, key)
    ra = rng.integers(0, nv, size=e).astype(np.int32)
    rb = rng.integers(0, nv, size=e).astype(np.int32)
    return (jnp.asarray(key, dtype), jnp.asarray(ra), jnp.asarray(rb))


@pytest.mark.parametrize("e,nv,block", [(1, 1, 4), (7, 3, 4), (33, 4, 8),
                                        (64, 5, 16), (100, 9, 1024),
                                        (3000, 37, 200)])
@pytest.mark.parametrize("dtype", ["int32", "int64"])
def test_kernel_matches_ref(e, nv, block, dtype):
    scope = "packed" if dtype == "int64" else "rank"
    with pk.key_scope(scope):
        key, ra, rb = _instance(e, nv, jnp.dtype(dtype), seed=e * 31 + nv)
        best_k, win_k = kernel.best_edge_reduce(key, ra, rb, nv,
                                                block_edges=block,
                                                interpret=True)
        best_r, win_r = ref.best_edge_reduce(key, ra, rb, nv)
    np.testing.assert_array_equal(np.asarray(best_k), np.asarray(best_r))
    np.testing.assert_array_equal(np.asarray(win_k), np.asarray(win_r))


def test_kernel_all_dead_lanes():
    with pk.key_scope("rank"):
        pad = pk.key_pad(jnp.int32)
        key = jnp.full(17, pad, jnp.int32)
        ra = jnp.zeros(17, jnp.int32)
        rb = jnp.zeros(17, jnp.int32)
        best, win = kernel.best_edge_reduce(key, ra, rb, 4, block_edges=8,
                                            interpret=True)
    assert np.all(np.asarray(best) == int(pad))
    assert np.all(np.asarray(win) == -1)


def test_kernel_tie_break_is_max_edge_index():
    # Three equal-key edges into vertex 0: the winner must be the highest
    # edge index (the deterministic Boruvka tie rule), not block order.
    with pk.key_scope("rank"):
        key = jnp.array([7, 7, 7, 2], jnp.int32)
        ra = jnp.array([0, 0, 0, 1], jnp.int32)
        rb = jnp.array([1, 1, 1, 0], jnp.int32)
        best, win = kernel.best_edge_reduce(key, ra, rb, 2, block_edges=2,
                                            interpret=True)
    np.testing.assert_array_equal(np.asarray(best), [7, 7])
    np.testing.assert_array_equal(np.asarray(win), [2, 2])


def test_ops_dispatch_routes_off_tpu_to_ref():
    # use_pallas=None off-TPU must be the XLA reference (same objects out).
    with pk.key_scope("rank"):
        key, ra, rb = _instance(20, 3, jnp.dtype(jnp.int32), seed=1)
        auto = phase_c_ops.best_edge_reduce(key, ra, rb, 3)
        forced = phase_c_ops.best_edge_reduce(key, ra, rb, 3,
                                              use_pallas=True,
                                              interpret=True)
        want = ref.best_edge_reduce(key, ra, rb, 3)
    for got in (auto, forced):
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))


# ---------------------------------------------------------------------------
# 2. Whole-diagram bit-identity: fused vs xla vs the scan merge
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("merge_keys", ["packed", "rank"])
@pytest.mark.parametrize("dtype,kind", [("float32", "gaussian"),
                                        ("float32", "plateau"),
                                        ("uint8", "plateau"),
                                        ("int16", "negative")])
def test_fused_matches_xla_and_scan(dtype, kind, merge_keys):
    img = _image(dtype, kind, 7)
    xla = run_path(img, merge_keys, merge_impl="boruvka",
                   phase_c_impl="xla")
    fused = run_path(img, merge_keys, merge_impl="boruvka",
                     phase_c_impl="fused")
    scan = run_path(img, merge_keys, merge_impl="scan")
    np.testing.assert_array_equal(fused, xla)
    np.testing.assert_array_equal(fused, scan)


@pytest.mark.parametrize("merge_keys", ["packed", "rank"])
def test_fused_matches_xla_truncated(merge_keys):
    img = _image("float32", "gaussian", 21)
    tv = float(np.median(img))
    h, w = img.shape
    kw = dict(max_features=h * w, max_candidates=h * w,
              merge_impl="boruvka", merge_keys=merge_keys)
    d_x = pixhomology(jnp.asarray(img), tv, phase_c_impl="xla", **kw)
    d_f = pixhomology(jnp.asarray(img), tv, phase_c_impl="fused", **kw)
    _assert_fields_equal(d_f, d_x, f"truncated/{merge_keys}")
    assert not bool(d_x.overflow)


def test_fused_pallas_kernel_end_to_end():
    # The fused path with the Pallas reduction forced on (interpret mode
    # off-TPU) must still be bit-identical at the diagram level.
    img = _image("float32", "gaussian", 5)
    want = run_path(img, "packed", merge_impl="boruvka", phase_c_impl="xla")
    got = run_path(img, "packed", merge_impl="boruvka",
                   phase_c_impl="fused", use_pallas=True, interpret=True)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("width", [3, 4, 8])
def test_tournament_width_bit_identical(width):
    img = _image("float32", "plateau", 11)
    base = run_path(img, "packed", merge_impl="boruvka",
                    phase_c_impl="fused", tournament_width=2)
    got = run_path(img, "packed", merge_impl="boruvka",
                   phase_c_impl="fused", tournament_width=width)
    np.testing.assert_array_equal(got, base)


def test_tournament_width_validated():
    from repro.core.packed_keys import select_descending
    from repro.ph import PHConfig
    with pytest.raises(ValueError):
        PHConfig(tournament_width=1)
    with pk.key_scope("packed"):
        key = pk.pack_keys(jnp.arange(8, dtype=jnp.float32))
        with pytest.raises(ValueError):
            select_descending(key, jnp.ones(8, bool), 2, width=1)


def test_overflow_flag_parity_under_root_overflow():
    # max_features below the root count: both impls must raise the same
    # overflow flag (the engine's regrow contract) even though their
    # pre-regrow rows may legitimately differ.
    img = _image("float32", "gaussian", 3)
    h, w = img.shape
    kw = dict(max_features=2, max_candidates=h * w, merge_impl="boruvka",
              merge_keys="packed")
    d_x = pixhomology(jnp.asarray(img), phase_c_impl="xla", **kw)
    d_f = pixhomology(jnp.asarray(img), phase_c_impl="fused", **kw)
    assert bool(d_x.overflow) and bool(d_f.overflow)


# ---------------------------------------------------------------------------
# 3. Boruvka merge-budget early exit
# ---------------------------------------------------------------------------

def test_early_exit_skips_verification_round():
    # Two live clusters, one edge: the forest is fully merged after round
    # 1; the merge budget (n_live - 1 == 1) must stop there, while the
    # uncapped loop needs a second round to observe no alive edges.
    v_rank = jnp.array([5, 3], jnp.int32)
    e_rank = jnp.array([1], jnp.int32)
    e_val = jnp.array([1.0], jnp.float32)
    e_pos = jnp.array([7], jnp.int32)
    e_a = jnp.array([0], jnp.int32)
    e_b = jnp.array([1], jnp.int32)
    base = boruvka_forest(v_rank, e_rank, e_val, e_pos, e_a, e_b)
    capped = boruvka_forest(v_rank, e_rank, e_val, e_pos, e_a, e_b,
                            n_live=jnp.int32(2))
    assert int(capped[2]) < int(base[2])
    np.testing.assert_array_equal(np.asarray(capped[0]),
                                  np.asarray(base[0]))
    np.testing.assert_array_equal(np.asarray(capped[1]),
                                  np.asarray(base[1]))


def test_early_exit_overestimated_budget_is_safe():
    # Over-estimating n_live (callers pass root counts, an upper bound)
    # must never change results — only potentially cost a round.
    img = _image("float32", "gaussian", 13)
    want = run_path(img, "packed", merge_impl="boruvka", phase_c_impl="xla")
    got = run_path(img, "packed", merge_impl="boruvka",
                   phase_c_impl="fused")
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# 4. Cross-path bit-identity matrix (path x phase_c_impl)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("phase_c_impl", ["fused", "xla"])
@pytest.mark.parametrize("path", ["whole", "batched", "sharded", "tiled"])
def test_cross_path_phase_c_matrix(path, phase_c_impl):
    """No {path} x {phase_c_impl} combination may diverge from the
    whole-image rank/scan reference — bit-for-bit, including
    p_birth/p_death."""
    from repro.ph import PHConfig, PHEngine, TileSpec
    want = _reference_diagram()
    h, w = _MATRIX_IMG.shape
    n = h * w
    config = PHConfig(max_features=n, max_candidates=n,
                      merge_impl="boruvka", phase_c_impl=phase_c_impl,
                      phase_c_block=64, strip_rows=4,
                      tile=TileSpec(grid=(2, 2)))
    engine = PHEngine(config)
    img = jnp.asarray(_MATRIX_IMG)

    if path == "whole":
        got = engine.run(_MATRIX_IMG).diagram
    elif path == "batched":
        res = engine.run_batch(_MATRIX_IMG[None]).diagram
        got = jax.tree.map(lambda x: x[0], res)
    elif path == "sharded":
        from repro.launch.mesh import make_small_context
        ctx = make_small_context(1, 1)
        plan = engine.sharded_plan(ctx, (1, h, w), jnp.dtype(jnp.float32),
                                   n, n)
        tvals = jnp.full((1,), -jnp.inf, jnp.float32)  # vanilla sentinel
        res = plan(img[None], tvals)
        got = jax.tree.map(lambda x: x[0], res)
    else:   # tiled
        got = engine.run_tiled(_MATRIX_IMG).diagram
    _assert_fields_equal(got, want, f"{path}/{phase_c_impl}",
                         tiled=path == "tiled")
