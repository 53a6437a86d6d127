"""Differential oracle harness for the rank-free phase C (packed keys).

Three layers of evidence that ``merge_keys="packed"`` changed *nothing*
but the compiled program:

1. unit tests of the key-packing primitive itself — monotonicity of the
   float32 -> uint32 bit trick over sorted values (including signed zeros
   and subnormals), integer dtypes, and the index round-trip;
2. a hypothesis property suite asserting packed phase C is bit-identical
   (diagram values AND ``p_birth``/``p_death`` positions) to both the
   ``rank`` path and the classical union-find oracle
   (``core/reference.py``), across dtypes, tie-heavy plateaus, negative
   values, and the degenerate single-pixel / all-equal images;
3. a cross-path bit-identity matrix sweeping
   {whole, batched, sharded, tiled} x {fused, pooled phase A}
   x {packed, rank merge keys} on one fixed seed image, so no path
   combination can silently diverge again.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (
    diagram_to_array,
    monotone_key32,
    pack_keys,
    packable_dtype,
    packed_index,
    persistence_oracle,
    pixhomology,
    resolve_merge_keys,
)
from repro.core import packed_keys as pk


def _image(dtype: str, kind: str, seed: int, shape=(12, 11)) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "plateau":            # tiny value range => massive ties
        img = rng.integers(0, 3, size=shape)
    elif kind == "negative":
        img = -np.abs(rng.normal(size=shape) * 50)
    else:
        img = rng.normal(size=shape) * 50
    if dtype == "uint8":
        return np.clip(np.abs(img), 0, 255).astype(np.uint8)
    if dtype == "int16":
        return img.astype(np.int16)
    return img.astype(np.float32)


def run_path(img: np.ndarray, merge_keys: str, **kw) -> np.ndarray:
    h, w = img.shape
    d = pixhomology(jnp.asarray(img), max_features=h * w,
                    max_candidates=h * w, merge_keys=merge_keys, **kw)
    assert not bool(d.overflow)
    return diagram_to_array(d)


# ---------------------------------------------------------------------------
# 1. The key-packing primitive
# ---------------------------------------------------------------------------

def _keys_under_scope(values: np.ndarray):
    with pk.key_scope("packed"):
        k32 = np.asarray(monotone_key32(jnp.asarray(values)))
        packed = np.asarray(pack_keys(jnp.asarray(values)))
        idx = np.asarray(packed_index(jnp.asarray(packed)))
    return k32, packed, idx


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_float32_key_monotone_over_sorted_values(seed):
    rng = np.random.default_rng(seed)
    vals = np.sort((rng.normal(size=64) *
                    10.0 ** rng.integers(-3, 4)).astype(np.float32))
    k32, _, _ = _keys_under_scope(vals)
    jeq = np.asarray(jnp.asarray(vals[1:]) == jnp.asarray(vals[:-1]))
    # Strictly increasing wherever the backend's own comparison says the
    # values differ, equal where it says they tie (flush-to-zero safe).
    assert np.all(np.where(jeq, k32[1:] == k32[:-1], k32[1:] > k32[:-1]))


def test_float32_key_signed_zeros_and_subnormals():
    vals = np.array([-np.inf, -1.0, -1e-45, -0.0, 0.0, 1e-45, 1e-38, 1.0,
                     np.inf], np.float32)
    k32, _, _ = _keys_under_scope(vals)
    iz, pz = 3, 4
    assert k32[iz] == k32[pz], "-0.0 and +0.0 must share a key (argsort ties)"
    # Same order the rank path (stable jnp.argsort) produces.
    with pk.key_scope("packed"):
        packed = np.asarray(pack_keys(jnp.asarray(vals)))
    want = np.asarray(jnp.argsort(jnp.asarray(vals), stable=True))
    assert np.array_equal(np.argsort(packed, kind="stable"), want)


def test_integer_keys_monotone():
    for dtype in (np.uint8, np.int16, np.int32, np.uint16):
        info = np.iinfo(dtype)
        vals = np.unique(np.array(
            [info.min, info.min + 1, -3, -1, 0, 1, 7, info.max - 1, info.max],
            np.int64).clip(info.min, info.max)).astype(dtype)
        k32, _, _ = _keys_under_scope(vals)
        assert np.all(np.diff(k32.astype(np.int64)) > 0), dtype


def test_packed_index_round_trip():
    rng = np.random.default_rng(5)
    vals = rng.normal(size=257).astype(np.float32)
    _, packed, idx = _keys_under_scope(vals)
    np.testing.assert_array_equal(idx, np.arange(257))
    # Packed order == (value, index) lexicographic order.
    order = np.argsort(packed, kind="stable")
    want = np.lexsort((np.arange(257), vals))
    np.testing.assert_array_equal(order, want)


def test_pad_sentinel_strictly_below_all_keys():
    # Even a full-range int32 image (values down to int32 min at pixel 0)
    # stays strictly above the pad sentinel: low word is index + 1 >= 1.
    vals = np.array([np.iinfo(np.int32).min, 0, np.iinfo(np.int32).max],
                    np.int32)
    _, packed, _ = _keys_under_scope(vals)
    assert np.all(packed > np.iinfo(np.int64).min)


def test_resolution_rules():
    assert resolve_merge_keys("rank", np.float32) == "rank"
    assert resolve_merge_keys("packed", np.float32) == "packed"
    assert resolve_merge_keys("packed", np.float64) == "rank"
    assert resolve_merge_keys("packed", np.int64) == "rank"
    assert packable_dtype(jnp.bfloat16) and packable_dtype(np.uint8)
    assert not packable_dtype(np.float64)
    with pytest.raises(ValueError):
        resolve_merge_keys("nope", np.float32)


# ---------------------------------------------------------------------------
# 2. Differential oracle: packed == rank == union-find reference
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.sampled_from(["float32", "int16", "uint8"]),
       st.sampled_from(["gaussian", "plateau", "negative"]),
       st.integers(0, 2 ** 31 - 1))
def test_packed_equals_rank_equals_oracle(dtype, kind, seed):
    img = _image(dtype, kind, seed)
    got_packed = run_path(img, "packed")
    got_rank = run_path(img, "rank")
    want = persistence_oracle(img)
    np.testing.assert_array_equal(got_packed, want,
                                  err_msg=f"packed vs oracle {dtype} {kind}")
    np.testing.assert_array_equal(got_packed, got_rank,
                                  err_msg=f"packed vs rank {dtype} {kind}")


@settings(max_examples=15, deadline=None)
@given(st.sampled_from(["scan", "boruvka"]), st.integers(0, 2 ** 31 - 1))
def test_packed_merge_impls_match_oracle(merge_impl, seed):
    img = _image("float32", "plateau", seed, shape=(9, 13))
    got = run_path(img, "packed", merge_impl=merge_impl)
    np.testing.assert_array_equal(got, persistence_oracle(img))


def test_degenerate_images():
    for img in (np.array([[3.5]], np.float32),            # single pixel
                np.zeros((6, 7), np.float32),             # all-equal
                np.full((5, 5), -2.25, np.float32),       # all-equal negative
                np.full((4, 9), 7, np.uint8)):            # all-equal integer
        got = run_path(img, "packed")
        np.testing.assert_array_equal(got, persistence_oracle(img))
        np.testing.assert_array_equal(got, run_path(img, "rank"))


def test_packed_with_truncation_matches_rank():
    img = _image("float32", "gaussian", 17, shape=(16, 12))
    t = float(np.median(img))
    h, w = img.shape
    for mi in ("scan", "boruvka"):
        a = pixhomology(jnp.asarray(img), t, max_features=h * w,
                        max_candidates=h * w, merge_keys="packed",
                        merge_impl=mi)
        b = pixhomology(jnp.asarray(img), t, max_features=h * w,
                        max_candidates=h * w, merge_keys="rank",
                        merge_impl="scan")
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_paper_candidate_mode_packed_matches_rank():
    img = _image("float32", "gaussian", 23)
    a = run_path(img, "packed", candidate_mode="paper")
    b = run_path(img, "rank", candidate_mode="paper")
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# 3. Cross-path bit-identity matrix
# ---------------------------------------------------------------------------

_MATRIX_IMG = _image("float32", "gaussian", 42, shape=(16, 16))


def _reference_diagram():
    h, w = _MATRIX_IMG.shape
    return pixhomology(jnp.asarray(_MATRIX_IMG), max_features=h * w,
                       max_candidates=h * w, merge_keys="rank",
                       phase_a_impl="pooled")


def _assert_fields_equal(got, want, msg, tiled=False):
    """Every field but ``overflow``; a ``tiled`` diagram's
    ``n_candidates`` counts the seam merge's pre-label candidates, never
    fewer than the whole image's."""
    if tiled:
        assert int(got.n_candidates) >= int(want.n_candidates), msg
    for f in want._fields:
        if f == "overflow" or (tiled and f == "n_candidates"):
            continue
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)),
                                      err_msg=f"{msg} field={f}")


@pytest.mark.parametrize("merge_keys", ["packed", "rank"])
@pytest.mark.parametrize("phase_a_impl", ["fused", "pooled"])
@pytest.mark.parametrize("path", ["whole", "batched", "sharded", "tiled"])
def test_cross_path_matrix(path, phase_a_impl, merge_keys):
    """No {path} x {phase A impl} x {key encoding} combination may ever
    diverge from the whole-image rank reference — bit-for-bit, including
    p_birth/p_death."""
    from repro.ph import PHConfig, PHEngine, TileSpec
    want = _reference_diagram()
    h, w = _MATRIX_IMG.shape
    n = h * w
    config = PHConfig(max_features=n, max_candidates=n,
                      merge_keys=merge_keys, phase_a_impl=phase_a_impl,
                      strip_rows=4, tile=TileSpec(grid=(2, 2)))
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
    _assert_fields_equal(got, want,
                         f"{path}/{phase_a_impl}/{merge_keys}",
                         tiled=path == "tiled")
