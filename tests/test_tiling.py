"""Tile-decomposed PixHomology: bit-identity with the whole-image path.

The acceptance bar is *exact* equality of every Diagram field — including
``p_birth``/``p_death`` in global pixel coordinates — against whole-image
``pixhomology`` (itself bit-tested against the union-find oracle), across
random grids, tie-heavy images, and basins/saddles spanning 3+ tiles; plus
two-level overflow regrow and the per-tile cost-model scaling property.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import persistence_oracle, pixhomology
from repro.core.tiling import (
    TiledDiagram,
    choose_grid,
    per_tile_cost,
    tiled_pixhomology,
    validate_grid,
)
from repro.ph import PHConfig, PHEngine, TileSpec


def assert_tiled_equal(img: np.ndarray, grid, tv=None):
    h, w = img.shape
    whole = pixhomology(jnp.asarray(img), tv, max_features=h * w,
                        max_candidates=h * w)
    tvj = None if tv is None else jnp.asarray(tv, jnp.float32)
    td = tiled_pixhomology(jnp.asarray(img), tvj, grid=tuple(grid),
                           max_features=h * w, tile_max_features=h * w,
                           tile_max_candidates=h * w)
    assert isinstance(td, TiledDiagram)
    assert not bool(td.tile_overflow) and not bool(td.merge_overflow)
    # The seam merge sweeps every tile's pre-label candidates: a superset
    # of the whole image's.
    assert int(td.diagram.n_candidates) == int(np.sum(td.n_tile_cands))
    assert int(td.diagram.n_candidates) >= int(whole.n_candidates)
    for field in whole._fields:
        if field in ("overflow", "n_candidates"):
            continue
        np.testing.assert_array_equal(
            np.asarray(getattr(whole, field)),
            np.asarray(getattr(td.diagram, field)),
            err_msg=f"grid={grid} field={field}")


# ---------------------------------------------------------------------------
# Property-based equivalence (shapes drawn from a small pool to bound
# compile count; every draw still exercises a distinct image)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.sampled_from([(8, 8), (12, 8), (8, 12), (12, 12)]),
       st.sampled_from([(1, 1), (2, 2), (4, 2), (2, 4), (4, 4)]),
       st.integers(0, 2 ** 31 - 1))
def test_tiled_matches_whole_gaussian(shape, grid, seed):
    img = np.random.default_rng(seed).normal(
        size=shape).astype(np.float32)
    assert_tiled_equal(img, grid)


@settings(max_examples=16, deadline=None)
@given(st.sampled_from([(8, 8), (12, 12)]),
       st.sampled_from([(2, 2), (4, 4)]),
       st.integers(0, 2 ** 31 - 1), st.integers(2, 4))
def test_tiled_matches_whole_heavy_ties(shape, grid, seed, levels):
    """Tiny integer range => massive (value) ties: the per-tile local rank
    must still reproduce the global (value, index) total order exactly."""
    img = np.random.default_rng(seed).integers(
        0, levels, size=shape).astype(np.float32)
    assert_tiled_equal(img, grid)


def test_tiled_int_dtype():
    img = np.random.default_rng(3).integers(
        0, 50, size=(12, 8)).astype(np.int32)
    assert_tiled_equal(img, (3, 2))


def test_tiled_matches_fused_kernel_whole_image():
    """The tiled path (shared stages: keyed_steepest_pointers +
    resolve_labels with the halo frozen) must equal the whole-image fused
    phase-A kernel route, including through the Pallas interpret backend.
    """
    import jax.numpy as jnp
    from repro.core.tiling import TiledDiagram, tiled_pixhomology
    img = np.random.default_rng(13).normal(size=(12, 12)).astype(np.float32)
    whole = pixhomology(jnp.asarray(img), max_features=144,
                        max_candidates=144, phase_a_impl="fused",
                        strip_rows=4, use_pallas=True, interpret=True)
    td = tiled_pixhomology(jnp.asarray(img), grid=(3, 3), max_features=144,
                           tile_max_features=144, tile_max_candidates=144)
    assert isinstance(td, TiledDiagram)
    assert int(td.diagram.n_candidates) >= int(whole.n_candidates)
    for field in whole._fields:
        if field in ("overflow", "n_candidates"):
            continue
        np.testing.assert_array_equal(
            np.asarray(getattr(whole, field)),
            np.asarray(getattr(td.diagram, field)), err_msg=field)


# ---------------------------------------------------------------------------
# Basins and merge saddles spanning 3+ tiles
# ---------------------------------------------------------------------------

def test_basin_spanning_all_tiles_monotone_ramp():
    """One basin covering every tile: every chain exits through seams."""
    img = np.arange(16 * 16, dtype=np.float32).reshape(16, 16)
    assert_tiled_equal(img, (4, 4))


def test_ridge_crossing_tile_rows():
    """A single ridge basin crossing a 4x4 grid horizontally, with noise
    maxima merging into it across seams."""
    rng = np.random.default_rng(0)
    yy, xx = np.mgrid[0:16, 0:16].astype(np.float32)
    img = -((yy - 8) ** 2) * 0.1 + xx * 0.01 \
        + rng.normal(scale=1e-3, size=(16, 16)).astype(np.float32)
    assert_tiled_equal(img, (4, 4))


def test_constant_image_pure_tiebreak():
    """All-equal values: label resolution and the single essential class
    are decided purely by the global-index tie-break across tiles."""
    assert_tiled_equal(np.zeros((12, 12), np.float32), (3, 3))


def test_two_blobs_saddle_on_seam():
    """Two maxima in different tiles whose merge saddle sits on the tile
    boundary column — the death must come from a seam edge."""
    yy, xx = np.mgrid[0:8, 0:16].astype(np.float32)
    img = (2.0 * np.exp(-((yy - 4) ** 2 + (xx - 3) ** 2) / 6.0)
           + 1.5 * np.exp(-((yy - 4) ** 2 + (xx - 12) ** 2) / 6.0))
    img += np.random.default_rng(1).normal(
        scale=1e-4, size=img.shape).astype(np.float32)
    assert_tiled_equal(img, (1, 2))   # seam at column 8, between the blobs
    assert_tiled_equal(img, (2, 2))


def test_tiled_truncation_matches_whole():
    rng = np.random.default_rng(5)
    img = rng.normal(size=(12, 12)).astype(np.float32)
    for tv in (-0.5, 0.3):
        assert_tiled_equal(img, (3, 3), tv=tv)


def test_degenerate_tiles():
    rng = np.random.default_rng(6)
    assert_tiled_equal(np.array([[3.5]], np.float32), (1, 1))
    assert_tiled_equal(rng.normal(size=(2, 2)).astype(np.float32), (2, 2))
    assert_tiled_equal(rng.normal(size=(1, 8)).astype(np.float32), (1, 4))


# ---------------------------------------------------------------------------
# Grid selection / validation
# ---------------------------------------------------------------------------

def test_validate_grid_rejects_nondividing():
    with pytest.raises(ValueError):
        validate_grid((12, 12), (5, 2))
    with pytest.raises(ValueError):
        validate_grid((12, 12), (0, 2))


def test_choose_grid_respects_budget_and_divides():
    h, w = 96, 64
    gr, gc = choose_grid((h, w), max_tile_pixels=1024)
    assert h % gr == 0 and w % gc == 0
    assert (h // gr) * (w // gc) <= 1024
    assert choose_grid((64, 64), max_tile_pixels=64 * 64) == (1, 1)


def test_tilespec_validation_and_json_roundtrip():
    with pytest.raises(ValueError):
        TileSpec(halo=2)
    with pytest.raises(ValueError):
        TileSpec(grid=(0, 2))
    with pytest.raises(ValueError):
        TileSpec(max_features_per_tile=0)
    cfg = PHConfig(tile=TileSpec(grid=(2, 2), max_features_per_tile=64))
    back = PHConfig.from_json(cfg.to_json())
    assert back == cfg and back.tile.grid == (2, 2)
    # TileSpec participates in the plan key
    assert PHConfig().plan_key() != cfg.plan_key()
    assert {cfg: 1}[cfg] == 1    # still hashable


# ---------------------------------------------------------------------------
# Engine: two-level overflow regrow (per tile AND seam merge)
# ---------------------------------------------------------------------------

def test_run_tiled_regrows_tile_capacities_to_oracle_equal():
    img = np.random.default_rng(2).normal(size=(16, 16)).astype(np.float32)
    engine = PHEngine(PHConfig(
        max_features=512,
        tile=TileSpec(grid=(4, 4), max_features_per_tile=1,
                      max_candidates_per_tile=1)))
    res = engine.run_tiled(img)
    assert res.regrow.attempts >= 1 and not res.regrow.overflow
    np.testing.assert_array_equal(res.to_array(), persistence_oracle(img))
    assert res.config.tile.max_features_per_tile > 1
    assert any(r["kind"] == "tiled" for r in engine.regrow_log)


def test_run_tiled_regrows_seam_merge_capacity():
    """Global diagram rows undersized while tiles are fine: only
    max_features must regrow (the seam-merge level)."""
    img = np.random.default_rng(4).normal(size=(16, 16)).astype(np.float32)
    engine = PHEngine(PHConfig(
        max_features=2,
        tile=TileSpec(grid=(2, 2), max_features_per_tile=256,
                      max_candidates_per_tile=256)))
    res = engine.run_tiled(img)
    assert res.regrow.attempts >= 1 and not res.regrow.overflow
    assert res.config.max_features > 2
    assert res.config.tile.max_features_per_tile == 64   # clamped, untouched
    np.testing.assert_array_equal(res.to_array(), persistence_oracle(img))


def test_run_tiled_regrow_sticky_and_plan_cached():
    img = np.random.default_rng(8).normal(size=(12, 12)).astype(np.float32)
    engine = PHEngine(PHConfig(
        max_features=4, tile=TileSpec(grid=(3, 3), max_features_per_tile=2,
                                      max_candidates_per_tile=2)))
    r1 = engine.run_tiled(img)
    assert r1.regrow.attempts >= 1
    r2 = engine.run_tiled(img)
    assert r2.regrow.attempts == 0
    stats = engine.plan_stats()
    assert stats["hits"] >= 1          # the regrown plan was reused

    small = PHEngine(PHConfig(max_features=256, tile=TileSpec(
        grid=(3, 3), max_features_per_tile=16, max_candidates_per_tile=32)))
    small.run_tiled(img)
    small.run_tiled(img.copy())
    assert small.plan_stats()["traces"] == 1


def test_run_tiled_respects_max_regrows():
    img = np.random.default_rng(9).normal(size=(16, 16)).astype(np.float32)
    engine = PHEngine(PHConfig(
        max_features=512, max_regrows=1,
        tile=TileSpec(grid=(4, 4), max_features_per_tile=1,
                      max_candidates_per_tile=1)))
    res = engine.run_tiled(img)
    assert res.regrow.attempts == 1
    assert res.regrow.overflow          # still undersized, reported


def test_run_tiled_honors_regrow_ceilings():
    img = np.random.default_rng(10).normal(size=(16, 16)).astype(np.float32)
    engine = PHEngine(PHConfig(
        max_features=2, max_candidates=8,
        regrow_features_ceiling=4, regrow_candidates_ceiling=8,
        tile=TileSpec(grid=(2, 2), max_features_per_tile=1,
                      max_candidates_per_tile=1)))
    res = engine.run_tiled(img)
    assert res.config.max_features <= 4
    assert res.config.tile.max_features_per_tile <= 4
    assert res.config.tile.max_candidates_per_tile <= 8
    assert res.regrow.overflow          # capped below need, reported


def test_run_tiled_rejects_paper_mode():
    engine = PHEngine(PHConfig(candidate_mode="paper"))
    with pytest.raises(ValueError):
        engine.run_tiled(np.zeros((4, 4), np.float32))


# ---------------------------------------------------------------------------
# num_candidates (capacity planning satellite)
# ---------------------------------------------------------------------------

def test_num_candidates_forwards_backend_and_engine_exposes_it():
    from repro.core import num_candidates
    img = np.random.default_rng(1).normal(size=(10, 10)).astype(np.float32)
    k_default = int(num_candidates(jnp.asarray(img)))
    k_ref = int(num_candidates(jnp.asarray(img), use_pallas=False))
    assert k_default == k_ref > 0
    engine = PHEngine(PHConfig(use_pallas=False))
    assert engine.num_candidates(img) == k_ref
    # threshold filtering matches the core helper
    assert engine.num_candidates(img, truncate_value=np.max(img)) <= k_ref


# ---------------------------------------------------------------------------
# Distributed: sharded tiles + pipeline routing of oversized images
# ---------------------------------------------------------------------------

def test_run_tiled_sharded_ctx_bit_identical():
    from repro.distributed.context import single_device_ctx
    img = np.random.default_rng(11).normal(size=(12, 12)).astype(np.float32)
    engine = PHEngine(PHConfig(max_features=256, tile=TileSpec(
        grid=(2, 2), max_features_per_tile=64, max_candidates_per_tile=64)))
    res = engine.run_tiled(img, ctx=single_device_ctx())
    np.testing.assert_array_equal(res.to_array(), persistence_oracle(img))


def test_pipeline_routes_oversized_images_through_tiles():
    engine = PHEngine(PHConfig(
        max_features=4096, filter_level="filter_std",
        tile=TileSpec(grid=(2, 2), max_features_per_tile=1024,
                      max_candidates_per_tile=2048,
                      max_tile_pixels=32 * 32)))
    assert engine.should_tile(64 * 64) and not engine.should_tile(32 * 32)
    res = engine.run_distributed([0, 1], image_size=64)
    assert len(res.diagrams) == 2
    assert all(not d["overflow"] for d in res.diagrams.values())
    # the tiled summaries match a whole-image engine bit-for-bit (at the
    # tile-budget-sampled Variant-2 threshold the streaming path uses)
    from repro.data import astro
    whole = PHEngine(PHConfig(max_features=4096,
                              filter_level="filter_std"))
    img = astro.generate_image(0, 64)
    t = astro.AstroImage(0, 64).filter_threshold("filter_std", sample=32)
    want = whole.run(img, t)
    assert res.diagrams[0]["count"] == int(want.diagram.count)
    np.testing.assert_allclose(
        res.diagrams[0]["top_births"],
        np.asarray(want.diagram.birth[:5], np.float64))


def test_run_tiled_accepts_provider_and_staged_tiles():
    """The streaming entry points: a tile provider (windowed loading) and
    pre-staged tile stacks must both be bit-identical to the whole-image
    array path, including p_birth/p_death."""
    from repro.core.tiling import load_tile_stacks
    from repro.data import astro
    engine = PHEngine(PHConfig(max_features=4096, tile=TileSpec(
        grid=(2, 2), max_features_per_tile=1024,
        max_candidates_per_tile=2048)))
    prov = astro.AstroImage(9, 48)
    img = astro.generate_image(9, 48)
    want = engine.run_tiled(img)
    got_prov = engine.run_tiled(prov)
    staged = load_tile_stacks(prov, (2, 2))
    assert staged.shape == (48, 48) and staged.grid == (2, 2)
    got_staged = engine.run_tiled(staged)
    for name, res in (("provider", got_prov), ("staged", got_staged)):
        for field in want.diagram._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(want.diagram, field)),
                np.asarray(getattr(res.diagram, field)),
                err_msg=f"{name}:{field}")


def test_run_tiled_provider_derives_threshold_and_staged_requires_it():
    from repro.data import astro
    engine = PHEngine(PHConfig(
        max_features=4096, filter_level="filter_std",
        tile=TileSpec(grid=(2, 2), max_features_per_tile=1024,
                      max_candidates_per_tile=2048)))
    prov = astro.AstroImage(3, 48)
    res = engine.run_tiled(prov)           # threshold from the provider
    t = prov.filter_threshold("filter_std")
    assert res.threshold == t
    want = engine.run_tiled(astro.generate_image(3, 48), t)
    np.testing.assert_array_equal(res.to_array(), want.to_array())

    class NoThreshold:
        shape = (48, 48)
        dtype = np.float32

        def halo_tile(self, t, grid, fill=-np.inf):
            return prov.halo_tile(t, grid, fill=fill)

    with pytest.raises(ValueError):
        engine.run_tiled(NoThreshold())


def test_halo_gidx_tile_matches_split():
    from repro.core.tiling import halo_gidx_tile, split_tiles
    h, w, grid = 24, 36, (2, 3)
    gidx2d = jnp.arange(h * w, dtype=jnp.int32).reshape(h, w)
    ref = np.asarray(split_tiles(gidx2d, grid, jnp.int32(-1)))
    for t in range(6):
        np.testing.assert_array_equal(halo_gidx_tile((h, w), grid, t),
                                      ref[t], err_msg=f"tile {t}")


# ---------------------------------------------------------------------------
# Cost model: per-tile working memory ~ tile size, not image size
# ---------------------------------------------------------------------------

def test_per_tile_memory_scales_with_tile_not_image():
    tile = (16, 16)
    small = per_tile_cost(tile, jnp.float32, n_tiles=4,
                          tile_max_features=64, tile_max_candidates=64)
    big = per_tile_cost(tile, jnp.float32, n_tiles=64,
                        tile_max_features=64, tile_max_candidates=64)
    # Phase A is strictly tile-local: byte-identical across image sizes.
    assert small["phase_a"] == big["phase_a"]
    # Phase B adds only the O(boundary) condensation table.
    extra = big["phase_b"]["peak_bytes_est"] \
        - small["phase_b"]["peak_bytes_est"]
    table_bytes = (big["table_entries"] - small["table_entries"]) * 4 * 2
    assert extra <= 2 * table_bytes
    # And a 16x-area whole image costs far more than its per-tile program.
    whole = per_tile_cost((64, 64), jnp.float32, n_tiles=1,
                          tile_max_features=64, tile_max_candidates=64)
    assert whole["phase_a"]["peak_bytes_est"] \
        > 4 * big["phase_a"]["peak_bytes_est"]
