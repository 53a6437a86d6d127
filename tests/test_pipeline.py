"""Distributed PH pipeline: scheduling, fault tolerance, work-log resume,
shape-bucketed heterogeneous rounds, prefetch overlap, tile streaming."""
import json

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.data import astro
from repro.distributed.context import single_device_ctx
from repro.ph import FilterLevel, PHConfig, PHEngine, TileSpec
from repro.pipeline.driver import FailureInjector, run_pipeline
from repro.pipeline.executor import ShardedPHExecutor
from repro.pipeline.scheduler import (BucketRound, ImageMeta, bucket_shape,
                                      make_bucketed_schedule, make_schedule,
                                      normalize_images, part_executors,
                                      part_images, part_lpt)


# ---------------------------------------------------------------------------
# Scheduler properties
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.integers(1, 60), st.integers(1, 12), st.integers(0, 2 ** 20))
def test_schedules_cover_all_images_exactly_once(n, m, seed):
    rng = np.random.default_rng(seed)
    ids = list(range(n))
    costs = {i: float(rng.uniform(1, 100)) for i in ids}
    for strat in ("part_executors", "part_images", "part_LPT"):
        sched = make_schedule(strat, ids, m, costs, seed=seed)
        flat = [i for q in sched.queues for i in q]
        assert sorted(flat) == ids, strat
        assert len(sched.queues) == m


@settings(max_examples=40, deadline=None)
@given(st.integers(4, 80), st.integers(2, 10), st.integers(0, 2 ** 20))
def test_lpt_beats_or_matches_static_on_skewed_costs(n, m, seed):
    """Paper fig 6: LPT's queue makespan <= static chunking, and is within
    the Graham 4/3 bound of the lower bound."""
    rng = np.random.default_rng(seed)
    ids = list(range(n))
    # heavy-tailed costs => stragglers exist
    costs = {i: float(rng.pareto(1.5) + 0.1) for i in ids}
    lpt = part_lpt(ids, m, costs).queue_makespan(costs)
    static = part_executors(ids, m, seed=seed).queue_makespan(costs)
    dynamic = part_images(ids, m, costs).queue_makespan(costs)
    lower = max(max(costs.values()), sum(costs.values()) / m)
    # Graham's theorems: LPT within 4/3 - 1/(3m) of OPT (>= lower bound);
    # greedy list scheduling within 2 - 1/m.
    assert lpt <= (4 / 3 - 1 / (3 * m)) * lower + 1e-6
    assert dynamic <= (2 - 1 / m) * lower + 1e-6
    # static is a valid schedule, so it can never beat the lower bound
    assert static >= lower - 1e-9
    assert lpt <= static * (4 / 3) + 1e-6


def test_lpt_beats_static_on_strong_skew():
    """Deterministic instance with a straggler: LPT clearly wins (fig 6)."""
    costs = {i: 1.0 for i in range(32)}
    costs[0] = 30.0
    ids = list(costs)
    m = 8
    lpt = part_lpt(ids, m, costs).queue_makespan(costs)
    static = np.mean([part_executors(ids, m, seed=s).queue_makespan(costs)
                      for s in range(10)])
    assert lpt == 30.0               # straggler isolated on its own executor
    assert static > lpt + 1.0        # chunking stacks work behind it


def test_lpt_requires_costs():
    with pytest.raises(ValueError):
        make_schedule("part_LPT", [1, 2], 2, None)
    with pytest.raises(ValueError):
        make_bucketed_schedule("part_LPT",
                               [ImageMeta(0, (8, 8))], 2, None)


# ---------------------------------------------------------------------------
# Shape-bucketed scheduling (heterogeneous datasets)
# ---------------------------------------------------------------------------

def _random_workload(rng, n, sizes=(64, 96, 128, 256, 512)):
    metas = [ImageMeta(i, (int(rng.choice(sizes)),) * 2) for i in range(n)]
    costs = {meta.image_id: meta.pixels * float(rng.uniform(0.2, 3.0))
             for meta in metas}
    return metas, costs


@settings(max_examples=60, deadline=None)
@given(st.integers(1, 24), st.integers(1, 8), st.integers(0, 2 ** 20))
def test_bucketed_schedule_covers_all_images_exactly_once(n, m, seed):
    rng = np.random.default_rng(seed)
    metas, costs = _random_workload(rng, n)
    for strat in ("part_executors", "part_images", "part_LPT"):
        for pad in (True, False):
            sched = make_bucketed_schedule(
                strat, metas, m, costs, rounding="pow2", pad=pad,
                max_tile_pixels=256 * 256, seed=seed)
            got = sorted(i for r in sched.rounds() for i in r.image_ids)
            assert got == list(range(n)), (strat, pad)
            for r in sched.rounds():
                assert len(r.entries) <= (m if r.kind == "whole" else 1)
                slots = [s for s, _ in r.entries]
                assert len(set(slots)) == len(slots)
                if r.kind == "whole":
                    for _, meta in r.entries:
                        assert meta.shape[0] <= r.shape[0]
                        assert meta.shape[1] <= r.shape[1]
                        if not pad:
                            assert tuple(meta.shape) == tuple(r.shape)
                else:
                    assert r.entries[0][1].pixels > 256 * 256


@settings(max_examples=60, deadline=None)
@given(st.integers(1, 24), st.integers(1, 8), st.integers(0, 2 ** 20))
def test_bucketed_lpt_beats_padded_part_images(n, m, seed):
    """The satellite property: on random heterogeneous workloads, the
    bucketed-LPT lockstep makespan never exceeds what shape-agnostic
    ``part_images`` pays once every image is padded to the global bucket
    (the only way a one-plan SPMD pipeline can run a mixed set)."""
    rng = np.random.default_rng(seed)
    metas, costs = _random_workload(rng, n)
    sched = make_bucketed_schedule("part_LPT", metas, m, costs,
                                   rounding="pow2", pad=True)
    base = make_schedule("part_images",
                         [meta.image_id for meta in metas], m, costs)
    pad_shape = bucket_shape(
        (max(meta.shape[0] for meta in metas),
         max(meta.shape[1] for meta in metas)), "pow2")
    baseline = base.padded_makespan(
        costs, {meta.image_id: meta for meta in metas}, pad_shape)
    assert sched.makespan(costs) <= baseline * (1 + 1e-9)


def test_bucketed_rounds_are_homogeneous_per_plan():
    """Every whole round carries exactly one padded shape (one compiled
    plan per round), and vanilla (pad=False) never mixes shapes at all."""
    metas = [ImageMeta(0, (64, 64)), ImageMeta(1, (96, 96)),
             ImageMeta(2, (64, 64)), ImageMeta(3, (128, 128))]
    costs = {i: float(metas[i].pixels) for i in range(4)}
    sched = make_bucketed_schedule("part_LPT", metas, 2, costs, pad=False)
    shapes = [r.shape for r in sched.rounds()]
    assert shapes == sorted(shapes, key=lambda s: -s[0] * s[1])
    for r in sched.rounds():
        assert {meta.shape for _, meta in r.entries} == {r.shape}


def test_normalize_images_accepts_mixed_specs():
    metas = normalize_images(
        [0, (1, 96), (2, (64, 48)), ImageMeta(3, (32, 32))],
        default_size=128)
    assert [meta.shape for meta in metas] == [
        (128, 128), (96, 96), (64, 48), (32, 32)]
    with pytest.raises(ValueError):
        normalize_images([0, (0, 64)])


# ---------------------------------------------------------------------------
# Driver: fault tolerance + resume
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pool():
    engine = PHEngine(PHConfig(max_features=2048, max_candidates=8192,
                               filter_level=FilterLevel.STD))
    return ShardedPHExecutor(engine, single_device_ctx(), image_size=128)


def test_pipeline_completes_and_counts_objects(pool):
    res = run_pipeline(pool, list(range(4)), strategy="part_LPT")
    assert len(res.diagrams) == 4
    for d in res.diagrams.values():
        assert d["count"] > 0 and not d["overflow"]


def test_failure_recovery(pool):
    inj = FailureInjector([0])       # first round dies once
    res = run_pipeline(pool, list(range(3)), strategy="part_images",
                       failure_injector=inj)
    assert res.failures == 1
    assert len(res.diagrams) == 3    # everything still computed


def test_exhausted_retries_chain_the_device_error(pool, monkeypatch):
    """A non-injected failure (a compile error raised from the executor)
    is retried like an injected one, and once the retries are exhausted
    the final error carries it as its cause."""
    import jax

    def refuse(staged):
        raise jax.errors.JaxRuntimeError(
            "INTERNAL: Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(pool, "run_staged", refuse)
    with pytest.raises(RuntimeError, match="could not finish") as info:
        run_pipeline(pool, [0, 1], max_retries=2)
    cause = info.value.__cause__
    assert isinstance(cause, jax.errors.JaxRuntimeError)
    assert "Mosaic failed to compile" in str(cause)


def test_worklog_resume(tmp_path, pool):
    log = tmp_path / "work.jsonl"
    res1 = run_pipeline(pool, [0, 1], work_log=log)
    assert len(res1.diagrams) == 2
    lines_before = log.read_text().count("\n")
    # Second run with a superset: already-done images are NOT recomputed.
    res2 = run_pipeline(pool, [0, 1, 2], work_log=log)
    assert len(res2.diagrams) == 3
    new_lines = log.read_text().count("\n") - lines_before
    assert new_lines == 1            # only image 2 was processed


def test_pipeline_results_deterministic(pool):
    r1 = run_pipeline(pool, [5, 6], strategy="part_executors")
    r2 = run_pipeline(pool, [5, 6], strategy="part_LPT")
    for i in (5, 6):                 # schedule must not change the math
        assert r1.diagrams[i]["top_births"] == r2.diagrams[i]["top_births"]
        assert r1.diagrams[i]["count"] == r2.diagrams[i]["count"]


def test_executor_costs_are_threaded_not_recomputed(pool, monkeypatch):
    """Satellite: the driver uses pool.estimate_costs (measured Variant-3
    costs after a load), not a private estimate_cost_from_id pass."""
    meta = ImageMeta(31, (32, 32))
    est = pool.estimate_costs([meta])[31]
    assert est == astro.estimate_cost_from_id(31, 32)   # nothing loaded yet
    run_pipeline(pool, [(31, 32)])
    measured = pool.estimate_costs([meta])[31]
    img = astro.generate_image(31, 32)
    assert measured == astro.estimate_cost(img, pool.engine.config.filter_level)
    assert measured != est
    # and the driver consults the pool, so a re-run sees measured costs
    calls = []
    orig = pool.estimate_costs
    monkeypatch.setattr(pool, "estimate_costs",
                        lambda metas: calls.append(1) or orig(metas))
    run_pipeline(pool, [(31, 32)])
    assert calls
    # shapes the astro loader cannot render fail at schedule time, not
    # mid-round on the prefetch thread
    monkeypatch.undo()
    with pytest.raises(ValueError):
        pool.estimate_costs([ImageMeta(40, (64, 48))])


# ---------------------------------------------------------------------------
# Heterogeneous end-to-end: padded buckets bit-identical per image
# ---------------------------------------------------------------------------

def _assert_rows_equal(got, want, f=None):
    """All valid diagram rows (and scalars) bit-equal; row arrays may have
    different capacities, so compare the common prefix past count."""
    assert int(got.count) == int(want.count)
    assert int(got.n_unmerged) == int(want.n_unmerged)
    k = min(got.birth.shape[0], want.birth.shape[0]) if f is None else f
    for field in ("birth", "death", "p_birth", "p_death"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field))[:k],
            np.asarray(getattr(want, field))[:k], err_msg=field)


def test_padded_round_bit_identical_to_unpadded(pool):
    """A 24x24 image computed inside a 32x32 bucket (pad + index remap +
    essential fixup) must equal the plain whole-image run on every field,
    including p_birth/p_death in unpadded coordinates."""
    import jax.numpy as jnp
    from repro.core import pixhomology
    meta = ImageMeta(7, (24, 24))
    staged = pool.load_round(BucketRound("whole", (32, 32), ((0, meta),)))
    got = pool.run_staged(staged)[7]
    img = astro.generate_image(7, 24)
    t, _ = astro.filter_threshold(img, "filter_std")
    want = pixhomology(jnp.asarray(img), t, max_features=2048,
                       max_candidates=8192)
    assert not bool(np.asarray(want.overflow))
    _assert_rows_equal(got, want)


def test_hetero_pipeline_matches_per_image_runs():
    """Mixed 24/32/48 set end-to-end: per-image summaries equal dedicated
    per-image engine runs, padded rounds and all."""
    import jax.numpy as jnp
    from repro.core import pixhomology
    engine = PHEngine(PHConfig(max_features=2048, max_candidates=8192,
                               filter_level=FilterLevel.STD))
    pool = ShardedPHExecutor(engine, single_device_ctx())
    res = run_pipeline(pool, [(0, 24), (1, 32), (2, 48), (3, 24)])
    assert len(res.diagrams) == 4
    for img_id, size in ((0, 24), (1, 32), (2, 48), (3, 24)):
        img = astro.generate_image(img_id, size)
        t, _ = astro.filter_threshold(img, "filter_std")
        want = pixhomology(jnp.asarray(img), t, max_features=2048,
                           max_candidates=8192)
        c = int(want.count)
        assert res.diagrams[img_id]["count"] == c
        np.testing.assert_array_equal(
            res.diagrams[img_id]["top_births"],
            np.asarray(want.birth[:5], np.float64))
        np.testing.assert_array_equal(
            res.diagrams[img_id]["top_deaths"],
            np.asarray(want.death[:5], np.float64))


def test_vanilla_hetero_uses_exact_buckets():
    """Without a finite threshold padding is not exact, so VANILLA runs
    must keep every shape in its own (unpadded) round — and still match
    dedicated vanilla per-image runs."""
    import jax.numpy as jnp
    from repro.core import pixhomology
    engine = PHEngine(PHConfig(max_features=2048, max_candidates=8192))
    pool = ShardedPHExecutor(engine, single_device_ctx())
    assert not pool.pad_ok
    res = run_pipeline(pool, [(0, 24), (1, 32)])
    assert res.rounds == 2           # one exact-shape round each
    for img_id, size in ((0, 24), (1, 32)):
        img = astro.generate_image(img_id, size)
        want = pixhomology(jnp.asarray(img), max_features=2048,
                           max_candidates=8192)
        assert res.diagrams[img_id]["count"] == int(want.count)
        np.testing.assert_array_equal(
            res.diagrams[img_id]["top_deaths"],
            np.asarray(want.death[:5], np.float64))


# ---------------------------------------------------------------------------
# Tiled rounds: streaming residency, fault injection, resume, prefetch
# ---------------------------------------------------------------------------

def _tiled_engine(**kw):
    kw.setdefault("max_features", 4096)
    kw.setdefault("filter_level", "filter_std")
    return PHEngine(PHConfig(tile=TileSpec(
        grid=(2, 2), max_features_per_tile=1024,
        max_candidates_per_tile=2048, max_tile_pixels=32 * 32), **kw))


def test_oversized_images_stream_without_whole_image_loads(monkeypatch):
    """Residency: an image above max_tile_pixels goes through the
    tile-provider path — generate_image is never called for it, and no
    window larger than one halo tile is ever materialized."""
    engine = _tiled_engine()
    whole_calls = []
    windows = []
    orig_img = astro.generate_image
    orig_win = astro.generate_window

    def spy_img(image_id, size=1024, **kw):
        whole_calls.append(image_id)
        return orig_img(image_id, size, **kw)

    def spy_win(image_id, r0, c0, h, w, **kw):
        windows.append((image_id, h * w))
        return orig_win(image_id, r0, c0, h, w, **kw)

    monkeypatch.setattr(astro, "generate_image", spy_img)
    monkeypatch.setattr(astro, "generate_window", spy_win)
    res = engine.run_distributed([(0, 24), (1, 32), (2, 64)])
    assert len(res.diagrams) == 3
    assert 2 not in whole_calls          # never whole-materialized
    tile_px = (64 // 2 + 2) * (64 // 2 + 2)
    assert windows                       # the tiled image loaded via windows
    assert max(px for i, px in windows if i == 2) <= tile_px


def test_tiled_result_matches_whole_image_at_same_threshold():
    engine = _tiled_engine()
    res = engine.run_distributed([(2, 64)])
    prov = astro.AstroImage(2, 64)
    # the executor samples the Variant-2 statistic at the tile budget
    t = prov.filter_threshold("filter_std", sample=32)
    whole = PHEngine(PHConfig(max_features=4096,
                              filter_level="filter_std"))
    want = whole.run(astro.generate_image(2, 64), t)
    assert res.diagrams[2]["count"] == int(want.diagram.count)
    np.testing.assert_array_equal(
        res.diagrams[2]["top_births"],
        np.asarray(want.diagram.birth[:5], np.float64))
    np.testing.assert_array_equal(
        res.diagrams[2]["top_deaths"],
        np.asarray(want.diagram.death[:5], np.float64))


def test_tiled_round_failure_recovery_and_worklog_resume(tmp_path):
    """Satellite: FailureInjector + work-log resume through *tiled* rounds
    (the schedule here is one whole round + one tiled round)."""
    engine = _tiled_engine()
    log = tmp_path / "tiled.jsonl"
    inj = FailureInjector([0, 1])    # both rounds die once each
    res = engine.run_distributed([(0, 32), (2, 64)], work_log=log,
                                 failure_injector=inj)
    assert res.failures == 2
    assert len(res.diagrams) == 2
    lines = [json.loads(l) for l in log.read_text().splitlines()]
    assert sorted(r["image_id"] for r in lines) == [0, 2]
    # resume: a superset run recomputes nothing already logged
    engine2 = _tiled_engine()
    res2 = engine2.run_distributed([(0, 32), (2, 64), (3, 32)],
                                   work_log=log)
    assert len(res2.diagrams) == 3
    lines2 = log.read_text().splitlines()
    assert len(lines2) - len(lines) == 1
    assert json.loads(lines2[-1])["image_id"] == 3
    # and the resumed summaries are the logged ones, bit for bit
    assert res2.diagrams[2] == res.diagrams[2]


def test_run_round_tiled_dedupes_any_identical_row():
    """Satellite: duplicate padded rows are computed once wherever they
    appear in the round, not only when consecutive."""
    engine = _tiled_engine()
    pool = ShardedPHExecutor(engine, single_device_ctx(), image_size=64)
    a = astro.generate_image(0, 64)
    b = astro.generate_image(1, 64)
    imgs = np.stack([a, b, a, b, a])          # non-consecutive duplicates
    t0, _ = astro.filter_threshold(a, "filter_std")
    t1, _ = astro.filter_threshold(b, "filter_std")
    tvals = np.asarray([t0, t1, t0, t1, t0], np.float32)
    calls = []
    orig = engine.run_tiled

    def spy(image, tv=None, **kw):
        calls.append(1)
        return orig(image, tv, **kw)

    engine.run_tiled = spy
    try:
        diags = pool._run_round_tiled(imgs, tvals)
    finally:
        engine.run_tiled = orig
    assert len(calls) == 2                    # one run per distinct image
    for i, j in ((0, 2), (0, 4), (1, 3)):
        for field in ("birth", "death", "p_birth", "p_death", "count"):
            np.testing.assert_array_equal(
                np.asarray(getattr(diags, field))[i],
                np.asarray(getattr(diags, field))[j], err_msg=field)
    # distinct rows stay distinct
    assert not np.array_equal(diags.p_birth[0], diags.p_birth[1])


def test_prefetch_and_serial_loading_agree():
    """Double-buffered rounds must be a pure latency optimization: same
    diagrams with prefetch_rounds=0 and 2, heterogeneous + tiled mix."""
    images = [(0, 24), (1, 32), (2, 64), (3, 32), (4, 24)]
    results = []
    for prefetch in (0, 2):
        engine = _tiled_engine(prefetch_rounds=prefetch)
        results.append(engine.run_distributed(images).diagrams)
    assert results[0] == results[1]


# ---------------------------------------------------------------------------
# Variant 2 data + filtering
# ---------------------------------------------------------------------------

def test_astro_images_deterministic_and_filterable():
    a = astro.generate_image(3, 128)
    b = astro.generate_image(3, 128)
    np.testing.assert_array_equal(a, b)
    c = astro.generate_image(4, 128)
    assert not np.array_equal(a, c)

    dropped = {}
    for level in ("vanilla", "filter_light", "filter_std", "filter_heavy"):
        _, frac = astro.filter_threshold(a, level)
        dropped[level] = frac
    assert dropped["vanilla"] == 0.0
    assert dropped["filter_light"] <= dropped["filter_std"] <= \
        dropped["filter_heavy"]
    assert dropped["filter_heavy"] > 0.5   # background dominates star fields


def test_generate_window_bit_identical_to_image_slice():
    """The tentpole's windowed loading contract: any window equals the
    same slice of the full frame, bit for bit."""
    img = astro.generate_image(11, 96)
    for r0, c0, h, w in ((0, 0, 96, 96), (17, 5, 41, 77), (95, 0, 1, 96),
                         (30, 30, 3, 3), (0, 64, 64, 32)):
        win = astro.generate_window(11, r0, c0, h, w, size=96)
        np.testing.assert_array_equal(win, img[r0:r0 + h, c0:c0 + w],
                                      err_msg=str((r0, c0, h, w)))
    with pytest.raises(ValueError):
        astro.generate_window(11, 90, 0, 10, 10, size=96)


def test_astro_image_provider_tiles_match_split():
    """AstroImage.halo_tile == split_tiles of the full frame (incl. the
    out-of-frame -inf halo), for every tile of a 3x2 grid."""
    import jax.numpy as jnp
    from repro.core.tiling import split_tiles
    prov = astro.AstroImage(5, 48)
    img = astro.generate_image(5, 48)
    ref = np.asarray(split_tiles(jnp.asarray(img), (3, 2), -jnp.inf))
    for t in range(6):
        np.testing.assert_array_equal(prov.halo_tile(t, (3, 2)), ref[t],
                                      err_msg=f"tile {t}")


def test_truncation_preserves_above_threshold_pairs():
    """Variant 2 must not change births OR deaths above the threshold
    (table 1: 'no relevant degradation in output quality'), and must
    shrink the sequential merge sweep (the speedup mechanism)."""
    import jax.numpy as jnp
    from repro.core import num_candidates, pixhomology

    img = astro.generate_image(7, 128)
    t, frac = astro.filter_threshold(img, "filter_std")
    assert frac > 0.5
    d0 = pixhomology(jnp.asarray(img), max_features=4096,
                     max_candidates=16384)
    d1 = pixhomology(jnp.asarray(img), t, max_features=4096,
                     max_candidates=16384)
    assert not bool(d1.overflow)

    def rows(d):
        c = int(d.count)
        return np.stack([np.asarray(d.birth)[:c], np.asarray(d.death)[:c],
                         np.asarray(d.p_birth)[:c]], 1)

    r0, r1 = rows(d0), rows(d1)
    # every truncated row's birth is above t
    assert np.all(r1[:, 0] >= t)
    # rows with death >= t are bit-identical between the two runs
    keep0 = r0[r0[:, 1] >= t]
    keep1 = r1[r1[:, 1] >= t]
    np.testing.assert_array_equal(keep0, keep1)
    # births above t all survive truncation (deaths clipped at t)
    np.testing.assert_array_equal(r0[r0[:, 0] >= t][:, [0, 2]],
                                  r1[:, [0, 2]])
    # and the sequential sweep got shorter
    k0 = int(num_candidates(jnp.asarray(img)))
    k1 = int(num_candidates(jnp.asarray(img), truncate_value=t))
    assert k1 < 0.25 * k0, (k0, k1)


def test_cost_estimate_correlates_with_true_cost():
    """Variant 3: the schedule-time estimate must rank images usefully."""
    est, true = [], []
    for i in range(12):
        img = astro.generate_image(i, 128)
        est.append(astro.estimate_cost_from_id(i, 128))
        true.append(astro.estimate_cost(img))
    r = np.corrcoef(est, true)[0, 1]
    assert r > 0.5, f"cost model too weak: r={r:.2f}"
