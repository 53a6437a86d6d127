"""The repro.ph facade: config validation, plan-cache reuse, auto-regrow."""
import json

import numpy as np
import pytest

from repro.core import num_candidates, persistence_oracle
from repro.data import astro
from repro.ph import FilterLevel, PHConfig, PHEngine


def _bumpy(seed=0, shape=(16, 16)):
    """Noise image with many local maxima -> many features + candidates."""
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


# ---------------------------------------------------------------------------
# PHConfig
# ---------------------------------------------------------------------------

def test_config_validation():
    with pytest.raises(ValueError):
        PHConfig(candidate_mode="nope")
    with pytest.raises(ValueError):
        PHConfig(merge_impl="bogus")
    with pytest.raises(ValueError):
        PHConfig(max_features=0)
    with pytest.raises(ValueError):
        PHConfig(dtype="float16")
    with pytest.raises(ValueError):
        PHConfig(max_features=100, regrow_features_ceiling=10)
    with pytest.raises(ValueError):
        PHConfig(filter_level="filter_extreme")


def test_config_accepts_filter_level_strings_and_enum():
    assert PHConfig(filter_level="filter_std").filter_level is FilterLevel.STD
    assert PHConfig(filter_level=FilterLevel.HEAVY).filter_level is \
        FilterLevel.HEAVY


def test_config_json_roundtrip():
    cfg = PHConfig(max_features=128, max_candidates=512,
                   candidate_mode="paper", merge_impl="boruvka",
                   filter_level=FilterLevel.LIGHT, auto_regrow=False)
    back = PHConfig.from_json(cfg.to_json())
    assert back == cfg
    assert json.loads(cfg.to_json())["filter_level"] == "filter_light"


def test_config_from_flags():
    import argparse
    ns = argparse.Namespace(max_features=64, max_candidates=256,
                            filter="filter_heavy", merge_impl="boruvka",
                            no_regrow=True)
    cfg = PHConfig.from_flags(ns)
    assert cfg.max_features == 64 and cfg.max_candidates == 256
    assert cfg.filter_level is FilterLevel.HEAVY
    assert cfg.merge_impl == "boruvka"
    assert not cfg.auto_regrow


def test_config_is_hashable_plan_key_ignores_regrow_policy():
    a = PHConfig(max_regrows=1)
    b = PHConfig(max_regrows=5)
    assert {a: 1}[a] == 1
    assert a.plan_key() == b.plan_key()


def test_config_stage_signature_keys_compiled_programs():
    with pytest.raises(ValueError):
        PHConfig(phase_a_impl="bogus")
    with pytest.raises(ValueError):
        PHConfig(strip_rows=0)
    # the stage signature selects compiled stage programs -> in the plan key
    assert PHConfig().plan_key() != \
        PHConfig(phase_a_impl="pooled").plan_key()
    assert PHConfig().plan_key() != PHConfig(strip_rows=16).plan_key()
    sig = PHConfig(phase_a_impl="fused", strip_rows=4).stage_signature()
    assert ("a", "fused", 4, None, False, "superlevel") in sig
    # filtration selects different compiled programs (key negation sites)
    assert PHConfig().plan_key() != \
        PHConfig(filtration="sublevel").plan_key()
    assert any(s[0] == "b" and "frontier" in s for s in sig)
    # pooled phase A resolves densely; fused on the compacted frontier
    assert any("dense" in s for s in
               PHConfig(phase_a_impl="pooled").stage_signature())
    cfg = PHConfig(phase_a_impl="pooled", strip_rows=32)
    assert PHConfig.from_json(cfg.to_json()) == cfg

    import argparse
    ns = argparse.Namespace(phase_a_impl="pooled", strip_rows=16)
    got = PHConfig.from_flags(ns)
    assert got.phase_a_impl == "pooled" and got.strip_rows == 16


def test_engine_stage_impls_agree_and_cache_separately():
    img = _bumpy(6, (12, 12))
    fused = PHEngine(PHConfig(max_features=256, max_candidates=256,
                              strip_rows=4))
    pooled = PHEngine(PHConfig(max_features=256, max_candidates=256,
                               phase_a_impl="pooled"))
    np.testing.assert_array_equal(fused.run(img).to_array(),
                                  pooled.run(img).to_array())
    np.testing.assert_array_equal(fused.run(img).to_array(),
                                  persistence_oracle(img))
    assert fused.num_candidates(img) == pooled.num_candidates(img)


def test_config_bucket_and_prefetch_knobs():
    with pytest.raises(ValueError):
        PHConfig(bucket_rounding="pow3")
    with pytest.raises(ValueError):
        PHConfig(prefetch_rounds=-1)
    cfg = PHConfig(bucket_rounding="exact", prefetch_rounds=3)
    back = PHConfig.from_json(cfg.to_json())
    assert back == cfg
    # bucket rounding picks compiled batch shapes -> in the plan key;
    # prefetch depth is pure host-side scheduling -> excluded.
    assert PHConfig(bucket_rounding="exact").plan_key() != \
        PHConfig(bucket_rounding="pow2").plan_key()
    assert PHConfig(prefetch_rounds=0).plan_key() == \
        PHConfig(prefetch_rounds=4).plan_key()

    import argparse
    ns = argparse.Namespace(bucket_rounding="exact", no_prefetch=True)
    cfg = PHConfig.from_flags(ns)
    assert cfg.bucket_rounding == "exact" and cfg.prefetch_rounds == 0
    ns = argparse.Namespace(prefetch_rounds=2)
    assert PHConfig.from_flags(ns).prefetch_rounds == 2


def test_astro_accepts_filter_level_enum():
    img = astro.generate_image(3, 64)
    t_str, frac_str = astro.filter_threshold(img, "filter_std")
    t_enum, frac_enum = astro.filter_threshold(img, FilterLevel.STD)
    assert t_str == t_enum and frac_str == frac_enum
    with pytest.raises(ValueError):
        astro.filter_threshold(img, "filter_bogus")


# ---------------------------------------------------------------------------
# Plan cache: the jitted callable is traced once across repeated calls
# ---------------------------------------------------------------------------

def test_plan_cache_traces_once_across_same_shape_calls():
    engine = PHEngine(PHConfig(max_features=256, max_candidates=256))
    for seed in range(4):
        engine.run(_bumpy(seed))
    stats = engine.plan_stats()
    assert stats["plans"] == 1
    assert stats["traces"] == 1          # compiled once, reused 3x
    assert stats["calls"] == 4
    assert stats["hits"] == 3 and stats["misses"] == 1


def test_plan_cache_distinct_shapes_get_distinct_plans():
    engine = PHEngine(PHConfig(max_features=256, max_candidates=256))
    engine.run(_bumpy(0, (8, 8)))
    engine.run(_bumpy(0, (8, 8)))
    engine.run(_bumpy(0, (12, 8)))
    stats = engine.plan_stats()
    assert stats["plans"] == 2 and stats["traces"] == 2


@pytest.mark.parametrize("use_pallas,interpret,tpu,want", [
    (None, False, True, "pallas"), (None, False, False, "xla"),
    (True, False, True, "pallas"), (False, False, True, "xla"),
    (None, True, False, "interpret"), (True, True, True, "interpret"),
    (False, True, False, "xla")])
def test_backend_resolution_table(monkeypatch, use_pallas, interpret, tpu,
                                  want):
    from repro.kernels import backend
    monkeypatch.setattr(backend, "on_tpu", lambda: tpu)
    assert backend.resolve("ph_phase_a", use_pallas, interpret) == want
    # A kernel Mosaic cannot lower runs its named XLA path on TPU.
    dist = "xla" if want == "pallas" else want
    assert backend.resolve("ph_distance", use_pallas, interpret) == dist


def test_backend_forced_pallas_off_tpu_is_an_error(monkeypatch):
    from repro.kernels import backend
    monkeypatch.setattr(backend, "on_tpu", lambda: False)
    with pytest.raises(ValueError, match="interpret=True"):
        backend.resolve("ph_phase_c", True, False)


def test_plan_stats_reports_resolved_impls():
    engine = PHEngine(PHConfig(max_features=256, max_candidates=256,
                               merge_impl="boruvka"))
    engine.run(_bumpy(0))
    assert engine.plan_stats()["impls"] == {"single": {
        "merge_keys": "packed", "ph_phase_a": "xla", "ph_phase_c": "xla"}}
    interp = PHEngine(PHConfig(max_features=256, max_candidates=256,
                               interpret=True))
    interp.run(_bumpy(0, (8, 8)))
    assert interp.plan_stats()["impls"]["single"]["ph_phase_a"] == \
        "interpret"


def test_batched_plan_reused():
    engine = PHEngine(PHConfig(max_features=128, max_candidates=128))
    imgs = np.stack([_bumpy(s, (10, 11)) for s in range(4)])
    r1 = engine.run_batch(imgs)
    r2 = engine.run_batch(imgs[::-1].copy())
    assert engine.plan_stats()["traces"] == 1
    np.testing.assert_array_equal(np.asarray(r1.diagram.birth)[0],
                                  np.asarray(r2.diagram.birth)[-1])


# ---------------------------------------------------------------------------
# Overflow: flag without regrow, oracle-equal diagram with regrow
# ---------------------------------------------------------------------------

def test_overflow_flag_without_regrow():
    img = _bumpy(1)
    k = int(num_candidates(img))
    assert k > 2                          # the tiny capacity truly undersizes
    engine = PHEngine(PHConfig(max_features=256, max_candidates=2,
                               auto_regrow=False))
    res = engine.run(img)
    assert bool(res.diagram.overflow)
    assert res.regrow.attempts == 0 and res.regrow.overflow
    assert engine.plan_stats()["regrows"] == 0


def test_auto_regrow_recovers_oracle_equal_diagram():
    img = _bumpy(2)
    engine = PHEngine(PHConfig(max_features=4, max_candidates=2))
    res = engine.run(img)
    assert res.regrow.attempts >= 1 and not res.regrow.overflow
    assert not bool(res.diagram.overflow)
    got = res.to_array()
    want = persistence_oracle(img)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    # the effective config records the grown capacities
    assert res.config.max_features > 4
    assert engine.plan_stats()["regrows"] == res.regrow.attempts


def test_regrow_is_sticky_across_same_shape_calls():
    engine = PHEngine(PHConfig(max_features=4, max_candidates=4))
    r1 = engine.run(_bumpy(2))
    assert r1.regrow.attempts >= 1
    r2 = engine.run(_bumpy(2))       # starts at the remembered capacity
    assert r2.regrow.attempts == 0
    assert r2.config.max_features == r1.config.max_features


def test_regrow_respects_max_regrows_and_ceiling():
    img = _bumpy(3)
    engine = PHEngine(PHConfig(max_features=2, max_candidates=2,
                               max_regrows=1))
    res = engine.run(img)
    assert res.regrow.attempts == 1
    assert res.config.max_features == 4   # one doubling only
    assert res.regrow.overflow            # still undersized, reported

    capped = PHEngine(PHConfig(max_features=4, max_candidates=4,
                               regrow_features_ceiling=8,
                               regrow_candidates_ceiling=8))
    r2 = capped.run(img)
    assert r2.config.max_features <= 8 and r2.config.max_candidates <= 8


def test_regrown_capacities_clamped_to_pixel_count():
    img = _bumpy(4, (6, 6))
    engine = PHEngine(PHConfig(max_features=1, max_candidates=1))
    res = engine.run(img)
    assert not res.regrow.overflow        # at n pixels overflow is impossible
    assert res.config.max_features <= img.size
    np.testing.assert_array_equal(res.to_array(), persistence_oracle(img))


def test_run_batch_regrows_on_any_overflow():
    imgs = np.stack([_bumpy(s) for s in range(3)])
    engine = PHEngine(PHConfig(max_features=4, max_candidates=8))
    res = engine.run_batch(imgs)
    assert res.regrow.attempts >= 1
    assert not np.any(np.asarray(res.diagram.overflow))
    for i in range(3):
        c = int(res.diagram.count[i])
        want = persistence_oracle(imgs[i])
        assert c == want.shape[0]


# ---------------------------------------------------------------------------
# run() semantics: filter level, dtype policy, explicit threshold
# ---------------------------------------------------------------------------

def test_run_applies_config_filter_level():
    img = astro.generate_image(7, 64)
    t, _ = astro.filter_threshold(img, "filter_std")
    eng_f = PHEngine(PHConfig(max_features=1024, max_candidates=4096,
                              filter_level=FilterLevel.STD))
    eng_v = PHEngine(PHConfig(max_features=1024, max_candidates=4096))
    res_f = eng_f.run(img)
    res_explicit = eng_v.run(img, truncate_value=t)
    assert res_f.threshold == pytest.approx(t)
    np.testing.assert_array_equal(res_f.to_array(), res_explicit.to_array())
    # every surviving birth is above the threshold
    assert np.all(res_f.to_array()[:, 0] >= t)


def test_int_image_fractional_threshold_not_truncated():
    # A fractional Variant-2 threshold on an integer image must not be
    # floor-cast to the image dtype (12.5 -> 12 would keep the 12-peak).
    img = np.zeros((5, 5), np.int32)
    img[1, 1] = 12
    img[3, 3] = 20
    engine = PHEngine(PHConfig(max_features=25, max_candidates=25))
    res = engine.run(img, truncate_value=12.5)
    assert int(res.diagram.count) == 1          # only the 20-peak survives
    res2 = engine.run(img, truncate_value=11.5)
    assert int(res2.diagram.count) == 2         # the 12-peak is back


def test_dtype_policy_casts_input():
    img = np.random.default_rng(0).integers(0, 50, (9, 9)).astype(np.int32)
    engine = PHEngine(PHConfig(max_features=128, max_candidates=128,
                               dtype="float32"))
    res = engine.run(img)
    assert np.asarray(res.diagram.birth).dtype == np.float32


def test_run_rejects_bad_rank():
    engine = PHEngine()
    with pytest.raises(ValueError):
        engine.run(np.zeros((2, 3, 4), np.float32))
    with pytest.raises(ValueError):
        engine.run_batch(np.zeros((3, 4), np.float32))


# ---------------------------------------------------------------------------
# Distributed entry point
# ---------------------------------------------------------------------------

def test_run_distributed_smoke_and_regrow():
    engine = PHEngine(PHConfig(max_features=16, max_candidates=16,
                               filter_level=FilterLevel.STD))
    res = engine.run_distributed([0, 1], image_size=64)
    assert len(res.diagrams) == 2
    assert all(not d["overflow"] for d in res.diagrams.values())
    assert engine.plan_stats()["regrows"] >= 1


# ---------------------------------------------------------------------------
# Persistent compile cache placement (launchers and chip_smoke.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("env_dir", [None, "/cache/from/env"])
def test_compile_cache_placement(monkeypatch, env_dir):
    import jax
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = compile_cache.setup_compile_cache()
        if env_dir is None:
            want = str(compile_cache.CHECKOUT / ".jax_cache")
            assert got == want
            assert jax.config.jax_compilation_cache_dir == want
            assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()
        else:
            # JAX reads the variable itself; nothing else is set in code.
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
