"""Roofline analyzer: loop expansion, collective parsing, param counting."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.roofline import analysis


def test_scan_flops_expanded():
    """XLA cost_analysis counts while bodies once; our analyzer must
    multiply by the trip count."""
    def body(c, _):
        return c @ c, None

    def scanned(x):
        y, _ = jax.lax.scan(body, x, None, length=8)
        return y

    comp = jax.jit(scanned).lower(
        jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    ca = comp.cost_analysis()
    summ = analysis.analyze_hlo(comp.as_text())
    per_matmul = 2 * 128 ** 3
    assert abs(ca["flops"] - per_matmul) / per_matmul < 0.01   # XLA: once
    assert abs(summ.flops - 8 * per_matmul) / (8 * per_matmul) < 0.01
    assert summ.n_whiles == 1 and summ.unresolved_trip_counts == 0
    fl, _ = analysis.blended_totals(summ, ca["flops"],
                                    ca.get("bytes accessed", 0.0))
    assert abs(fl - 8 * per_matmul) / (8 * per_matmul) < 0.01


def test_collective_parse_synthetic_hlo():
    text = """
ENTRY %main.1 (p0: f32[256,256]) -> f32[256,256] {
  %p0 = f32[256,256]{1,0} parameter(0)
  %ag = f32[256,256]{1,0} all-gather(%p0), replica_groups={}
  %ar = f32[256,256]{1,0} all-reduce(%ag), to_apply=%add
  %a2a = f32[256,256]{1,0} all-to-all(%ar), replica_groups={}
  ROOT %cp = f32[256,256]{1,0} collective-permute(%a2a), source_target_pairs={}
}
"""
    summ = analysis.analyze_hlo(text)
    n = 256 * 256 * 4
    by = summ.coll_by_type
    assert by["all-gather"] == n
    assert by["all-reduce"] == 2 * n          # 2x ring accounting
    assert by["all-to-all"] == n
    assert by["collective-permute"] == n
    assert summ.coll_bytes == 5 * n


def test_async_collectives_counted_once():
    text = """
ENTRY %main.2 (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %ags = f32[64]{0} all-gather-start(%p0), replica_groups={}
  ROOT %agd = f32[64]{0} all-gather-done(%ags)
}
"""
    summ = analysis.analyze_hlo(text)
    assert summ.coll_bytes == 64 * 4


def test_trip_count_from_compare_constant():
    text = """
%cond (s: (s32[], f32[4])) -> pred[] {
  %s = (s32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element(%s), index=0
  %c999 = s32[] constant(999999)
  %lim = s32[] constant(7)
  ROOT %cmp = pred[] compare(%i, %lim), direction=LT
}

%bodyc (s: (s32[], f32[4])) -> (s32[], f32[4]) {
  %s = (s32[], f32[4]) parameter(0)
  %x = f32[4]{0} get-tuple-element(%s), index=1
  %ar = f32[4]{0} all-reduce(%x), to_apply=%add
  ROOT %t = (s32[], f32[4]) tuple(%s, %ar)
}

ENTRY %main.3 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %w = (s32[], f32[4]) while(%p0), condition=%cond, body=%bodyc
}
"""
    summ = analysis.analyze_hlo(text)
    # trip count must come from the compare operand (7), NOT max const 999999
    assert summ.coll_bytes == 7 * 2 * 16


def test_param_counts_match_known_sizes():
    from repro.configs.base import get_config
    qwen = analysis.total_params(get_config("qwen1_5_0_5b"))
    assert 0.35e9 < qwen < 0.7e9                 # "0.5B" class
    dbrx = analysis.total_params(get_config("dbrx_132b"))
    assert 1.15e11 < dbrx < 1.55e11              # "132B" class
    scout_total = analysis.total_params(get_config("llama4_scout_17b_a16e"))
    scout_active = analysis.active_params(
        get_config("llama4_scout_17b_a16e"))
    assert 0.9e11 < scout_total < 1.3e11         # "109B" total
    assert 1.4e10 < scout_active < 2.3e10        # "17B" active
    assert scout_active < scout_total / 3


def test_roofline_terms_and_bottleneck():
    t = analysis.roofline_terms(197e12, 819e9 * 2, 50e9)
    assert t["compute_s"] == 1.0 and t["memory_s"] == 2.0
    assert t["bottleneck"] == "memory_s"
    assert np.isclose(t["roofline_fraction"], 0.5)
