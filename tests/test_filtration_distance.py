"""Dual-filtration contract, diagram-distance kernels, NaN edge sweep.

Three test families for the filtration axis and the ``ph_distance``
kernel package:

* **Duality** — ``sublevel(x)`` must equal ``superlevel(-x)`` with every
  birth/death negated, *bit-identically*, across the full path matrix
  ({whole, batched, sharded, tiled} x {fused, xla} phase C) and as a
  seeded property sweep.  Padded dispatch keeps the identity even when
  the essential extremum sits in the padded margin (the
  filtration-aware ``pad_fixup`` bug regression).

* **Distances** — the Pallas kernel is bit-identical to the XLA
  reference (interpret mode: CI's parity path), both agree with a dense
  O(n^2) numpy re-implementation, the metric axioms hold (symmetry,
  zero diagonal, sampled triangle inequality), capacity pads are inert,
  and the engine's "distance" plan kind caches.

* **Edge cases** — NaN raises the same clear error on every public
  entry point (engine casts, core wrappers, tiled wrappers, the
  distance boundary, under *both* key encodings); ±inf is rejected at
  the engine boundary; subnormals compute correct diagrams.

Satellite: serving metrics reservoirs summarize all-zero when empty
(fresh-server snapshot) and the perf gate's percentile rule skips
degenerate (< 2 sample) windows.
"""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import persistence_oracle, pixhomology, tiled_pixhomology
from repro.kernels.ph_distance import ops as dist_ops
from repro.kernels.ph_distance import ref as dist_ref
from repro.ph import PHConfig, PHEngine, TileSpec

H = W = 16
N = H * W


def _image(seed, shape=(H, W)):
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:shape[0], 0:shape[1]].astype(np.float32)
    img = rng.normal(0.0, 0.1, shape).astype(np.float32)
    for _ in range(5):
        cy, cx = rng.uniform(0, shape[0]), rng.uniform(0, shape[1])
        img += rng.uniform(0.5, 2.0) * np.exp(
            -((yy - cy) ** 2 + (xx - cx) ** 2) / 6.0).astype(np.float32)
    return img


def _config(filtration, **kw):
    kw.setdefault("max_features", N)
    kw.setdefault("max_candidates", N)
    kw.setdefault("strip_rows", 4)
    kw.setdefault("tile", TileSpec(grid=(2, 2)))
    return PHConfig(filtration=filtration, **kw)


def _assert_dual(sub, sup, msg):
    """sublevel diagram == superlevel diagram of the negated image with
    births/deaths negated — bit-for-bit, positions included."""
    np.testing.assert_array_equal(np.asarray(sub.birth),
                                  -np.asarray(sup.birth), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(sub.death),
                                  -np.asarray(sup.death), err_msg=msg)
    for f in ("p_birth", "p_death", "count"):
        np.testing.assert_array_equal(np.asarray(getattr(sub, f)),
                                      np.asarray(getattr(sup, f)),
                                      err_msg=f"{msg} field={f}")


# ---------------------------------------------------------------------------
# 1. Dual-filtration bit-identity across the path matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("phase_c_impl", ["fused", "xla"])
@pytest.mark.parametrize("path", ["whole", "batched", "sharded", "tiled"])
def test_sublevel_matches_negated_superlevel(path, phase_c_impl):
    img = _image(3)
    sub_e = PHEngine(_config("sublevel", phase_c_impl=phase_c_impl))
    sup_e = PHEngine(_config("superlevel", phase_c_impl=phase_c_impl))

    if path == "whole":
        sub = sub_e.run(img).diagram
        sup = sup_e.run(-img).diagram
    elif path == "batched":
        sub = jax.tree.map(lambda x: x[0],
                           sub_e.run_batch(img[None]).diagram)
        sup = jax.tree.map(lambda x: x[0],
                           sup_e.run_batch(-img[None]).diagram)
    elif path == "sharded":
        from repro.launch.mesh import make_small_context
        ctx = make_small_context(1, 1)
        dt = jnp.dtype(jnp.float32)
        sub_p = sub_e.sharded_plan(ctx, (1, H, W), dt, N, N)
        sup_p = sup_e.sharded_plan(ctx, (1, H, W), dt, N, N)
        # Each filtration's inert "no truncation" sentinel, user space.
        sub = jax.tree.map(lambda x: x[0], sub_p(
            jnp.asarray(img)[None], jnp.full((1,), jnp.inf, jnp.float32)))
        sup = jax.tree.map(lambda x: x[0], sup_p(
            jnp.asarray(-img)[None],
            jnp.full((1,), -jnp.inf, jnp.float32)))
    else:   # tiled
        sub = sub_e.run_tiled(img).diagram
        sup = sup_e.run_tiled(-img).diagram
    _assert_dual(sub, sup, f"{path}/{phase_c_impl}")


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_sublevel_duality_property(seed):
    """Seeded sweep of the whole-image duality (the shapes stay fixed so
    every example reuses two compiled programs)."""
    img = _image(seed)
    sub = pixhomology(jnp.asarray(-img), max_features=N, max_candidates=N,
                      filtration="sublevel")
    sup = pixhomology(jnp.asarray(img), max_features=N, max_candidates=N)
    _assert_dual(sub, sup, f"seed={seed}")
    # And against the oracle: sublevel features of -x are superlevel
    # features of x with both coordinates negated.
    want = persistence_oracle(img)
    rows = int(np.asarray(sub.count))
    got = np.stack([-np.asarray(sub.birth, np.float64)[:rows],
                    -np.asarray(sub.death, np.float64)[:rows],
                    np.asarray(sub.p_birth, np.float64)[:rows],
                    np.asarray(sub.p_death, np.float64)[:rows]], axis=1)
    np.testing.assert_array_equal(got, want)


def test_sublevel_requires_floating_dtype():
    with pytest.raises(ValueError, match="floating"):
        PHConfig(filtration="sublevel", dtype="int32")
    with pytest.raises(ValueError, match="float"):
        pixhomology(jnp.arange(16, dtype=jnp.int32).reshape(4, 4),
                    max_features=4, max_candidates=16,
                    filtration="sublevel")


# ---------------------------------------------------------------------------
# 2. Padded dispatch: essential extremum in the padded margin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("filtration", ["superlevel", "sublevel"])
def test_padded_batch_bit_identical_extremum_on_border(filtration):
    """The pad fixup must restore the essential death even when the
    image's global extremum sits on the row/column that abuts the pad
    margin (the fill used to be assumed to be the global minimum —
    wrong side entirely under sublevel)."""
    img = _image(11, shape=(13, 11))
    ext = np.argmin(img) if filtration == "superlevel" else np.argmax(img)
    r, c = np.unravel_index(ext, img.shape)
    # Move the extremum to the bottom-right corner (adjacent to pads).
    img[-1, -1], img[r, c] = img[r, c], img[-1, -1]
    eng = PHEngine(_config(filtration, tile=None))
    whole = eng.run(img).diagram
    padded = jax.tree.map(
        lambda x: x[0], eng.run_batch([img], bucket=(16, 16)).diagram)
    count = int(np.asarray(whole.count))
    assert int(np.asarray(padded.count)) == count
    # Capacities differ (143-pixel whole plan vs 256-pixel bucket), so
    # compare the count-trimmed records — row 0 carries the essential
    # class whose death the fixup restored.
    for f in ("birth", "death", "p_birth", "p_death"):
        np.testing.assert_array_equal(
            np.asarray(getattr(padded, f))[:count],
            np.asarray(getattr(whole, f))[:count],
            err_msg=f"{filtration} field={f}")


# ---------------------------------------------------------------------------
# 3. NaN / inf / subnormal boundary sweep
# ---------------------------------------------------------------------------

def _nan_image():
    img = _image(5)
    img[3, 7] = np.nan
    return img


@pytest.mark.parametrize("merge_keys", ["packed", "rank"])
def test_nan_rejected_on_every_entry_point(merge_keys):
    img = _nan_image()
    eng = PHEngine(_config("superlevel", merge_keys=merge_keys))
    for call in (lambda: eng.run(img),
                 lambda: eng.run_batch(img[None]),
                 lambda: eng.run_tiled(img),
                 lambda: eng.cast_input(img),
                 lambda: eng.cast_input_host(img),
                 lambda: pixhomology(img, max_features=N,
                                     max_candidates=N,
                                     merge_keys=merge_keys),
                 lambda: tiled_pixhomology(img, grid=(2, 2),
                                           max_features=N,
                                           tile_max_features=N,
                                           tile_max_candidates=N,
                                           merge_keys=merge_keys)):
        with pytest.raises(ValueError, match="ordered by a filtration"):
            call()


def test_inf_rejected_at_engine_boundary_only():
    img = _image(6)
    img[0, 0] = np.inf
    eng = PHEngine(_config("superlevel"))
    with pytest.raises(ValueError, match="pad sentinels"):
        eng.run(img)
    with pytest.raises(ValueError, match="pad sentinels"):
        eng.cast_input_host(img)
    # The core wrappers allow ±inf (padded/halo frames legitimately
    # carry the fill) — only NaN is rejected there.
    pixhomology(jnp.asarray(img), max_features=N, max_candidates=N)


def test_subnormals_accepted_and_correct():
    # A subnormal pixel among normal-scale values: accepted (the finite
    # check must not reject it) and ordered exactly — with no zeros and
    # a single subnormal, backend flush-to-zero cannot reorder anything,
    # so the diagram matches the (non-flushing) numpy oracle bitwise.
    img = _image(7)
    assert not (img == 0).any()
    img[5, 5] = np.float32(1e-40)
    assert 0 < img[5, 5] < np.finfo(np.float32).tiny
    d = PHEngine(_config("superlevel", tile=None)).run(img)
    np.testing.assert_array_equal(d.to_array(), persistence_oracle(img))

    # All-subnormal magnitudes: still accepted, and both key encodings
    # agree bit-for-bit under whatever flush semantics the backend has
    # (the packed_keys contract: key equality == comparison equality).
    tiny = (_image(7) * np.float32(1e-42)).astype(np.float32)
    packed = PHEngine(_config("superlevel", tile=None,
                              merge_keys="packed")).run(tiny).diagram
    rank = PHEngine(_config("superlevel", tile=None,
                            merge_keys="rank")).run(tiny).diagram
    for f in ("birth", "death", "p_birth", "p_death", "count"):
        np.testing.assert_array_equal(np.asarray(getattr(packed, f)),
                                      np.asarray(getattr(rank, f)),
                                      err_msg=f"field={f}")


def test_nan_rejected_at_distance_boundary():
    img = _image(8)
    eng = PHEngine(_config("superlevel", tile=None))
    res = eng.run(img)
    birth, death, p_birth = eng._stack_diagrams(res)
    birth[0, 0] = np.nan
    with pytest.raises(ValueError, match="ordered by a filtration"):
        eng.distance_matrix((birth, death, p_birth))
    with pytest.raises(ValueError, match="ordered by a filtration"):
        dist_ops.diagram_distances(birth, death, p_birth)


# ---------------------------------------------------------------------------
# 4. Distance kernels: parity, axioms, inertness, plan cache
# ---------------------------------------------------------------------------

def _diagram_batch(n=5, seed=9):
    eng = PHEngine(_config("superlevel", tile=None))
    imgs = np.stack([_image(seed + i) for i in range(n)])
    return eng, eng._stack_diagrams(eng.run_batch(imgs))


def test_pallas_kernel_bit_identical_to_ref():
    _, (birth, death, p_birth) = _diagram_batch()
    sw_x, bn_x = dist_ops.diagram_distances(birth, death, p_birth)
    sw_p, bn_p = dist_ops.diagram_distances(birth, death, p_birth,
                                            use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(sw_x), np.asarray(sw_p))
    np.testing.assert_array_equal(np.asarray(bn_x), np.asarray(bn_p))


def _np_points(birth, death, p_birth, i):
    m = p_birth[i] >= 0
    return np.stack([birth[i][m], death[i][m]], axis=1).astype(np.float64)


def _np_sw(pa, pb, n_dirs=16):
    theta = (np.arange(n_dirs) + 0.5) * np.pi / n_dirs
    total = 0.0
    for t in theta:
        c, s = np.cos(t), np.sin(t)
        proj = lambda p: p[:, 0] * c + p[:, 1] * s          # noqa: E731
        dpro = lambda p: (p[:, 0] + p[:, 1]) / 2 * (c + s)  # noqa: E731
        va = np.sort(np.concatenate([proj(pa), dpro(pb)]))
        vb = np.sort(np.concatenate([proj(pb), dpro(pa)]))
        total += np.abs(va - vb).sum()
    return total / n_dirs


def _np_bn(pa, pb, f):
    prof = lambda p: np.sort(np.concatenate(       # noqa: E731
        [np.abs(p[:, 0] - p[:, 1]), np.zeros(f - len(p))]))[::-1]
    return 0.5 * np.abs(prof(pa) - prof(pb)).max()


def test_distances_match_dense_numpy_reference():
    _, (birth, death, p_birth) = _diagram_batch()
    sw, bn = (np.asarray(a) for a in
              dist_ops.diagram_distances(birth, death, p_birth))
    f = birth.shape[1]
    for i in range(birth.shape[0]):
        for j in range(birth.shape[0]):
            pa = _np_points(birth, death, p_birth, i)
            pb = _np_points(birth, death, p_birth, j)
            np.testing.assert_allclose(sw[i, j], _np_sw(pa, pb),
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(bn[i, j], _np_bn(pa, pb, f),
                                       rtol=1e-5, atol=1e-6)


def test_distance_metric_axioms():
    _, (birth, death, p_birth) = _diagram_batch(n=6)
    for mat in dist_ops.diagram_distances(birth, death, p_birth):
        m = np.asarray(mat)
        n = m.shape[0]
        np.testing.assert_array_equal(m, m.T)             # symmetry
        np.testing.assert_array_equal(np.diag(m), 0.0)    # d(A, A) = 0
        assert (m >= 0).all()
        eps = 1e-5 * max(m.max(), 1.0)
        for i in range(n):                # triangle inequality, all triples
            for j in range(n):
                for k in range(n):
                    assert m[i, j] <= m[i, k] + m[k, j] + eps


def test_capacity_pads_are_inert():
    _, (birth, death, p_birth) = _diagram_batch()
    sw1, bn1 = (np.asarray(a) for a in
                dist_ops.diagram_distances(birth, death, p_birth))
    grow = lambda a, fill: np.concatenate(    # noqa: E731
        [a, np.full_like(a, fill)], axis=1)
    sw2, bn2 = (np.asarray(a) for a in dist_ops.diagram_distances(
        grow(birth, -np.inf), grow(death, -np.inf), grow(p_birth, -1)))
    np.testing.assert_array_equal(bn1, bn2)   # profile pads: bit-exact
    np.testing.assert_allclose(sw1, sw2, rtol=1e-5)  # sum reassociates


def test_engine_distance_plan_cached_and_filtration_exact():
    eng, (birth, death, p_birth) = _diagram_batch(n=4)
    eng.distance_matrix((birth, death, p_birth))
    before = eng.plan_stats()["traces"]
    sw_a, bn_a = eng.distance_matrix((birth, death, p_birth))
    assert eng.plan_stats()["traces"] == before     # cached plan, no trace

    # Sublevel engine on the sublevel view of the same diagrams -> the
    # canonicalization makes the matrices bit-equal.
    sub = PHEngine(_config("sublevel", tile=None))
    sw_s, bn_s = sub.distance_matrix((-birth, -death, p_birth))
    np.testing.assert_array_equal(np.asarray(sw_a), np.asarray(sw_s))
    np.testing.assert_array_equal(np.asarray(bn_a), np.asarray(bn_s))


def test_profiles_match_across_key_encodings():
    from repro.core.packed_keys import key_scope
    _, (birth, death, p_birth) = _diagram_batch(n=3)
    with key_scope("packed"):
        packed = np.asarray(dist_ref.persistence_profiles(
            birth, death, p_birth, merge_keys="packed"))
    rank = np.asarray(dist_ref.persistence_profiles(
        birth, death, p_birth, merge_keys="rank"))
    np.testing.assert_array_equal(packed, rank)
    assert (np.diff(rank, axis=1) <= 0).all()       # descending


# ---------------------------------------------------------------------------
# 5. Serving metrics: empty/degenerate reservoirs
# ---------------------------------------------------------------------------

def test_empty_reservoir_zeroed_not_raising():
    from repro.serving.metrics import Reservoir, ServeMetrics
    r = Reservoir(8)
    assert r.percentile(99.0) == 0.0
    assert r.summary() == {"count": 0, "mean": 0.0, "p50": 0.0,
                           "p95": 0.0, "p99": 0.0, "max": 0.0}
    r.add(0.25)     # single sample: every percentile is that sample
    s = r.summary()
    assert s["count"] == 1 and s["p50"] == s["p95"] == s["p99"] == 0.25

    m = ServeMetrics(batch_cap=4)
    m.record_submit((16, 16))       # bucket exists, nothing dispatched
    snap = m.snapshot()["buckets"]["16x16"]
    assert snap["e2e_s"]["p99"] == 0.0 and snap["e2e_s"]["count"] == 0
    assert m.mean_batch_seconds((16, 16)) is None   # server retry fallback


def _load_perf_gate():
    p = pathlib.Path(__file__).resolve().parent.parent / "benchmarks" \
        / "perf_gate.py"
    spec = importlib.util.spec_from_file_location("perf_gate_under_test", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_perf_gate_skips_degenerate_latency_windows():
    gate = _load_perf_gate()
    summary = {"count": 1, "mean": 1.0, "p50": 9.0, "p95": 1.0, "p99": 1.0}
    doc = {"steady": {"buckets": {"16x16": {
        "occupancy": 0.5, "queue_wait_s": summary, "e2e_s": summary}}}}
    assert gate._serve_latency_summaries(doc) is None   # < 2 samples: skip
    bad = dict(summary, count=2)
    doc["steady"]["buckets"]["16x16"]["e2e_s"] = bad
    assert "unordered" in gate._serve_latency_summaries(doc)


def test_perf_gate_distance_rules():
    gate = _load_perf_gate()
    row = {"name": "distance/b6_s48", "distance_bit_identical": True,
           "sublevel_bit_identical": True, "pad_inert_bn": True,
           "pad_inert_sw_rel": 0.0, "steady_traces": 0}
    assert gate._distance_invariants([row]) is None
    assert "diverged" in gate._distance_invariants(
        [dict(row, distance_bit_identical=False)])
    assert "steady-state" in gate._distance_invariants(
        [dict(row, steady_traces=2)])
    traj = gate._distance_trajectory([row])
    assert traj([row]) is None
    assert traj([dict(row, sublevel_bit_identical=False)]) is not None
