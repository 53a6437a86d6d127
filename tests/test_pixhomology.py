"""Correctness of the PixHomology core vs the classical union-find oracle.

The paper validates against Ripser with bottleneck distance 0 (fig 7); we
assert *exact* equality (values AND pixel coordinates) against the oracle,
which is stronger, plus property-based sweeps with hypothesis.
"""
import importlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jcore
from _hypothesis_compat import given, settings, st

from repro.core import (
    Diagram,
    batched_pixhomology,
    diagram_to_array,
    num_candidates,
    persistence_oracle,
    pixhomology,
)


def run_exact(img: np.ndarray, mode: str = "exact") -> np.ndarray:
    h, w = img.shape
    d = pixhomology(jnp.asarray(img), max_features=h * w,
                    max_candidates=h * w, candidate_mode=mode)
    assert not bool(d.overflow)
    return diagram_to_array(d)


# ---------------------------------------------------------------------------
# Exact equality with the oracle
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(st.integers(1, 14), st.integers(1, 14), st.integers(0, 2 ** 31 - 1))
def test_matches_oracle_gaussian(h, w, seed):
    img = np.random.default_rng(seed).normal(size=(h, w)).astype(np.float32)
    got = run_exact(img)
    want = persistence_oracle(img)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=40, deadline=None)
@given(st.integers(2, 12), st.integers(2, 12), st.integers(0, 2 ** 31 - 1),
       st.integers(2, 4))
def test_matches_oracle_heavy_ties(h, w, seed, levels):
    """Tiny integer range => massive value ties; the paper's strict-max
    precondition is violated, the total order must still make both sides agree."""
    img = np.random.default_rng(seed).integers(
        0, levels, size=(h, w)).astype(np.float32)
    np.testing.assert_array_equal(run_exact(img), persistence_oracle(img))


def test_matches_oracle_integer_dtype():
    img = np.random.default_rng(3).integers(0, 50, size=(17, 9)).astype(np.int32)
    got = run_exact(img)
    np.testing.assert_array_equal(got, persistence_oracle(img))


def test_constant_image():
    img = np.zeros((6, 7), np.float32)
    got = run_exact(img)
    want = persistence_oracle(img)
    np.testing.assert_array_equal(got, want)
    assert got.shape[0] == 1  # single component, pure tie-break order


def test_single_pixel():
    img = np.array([[3.5]], np.float32)
    got = run_exact(img)
    assert got.shape == (1, 4)
    assert got[0, 0] == got[0, 1] == pytest.approx(3.5)


def test_monotone_ramp():
    img = np.arange(30, dtype=np.float32).reshape(5, 6)
    got = run_exact(img)
    assert got.shape[0] == 1
    np.testing.assert_array_equal(got, persistence_oracle(img))


def test_two_gaussian_blobs_known_saddle():
    """Two bumps joined by a col: the younger dies exactly at the col value."""
    yy, xx = np.mgrid[0:41, 0:81].astype(np.float32)
    img = (2.0 * np.exp(-((yy - 20) ** 2 + (xx - 20) ** 2) / 40.0)
           + 1.5 * np.exp(-((yy - 20) ** 2 + (xx - 60) ** 2) / 40.0))
    img += np.random.default_rng(0).normal(scale=1e-4, size=img.shape).astype(np.float32)
    got = run_exact(img)
    want = persistence_oracle(img)
    np.testing.assert_array_equal(got, want)
    # Row 0: essential class born at the global max; row 1: the smaller bump.
    assert got[0, 0] == pytest.approx(2.0, abs=0.05)
    assert got[1, 0] == pytest.approx(1.5, abs=0.05)
    assert got[1, 1] < got[1, 0]


# ---------------------------------------------------------------------------
# Paper-literal distillation: births exact, deaths may only move DOWN
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(3, 12), st.integers(3, 12), st.integers(0, 2 ** 31 - 1))
def test_paper_mode_births_exact_deaths_lower(h, w, seed):
    img = np.random.default_rng(seed).normal(size=(h, w)).astype(np.float32)
    got = run_exact(img, mode="paper")
    want = persistence_oracle(img)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got[:, [0, 2]], want[:, [0, 2]])  # births
    # A missed saddle can only postpone a merge to a lower value.
    assert np.all(got[:, 1] <= want[:, 1] + 0)


# ---------------------------------------------------------------------------
# Batched / capacity / diagnostics behaviour
# ---------------------------------------------------------------------------

def test_batched_matches_single():
    rng = np.random.default_rng(7)
    imgs = rng.normal(size=(4, 10, 11)).astype(np.float32)
    batched = batched_pixhomology(jnp.asarray(imgs), max_features=128,
                                  max_candidates=128)
    for i in range(imgs.shape[0]):
        single = pixhomology(jnp.asarray(imgs[i]), max_features=128,
                             max_candidates=128)
        for a, b in zip(batched, single):
            np.testing.assert_array_equal(np.asarray(a[i]), np.asarray(b))


def test_feature_overflow_flag():
    img = np.random.default_rng(0).normal(size=(16, 16)).astype(np.float32)
    full = pixhomology(jnp.asarray(img), max_features=256, max_candidates=256)
    c = int(full.count)
    assert c > 4
    small = pixhomology(jnp.asarray(img), max_features=4, max_candidates=256)
    assert bool(small.overflow)
    assert int(small.count) == 4
    # The 4 retained rows are the highest-birth ones, in the same order.
    np.testing.assert_array_equal(np.asarray(small.birth),
                                  np.asarray(full.birth[:4]))


def test_candidate_overflow_flag():
    img = np.random.default_rng(1).normal(size=(16, 16)).astype(np.float32)
    k = int(num_candidates(jnp.asarray(img)))
    assert k > 2
    d = pixhomology(jnp.asarray(img), max_features=256, max_candidates=2)
    assert bool(d.overflow)


def test_diagram_is_sorted_and_padded():
    img = np.random.default_rng(2).normal(size=(12, 12)).astype(np.float32)
    d = pixhomology(jnp.asarray(img), max_features=512, max_candidates=512)
    c = int(d.count)
    b = np.asarray(d.birth)
    assert np.all(np.diff(b[:c]) <= 0)          # descending births
    assert np.all(b[c:] == -np.inf)             # padding
    assert np.all(np.asarray(d.p_birth)[c:] == -1)
    assert int(d.n_unmerged) == 0
    # All finite deaths lie strictly below their births (superlevel PD is
    # below the diagonal in (birth, death) with death < birth).
    dd = np.asarray(d.death)[:c]
    assert np.all(dd[1:] < b[1:c] + 1e-9)


def test_jit_cache_stable_across_shapes():
    # Different shapes are distinct jit traces; results stay correct.
    for shape in [(5, 9), (9, 5), (7, 7)]:
        img = np.random.default_rng(0).normal(size=shape).astype(np.float32)
        np.testing.assert_array_equal(run_exact(img), persistence_oracle(img))


# ---------------------------------------------------------------------------
# Stage graph: fused and pooled phase A are interchangeable implementations
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(1, 14), st.integers(1, 14), st.integers(0, 2 ** 31 - 1))
def test_pooled_stage_matches_oracle(h, w, seed):
    """The unfused baseline stage pipeline stays oracle-exact (the suite's
    other oracle tests run the fused default)."""
    img = np.random.default_rng(seed).normal(size=(h, w)).astype(np.float32)
    d = pixhomology(jnp.asarray(img), max_features=h * w,
                    max_candidates=h * w, phase_a_impl="pooled")
    np.testing.assert_array_equal(diagram_to_array(d),
                                  persistence_oracle(img))


def test_fused_stage_with_boruvka_and_truncation():
    """Stage choices compose: fused phase A x Boruvka merge x Variant-2
    truncation must all agree with the pooled/scan reference."""
    img = np.random.default_rng(11).normal(size=(14, 10)).astype(np.float32)
    for tv in (None, 0.2):
        want = pixhomology(jnp.asarray(img), tv, max_features=140,
                           max_candidates=140, phase_a_impl="pooled",
                           merge_impl="scan")
        got = pixhomology(jnp.asarray(img), tv, max_features=140,
                          max_candidates=140, phase_a_impl="fused",
                          strip_rows=4, merge_impl="boruvka")
        for a, b in zip(want, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_num_candidates_agrees_across_stage_impls():
    img = jnp.asarray(np.random.default_rng(3).normal(
        size=(12, 12)).astype(np.float32))
    k_fused = int(num_candidates(img, phase_a_impl="fused", strip_rows=4))
    k_pooled = int(num_candidates(img, phase_a_impl="pooled"))
    assert k_fused == k_pooled > 0
    t = float(np.asarray(img).mean())
    assert int(num_candidates(img, truncate_value=t)) == \
        int(num_candidates(img, truncate_value=t, phase_a_impl="pooled"))


# ---------------------------------------------------------------------------
# Merge sweep length: the scan runs min(n_cand, max_candidates) steps
# ---------------------------------------------------------------------------

_ph_mod = importlib.import_module("repro.core.pixhomology")


def _fixed_length_sweep(shape, m, top_pix, top_keys, image_flat, key_flat,
                        labels_flat):
    """Reference: the same step over every top-k entry, pad keys included."""
    n = shape[0] * shape[1]
    neg_inf = (-jnp.inf if jnp.issubdtype(image_flat.dtype, jnp.floating)
               else jnp.iinfo(image_flat.dtype).min)
    carry = (jnp.arange(n, dtype=jnp.int32),
             jnp.full(n, neg_inf, image_flat.dtype), jnp.full(n, -1, jnp.int32))

    def body(carry, i):
        return _ph_mod._merge_step(shape, i, carry, top_pix, top_keys,
                                   image_flat, key_flat, labels_flat), None

    (_, dval, dpos), _ = jax.lax.scan(
        body, carry, jnp.arange(top_pix.shape[0], dtype=jnp.int32))
    return dval, dpos


def _sweep_loops(jaxpr):
    """(kind, length or predicate shape, outvar shapes) of every loop."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(("scan", eqn.params["length"], None))
        elif eqn.primitive.name == "while":
            found.append(("while", eqn.params["cond_jaxpr"].out_avals[0].shape,
                          [v.aval.shape for v in eqn.outvars]))
        for p in eqn.params.values():
            for q in p if isinstance(p, (tuple, list)) else (p,):
                if isinstance(q, jcore.ClosedJaxpr):
                    found += _sweep_loops(q.jaxpr)
                elif isinstance(q, jcore.Jaxpr):
                    found += _sweep_loops(q)
    return found


def _sweep_case(case, truncated):
    """(images, truncate values or None, max_candidates) for one case."""
    rng = np.random.default_rng(17)
    h, w = 9, 10
    if case == "one_candidate":
        # Two peaks joined through one saddle pixel.
        imgs = np.array([[[3.0, 0.0, 2.0]]], np.float32)
    elif case == "batch":
        r, c = np.mgrid[:h, :w]
        two_cones = np.maximum(-((r - 2) ** 2 + (c - 2) ** 2),
                               -((r - 6) ** 2 + (c - 7) ** 2) - 1)
        imgs = np.stack([np.full((h, w), 2.0, np.float32),
                         rng.normal(size=(h, w)).astype(np.float32),
                         two_cones.astype(np.float32),
                         rng.normal(size=(h, w)).astype(np.float32) * 3])
    elif case == "zero_candidates":
        imgs = np.full((1, h, w), 5.0, np.float32)
    else:
        imgs = rng.normal(size=(1, h, w)).astype(np.float32)
    tvals = (np.quantile(imgs.reshape(len(imgs), -1), 0.3, axis=1
                         ).astype(np.float32) if truncated else None)
    counts = [int(num_candidates(jnp.asarray(im),
                                 truncate_value=None if tvals is None
                                 else tvals[i]))
              for i, im in enumerate(imgs)]
    n = imgs[0].size
    k = {"less": max(counts) + 3, "equal": max(counts),
         "overflow": max(counts) // 2, "batch": int(np.median(counts))}.get(
             case, n)
    if case == "zero_candidates":
        assert counts == [0]
    if case == "one_candidate" and not truncated:
        assert counts == [1]
    if case == "batch":
        assert len(set(counts)) == len(counts)   # a different count each
        assert min(counts) < k < max(counts)     # one image overflows
    return imgs, tvals, max(k, 1)


@pytest.mark.parametrize("merge_keys", ["rank", "packed"])
@pytest.mark.parametrize("truncated", [False, True],
                         ids=["untruncated", "truncated"])
@pytest.mark.parametrize("case", ["zero_candidates", "one_candidate", "less",
                                  "equal", "overflow", "batch"])
def test_merge_sweep_stops_at_live_candidates(case, truncated, merge_keys,
                                              monkeypatch):
    """Bounding the sweep by the live count changes no bit of the diagram.

    The diagram (and overflow flag) equals the one a fixed-length sweep
    over all ``max_candidates`` entries gives, and the oracle's when
    nothing overflows; the program holds no scan of that length, and its
    merge loop runs on a scalar bound, batched or not."""
    imgs, tvals, k = _sweep_case(case, truncated)
    kw = dict(max_features=imgs[0].size, max_candidates=k,
              merge_keys=merge_keys)
    args = (jnp.asarray(imgs),) if tvals is None else \
        (jnp.asarray(imgs), jnp.asarray(tvals))

    def make_run():
        # A fresh function per sweep, so no trace cached under the other
        # sweep is reused.
        def run(x, t=None):
            if case == "batch":
                return batched_pixhomology(x, t, **kw)
            return jax.tree.map(lambda a: a[None], pixhomology(
                x[0], None if t is None else t[0], **kw))
        return run

    got = jax.jit(make_run())(*args)
    loops = _sweep_loops(jax.make_jaxpr(make_run())(*args).jaxpr)
    with monkeypatch.context() as mp:
        mp.setattr(_ph_mod, "_merge_sweep", _fixed_length_sweep)
        raw = _ph_mod._pixhomology.__wrapped__
        mp.setattr(_ph_mod, "_pixhomology", lambda *a, **kws: raw(*a, **kws))
        want = jax.jit(make_run())(*args)
        assert ("scan", k, None) in _sweep_loops(
            jax.make_jaxpr(make_run())(*args).jaxpr)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    n_cand = np.asarray(got.n_candidates)
    np.testing.assert_array_equal(np.asarray(got.overflow), n_cand > k)
    for i, img in enumerate(imgs):
        if tvals is None and n_cand[i] <= k:
            one = Diagram(*(np.asarray(f)[i] for f in got))
            np.testing.assert_array_equal(diagram_to_array(one),
                                          persistence_oracle(img))

    n = imgs[0].size
    assert ("scan", k, None) not in loops
    merge = [(pred, shapes) for kind, pred, shapes in loops
             if kind == "while" and shapes[1:] == [shapes[1]] * 3
             and shapes[1][-1:] == (n,)]
    assert [pred for pred, _ in merge] == [()]
