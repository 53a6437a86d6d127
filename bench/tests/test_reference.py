"""The plain reference against a per-pixel union-find and the engine."""
import numpy as np
import pytest

from bench import frames, reference
from bench.tests.test_frames import RECIPE
from repro.core import diagram_to_array, persistence_oracle
from repro.pipeline.driver import _summarize


@pytest.mark.parametrize("frame_id", [0, 1, 2])
def test_equals_union_find_unthresholded(frame_id):
    img = frames.render(frame_id, 48, RECIPE)
    assert np.array_equal(reference.diagram(img), persistence_oracle(img))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_equals_union_find_with_ties(seed):
    img = np.random.default_rng(seed).integers(0, 4, (21, 26))
    img = img.astype(np.float32)
    assert np.array_equal(reference.diagram(img), persistence_oracle(img))


@pytest.fixture(scope="module")
def engine():
    from repro.ph import FilterLevel, PHConfig, PHEngine
    return PHEngine(PHConfig(filter_level=FilterLevel.STD,
                             max_features=4096, max_candidates=4096))


@pytest.mark.parametrize("frame_id", [100, 101, 2 ** 40 - 3])
def test_equals_engine_thresholded(engine, frame_id):
    img = frames.render(frame_id, 128, RECIPE)
    t = frames.threshold(img, 1.0)
    res = engine.run(img)
    assert res.threshold == t
    want = reference.diagram(img, t)
    assert np.array_equal(diagram_to_array(res.diagram), want)
    assert reference.summary(want) == {
        k: v for k, v in _summarize(res.diagram).items() if k != "overflow"}


def test_truncated_is_untruncated_cut():
    img = frames.render(4, 64, RECIPE)
    t = frames.threshold(img, 1.0)
    full = reference.diagram(img)
    cut = reference.diagram(img, t)
    keep = full[full[:, 0] >= np.float32(t)]
    assert np.array_equal(cut[:, [0, 2]], keep[:, [0, 2]])
    alive = keep[:, 1] < np.float32(t)
    alive[0] = False                      # the essential class
    assert np.all(cut[alive, 1] == np.float32(t))
    assert np.all(cut[alive, 3] == -1)
    assert np.array_equal(cut[~alive], keep[~alive])
