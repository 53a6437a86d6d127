"""The benchmark's frame recipe against the program's loader (astro)."""
import numpy as np
import pytest

from bench import frames
from repro.data import astro

RECIPE = {"density_per_kpx2": 3.4, "sky": 100.0, "read_noise": 5.0,
          "amp_min": 10.0, "amp_max": 5000.0, "sigma_min": 1.0,
          "sigma_max": 2.5, "count_factor_min": 0.6,
          "count_factor_max": 1.4, "stamp": 15}


@pytest.mark.parametrize("frame_id,size", [(0, 64), (3, 256), (2 ** 39 + 5, 512),
                                           (11, 1024)])
def test_pixels_equal_program_loader(frame_id, size):
    assert np.array_equal(frames.render(frame_id, size, RECIPE),
                          astro.generate_image(frame_id, size))


@pytest.mark.parametrize("frame_id,size", [(1, 64), (7, 512), (2 ** 40 - 1, 256)])
def test_star_draws_equal_program_loader(frame_id, size):
    got = frames.star_params(frame_id, size, RECIPE)
    want = astro.star_params(frame_id, size)
    for a, b in zip(got, want):
        assert np.array_equal(a, b)


def test_star_counts_per_frame():
    base = int(3.4 / 1000.0 * 256 * 256)
    counts = np.array([len(frames.star_params(i, 256, RECIPE)[0])
                       for i in range(400)])
    assert counts.min() >= int(0.6 * base)
    assert counts.max() <= int(1.4 * base)
    assert abs(counts.mean() / base - 1.0) < 0.03
    assert counts.std() / base > 0.2      # uniform over +-40%: 0.23


def test_noise_mean_and_sigma():
    img = frames.noise(5, 512, RECIPE)
    assert img.dtype == np.float32
    assert abs(float(img.mean()) - 100.0) < 0.05
    assert abs(float(img.std()) - 5.0) < 0.05


@pytest.mark.parametrize("frame_id", [2, 9])
def test_threshold_equals_program(frame_id):
    img = frames.render(frame_id, 256, RECIPE)
    assert frames.threshold(img, 1.0) == \
        astro.filter_threshold(img, "filter_std")[0]
