"""The check catches a broken timed path, and the control, at tiny sizes:
every run below must come out not correct."""
import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload,fault,check", [
    ("frame4k.whole", "answer_altered", "rows_differing"),
    ("batch1k.x4", "answer_altered", "summary_values_differing"),
    ("batch1k.x4", "half_batch", "frames_missing"),
])
def test_fault_is_not_correct(root, workload, fault, check):
    code, result, err = tiny.drive(root, workload, 11, fault=fault)
    assert code == 0, err
    assert result["correct"] is False
    assert result["checks"][check]["value"] > result["checks"][check]["limit"]


@pytest.mark.parametrize("workload,check", [
    ("frame4k.whole", "rows_differing"),
    ("batch1k.x4", "summary_values_differing"),
])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct(root, workload, check, seed):
    code, result, err = tiny.drive(root, workload, seed, fault="control")
    assert code == 0, err
    assert result["correct"] is False
    assert result["checks"][check]["value"] > 0
