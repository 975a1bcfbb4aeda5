"""The harness, with the timed path broken underneath, reports `correct`
false: each fault a single-chip cell can have, planted in the program
at a size a CPU run holds (see `chip_bench_faults.py`)."""
import chip_bench_faults
import chip_bench_tiny
import pytest

SEED = 2**31 + 91


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return chip_bench_tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("fault", ["unchanged", "half_left_out", "altered"])
@pytest.mark.parametrize("name", chip_bench_tiny.SINGLE)
def test_fault_is_not_correct(root, name, fault):
    res = chip_bench_faults.drive(root, name, SEED, fault)
    assert res["correct"] is False, res["checks"]
    assert res["failed"] == res["attempted"] == 1
