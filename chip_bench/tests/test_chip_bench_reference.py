"""The plain reference against the program, and the lower-precision
control against the reference, at a size a CPU run holds: a 2-round,
4-client federation on 512 images under each cell's traffic mix, held
to that cell's committed limits (the four-chip cell's program side runs
in `test_chip_bench_mesh_faults.py`, which needs four devices)."""
import chip_bench_tiny
import jax.numpy as jnp
import pytest

from chip_bench import cells, compare, run
from chip_bench.reference import federation as ref_mod

SEED = 2**31 + 77          # the driver's seeds exceed 32 signed bits


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return chip_bench_tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("name", chip_bench_tiny.SINGLE)
def test_program_agrees_with_the_reference(root, name):
    cell = cells.load(name, root=root)
    res = run.run_cell(cell, SEED, 0.0, False)
    assert res["correct"] is True
    assert res["attempted"] == 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    for check in res["checks"].values():
        # both sides compute in float32 on the CPU; they round apart only
        # where max-pooling meets a tie
        assert check["value"] <= 0.5 * check["limit"]


@pytest.mark.parametrize("name", chip_bench_tiny.SINGLE
                         + (chip_bench_tiny.MESH,))
def test_the_lower_precision_control_fails(root, name):
    """The reference computed in bfloat16, put in the program's place,
    breaks at least one of the cell's limits."""
    cell = cells.load(name, root=root)
    dataset = cell.family.render(cell.config["data"], SEED)
    model = cell.family.reference_model()
    ref = ref_mod.run(cell.spec, dataset, SEED, model)
    ctrl = ref_mod.run(cell.spec, dataset, SEED, model, dtype=jnp.bfloat16)
    checks, failed = compare.judge([compare.gaps(ctrl, ref)], cell.limits)
    assert failed == 1, checks
