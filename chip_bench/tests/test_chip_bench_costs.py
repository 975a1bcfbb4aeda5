"""The benchmark's cost model, the paper CNN family's counts and the
peaks table."""
import chip_bench_tiny
import pytest

from chip_bench import cells, costs

CELL = cells.load(chip_bench_tiny.SINGLE[0])
CNN = CELL.family


def test_paper_cnn_forward_macs():
    # conv1 28*28*16*9*1 + conv2 14*14*12*9*16 + conv3 7*7*10*9*12
    # + dense 490*10
    assert CNN.forward_macs() == 509_404
    assert (112_896 + 338_688 + 52_920 + 4_900) == 509_404
    model = CELL.config["model"]
    assert CNN.forward_flops(model) == 2 * 509_404
    assert model["forward_macs_per_image"] == 509_404


def test_paper_cnn_params():
    assert CNN.param_count() == 7_900
    assert CELL.config["model"]["params"] == 7_900


def test_fedavg_bytes_at_ten_clients():
    assert costs.fedavg_bytes(10, 7_900) == (10 * 7_900 + 10 + 7_900) * 4
    assert costs.fedavg_bytes(10, 7_900) == 347_640


def test_median_bytes():
    assert costs.median_bytes(1024, 7_900) == (1024 * 7_900 + 7_900) * 4


def test_unknown_device_kind_raises():
    assert costs.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        costs.peaks("_doc")


@pytest.mark.parametrize("fed, samples", [
    # HFL: 10 clients x 2 epochs x 187 batches x 32 x 2 rounds
    (dict(strategy="hfl", num_clients=10, local_batch_size=32,
          local_epochs=2, rounds=2), 10 * 2 * 187 * 32 * 2),
    # CFL: 10 visits x 1 epoch x 187 x 32 x 4 rounds
    (dict(strategy="cfl", num_clients=10, local_batch_size=32,
          local_epochs=1, rounds=4), 10 * 187 * 32 * 4),
    # AFL: 1,024 clients of 58 or 59 samples -> 3 batches of 16
    (dict(strategy="afl", participation=1.0, num_clients=1024,
          local_batch_size=16, local_epochs=1, rounds=10),
     1024 * 3 * 16 * 10),
])
def test_client_samples_per_run(fed, samples):
    spec = {"model": {"image": [28, 28, 1], "filters": [16, 12, 10],
                      "kernel": 3, "pool": 2, "classes": 10},
            "data": {"n_train": 60_000, "n_test": 10_000},
            "federation": fed}
    w = costs.run_work(spec, CNN.forward_flops(spec["model"]))
    assert w["client_samples"] == samples
    assert w["train_flops"] == 6 * 509_404 * samples
