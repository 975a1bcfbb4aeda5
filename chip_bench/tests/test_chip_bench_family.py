"""A model family is a file, `families/<arch>.py`, found by the
configuration's `model.arch`: a family added to a benchmark root as
files alone runs end to end, an unknown arch names the families there,
and the paper CNN family gives, to the bit, what the harness gave while
the CNN was built into it."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import chip_bench_tiny
import pytest

from chip_bench import cells, run

HERE = pathlib.Path(__file__).resolve().parent
SEED = 2**31 + 77
PIN_SEEDS = (7, 3914000111)
# Digests (`family_pins.py`, on one CPU) of the harness before the model
# family became a file: `data.render`, `costs.run_work` (6 x forward MACs
# a trained sample) and `reference/federation.py` with the CNN inside it,
# at the tiny root's size.
PARENT = {
    "render": {
        "mnist_like/7": "c43857e7b80e5eb4",
        "mnist_like/3914000111": "6dd912202c9a476c",
        "fashion_like/7": "8094f880fcb619c8",
        "fashion_like/3914000111": "2ea0c5c97b1e9f61"},
    "run_work": {
        "mnist_c10.hfl": {"client_samples": 2048, "eval_images": 1280,
                          "train_flops": 6259556352,
                          "eval_flops": 1304074240},
        "fmnist_c1024.afl_median": {"client_samples": 1024,
                                    "eval_images": 1280,
                                    "train_flops": 3129778176,
                                    "eval_flops": 1304074240},
        "mnist_c10.cfl": {"client_samples": 1024, "eval_images": 1280,
                          "train_flops": 3129778176,
                          "eval_flops": 1304074240},
        "fmnist_c1024.afl_mesh4": {"client_samples": 1024,
                                   "eval_images": 1280,
                                   "train_flops": 3129778176,
                                   "eval_flops": 1304074240}},
    "reference": {
        "mnist_c10.hfl/7": {
            "round_loss": "f6718ec480e93914",
            "round_test_acc": "b09696af0fd11721",
            "init": "488bc6ede6ca174d", "final": "f7fd959cd0ac8380"},
        "mnist_c10.hfl/3914000111": {
            "round_loss": "bd9aa693814c3856",
            "round_test_acc": "c406bdae66e5aefb",
            "init": "900a9939913619c5", "final": "a168e0c867742359"},
        "fmnist_c1024.afl_median/7": {
            "round_loss": "ee89f7a20fa0f58c",
            "round_test_acc": "f16cd6b158e84c15",
            "init": "488bc6ede6ca174d", "final": "5bea023396db5b54"},
        "fmnist_c1024.afl_median/3914000111": {
            "round_loss": "6ff0b4f13fa4f792",
            "round_test_acc": "8004d8602843ab09",
            "init": "900a9939913619c5", "final": "5bb0e31abc4be317"},
        "mnist_c10.cfl/7": {
            "round_loss": "1f3277ecf5546a26",
            "round_test_acc": "23dbe788ba8ca85c",
            "init": "488bc6ede6ca174d", "final": "b80db3a4b02df42d"},
        "mnist_c10.cfl/3914000111": {
            "round_loss": "dfed886aa2ff4717",
            "round_test_acc": "1910cc992d7fcbd8",
            "init": "900a9939913619c5", "final": "8eb0e73204391789"},
        "fmnist_c1024.afl_mesh4/7": {
            "round_loss": "1fdb0f6540700039",
            "round_test_acc": "5a473ba48ea15339",
            "init": "488bc6ede6ca174d", "final": "8ceb1df24519cf66"},
        "fmnist_c1024.afl_mesh4/3914000111": {
            "round_loss": "bfbdcb4888a714ac",
            "round_test_acc": "eb9ec19125963178",
            "init": "900a9939913619c5", "final": "6f40770da658e0a9"}},
}


@pytest.fixture(scope="module")
def pins(tmp_path_factory):
    root = chip_bench_tiny.make_root(tmp_path_factory.mktemp("tiny"))
    p = subprocess.run(
        [sys.executable, str(HERE / "family_pins.py"), str(root)]
        + [str(s) for s in PIN_SEEDS],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.splitlines()[-1])


@pytest.mark.parametrize("key", sorted(PARENT["render"]))
def test_rendered_data_matches_the_parent(pins, key):
    assert pins["render"][key] == PARENT["render"][key]


@pytest.mark.parametrize("name", sorted(PARENT["run_work"]))
def test_run_work_matches_the_parent(pins, name):
    assert pins["run_work"][name] == PARENT["run_work"][name]


@pytest.mark.parametrize("key", sorted(PARENT["reference"]))
def test_reference_matches_the_parent(pins, key):
    assert pins["reference"][key] == PARENT["reference"][key]


def _add_config(root, name, arch, generator):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "chip_bench" / "configs"
                      / "cnn.mnist.c10.json").read_text())
    cfg["name"] = name
    cfg["model"]["arch"] = arch
    cfg["data"]["generator"] = generator
    path = f"chip_bench/configs/{name}.json"
    (root / path).write_text(json.dumps(cfg))
    bench["configs"].append({"name": name, "source": "a test", "file": path,
                             "reduced": [], "why": "a family added as a file"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


def test_a_family_added_as_files_alone_runs(tmp_path):
    root = chip_bench_tiny.make_root(tmp_path)
    bdir = root / "chip_bench"
    family = bdir / "families" / "tiny_cnn_copy.py"
    shutil.copy(HERE / "fixtures" / "tiny_cnn_copy.py", family)
    bench = _add_config(root, "cnn_copy.checker.c4", "tiny_cnn_copy",
                        "checker_like")
    (bdir / "traffic" / "afl_all.json").write_text(json.dumps(
        {"why": "every client each round",
         "federation": {"strategy": "afl", "participation": 1.0,
                        "local_epochs": 1, "rounds": 2}}))
    (bdir / "limits" / "cnn_copy.checker.afl_all.json").write_text(
        json.dumps({"limits": {"param_gap": {"limit": 0.05},
                               "loss_gap": {"limit": 2.6e-4}}}))
    bench["workloads"].append({"name": "cnn_copy.checker.afl_all",
                               "config": "cnn_copy.checker.c4",
                               "traffic": "afl_all", "chips": 1,
                               "why": "a later family"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # every harness module of the root is the committed one, unedited
    for p in bdir.rglob("*.py"):
        if p != family:
            rel = p.relative_to(bdir)
            assert p.read_bytes() == (chip_bench_tiny.ROOT / "chip_bench"
                                      / rel).read_bytes(), rel

    cell = cells.load("cnn_copy.checker.afl_all", root=root)
    assert pathlib.Path(cell.family.__file__) == family
    data = cell.family.render(cell.config["data"], SEED & 0xFFFFFFFF)
    assert data["name"] == "checker_like"
    assert data["train"][0].shape == (512, 28, 28, 1)
    res = run.run_cell(cell, SEED, 0.0, False)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == 1 and res["failed"] == 0


def test_an_unknown_arch_names_the_families(tmp_path):
    root = chip_bench_tiny.make_root(tmp_path)
    bench = _add_config(root, "cnn.none.c4", "no_such_arch", "mnist_like")
    bench["workloads"].append({"name": "cnn_none.hfl", "config": "cnn.none.c4",
                               "traffic": "hfl", "chips": 1, "why": "none"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "chip_bench" / "limits" / "cnn_none.hfl.json").write_text(
        json.dumps({"limits": {"param_gap": {"limit": 0.05}}}))
    cell = cells.load("cnn_none.hfl", root=root)
    with pytest.raises(KeyError, match="no_such_arch.*paper_cnn"):
        cell.family
