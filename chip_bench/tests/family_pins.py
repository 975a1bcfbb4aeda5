"""Digests of what the paper CNN family gives at the tiny root's size:
the rendered data sets, each cell's `run_work`, and the plain
reference's results, for the seeds given; also for the parked traffic
mixes (`PARKED`), which no cell of `BENCHMARK.json` runs.

    python family_pins.py ROOT SEED...

Prints one JSON object. The process keeps to one CPU, so that XLA's CPU
backend splits no reduction over threads and the digests do not depend
on the machine's core count.
"""
from __future__ import annotations

import os

os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})

import hashlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

import chip_bench_tiny  # noqa: E402
import numpy as np  # noqa: E402

from chip_bench import cells, costs  # noqa: E402
from chip_bench.reference import federation as ref_mod  # noqa: E402

GENERATORS = ("mnist_like", "fashion_like")
CELLS = chip_bench_tiny.SINGLE + (chip_bench_tiny.MESH,)
# traffic that no cell runs at present, pinned so that its reference
# stays as it was: name -> (configuration, traffic mix)
PARKED = {"mnist_c10.cfl": ("cnn.mnist.c10", "cfl")}


def digest(obj):
    """sha256 of arrays (a dict of them by sorted key), with their names,
    dtypes and shapes; 16 hex digits."""
    h = hashlib.sha256()
    items = sorted(obj.items()) if isinstance(obj, dict) else [("", obj)]
    for k, v in items:
        a = np.ascontiguousarray(v)
        h.update(f"{k}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def parked(root, name):
    """A cell of a parked traffic mix, with no limits or metrics."""
    config, traffic = PARKED[name]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    path = {c["name"]: c["file"] for c in bench["configs"]}[config]
    return cells.Cell(
        name=name, chips=1, config=json.loads((root / path).read_text()),
        traffic=json.loads((root / cells.BENCH_DIR / "traffic"
                            / f"{traffic}.json").read_text()),
        limits={}, end_to_end=[], per_layer=[], root=root)


def pins(root, seeds):
    out = {"render": {}, "run_work": {}, "reference": {}}
    family = cells.load(CELLS[0], root=root).family
    for gen in GENERATORS:
        for s in seeds:
            d = family.render({"generator": gen, "n_train": 512,
                               "n_test": 128}, s)
            out["render"][f"{gen}/{s}"] = digest(
                {"train_x": d["train"][0], "train_y": d["train"][1],
                 "test_x": d["test"][0], "test_y": d["test"][1]})
    for name in CELLS + tuple(PARKED):
        cell = (parked(root, name) if name in PARKED
                else cells.load(name, root=root))
        out["run_work"][name] = costs.run_work(
            cell.spec, cell.family.forward_flops(cell.config["model"]))
        for s in seeds:
            d = cell.family.render(cell.config["data"], s)
            ref = ref_mod.run(cell.spec, d, s, cell.family.reference_model())
            out["reference"][f"{name}/{s}"] = {
                "round_loss": digest(np.asarray(ref["round_loss"])),
                "round_test_acc": digest(np.asarray(ref["round_test_acc"])),
                "init": digest(ref["init"]), "final": digest(ref["final"])}
    return out


if __name__ == "__main__":
    print(json.dumps(pins(pathlib.Path(sys.argv[1]),
                          [int(s) for s in sys.argv[2:]])))
