"""A benchmark root with the real cells cut to a size a CPU test holds:
4 clients, 512 training and 128 test images, 2 rounds per run, chunks
of 1 client. Everything else (model, traffic mix, limits, metric
readers) is the committed benchmark's, copied as files."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

SINGLE = ("mnist_c10.hfl", "fmnist_c1024.afl_median", "mnist_c10.cfl")
# a four-chip cell is not in the benchmark yet; `add_mesh_cell` adds it to
# a tiny root as files alone, from the committed `afl_mesh4` traffic mix
MESH = "fmnist_c1024.afl_mesh4"


def make_root(dst: pathlib.Path) -> pathlib.Path:
    shutil.copytree(ROOT / "chip_bench", dst / "chip_bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["data"].update(n_train=512, n_test=128)
        cfg["federation"]["num_clients"] = 4
        if cfg["federation"].get("fused_chunk"):
            cfg["federation"]["fused_chunk"] = 1
        (dst / c["file"]).write_text(json.dumps(cfg))
    for p in (dst / "chip_bench" / "traffic").glob("*.json"):
        tr = json.loads(p.read_text())
        tr["federation"]["rounds"] = 2
        p.write_text(json.dumps(tr))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def add_mesh_cell(root: pathlib.Path, limit: float = 0.05) -> None:
    """The 1,024-client AFL sharded over four chips, as a cell of the tiny
    root: a workload entry and a limits file."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": MESH, "config": "cnn.fmnist.c1024",
                               "traffic": "afl_mesh4", "chips": 4,
                               "why": "the client axis over four chips"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "chip_bench" / "limits" / f"{MESH}.json").write_text(
        json.dumps({"limits": {"param_gap": {"limit": limit}}}))
