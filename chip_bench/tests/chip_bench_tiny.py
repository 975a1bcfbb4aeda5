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

SINGLE = ("mnist_c10.hfl", "fmnist_c1024.afl_median")
# the four-chip cell: its client axis sharded over four devices, which a
# CPU test gets only in a child process (`test_chip_bench_mesh_faults.py`)
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
