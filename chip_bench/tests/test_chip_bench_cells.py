"""`BENCHMARK.json` resolves, cell by cell, to files found by name; a
cell added as files alone loads; off a TPU the command prints no
result."""
import json
import os
import re
import subprocess
import sys

import chip_bench_tiny
import pytest

from chip_bench import cells

ROOT = chip_bench_tiny.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_to_its_files(name):
    cell = cells.load(name)
    assert cell.chips in (1, 4)
    spec = cell.spec
    assert spec["federation"]["strategy"] in ("hfl", "afl", "cfl")
    from chip_bench import compare
    assert set(cell.limits) <= set(compare.NUMBERS)
    assert cell.limits and all(v["limit"] > 0 for v in cell.limits.values())
    kw = cell.fl_kwargs(seed=3)
    assert kw["engine"] == "fused" and kw["seed"] == 3
    assert {m["name"] for m in cell.end_to_end} >= {
        "setup_s", "client_samples_per_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]))


def test_benchmark_file_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chip_bench"]
    assert BENCH["command"][1].startswith("chip_bench/")
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= len(BENCH["workloads"]) // 2
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layer_metrics = {m["name"] for m in BENCH["per_layer"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert (ROOT / "chip_bench" / "metrics" / f"{m['name']}.py").is_file()
    for w in BENCH["workloads"]:
        listed = [m for m in BENCH["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])]
        assert listed, w["name"]
    assert layer_metrics


def test_a_cell_added_as_files_alone_loads(tmp_path):
    root = chip_bench_tiny.make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / "chip_bench" / "traffic" / "afl_half.json").write_text(
        json.dumps({"why": "half participation",
                    "federation": {"strategy": "afl", "participation": 0.5,
                                   "local_epochs": 1, "rounds": 3}}))
    (root / "chip_bench" / "limits" / "mnist_c10.afl_half.json").write_text(
        json.dumps({"limits": {"param_gap": {"limit": 0.05}}}))
    (root / "chip_bench" / "metrics" / "runs.count.py").write_text(
        "def read(ctx):\n    return float(len(ctx['runs']))\n")
    bench["workloads"].append({"name": "mnist_c10.afl_half",
                               "config": "cnn.mnist.c10",
                               "traffic": "afl_half", "chips": 1,
                               "why": "a later cell"})
    bench["per_layer"].append({"name": "runs.count", "unit": "runs",
                               "better": "higher", "source": "host_clock",
                               "layer": "run driver",
                               "moves": "client_samples_per_s",
                               "workloads": ["mnist_c10.afl_half"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load("mnist_c10.afl_half", root=root)
    assert cell.spec["federation"]["participation"] == 0.5
    assert cell.spec["federation"]["num_clients"] == 4
    assert [m["name"] for m in cell.per_layer][-1] == "runs.count"
    assert cell.reader("runs.count")({"runs": [1, 2]}) == 2.0
    with pytest.raises(KeyError):
        cells.load("no.such_cell", root=root)


def _run_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chip_bench/run.py", "--workload", "mnist_c10.hfl",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metrics" in obj:
            return True
    return False


def test_off_a_tpu_the_command_prints_no_result():
    p = _run_cmd(ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "TPU" in p.stderr


def test_without_the_program_the_command_prints_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files has no system under test."""
    import shutil
    shutil.copytree(ROOT / "chip_bench", tmp_path / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run_cmd(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not _has_result(p.stdout)
