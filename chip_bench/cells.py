"""Resolve a cell of `BENCHMARK.json` to its files, by name.

A cell (an entry of `workloads`) names a configuration and a traffic
mix. Everything that belongs to one of them sits in a file of its own,
so a cell is added by adding files and entries:

* the configuration's `file` (`configs/<config>.json`): the model, the
  data set and its sizes, the federation's sizes and optimiser;
* `families/<arch>.py`, named by the configuration's `model.arch`: the
  model family — `render(data_spec, seed)`, `forward_flops(model_spec)`,
  `reference_model()` (init, loss and accuracy of its plain reference)
  and `program_kwargs(config)`; configurations share a family by naming
  it;
* `traffic/<traffic>.json`: strategy, defense, attack, mesh, epochs and
  rounds per run;
* `limits/<cell>.json`: the limits of the comparison that decides
  `correct`, with the readings they were set from;
* `metrics/<metric>.py`: one reader per per-layer metric, `read(ctx)`.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
from typing import Any, Dict, List

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH_DIR = "chip_bench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: pathlib.Path

    @property
    def spec(self) -> Dict[str, Any]:
        """The federation as run: config and traffic merged."""
        fed = dict(self.config["federation"])
        fed.update(self.traffic["federation"])
        return {"model": self.config["model"], "data": self.config["data"],
                "federation": fed}

    def fl_kwargs(self, seed: int) -> Dict[str, Any]:
        """Keyword arguments of the program's `FLConfig` for one run."""
        kw = dict(self.spec["federation"])
        kw.update(self.family.program_kwargs(self.config))
        kw.update(engine="fused", seed=seed)
        return kw

    @functools.cached_property
    def family(self):
        """The module `families/<model.arch>.py`, loaded once a cell."""
        arch = self.config["model"]["arch"]
        fdir = self.root / BENCH_DIR / "families"
        path = fdir / f"{arch}.py"
        if not path.is_file():
            known = sorted(p.stem for p in fdir.glob("*.py"))
            raise KeyError(f"no model family {arch!r} in {fdir} "
                           f"(known: {', '.join(known)})")
        return _load(path, "chip_bench_family_" + arch.replace(".", "_"))

    def reader(self, metric: str):
        path = self.root / BENCH_DIR / "metrics" / f"{metric}.py"
        return _load(path, "chip_bench_metric_" + metric.replace(".", "_")).read


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric, cell_name):
    return cell_name in metric.get("workloads", [cell_name])


def load(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {', '.join(sorted(cells))})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    bdir = root / BENCH_DIR
    traffic = json.loads((bdir / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits = json.loads((bdir / "limits" / f"{name}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits["limits"],
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                root=root)
