"""Readings that the limits of a cell's comparison are set from.

    python3 chip_bench/calibrate.py --workload mnist_c10.hfl \
        --seeds 101,102,103

For each seed, in one process: one whole federation run of the cell
through the same entry as the benchmark's window, the plain reference
in float32 at HIGHEST precision, and the control (the same reference
in bfloat16). Prints one JSON line per seed with the compared numbers
of the program against the reference (`program`) and of the control
against the reference (`control`), then a summary: the largest program
reading (the lower reading) and the smallest control reading (the upper
reading) of each number. Needs the chip, like `run.py`.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chip_bench import cells as cells_mod  # noqa: E402
from chip_bench.run import enable_compile_cache  # noqa: E402


def readings(cell, seed):
    import jax.numpy as jnp
    from chip_bench import compare
    from chip_bench import run as run_mod
    from chip_bench.reference import federation as ref_mod
    model = cell.family.reference_model()
    t0 = time.perf_counter()
    dataset = cell.family.render(cell.config["data"], seed)
    t1 = time.perf_counter()
    prog = run_mod.one_run(cell, dataset, seed, trace=False)
    gc.collect()
    t2 = time.perf_counter()
    ref = ref_mod.run(cell.spec, dataset, seed, model)
    t3 = time.perf_counter()
    ctrl = ref_mod.run(cell.spec, dataset, seed, model, dtype=jnp.bfloat16)
    t4 = time.perf_counter()
    ctrl_run = {"round_loss": ctrl["round_loss"],
                "round_test_acc": ctrl["round_test_acc"],
                "final": ctrl["final"]}
    return {"seed": seed, "program": compare.gaps(prog, ref),
            "control": compare.gaps(ctrl_run, ref),
            "leaf_change": {"program": compare.leaf_norms(prog, ref),
                            "reference": compare.leaf_norms(ref, ref),
                            "control": compare.leaf_norms(ctrl_run, ref)},
            "round_loss": {"program": list(map(float, prog["round_loss"])),
                           "reference": list(map(float, ref["round_loss"])),
                           "control": list(map(float, ctrl["round_loss"]))},
            "round_test_acc": {
                "program": list(map(float, prog["round_test_acc"])),
                "reference": list(map(float, ref["round_test_acc"])),
                "control": list(map(float, ctrl["round_test_acc"]))},
            "seconds": {"data": t1 - t0, "program": t2 - t1,
                        "reference": t3 - t2, "control": t4 - t3}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = cells_mod.load(args.workload)
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("calibrate needs the cell's chips", file=sys.stderr)
        return 2
    rows = []
    for s in args.seeds.split(","):
        row = readings(cell, int(s) & 0xFFFFFFFF)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {n: {"lower": max(r["program"][n] for r in rows),
                   "upper": min(r["control"][n] for r in rows)}
               for n in rows[0]["program"]}
    print(json.dumps({"workload": cell.name, "seeds": len(rows),
                      "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
