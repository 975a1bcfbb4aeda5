"""Benchmark harness — one section per paper table/figure + kernel
micro-benches + the dry-run roofline table.

    PYTHONPATH=src python -m benchmarks.run [--scale quick|full|smoke]

Sections:
  paper_table1   — HFL/AFL/CFL accuracy + build/classification time
  paper_table2   — precision/recall/F1/accuracy
  paper_fig9_11  — per-round accuracy/loss curves (CSV rows)
  paper_fig13_14 — derived comparisons (accuracy & efficiency ranking)
  kernels        — micro-bench CSV (name,us_per_call,derived), including
                   the loop-vs-vectorized engine round-throughput sweep
                   over client counts (8 -> 256 at --scale full) and the
                   robust trimmed-mean aggregation sweep (8 -> 256
                   clients, DESIGN.md §8)
  scenarios      — the registry's CI smoke grid (core/scenarios.py), CSV
                   rows in the stable result schema's key metrics
  roofline       — per (arch x shape x mesh) terms from the dry-run cache
"""
import argparse
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="quick",
                    choices=["smoke", "quick", "full"])
    ap.add_argument("--skip-study", action="store_true",
                    help="reuse cached paper-study results if present")
    ap.add_argument("--scenarios", default="ci",
                    help="comma-separated scenario names, 'ci' for the "
                         "smoke grid, or 'none' to skip the section")
    args = ap.parse_args()

    from benchmarks import kernel_bench, paper_tables, roofline_table

    print("== paper_table1 / paper_table2 "
          f"(scale={args.scale}) ==", flush=True)
    import json
    import os
    cache = f"experiments/paper_repro/results_{args.scale}.json"
    if args.skip_study and os.path.exists(cache):
        with open(cache) as f:
            payload = json.load(f)
        t1, t2 = payload["table1"], payload["table2"]
        claims = payload["claims"]
        curves = payload["curves"]
    else:
        results = paper_tables.run_study(args.scale)
        paper_tables.save_results(results, scale=args.scale)
        t1 = paper_tables.table1(results)
        t2 = paper_tables.table2(results)
        claims = {k: bool(v)
                  for k, v in paper_tables.claims_check(results).items()}
        curves = {f"{r.dataset}/{r.strategy}":
                  {"train_acc": r.round_train_acc,
                   "train_loss": r.round_train_loss,
                   "test_acc": r.round_test_acc} for r in results}

    print("name,dataset,env,train_acc,test_acc,build_s,class_s")
    for row in t1:
        print("paper_table1," + ",".join(
            f"{x:.3f}" if isinstance(x, float) else str(x) for x in row))
    print("name,dataset,env,precision,recall,f1,accuracy")
    for row in t2:
        print("paper_table2," + ",".join(
            f"{x:.3f}" if isinstance(x, float) else str(x) for x in row))

    print("\n== paper_fig9_11 (curves: name,ds/env,round,train_acc,"
          "train_loss,test_acc) ==")
    for key, c in curves.items():
        for i, (ta, tl, te) in enumerate(zip(c["train_acc"],
                                             c["train_loss"],
                                             c["test_acc"])):
            print(f"paper_fig9_11,{key},{i},{ta:.3f},{tl:.3f},{te:.3f}")

    print("\n== paper_fig13_14 (claims / derived rankings) ==")
    for k, v in claims.items():
        print(f"paper_fig13_14,{k},{'PASS' if v else 'FAIL'}")

    print("\n== kernels + engine sweep (name,us_per_call,derived) ==")
    kernel_bench.main(args.scale)

    if args.scenarios != "none":
        from repro.core import scenarios as scen
        todo = (list(scen.CI_SMOKE_GRID) if args.scenarios == "ci"
                else args.scenarios.split(","))
        print("\n== scenarios (name,scenario,strategy/topology/engine,"
              "test_acc,f1,build_s,rounds_per_s) ==")
        for name in todo:
            res = scen.run_scenario(name)
            s, m, t = res["spec"], res["metrics"], res["timing"]
            print(f"scenario,{name},{s['strategy']}/{s['topology']}/"
                  f"{s['engine']},{m['test_accuracy']:.3f},{m['f1']:.3f},"
                  f"{t['build_time_s']:.2f},{t['rounds_per_s']:.3f}")

    print("\n== roofline (from experiments/dryrun cache) ==")
    roofline_table.main()


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
