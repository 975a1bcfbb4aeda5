"""The comparison that decides `correct`: one federation run's results
against the plain reference's run of the same cell and seed.

Three numbers, each held to a limit of the cell's own
(`limits/<cell>.json`, set from chip readings as PERF.md records):

* loss_gap  — the widest relative gap of a round's training loss (the
  mean loss of the last local epoch over the round's participants):
  local training, and through the starting models every aggregation
  before it.
* param_gap — the worst leaf of the final global model, by the gap
  between the norms of its change from the initial model in the run and
  in the reference, against the larger of the reference's norm for that
  leaf and for the median leaf: aggregation and training together.
* model_gap — the same gap of change norms, over the whole flattened
  model at once.
* median_leaf_gap — the median over leaves of the same per-leaf gap:
  steady from seed to seed where the worst leaf is not.
* acc_gap   — the widest absolute gap of a round's test accuracy, the
  in-scan evaluation of the round model on the whole test set.

A cell compares the numbers its limits file names; the others are
reported by `calibrate.py` only.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("loss_gap", "param_gap", "model_gap", "median_leaf_gap",
           "acc_gap")


def gaps(run, ref):
    """run: {"round_loss", "round_test_acc", "final"} from the program;
    ref: the same keys plus "init" from `reference.federation.run`."""
    rl = np.asarray(ref["round_loss"], np.float64)
    pl = np.asarray(run["round_loss"], np.float64)
    if pl.shape != rl.shape or not np.all(np.isfinite(pl)):
        return {n: float("inf") for n in NUMBERS}
    loss_gap = float(np.max(np.abs(pl - rl) / np.abs(rl)))
    acc_gap = float(np.max(np.abs(np.asarray(run["round_test_acc"])
                                  - np.asarray(ref["round_test_acc"]))))
    init = {k: np.asarray(v, np.float64) for k, v in ref["init"].items()}
    fin = {k: np.asarray(v, np.float64) for k, v in ref["final"].items()}
    dr = {k: float(np.linalg.norm(fin[k] - init[k])) for k in init}
    dp = {}
    for k in init:
        leaf = run["final"].get(k)
        if leaf is None or leaf.shape != init[k].shape \
                or not np.all(np.isfinite(leaf)):
            return {"loss_gap": loss_gap, "param_gap": float("inf"),
                    "model_gap": float("inf"),
                    "median_leaf_gap": float("inf"), "acc_gap": acc_gap}
        dp[k] = float(np.linalg.norm(np.asarray(leaf, np.float64) - init[k]))
    med = float(np.median(list(dr.values())))
    leaf_gaps = [abs(dp[k] - dr[k]) / max(dr[k], med) for k in init]
    whole_p = float(np.sqrt(sum(v * v for v in dp.values())))
    whole_r = float(np.sqrt(sum(v * v for v in dr.values())))
    return {"loss_gap": loss_gap, "param_gap": float(max(leaf_gaps)),
            "model_gap": abs(whole_p - whole_r) / whole_r,
            "median_leaf_gap": float(np.median(leaf_gaps)),
            "acc_gap": acc_gap}


def leaf_norms(run, ref):
    """Per leaf: the norm of its change from the initial model."""
    return {k: float(np.linalg.norm(np.asarray(run["final"][k], np.float64)
                                    - np.asarray(v, np.float64)))
            for k, v in ref["init"].items()}


def judge(run_gaps, limits):
    """Worst reading of each compared number over the window's runs,
    beside its limit; and how many runs broke a limit."""
    checks = {}
    failed = 0
    for g in run_gaps:
        if any(not g[n] <= limits[n]["limit"] for n in limits):
            failed += 1
    for n in limits:
        worst = max(g[n] for g in run_gaps) if run_gaps else float("inf")
        checks[n] = {"value": worst, "limit": limits[n]["limit"]}
    return checks, failed
