"""Beyond-paper ablation — the paper's future-work direction 1
("Exploring Data Distribution Combinations"): how the three aggregation
strategies degrade as client data shifts from IID to Dirichlet label skew.

    PYTHONPATH=src python -m benchmarks.ablation_noniid

CSV: name,dataset,strategy,partition,test_acc,f1
"""
import json
import os
import sys

import numpy as np

from repro.core.fl_types import FLConfig
from repro.core.simulation import FederatedSimulation
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import mnist_like


def run(n_train=2000, n_test=500, clients=8, rounds=8, seed=0):
    ds = mnist_like(seed=seed, n_train=n_train, n_test=n_test)
    xtr, ytr = ds["train"]
    rows = []
    partitions = {
        "iid": None,
        "dirichlet_1.0": dirichlet_partition(ytr, clients, alpha=1.0,
                                             seed=seed),
        "dirichlet_0.3": dirichlet_partition(ytr, clients, alpha=0.3,
                                             seed=seed),
    }
    for pname, parts in partitions.items():
        for strategy in ("hfl", "afl", "cfl"):
            fl = FLConfig(strategy=strategy, num_clients=clients,
                          num_groups=2, rounds=rounds,
                          local_epochs=2 if strategy != "cfl" else 1,
                          participation=0.5, local_batch_size=32,
                          lr=0.03, momentum=0.9, seed=seed)
            sim = FederatedSimulation(fl, ds)
            if parts is not None:
                sim.parts = parts
                sim.client_data = [(xtr[p], ytr[p]) for p in parts]
                sim.weights = [len(p) for p in parts]
            r = sim.run()
            rows.append((ds["name"], strategy, pname,
                         round(r.test_accuracy, 4), round(r.f1, 4)))
            print(f"ablation_noniid,{ds['name']},{strategy},{pname},"
                  f"{r.test_accuracy:.4f},{r.f1:.4f}", flush=True)
    os.makedirs("experiments/paper_repro", exist_ok=True)
    with open("experiments/paper_repro/ablation_noniid.json", "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
