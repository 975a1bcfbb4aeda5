"""The benchmark's cost model: the work a cell asks for, counted from its
own sizes, whatever implements it. Nothing here knows a model: the FLOPs
of one sample's forward pass come from the configuration's family
(`families/<arch>.py`, `forward_flops`).

* `run_work` — client-samples trained and samples evaluated in one whole
  federation run of a cell, and the model FLOPs of both. Backward is
  counted as twice the forward, so one trained sample costs 3x forward.
* `fedavg_bytes` / `median_bytes` — compulsory HBM bytes of one
  aggregation over a (C, N) float32 stack: read the stack once, read the
  C weights (FedAvg), write the N-vector.
* `peaks` — the row of `peaks.json` for a device kind; an unknown kind
  raises.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")
F32 = 4


def participants(fed):
    """Clients trained per round."""
    C = fed["num_clients"]
    if fed["strategy"] == "afl":
        return max(1, int(round(fed.get("participation", 0.5) * C)))
    return C


def shard_sizes(n_train, C):
    """IID shard sizes: `np.array_split` of n_train into C parts."""
    base, extra = divmod(n_train, C)
    return [base + 1] * extra + [base] * (C - extra)


def run_work(spec, forward_flops):
    """Work of one whole federation run of a cell spec (the merged
    config + traffic dict of `cells.Cell.spec`), whose model's forward
    pass costs `forward_flops` a sample."""
    fed, data = spec["federation"], spec["data"]
    C, B = fed["num_clients"], fed["local_batch_size"]
    sizes = shard_sizes(data["n_train"], C)
    nb = min(sizes) // B
    k = participants(fed)
    R, E = fed["rounds"], fed.get("local_epochs", 1)
    samples = k * E * nb * B * R
    n_eval = min(512, min(sizes))
    # in-scan evaluation per round: every participant's local model on
    # its eval shard, and the round model on the whole test set
    eval_images = R * (k * n_eval + data["n_test"])
    return {"client_samples": samples, "eval_images": eval_images,
            "train_flops": 3 * forward_flops * samples,
            "eval_flops": forward_flops * eval_images}


def fedavg_bytes(C, N):
    return (C * N + C + N) * F32


def median_bytes(C, N):
    return (C * N + N) * F32


def peaks(device_kind):
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}")
    return table[device_kind]
