"""The benchmark's cost model: the work a cell asks for, counted from its
own sizes, whatever implements it.

* `cnn_forward_macs` — multiply-accumulates of one image's forward pass
  through the paper CNN, from the layer shapes (convolutions and the
  dense head; activations and pooling are not counted). Backward is
  counted as twice the forward, so one trained sample costs 3x forward.
* `run_work` — client-samples trained and images evaluated in one whole
  federation run of a cell, and the model FLOPs of both.
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


def cnn_forward_macs(image=(28, 28, 1), filters=(16, 12, 10), kernel=3,
                     pool=2, classes=10):
    """Forward MACs of the paper CNN: SAME 3x3 convs with a 2x2 max-pool
    after each but the last, then a dense layer to `classes`."""
    h, w, cin = image
    macs = 0
    for i, cout in enumerate(filters):
        macs += h * w * cout * kernel * kernel * cin
        cin = cout
        if i < len(filters) - 1:
            h, w = h // pool, w // pool
    return macs + h * w * cin * classes


def cnn_params(image=(28, 28, 1), filters=(16, 12, 10), kernel=3, pool=2,
               classes=10):
    """Parameter count N of the paper CNN (the aggregation row width)."""
    h, w, cin = image
    n = 0
    for i, cout in enumerate(filters):
        n += kernel * kernel * cin * cout + cout
        cin = cout
        if i < len(filters) - 1:
            h, w = h // pool, w // pool
    return n + h * w * cin * classes + classes


def model_macs(model):
    return cnn_forward_macs(tuple(model["image"]), tuple(model["filters"]),
                            model["kernel"], model["pool"], model["classes"])


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


def run_work(spec):
    """Work of one whole federation run of a cell spec (the merged
    config + traffic dict of `cells.Cell.spec`)."""
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
    fwd = model_macs(spec["model"])
    return {"client_samples": samples, "eval_images": eval_images,
            "train_flops": 6 * fwd * samples,
            "eval_flops": 2 * fwd * eval_images}


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
