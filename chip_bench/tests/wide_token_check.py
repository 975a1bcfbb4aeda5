"""The plain reference on a federation whose clients do not fit side by
side on one chip: four clients of the token family `fixtures/token_mlp.py`
at ~500 M float32 parameters each (an embedding and a head of 12,800 x
2,048, ten residual blocks 2,048 <-> 10,944), fed int32 ids, as one AFL
spec through `reference.federation.run`.

    python3 chip_bench/tests/wide_token_check.py --dtype float32
    python3 chip_bench/tests/wide_token_check.py --dtype bfloat16

`float32` is the reference at HIGHEST precision, `bfloat16` the control.
In one process it runs the federation twice (the first run compiles)
and prints one JSON line: the path the byte rule took, the client's
bytes, the device's memory stats (`bytes_limit`, peak bytes in use), the
bytes of the client step's arguments and temporaries as XLA counts them,
the seconds of each run, and the seconds a client step of the second
run (its wall time over the participants' local steps, so local
accuracy, aggregation and the test set are in it). Off a TPU it exits
with code 2.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

WIDE = {
    "model": {"vocab": 12800, "hidden": 2048, "ffn": 10944, "layers": 10},
    "data": {"vocab": 12800, "seq_len": 128, "n_train": 256, "n_test": 128},
    "federation": {"strategy": "afl", "participation": 1.0,
                   "num_clients": 4, "local_batch_size": 8, "lr": 0.005,
                   "momentum": 0.9, "local_epochs": 1, "rounds": 2}}


def token_family():
    path = HERE / "fixtures" / "token_mlp.py"
    spec = importlib.util.spec_from_file_location("token_mlp", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def step_memory(spec, dtype, model, seed):
    """Bytes of the streamed client step's arguments and temporaries, as
    XLA's compile of it for this device counts them."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from chip_bench.reference import federation as ref_mod
    init, loss, accuracy = model
    dt = getattr(jnp, dtype)
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, dt),
                          jax.eval_shape(lambda: init(seed, spec["model"])))
    fed, data = spec["federation"], spec["data"]
    n, L = data["n_train"], data["seq_len"]
    nb = n // fed["num_clients"] // fed["local_batch_size"]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa
    scalar = jax.ShapeDtypeStruct((), dt)
    mem = ref_mod.stream_client.lower(
        params, params, i32(n, L), i32(n, L),
        i32(nb * fed["local_epochs"], fed["local_batch_size"]),
        i32(min(512, n // fed["num_clients"])), scalar,
        jax.ShapeDtypeStruct((), jnp.bool_), scalar, scalar, scalar,
        loss=loss, accuracy=accuracy,
        prec=(lax.Precision.HIGHEST if dtype == "float32"
              else lax.Precision.DEFAULT)).compile().memory_analysis()
    return {"argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes}


def check(spec, dtype, seed=1):
    """Two runs of `spec` through the reference, in `dtype` ("float32"
    or "bfloat16"); the readings above."""
    import jax
    import jax.numpy as jnp
    from chip_bench.reference import federation as ref_mod
    family = token_family()
    model = family.reference_model()
    data = family.render(spec["data"], seed)
    nbytes = ref_mod.client_bytes(model[0], seed, spec["model"])
    dev = jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    fed = spec["federation"]
    steps = (ref_mod.round_size(fed) * fed["rounds"] * fed["local_epochs"]
             * (spec["data"]["n_train"] // fed["num_clients"]
                // fed["local_batch_size"]))
    seconds, res = [], None
    for _ in range(2):
        t0 = time.perf_counter()
        res = ref_mod.run(spec, data, seed, model,
                          dtype=getattr(jnp, dtype))
        seconds.append(time.perf_counter() - t0)
    stats = dev.memory_stats() or {}
    return {"dtype": dtype, "device": dev.device_kind,
            "step_memory": step_memory(spec, dtype, model, seed),
            "memory_stats": stats,
            "path": ("stacked" if ref_mod.stacked(spec, nbytes, limit)
                     else "streamed"),
            "client_bytes": nbytes,
            "bytes_limit": limit,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "run_seconds": seconds, "client_steps": steps,
            "seconds_a_client_step": seconds[1] / steps,
            "round_loss": [float(v) for v in res["round_loss"]],
            "round_test_acc": [float(v) for v in res["round_test_acc"]]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dtype", choices=("float32", "bfloat16"),
                    required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("wide_token_check needs a TPU", file=sys.stderr)
        return 2
    print(json.dumps(check(WIDE, args.dtype)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
