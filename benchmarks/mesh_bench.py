"""Mesh-sharded fused executor benchmark (DESIGN.md §11).

Measures the fused round throughput of the SAME run single-device vs
sharded over N forced host devices, and prints one JSON document on the
last stdout line — the ci_bench "mesh" section and `make mesh-demo`
both consume it.

Standalone by necessity: `--xla_force_host_platform_device_count` must
be set before jax is first imported, so this module sets XLA_FLAGS at
the top of `main` and only then imports anything that pulls in jax.
Run it as its own process (the ci_bench caller does):

    PYTHONPATH=src python -m benchmarks.mesh_bench --devices 8

On a real multi-core host the sharded run parallelizes local training
across shards; on an oversubscribed CI container the N fake devices
share the same cores and the measurement instead tracks the COST of the
shard_map partitioning (collective dispatch, smaller fusion windows).
The ci_bench floor is calibrated to the latter (see MESH_RATIO_FLOOR
there): it guards the sharded path staying within a constant factor of
single-device, not a speedup.
"""
import argparse
import json
import os
import sys


def measure(devices, clients, rounds, strategy="afl", chunk=0):
    """{single,sharded} rounds/s for one fused config. Import-safe only
    after XLA_FLAGS is set (see module docstring)."""
    from repro.core.fl_types import FLConfig
    from repro.core.simulation import FederatedSimulation
    from repro.data.synthetic import mnist_like

    ds = mnist_like(n_train=clients * 8, n_test=128)
    per = {}
    for label, mesh in (("single", 0), ("sharded", devices)):
        fl = FLConfig(strategy=strategy, num_clients=clients,
                      num_groups=devices, participation=1.0,
                      rounds=rounds, local_epochs=1, local_batch_size=8,
                      lr=0.05, seed=0, engine="fused", mesh_devices=mesh,
                      fused_chunk=chunk)
        per[label] = min(FederatedSimulation(fl, ds).run().build_time_s
                         for _ in range(2)) / rounds
    return {
        "devices": devices, "clients": clients, "rounds": rounds,
        "strategy": strategy,
        "single_round_s": per["single"],
        "sharded_round_s": per["sharded"],
        "single_rounds_per_s": 1.0 / per["single"],
        "sharded_rounds_per_s": 1.0 / per["sharded"],
        "sharded_single_ratio": per["single"] / per["sharded"],
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--strategy", default="afl",
                    choices=("afl", "hfl", "fedprox", "fedavgm",
                             "fedadam"))
    ap.add_argument("--chunk", type=int, default=0,
                    help="FLConfig.fused_chunk for both runs")
    args = ap.parse_args(argv)

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
        f"device_count={args.devices}").strip()
    if "jax" in sys.modules:        # the flag above would be a silent no-op
        raise RuntimeError(
            "benchmarks.mesh_bench must run in its own process: jax was "
            "imported before the forced-device-count flag could be set")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    doc = measure(args.devices, args.clients, args.rounds,
                  strategy=args.strategy, chunk=args.chunk)
    print(f"mesh_bench devices={args.devices} clients={args.clients}: "
          f"single {doc['single_round_s']:.3f}s/round, sharded "
          f"{doc['sharded_round_s']:.3f}s/round "
          f"(ratio {doc['sharded_single_ratio']:.2f}x)", file=sys.stderr)
    print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
