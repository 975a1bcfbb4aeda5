"""Smoke run of the federation on a TPU: the quickest proof that the main
path still starts on the chip.

    python chip_smoke.py              # one chip: phases A, B and C
    python chip_smoke.py --chips 4    # four chips: the mesh-sharded
                                      # fused executor against one device

It drives the paper CNN at full width (28x28x1 inputs, 10 classes)
through the public entry points (`repro.api.FederatedSimulation`,
`repro.api.FLConfig`, `repro.data.synthetic.mnist_like`) on an
MNIST-shaped set of 60,000 train / 10,000 test images generated from
`--seed`, in this one process (a chip belongs to one process at a
time, so nothing here starts a child).

* Phase A: the paper's three architectures (hfl, afl, cfl) on the fused
  engine, 64 clients, 3 rounds; hfl also on the vectorized and loop
  engines, which must agree with the fused run.
* Phase B: every federated-learning kernel natively on the device —
  sign-flip attackers under the median defense (`robust_agg`), AFL
  gossip under churn (`gossip_mix`), plain HFL/AFL (`fedavg_agg`)
  inside the fused scan, one qsgd round for the codec path, and each
  kernel called directly against its jnp reference (`dequant_agg` has
  no in-scan caller: the qsgd codec decodes to dense uploads).
* Phase C: fused AFL with 1,024 clients, chunked local training.

With `--chips 4` it runs only fused afl and hfl with the client axis
sharded over four chips (`mesh_devices=4`) and the same runs on one
device, one round each, and compares them.

Lines before the last are a record of each phase (device kind, warmup
i.e. compile seconds, steady seconds, accuracy), not a benchmark. The
last line is `{"ok": true, "device": {...}}`. Any failed check raises,
so the exit code is non-zero and no such line is printed; so does a
run where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.data.synthetic import mnist_like  # noqa: E402
from repro.kernels import (comm_agg, fedavg_agg, gossip_mix, ref,  # noqa: E402
                           robust_agg)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

N_TRAIN, N_TEST = 60_000, 10_000
CLIENTS, ROUNDS, GROUPS = 64, 3, 4
# FLConfig's default lr of 0.05 (momentum 0.9) overshoots at this shape:
# on XLA:CPU fused hfl and afl climb back to a loss of 2.30 and chance
# accuracy by round 3. At 0.01 they learn steadily (hfl round losses
# 2.26, 1.49, 0.29; test accuracy 0.98).
LR = 0.01
BIG_CLIENTS, BIG_ROUNDS, BIG_CHUNK, BIG_BATCH = 1024, 2, 128, 16
CHANCE = 0.1
# a native kernel against its jnp reference: both accumulate in f32,
# in different orders (the median selects values, so it is exact)
KERNEL_TOL = 1e-5
# engines against the fused run, after 3 rounds of SGD: the loop engine
# convolves with lax.conv, the stacked engines with per-client GEMMs,
# and the TPU runs f32 convolutions and matmuls at its default
# precision (bf16 multiplication passes), so the three round their
# floats differently and SGD carries the difference forward
ENGINE_LOSS_TOL, ENGINE_ACC_TOL = 5e-2, 3e-2
# the sharded fused run against one device (tests/test_mesh_fused.py
# pins the same bound on host devices): the same per-client math, only
# the aggregation sums run as a psum across chips. One round isolates
# that summation order from SGD's amplification of float rounding over
# later rounds (on 4 host devices at 16,384 samples, AFL agrees to
# 2.4e-7 in round 1 but its round-3 loss moves by 1.3e-4)
MESH_TOL, MESH_ROUNDS = 1e-5, 1
# accuracies of the same pair: a sample whose logits sit on a near-tie
# flips its argmax on a 1e-7 change, and each flip moves an accuracy by
# one sample's share (a TPU v5e moved the round train accuracy by
# 3.4e-4 while losses agreed to 7.2e-7)
MESH_ACC_TOL = 1e-3


def tpu_device():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind})")
    return dev


def fl_config(**kw):
    base = dict(num_clients=CLIENTS, rounds=ROUNDS, num_groups=GROUPS,
                local_epochs=1, local_batch_size=32, lr=LR,
                engine="fused")
    base.update(kw)
    return api.FLConfig(**base)


def run(ds, **kw):
    sim = api.FederatedSimulation(fl_config(**kw), ds)
    return sim, sim.run()


def report(phase, dev, res, **extra):
    print(json.dumps({"phase": phase, "device_kind": dev.device_kind,
                      "warmup_s": res.warmup_time_s,
                      "steady_s": res.build_time_s,
                      "test_accuracy": res.test_accuracy, **extra}),
          flush=True)


def check_learns(name, res):
    losses = np.asarray(res.round_train_loss)
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{name}: non-finite round losses {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{name}: last-round train loss {losses[-1]} "
                             f"is not below the first {losses[0]}")
    if not res.test_accuracy > CHANCE:
        raise AssertionError(f"{name}: test accuracy {res.test_accuracy} "
                             f"is not above chance ({CHANCE})")


def check_native(name, sim, kernels):
    """The run's compiled scan holds each named Pallas kernel: an
    instruction named after the kernel's jitted wrapper
    (`%fedavg_agg.3 = ... custom-call(...)`) that calls
    `tpu_custom_call`."""
    lines = sim.fused_program.as_text().splitlines()
    for k in kernels:
        pat = re.compile(r"%" + k + r"(\.\d+)? = .*tpu_custom_call")
        if not any(pat.search(line) for line in lines):
            raise AssertionError(f"{name}: no {k} kernel in the fused scan")


def max_diff(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


def kernel_parity(C, N, seed):
    """Each FL kernel's native call against its jnp reference at (C, N)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k[0], (C, N), jnp.float32)
    w = jax.nn.softmax(jax.random.normal(k[1], (C,)))
    mix = jax.random.uniform(k[2], (C, C), jnp.float32)
    mix = mix / jnp.sum(mix, axis=1, keepdims=True)
    scale = jnp.max(jnp.abs(x), axis=1) / 127.0
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127).astype(jnp.int8)
    diffs = {
        "fedavg_agg": max_diff(fedavg_agg.fedavg_agg(x, w),
                               ref.fedavg_agg_ref(x, w)),
        "dequant_agg": max_diff(comm_agg.dequant_agg(q, scale, w),
                                comm_agg.dequant_agg_jnp(q, scale, w)),
        "gossip_mix_agg": max_diff(gossip_mix.gossip_mix_agg(x, mix),
                                   gossip_mix.gossip_mix_jnp(x, mix)),
        "median_agg": max_diff(robust_agg.median_agg(x),
                               ref.median_ref(x)),
    }
    for name, d in diffs.items():
        tol = 0.0 if name == "median_agg" else KERNEL_TOL
        if not d <= tol:
            raise AssertionError(f"{name} at C={C}, N={N}: native vs "
                                 f"reference differ by {d} > {tol}")
    return diffs


def phase_a(ds, dev, seed):
    """The paper's three architectures, fused; hfl on every engine.
    Returns the model's flattened parameter count."""
    fused = {}
    for strategy in ("hfl", "afl", "cfl"):
        sim, res = run(ds, strategy=strategy, seed=seed)
        check_learns(f"A/{strategy}", res)
        if strategy != "cfl":
            check_native(f"A/{strategy}", sim, ["fedavg_agg"])
        fused[strategy] = res
        report(f"A/{strategy}/fused", dev, res)
    model_dim = sim.model_dim
    for engine in ("vectorized", "loop"):
        _, res = run(ds, strategy="hfl", engine=engine, seed=seed)
        check_learns(f"A/hfl/{engine}", res)
        d_loss = float(np.max(np.abs(np.asarray(res.round_train_loss)
                                     - fused["hfl"].round_train_loss)))
        d_acc = abs(res.test_accuracy - fused["hfl"].test_accuracy)
        if not (d_loss <= ENGINE_LOSS_TOL and d_acc <= ENGINE_ACC_TOL):
            raise AssertionError(
                f"hfl {engine} vs fused: round loss differs by {d_loss} "
                f"(bound {ENGINE_LOSS_TOL}), test accuracy by {d_acc} "
                f"(bound {ENGINE_ACC_TOL})")
        report(f"A/hfl/{engine}", dev, res, d_loss_vs_fused=d_loss,
               d_acc_vs_fused=d_acc)
    return model_dim


def phase_b(ds, dev, seed, model_dim):
    """Every FL kernel natively on the device: three inside the fused
    scan, and each called directly against its jnp reference."""
    for name, kw, kernels in [
        ("median", dict(strategy="afl", participation=1.0,
                        attack="sign_flip", defense="median"),
         ["trimmed_mean_agg"]),
        ("gossip_churn", dict(strategy="afl", participation=1.0,
                              afl_mode="gossip", fault_profile="churn"),
         ["gossip_mix_agg"]),
        ("qsgd", dict(strategy="hfl", codec="qsgd", quant_bits=8,
                      rounds=1), ["fedavg_agg"]),
    ]:
        sim, res = run(ds, seed=seed, **kw)
        losses = np.asarray(res.round_train_loss)
        if not np.all(np.isfinite(losses)):
            raise AssertionError(f"B/{name}: non-finite losses {losses}")
        check_native(f"B/{name}", sim, kernels)
        report(f"B/{name}", dev, res)
    diffs = kernel_parity(CLIENTS, model_dim, seed)
    print(json.dumps({"phase": "B/kernel_parity", "device_kind":
                      dev.device_kind, "C": CLIENTS, "N": model_dim,
                      "max_abs_diff": diffs}), flush=True)


def phase_c(ds, dev, seed, model_dim):
    """The large federation: 1,024 clients, chunked local training."""
    sim, res = run(ds, strategy="afl", participation=1.0,
                   num_clients=BIG_CLIENTS, rounds=BIG_ROUNDS,
                   fused_chunk=BIG_CHUNK, local_batch_size=BIG_BATCH,
                   seed=seed)
    losses = np.asarray(res.round_train_loss)
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"C: non-finite losses {losses}")
    check_native("C", sim, ["fedavg_agg"])
    report(f"C/afl_{BIG_CLIENTS}c_chunk{BIG_CHUNK}", dev, res)
    diffs = kernel_parity(BIG_CLIENTS, model_dim, seed)
    print(json.dumps({"phase": "C/kernel_parity", "device_kind":
                      dev.device_kind, "C": BIG_CLIENTS, "N": model_dim,
                      "max_abs_diff": diffs}), flush=True)


def carry_devices(shardings):
    """Distinct devices the scan carry's leaves live on, and whether any
    leaf is split across them."""
    leaves = jax.tree.leaves(shardings)
    devices = set().union(*(s.device_set for s in leaves))
    return devices, any(not s.is_fully_replicated for s in leaves)


def mesh_parity(ds, dev, seed, strategy):
    """Fused `strategy` with the client axis sharded over four chips
    against the same run on one device."""
    kw = dict(strategy=strategy, participation=1.0, rounds=MESH_ROUNDS,
              seed=seed)
    single_sim, single = run(ds, **kw)
    mesh_sim, sharded = run(ds, mesh_devices=4, **kw)
    if not np.all(np.isfinite(sharded.round_train_loss)):
        raise AssertionError(f"{strategy}: non-finite sharded losses")
    tols = {"round_train_loss": MESH_TOL, "final_params": MESH_TOL,
            "round_train_acc": MESH_ACC_TOL, "round_test_acc": MESH_ACC_TOL}
    d = {k: float(np.max(np.abs(np.asarray(getattr(single, k))
                                - np.asarray(getattr(sharded, k)))))
         for k in ("round_train_loss", "round_train_acc", "round_test_acc")}
    strat = mesh_sim.strategy
    d["final_params"] = max(
        max_diff(a, b) for a, b in zip(
            jax.tree.leaves(strat.round_model(single_sim.final_state)),
            jax.tree.leaves(strat.round_model(mesh_sim.final_state))))
    report(f"mesh/{strategy}", dev, sharded, max_abs_diff=d,
           single_warmup_s=single.warmup_time_s,
           single_steady_s=single.build_time_s)
    if not all(d[k] <= tol for k, tol in tols.items()):
        raise AssertionError(f"{strategy}: sharded vs single device "
                             f"differ by {d}, bounds {tols}")
    program = mesh_sim.fused_program
    carry_in = program.input_shardings[0][0]
    carry_out = program.output_shardings[0]
    for label, sh in (("input", carry_in), ("output", carry_out)):
        devices, split = carry_devices(sh)
        if len(devices) != 4 or not split:
            raise AssertionError(
                f"{strategy}: scan carry {label} spans "
                f"{len(devices)} device(s), split={split}")
    if "all-reduce" not in program.as_text():
        raise AssertionError(f"{strategy}: no all-reduce in the "
                             f"sharded program")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev = tpu_device()
    ds = mnist_like(seed=args.seed, n_train=N_TRAIN, n_test=N_TEST)
    if args.chips == 4:
        if len(jax.devices()) < 4:
            raise RuntimeError(f"--chips 4 needs 4 devices; JAX found "
                               f"{len(jax.devices())}")
        for strategy in ("afl", "hfl"):
            mesh_parity(ds, dev, args.seed, strategy)
    else:
        model_dim = phase_a(ds, dev, args.seed)
        phase_b(ds, dev, args.seed, model_dim)
        phase_c(ds, dev, args.seed, model_dim)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
