"""Faults planted in the program under the benchmark, each a context
manager that patches one function of `repro` and restores it:

* `unchanged`     — the round's aggregation returns its state unchanged;
* `half_left_out` — each aggregation takes the mean (or median) over the
                    first half of the uploads and leaves the rest out;
* `no_exchange`   — the mesh aggregation skips the exchange between
                    chips: each shard averages its own clients;
* `altered`       — the aggregated model is altered where it is produced
                    (scaled by 1.05).

Run as a script (`python chip_bench_faults.py ROOT CELL SEED FAULT...`)
it drives the harness once per fault and prints `FAULT correct` lines:
the mesh faults need host devices set before JAX starts.
"""
from __future__ import annotations

import contextlib
import sys

import chip_bench_tiny  # noqa: F401  (puts the checkout on sys.path)


@contextlib.contextmanager
def _patched(obj, name, fn):
    """Swap `obj.name` for `fn(original)`; JAX's trace caches are cleared
    on the way in and out, so jitted callers pick the swap up and drop
    it again."""
    import jax
    orig = getattr(obj, name)
    setattr(obj, name, fn(orig))
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(obj, name, orig)
        jax.clear_caches()


def unchanged():
    from repro.core import engine, strategies
    stack = contextlib.ExitStack()
    for cls in (strategies.HFLStrategy, strategies.AFLStrategy):
        stack.enter_context(_patched(
            cls, "scan_aggregate",
            lambda orig: lambda self, fx, carry, xs, uploads: carry))

    def cfl(orig):
        def f(model, *a, **k):
            _, losses, accs = orig(model, *a, **k)
            return model, losses, accs
        return f
    stack.enter_context(_patched(engine, "cfl_round_scan", cfl))
    return stack


def half_left_out():
    import jax
    import jax.numpy as jnp
    from repro.core import aggregation
    from repro.kernels import ops
    stack = contextlib.ExitStack()

    def fedavg(orig):
        def f(stacked, weights, **k):
            h = max(1, stacked.shape[0] // 2)
            w = weights[:h]
            return orig(stacked[:h], w / jnp.sum(w), **k)
        return f

    def median(orig):
        return lambda stacked, **k: orig(
            stacked[:max(1, stacked.shape[0] // 2)], **k)

    def mesh(orig):
        # the clients of the upper half of the shards are left out
        def f(stacked, weights, *, axis="data"):
            w = jnp.asarray(weights, jnp.float32)
            low = (jax.lax.axis_index(axis)
                   < jax.lax.axis_size(axis) // 2)
            return orig(stacked, jnp.where(low, w, 0.0), axis=axis)
        return f
    stack.enter_context(_patched(ops, "fedavg_aggregate", fedavg))
    stack.enter_context(_patched(ops, "median_aggregate", median))
    stack.enter_context(_patched(aggregation, "mesh_fedavg_stacked", mesh))
    return stack


def no_exchange():
    import jax
    import jax.numpy as jnp
    from repro.core import aggregation

    def mesh(orig):
        def f(stacked, weights, *, axis="data"):
            w = jnp.asarray(weights, jnp.float32)
            w = w / jnp.sum(w)
            return jax.tree.map(
                lambda p: jnp.tensordot(w, p.astype(jnp.float32), axes=1)
                .astype(p.dtype), stacked)
        return f
    return _patched(aggregation, "mesh_fedavg_stacked", mesh)


def altered():
    from repro.core import aggregation
    from repro.kernels import ops
    stack = contextlib.ExitStack()
    stack.enter_context(_patched(
        ops, "tree_unravel",
        lambda orig: lambda template, vec: orig(template, vec * 1.05)))

    def mesh(orig):
        def f(*a, **k):
            import jax
            return jax.tree.map(lambda p: p * 1.05, orig(*a, **k))
        return f
    stack.enter_context(_patched(aggregation, "mesh_fedavg_stacked", mesh))
    return stack


FAULTS = {"unchanged": unchanged, "half_left_out": half_left_out,
          "no_exchange": no_exchange, "altered": altered}


def drive(root, cell_name, seed, fault):
    """One harness run of the cell with `fault` planted (None: sound)."""
    from chip_bench import cells, run
    cell = cells.load(cell_name, root=root)
    with (FAULTS[fault]() if fault else contextlib.nullcontext()):
        return run.run_cell(cell, seed, 0.0, False)


if __name__ == "__main__":
    import pathlib
    root, cell_name, seed = pathlib.Path(sys.argv[1]), sys.argv[2], \
        int(sys.argv[3])
    for fault in sys.argv[4:]:
        res = drive(root, cell_name, seed, None if fault == "sound" else fault)
        print(f"FAULT {fault} {res['correct']} "
              f"{ {k: v['value'] for k, v in res['checks'].items()} }",
              flush=True)
