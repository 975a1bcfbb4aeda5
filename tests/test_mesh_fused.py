"""Mesh-sharded fused executor (DESIGN.md §11, ISSUE 6 acceptance).

Subprocess tests (XLA_FLAGS must precede the jax import): the fused run
with the client axis sharded over 8 forced host devices must match the
single-device fused run to float tolerance — curves AND final metrics —
for all three paper architectures (HFL hierarchical, AFL star, AFL
gossip), and HFL's tier-1 event must be provably shard-local (ZERO
collectives in its compiled HLO; only tier 2 communicates).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

PARITY_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, {src!r})
    import numpy as np
    from repro.core.fl_types import FLConfig
    from repro.core.simulation import FederatedSimulation
    from repro.data.synthetic import mnist_like

    ds = mnist_like(seed=0, n_train=1024, n_test=256)

    def run(mesh, chunk):
        fl = FLConfig(strategy={strategy!r}, num_clients=16, rounds=3,
                      num_groups=8, local_epochs=1, local_batch_size=16,
                      lr=0.05, seed=0, participation=1.0, engine="fused",
                      afl_mode={mode!r}, mesh_devices=mesh,
                      fused_chunk=chunk, attack={attack!r},
                      attack_fraction=0.25, attack_scale=0.5)
        return FederatedSimulation(fl, ds).run_fused()

    single = run(0, 0)
    sharded = run(8, {chunk})
    print(json.dumps({{
        "d_acc": max(abs(a - b) for a, b in zip(
            single.round_train_acc, sharded.round_train_acc)),
        "d_loss": max(abs(a - b) for a, b in zip(
            single.round_train_loss, sharded.round_train_loss)),
        "d_test": max(abs(a - b) for a, b in zip(
            single.round_test_acc, sharded.round_test_acc)),
        "d_final_test": abs(single.test_accuracy - sharded.test_accuracy),
        "d_final_train": abs(single.train_accuracy
                             - sharded.train_accuracy),
        "d_f1": abs(single.f1 - sharded.f1),
    }}))
""")


@pytest.mark.parametrize("strategy,mode,attack,chunk", [
    ("hfl", "fedavg", "none", 0),       # hierarchical: local tier 1 +
                                        # tier-2 psum
    ("afl", "fedavg", "none", 0),       # star: one weighted psum
    ("afl", "gossip", "none", 0),       # ring: masked all-to-all mix
    ("hfl", "fedavg", "gauss", 0),      # per-client corruption shards
                                        # cleanly (absolute-id keys)
    ("afl", "fedavg", "none", 1),       # memory-bounded chunked training
                                        # under the mesh
])
def test_sharded_fused_matches_single_device(strategy, mode, attack, chunk):
    code = PARITY_SNIPPET.format(src=SRC, strategy=strategy, mode=mode,
                                 attack=attack, chunk=chunk)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["d_acc"] <= 1e-5, d
    assert d["d_loss"] <= 1e-4, d
    assert d["d_test"] <= 1e-5, d
    assert d["d_final_test"] <= 1e-5, d
    assert d["d_final_train"] <= 1e-5, d
    assert d["d_f1"] <= 1e-5, d


# ---------------------------------------------------------------------------
# a shard lowers its slice of the stack as one device lowers the whole
# ---------------------------------------------------------------------------

LOWERING_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, {src!r})
    from repro.core.fl_types import FLConfig
    from repro.core.simulation import FederatedSimulation
    from repro.data.synthetic import mnist_like
    from repro.models import cnn

    # grouped for stacks of at most {grouped_max} clients
    cnn.stacked_lowering = (
        lambda n, backend=None: "grouped" if n <= {grouped_max} else "patch")
    ds = mnist_like(seed=0, n_train=512, n_test=64)

    def run(mesh):
        fl = FLConfig(strategy="afl", num_clients=16, rounds=1,
                      local_batch_size=16, seed=0, participation=1.0,
                      engine="fused", mesh_devices=mesh)
        sim = FederatedSimulation(fl, ds)
        r = sim.run()
        return {{"counter": r.extra["telemetry"]["counters"].get(
                     "local_train.grouped_conv"),
                 "grouped": "feature_group_count"
                            in sim.fused_program.as_text()}}

    print(json.dumps({{"single": run(0), "sharded": run(8)}}))
""")


@pytest.mark.parametrize("grouped_max,expect", [
    (2, 0.0),       # a shard's 2 clients would pick grouped on their own
    (16, 1.0),      # the whole stack of 16 picks grouped
])
def test_sharded_fused_lowers_as_single_device(grouped_max, expect):
    """The lowering of each shard's 2-client slice is the one the single-
    device run picks for all 16 clients, so the two compute alike."""
    code = LOWERING_SNIPPET.format(src=SRC, grouped_max=grouped_max)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    want = {"counter": expect, "grouped": expect == 1.0}
    assert r == {"single": want, "sharded": want}, r


# ---------------------------------------------------------------------------
# HFL tier 1 is shard-local: zero collectives in its compiled HLO
# ---------------------------------------------------------------------------

TIER1_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, {src!r})
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import aggregation as agg
    from repro.launch import mesh as mesh_mod
    from repro.launch import roofline as rl

    C, N, G = 16, 500, 8               # 2 clients/shard, 1 group/shard
    rng = np.random.default_rng(0)
    stacked = jnp.asarray(rng.normal(size=(C, N)).astype(np.float32))
    weight = jnp.asarray(rng.uniform(1.0, 2.0, C).astype(np.float32))
    mesh = mesh_mod.make_client_mesh(8)

    def tier1(p, w):
        return agg.hfl_tier1_local(p, w, 1)        # 1 group per shard

    f = jax.shard_map(
        tier1, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data")), check_vma=False)
    compiled = jax.jit(f).lower(stacked, weight).compile()
    tier1_coll = rl.parse_collective_bytes(compiled.as_text())["count"]

    # control: the FULL two-tier event on the same inputs must
    # communicate (tier 2's psum) — proving the parser sees collectives
    # in this HLO dialect at all
    g = jax.shard_map(
        lambda p, w: agg.mesh_hfl_stacked(p, w, G, axis="data"),
        mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P(),
        check_vma=False)
    compiled2 = jax.jit(g).lower(stacked, weight).compile()
    full_coll = rl.parse_collective_bytes(compiled2.as_text())["count"]

    # group math sanity: shard-local tier 1 equals the host reshape
    groups, gw = jax.jit(f)(stacked, weight)
    wb = np.asarray(weight).reshape(G, 2)
    want = ((np.asarray(stacked).reshape(G, 2, N)
             * wb[..., None]).sum(1) / wb.sum(1)[:, None])
    err = float(np.max(np.abs(np.asarray(groups) - want)))
    print(json.dumps({{"tier1_coll": tier1_coll,
                       "full_coll": full_coll, "err": err}}))
""")


def test_hfl_tier1_is_shard_local():
    code = TIER1_SNIPPET.format(src=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["tier1_coll"] == 0, \
        f"tier 1 must not cross shard boundaries: {r}"
    assert r["full_coll"] > 0, \
        f"control failed — no collectives found in the two-tier HLO: {r}"
    assert r["err"] < 1e-5, r


# ---------------------------------------------------------------------------
# mesh preconditions raise with actionable messages
# ---------------------------------------------------------------------------

PRECONDITION_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, {src!r})
    from repro.core.fl_types import FLConfig
    from repro.core.simulation import FederatedSimulation
    from repro.data.synthetic import mnist_like

    ds = mnist_like(seed=0, n_train=512, n_test=64)

    def run(**kw):
        base = dict(strategy="afl", num_clients=16, rounds=1,
                    num_groups=8, local_batch_size=16, seed=0,
                    participation=1.0, engine="fused", mesh_devices=8)
        base.update(kw)
        return FederatedSimulation(FLConfig(**base), ds).run_fused()

    got = {{}}
    for label, kw in [
        ("cfl", dict(strategy="cfl")),
        ("defense", dict(defense="median")),
        ("partial", dict(participation=0.5)),
        ("indivisible", dict(mesh_devices=3)),
        ("groups", dict(strategy="hfl", num_groups=4)),
        ("chunk", dict(fused_chunk=3)),
    ]:
        try:
            run(**kw)
            got[label] = None
        except ValueError as e:
            got[label] = str(e)
    print(json.dumps(got))
""")


def test_mesh_preconditions_raise():
    code = PRECONDITION_SNIPPET.format(src=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    for label, needle in [
        ("cfl", "supports_mesh"), ("defense", "defense"),
        ("partial", "full participation"), ("indivisible", "equal shards"),
        ("groups", "aligned to shards"), ("chunk", "fused_chunk"),
    ]:
        assert got[label] is not None, f"{label}: no error raised"
        assert needle in got[label], (label, got[label])


def test_mesh_devices_requires_fused_engine():
    from repro.core.fl_types import FLConfig
    with pytest.raises(ValueError, match="fused"):
        FLConfig(engine="vectorized", mesh_devices=4)
