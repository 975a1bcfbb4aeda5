"""fl_train_step on a real multi-device mesh (subprocess, 8 fake devices):
the paper's aggregation strategies must lower+compile with the client axis
sharded, and each strategy's collective signature must appear in the HLO."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.configs.registry import get_config
    from repro.core.fl_types import FLConfig
    from repro.core.trainer import (FederatedTrainer, fl_tree_shardings,
                                    fl_tree_shardings_opt)
    from repro.models.model import build_model
    from repro.sharding import specs as sh
    from repro.launch import roofline as rl

    cfg = get_config("phi3-mini-3.8b").reduced().with_updates(vocab_size=512)
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    fl = FLConfig(strategy="{strategy}", num_clients=4, num_groups=2,
                  local_steps=2, lr=0.05, afl_mode="{mode}")
    model = build_model(cfg)
    tr = FederatedTrainer(model, fl, mesh)
    state_shape = jax.eval_shape(tr.init_state, jax.random.PRNGKey(0))
    shardings = {{
        "client_params": fl_tree_shardings(state_shape["client_params"], mesh),
        "opt": fl_tree_shardings_opt(state_shape["opt"], mesh),
        "round": NamedSharding(mesh, P()),
    }}
    if "global_params" in state_shape:
        shardings["global_params"] = sh.tree_shardings(
            state_shape["global_params"], mesh)
    ssds = jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                          sharding=s),
                        state_shape, shardings)
    bs = tr.fl_batch_specs(64, 2)
    bsh = jax.tree.map(lambda s: NamedSharding(
        mesh, sh.fit_spec(s.shape, P("data"), mesh)), bs)
    bsds = jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                          sharding=s),
                        bs, bsh)
    wsds = jax.ShapeDtypeStruct((4,), jnp.float32)
    psds = jax.ShapeDtypeStruct((4,), jnp.bool_)
    with jax.sharding.set_mesh(mesh):
        compiled = jax.jit(tr.fl_train_step).lower(
            ssds, bsds, wsds, psds).compile()
    coll = rl.parse_collective_bytes(compiled.as_text())
    print(json.dumps({{"ok": True, "coll": coll["total"],
                       "permutes": coll["collective-permute"],
                       "count": coll["count"]}}))
""")


@pytest.mark.parametrize("strategy,mode", [
    ("hfl", "fedavg"), ("afl", "fedavg"), ("afl", "gossip"),
    ("cfl", "fedavg"),
])
def test_fl_step_lowers_on_mesh(strategy, mode):
    code = SNIPPET.format(src=SRC, strategy=strategy, mode=mode)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["ok"]
    assert result["count"] > 0, "aggregation must lower to collectives"
    if mode == "gossip":
        assert result["permutes"] > 0, \
            "gossip must lower to collective-permute (ring exchange)"


# ---------------------------------------------------------------------------
# mesh_hfl two-tier math pinned against the host aggregate
# ---------------------------------------------------------------------------
# Regression for the single-pod tier-2 reduction: each group model is
# replicated across its (equal-size) group before the global psum, so the
# group size cancels between numerator and denominator. This test fails if
# either tier double-counts.

MESH_HFL_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, {src!r})
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.core import aggregation as strategies
    from repro.core import topology

    C, N, G = 8, 1000, {groups}
    rng = np.random.default_rng(0)
    stacked = jnp.asarray(rng.normal(size=(C, N)).astype(np.float32))
    weight = jnp.asarray(rng.uniform(10.0, 100.0, C).astype(np.float32))
    multi_pod = {multi_pod}
    if multi_pod:
        mesh = jax.make_mesh((G, C // G), ("pod", "data"),
                             axis_types=(AxisType.Auto,) * 2)
        fn = lambda p, w: strategies.mesh_hfl(
            p, w[0], client_axis="data", pod_axis="pod")
        specs = (P(("pod", "data")), P(("pod", "data")))
        out_spec = P(("pod", "data"))
    else:
        mesh = jax.make_mesh((C,), ("data",),
                             axis_types=(AxisType.Auto,))
        fn = lambda p, w: strategies.mesh_hfl(
            p, w[0], client_axis="data", num_groups=G)
        specs = (P("data"), P("data"))
        out_spec = P("data")
    f = jax.shard_map(fn, mesh=mesh, in_specs=specs, out_specs=out_spec,
                      check_vma=False)
    out = np.asarray(jax.jit(f)(stacked, weight))
    replicated = bool(np.allclose(out, out[0:1], atol=1e-5))

    clients = [{{"w": stacked[i]}} for i in range(C)]
    groups = topology.hierarchical_groups(C, G)
    host = strategies.hfl_aggregate(clients, groups,
                                    weights=np.asarray(weight))
    err = float(np.max(np.abs(out[0] - np.asarray(host["w"]))))
    print(json.dumps({{"replicated": replicated, "err": err}}))
""")


@pytest.mark.parametrize("groups,multi_pod", [
    (2, False), (4, False), (2, True),
])
def test_mesh_hfl_matches_host(groups, multi_pod):
    code = MESH_HFL_SNIPPET.format(src=SRC, groups=groups,
                                   multi_pod=multi_pod)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["replicated"], "every client must hold the global model"
    assert result["err"] < 1e-4, \
        f"mesh_hfl diverges from host hfl_aggregate: {result['err']}"


# ---------------------------------------------------------------------------
# mesh_hfl_stacked (sharded client STACKS, C > devices) vs host aggregate
# ---------------------------------------------------------------------------
# The fused executor's general mesh operator: 16 clients over 8 shards
# (2 clients per shard), exercising group sizes that nest inside a shard
# (G=16), align exactly (G=8), and span multiple shards (G=4 — the
# grouped tier-1 psum).

MESH_HFL_STACKED_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, {src!r})
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import aggregation as agg
    from repro.core import topology
    from repro.launch import mesh as mesh_mod

    C, N, G = 16, 500, {groups}
    rng = np.random.default_rng(0)
    stacked = jnp.asarray(rng.normal(size=(C, N)).astype(np.float32))
    weight = jnp.asarray(rng.uniform(10.0, 100.0, C).astype(np.float32))
    mesh = mesh_mod.make_client_mesh(8)

    def fn(p, w):
        # the global model has no client axis; re-tile each shard's copy
        # so the host side can check cross-shard replication
        g = agg.mesh_hfl_stacked(p, w, G, axis="data")
        return g[None, :]

    f = jax.shard_map(fn, mesh=mesh, in_specs=(P("data"), P("data")),
                      out_specs=P("data"), check_vma=False)
    out = np.asarray(jax.jit(f)(stacked, weight))      # (8, N) shard copies
    replicated = bool(np.allclose(out, out[0:1], atol=1e-5))

    clients = [{{"w": stacked[i]}} for i in range(C)]
    host = agg.hfl_aggregate(clients, topology.hierarchical_groups(C, G),
                             weights=np.asarray(weight))
    err = float(np.max(np.abs(out[0] - np.asarray(host["w"]))))
    print(json.dumps({{"replicated": replicated, "err": err}}))
""")


@pytest.mark.parametrize("groups", [
    16,                    # groups nest inside one shard (pure local tier 1)
    8,                     # group == shard (the fused executor's regime)
    4,                     # groups span 2 shards: grouped psum
])
def test_mesh_hfl_stacked_matches_host(groups):
    code = MESH_HFL_STACKED_SNIPPET.format(src=SRC, groups=groups)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["replicated"], "every shard must hold the global model"
    assert result["err"] < 1e-4, \
        f"mesh_hfl_stacked diverges from host: {result['err']}"


# ---------------------------------------------------------------------------
# make_host_mesh divisor clamping (ISSUE 6 satellite: min(data, n) built
# impossible factorizations at non-power-of-two device counts)
# ---------------------------------------------------------------------------

HOST_MESH_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               "{ndev}")
    import sys, json
    sys.path.insert(0, {src!r})
    import jax
    from repro.launch import mesh as mesh_mod
    shapes = []
    for data, model in {requests}:
        m = mesh_mod.make_host_mesh(data, model)
        shapes.append(dict(zip(m.axis_names, (m.devices.shape))))
    print(json.dumps(shapes))
""")


@pytest.mark.parametrize("ndev,requests,want", [
    # 6 devices: data=4 does not divide -> clamp to 3 (largest divisor),
    # NOT min(4, 6) = 4 which 6 cannot factor
    (6, [(4, 1), (6, 1), (4, 4), (5, 5)],
     [(3, 1), (6, 1), (3, 2), (3, 2)]),
    (8, [(4, 2), (3, 1), (16, 1), (8, 8)],
     [(4, 2), (2, 1), (8, 1), (8, 1)]),
])
def test_make_host_mesh_clamps_to_divisors(ndev, requests, want):
    code = HOST_MESH_SNIPPET.format(src=SRC, ndev=ndev, requests=requests)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    shapes = json.loads(out.stdout.strip().splitlines()[-1])
    got = [(s["data"], s["model"]) for s in shapes]
    assert got == [tuple(w) for w in want]


def test_largest_divisor_at_most():
    from repro.launch.mesh import largest_divisor_at_most
    assert largest_divisor_at_most(6, 4) == 3
    assert largest_divisor_at_most(6, 6) == 6
    assert largest_divisor_at_most(8, 5) == 4
    assert largest_divisor_at_most(7, 3) == 1
    assert largest_divisor_at_most(12, 0) == 1
    assert largest_divisor_at_most(12, 99) == 12
