"""Sharding-spec properties + a small-mesh dry-run in a subprocess (the
main test process must keep the single real CPU device)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.sharding import specs as sh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 4, "model": 2}
    size = 8


@settings(max_examples=50, deadline=None)
@given(d0=st.integers(1, 64), d1=st.integers(1, 64))
def test_fit_spec_always_divides(d0, d1):
    m = FakeMesh()
    spec = sh.fit_spec((d0, d1), P("data", "model"), m)
    for dim, ax in zip((d0, d1), list(spec) + [None, None]):
        if ax is not None:
            assert dim % sh.axis_size(m, ax) == 0


def test_fit_spec_compound_prefix_fallback():
    m = FakeMesh()
    # 4 divides by ("data",) but not ("data","model")=8
    spec = sh.fit_spec((4, 8), P(("data", "model"), None), m)
    assert spec[0] in (("data",), "data")   # prefix kept, tuple may unwrap


def test_param_rules_profiles():
    m = FakeMesh()
    sh.set_profile("tp")
    assert sh.spec_for_param("layers/attn/wq/kernel", (64, 32), m) \
        == P("data", "model")
    sh.set_profile("dp")
    assert sh.spec_for_param("layers/attn/wq/kernel", (64, 32), m) == P()
    sh.set_profile("fsdp")
    s = sh.spec_for_param("layers/attn/wq/kernel", (64, 32), m)
    assert s[0] == ("data", "model")
    sh.set_profile("tp")


def test_norm_params_replicated():
    m = FakeMesh()
    sh.set_profile("tp")
    got = sh.spec_for_param("layers/attn_norm/scale", (64,), m)
    assert all(e is None for e in got)      # replicated (P() or P(None))


DRYRUN_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
    from repro.configs.registry import get_config
    from repro.models.model import build_model
    from repro.launch import train as tm, roofline as rl
    from repro.optim import optimizers
    from repro.sharding import specs as sh

    cfg = get_config("{arch}").reduced().with_updates(
        sharding_profile="{profile}", vocab_size=512)
    sh.set_profile(cfg.sharding_profile)
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    model = build_model(cfg)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    psh = sh.tree_shardings(params_shape, mesh)
    psds = jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                          sharding=s),
                        params_shape, psh)
    opt = optimizers.adamw(1e-3)
    opt_shape = jax.eval_shape(opt.init, params_shape)
    _, osh = tm.train_state_shardings(params_shape, opt_shape, mesh)
    osds = jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                          sharding=s),
                        opt_shape, osh)
    bs = model.train_batch_specs(8, 64)
    bsh = tm.batch_shardings(bs, mesh)
    bsds = jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                          sharding=s),
                        bs, bsh)
    step = tm.make_train_step(model, opt)
    with jax.sharding.set_mesh(mesh):
        compiled = jax.jit(step).lower(psds, osds, bsds).compile()
    roof = rl.analyze(compiled, 8)
    print(json.dumps({{"ok": True,
                       "coll": roof.collective_bytes_per_device,
                       "ops": roof.collective_count,
                       "flops": roof.flops_per_device}}))
""")


@pytest.mark.parametrize("arch,profile", [
    ("phi3-mini-3.8b", "tp"),
    ("qwen3-moe-30b-a3b", "tp"),
    ("zamba2-1.2b", "fsdp"),
    ("xlstm-125m", "dp"),
])
def test_small_mesh_dryrun_subprocess(arch, profile):
    """Reduced arch x 4x2 mesh: lower+compile must succeed and the
    roofline parser must see collectives (tp/fsdp) in the HLO."""
    code = DRYRUN_SNIPPET.format(src=os.path.abspath(SRC), arch=arch,
                                 profile=profile)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["ok"]
    assert result["flops"] > 0
    if profile in ("tp", "fsdp"):
        assert result["ops"] > 0, "expected collectives in sharded training"


def test_collective_parser():
    hlo = """
      %ar = f32[128,256] all-reduce(f32[128,256] %x), replica_groups={}
      %ag = bf16[64]{0} all-gather(bf16[32]{0} %y), dimensions={0}
      %cp = f32[8,8] collective-permute(f32[8,8] %z)
      %tuple.1 = (f32[16,16], f32[4]) all-to-all(%a, %b)
    """
    from repro.launch.roofline import parse_collective_bytes
    got = parse_collective_bytes(hlo)
    assert got["count"] == 4
    assert got["all-reduce"] == 2 * 128 * 256 * 4     # 2x ring weight
    assert got["all-gather"] == 64 * 2
    assert got["collective-permute"] == 8 * 8 * 4
    assert got["all-to-all"] == 16 * 16 * 4 + 4 * 4


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "_doc"])
def test_peak_table_refuses_unknown_device(kind):
    """The roofline reads v5e peaks from the one table; any kind the
    table does not list is an error, never a silent v5e default."""
    from repro.launch import roofline as rl
    assert rl.device_peaks(rl.V5E)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        rl.device_peaks(kind)


DECODE_SHARD_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.launch.serve import decode_state_shardings

    leaves = {{
        "kv_div":     jax.ShapeDtypeStruct((2, 2048, 8, 16), jnp.float32),
        "kv_nondiv":  jax.ShapeDtypeStruct((2, 2048, 6, 16), jnp.float32),
        "kv_short":   jax.ShapeDtypeStruct((2, 64, 6, 16), jnp.float32),
        "conv":       jax.ShapeDtypeStruct((2, 3, 8), jnp.float32),
        "stack_div":  jax.ShapeDtypeStruct((4, 2, 2048, 8, 16),
                                           jnp.float32),
        "stack_nondiv": jax.ShapeDtypeStruct((4, 2, 2048, 6, 16),
                                             jnp.float32),
        "index":      jax.ShapeDtypeStruct((), jnp.int32),
    }}

    def dump(mesh):
        sh = decode_state_shardings(leaves, mesh, None)
        out = {{}}
        for k, ns in sh.items():
            spec = list(ns.spec) + [None] * (leaves[k].ndim
                                             - len(ns.spec))
            out[k] = [None if e is None else str(e) for e in spec]
        return out

    mm = jax.make_mesh((2, 4), ("data", "model"),
                       axis_types=(AxisType.Auto,) * 2)
    md = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    print(json.dumps({{"model_mesh": dump(mm), "data_mesh": dump(md)}}))
""")


def test_decode_state_sharding_rules_subprocess():
    """Pin decode_state_shardings leaf rules on a real 2x4 host mesh
    (PR 9 bugfix satellite): divisible heads go over "model",
    non-divisible heads fall back to cache-sequence sharding (> 1024
    only), the layer dim of 5-dim stacked caches is NEVER sharded, and
    meshes without a "model" axis shard batch only (no KeyError)."""
    code = DECODE_SHARD_SNIPPET.format(src=os.path.abspath(SRC))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["model_mesh"] == {
        "kv_div":       ["data", None, "model", None],
        "kv_nondiv":    ["data", "model", None, None],
        "kv_short":     ["data", None, None, None],
        "conv":         ["data", None, "model"],
        "stack_div":    [None, "data", None, "model", None],
        "stack_nondiv": [None, "data", "model", None, None],
        "index":        [],
    }
    # 1-D client mesh: no "model" axis anywhere, batch-only sharding
    assert got["data_mesh"]["kv_div"] == [None, None, None, None]  # 2 % 8
    assert got["data_mesh"]["conv"] == [None, None, None]
    for spec in got["data_mesh"].values():
        assert "model" not in spec
