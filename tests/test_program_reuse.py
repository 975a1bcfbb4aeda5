"""Reuse of the compiled fused round scan (DESIGN.md §3, §10).

A second simulation whose program key (`FusedStatic`) equals an earlier
one's — here one that differs only in its seed, and one on another data
set of the same shapes — neither traces nor compiles its scan: it counts
`fused.program_reused` 1 and no compile request inside its `lower` and
`compile` spans. Its curves and final parameters are bitwise those of
the same simulation built afresh after `jax.clear_caches()`, and its
compiled program has the same text. Single-device HFL and AFL under
sign-flip with the median defense run in process; the mesh path runs in
a subprocess with 4 forced host devices (XLA_FLAGS must precede the jax
import).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.fl_types import FLConfig
from repro.core.simulation import FederatedSimulation
from repro.data.synthetic import mnist_like

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _runs(*runs):
    """One result per (FLConfig, data set, clear JAX's caches first): the
    reused counter, compile requests, curves, final leaves and HLO text.
    Every simulation runs from this one line, so the source locations in
    the programs' metadata agree."""
    out = []
    for fl, ds, clear in runs:
        if clear:
            jax.clear_caches()
        sim = FederatedSimulation(fl, ds)
        r = sim.run()
        counters = r.extra["telemetry"]["counters"]
        final = sim.strategy.round_model(sim.final_state)
        out.append({
            "reused": counters.get("fused.program_reused"),
            "requests": counters.get("compile.requests", 0.0),
            "curves": (r.round_train_acc, r.round_train_loss,
                       r.round_test_acc),
            "final": [np.asarray(l) for l in jax.tree.leaves(final)],
            "text": sim.fused_program.as_text()})
    return out


@pytest.mark.parametrize("kw", [
    dict(strategy="hfl", num_groups=2),
    dict(strategy="afl", attack="sign_flip", attack_fraction=0.25,
         defense="median"),
], ids=["hfl", "afl_signflip_median"])
def test_reused_program_matches_fresh_build(kw):
    base = dict(num_clients=8, rounds=2, local_epochs=1, local_batch_size=8,
                lr=0.05, participation=1.0, engine="fused", **kw)
    ds0 = mnist_like(seed=0, n_train=256, n_test=64)
    ds1 = mnist_like(seed=1, n_train=256, n_test=64)
    seed0, seed1 = FLConfig(seed=0, **base), FLConfig(seed=1, **base)
    built, other_seed, other_data, fresh_seed, fresh_data = _runs(
        (seed0, ds0, True), (seed1, ds0, False), (seed0, ds1, False),
        (seed1, ds0, True), (seed0, ds1, True))
    assert built["reused"] == 0.0 and built["requests"] >= 1
    for reused, fresh in ((other_seed, fresh_seed),
                          (other_data, fresh_data)):
        assert reused["reused"] == 1.0 and reused["requests"] == 0.0
        assert reused["curves"] != built["curves"]
        assert fresh["reused"] == 0.0
        assert reused["curves"] == fresh["curves"]
        assert len(reused["final"]) == len(fresh["final"])
        for x, y in zip(reused["final"], fresh["final"]):
            assert x.dtype == y.dtype and np.array_equal(x, y)
        assert reused["text"] == fresh["text"]


MESH_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys, json
    sys.path.insert(0, {src!r})
    import jax
    import numpy as np
    from repro.core.fl_types import FLConfig
    from repro.core.simulation import FederatedSimulation
    from repro.data.synthetic import mnist_like

    def run(seed, ds, clear):
        if clear:
            jax.clear_caches()
        fl = FLConfig(strategy="afl", num_clients=8, rounds=2,
                      local_epochs=1, local_batch_size=8, lr=0.05,
                      seed=seed, participation=1.0, engine="fused",
                      mesh_devices=4, fused_chunk=1)
        sim = FederatedSimulation(fl, ds)
        r = sim.run()
        c = r.extra["telemetry"]["counters"]
        final = sim.strategy.round_model(sim.final_state)
        return {{"reused": c.get("fused.program_reused"),
                 "requests": c.get("compile.requests", 0.0),
                 "curves": [r.round_train_acc, r.round_train_loss,
                            r.round_test_acc],
                 "final": [np.asarray(l).tobytes().hex()
                           for l in jax.tree.leaves(final)],
                 "text": sim.fused_program.as_text()}}

    ds0 = mnist_like(seed=0, n_train=256, n_test=64)
    ds1 = mnist_like(seed=1, n_train=256, n_test=64)
    # every simulation runs from one line: the programs' source
    # locations agree
    names = ("built", "seed", "data", "fresh_seed", "fresh_data")
    runs = ((0, ds0, True), (1, ds0, False), (0, ds1, False),
            (1, ds0, True), (0, ds1, True))
    out = {{n: run(*a) for n, a in zip(names, runs)}}
    print(json.dumps(out))
""")


def test_mesh_reused_program_matches_fresh_build():
    out = subprocess.run(
        [sys.executable, "-c", MESH_SNIPPET.format(src=SRC)],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["built"]["reused"] == 0.0 and d["built"]["requests"] >= 1
    for name in ("seed", "data"):
        reused, fresh = d[name], d["fresh_" + name]
        assert reused["reused"] == 1.0 and reused["requests"] == 0.0
        assert reused["curves"] != d["built"]["curves"]
        assert fresh["reused"] == 0.0
        assert reused["curves"] == fresh["curves"]
        assert reused["final"] == fresh["final"]
        assert reused["text"] == fresh["text"]
