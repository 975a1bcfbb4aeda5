"""The fused program key (`FusedStatic`, DESIGN.md §10) is complete.

Every Python value the fused scan's trace reads is in the key, so a
simulation that changes one of them builds its own program
(`fused.program_reused` 0) and never runs another configuration's. A
change of seed alone is the control: it reuses. The memoised program
holds no simulation: one that is deleted is collected, and its program
is still reused.
"""
import gc
import weakref

import pytest

from repro.core.fl_types import FLConfig
from repro.core.simulation import FederatedSimulation
from repro.data.synthetic import mnist_like

# a shape no other test module runs, so no earlier program shares a key
BASE = dict(strategy="afl", num_clients=4, rounds=2, local_epochs=1,
            local_batch_size=8, lr=0.0625, seed=3, participation=1.0,
            engine="fused")


@pytest.fixture(scope="module")
def key_ds():
    return mnist_like(seed=0, n_train=160, n_test=32)


def _reused(ds, **kw):
    sim = FederatedSimulation(FLConfig(**dict(BASE, **kw)), ds)
    r = sim.run()
    counters = r.extra["telemetry"].get("counters", {})
    return counters.get("fused.program_reused")


def _grouped(monkeypatch):
    from repro.models import cnn as cnn_mod
    monkeypatch.setattr(cnn_mod, "stacked_lowering",
                        lambda n, backend=None: "grouped")


@pytest.mark.parametrize("first,second,patch,expect", [
    ({}, {"seed": 11}, None, 1.0),                 # control: seed only
    ({}, {"lr": 0.03125}, None, 0.0),
    ({}, {"attack": "sign_flip"}, None, 0.0),
    ({}, {"defense": "median"}, None, 0.0),
    ({}, {"fused_chunk": 2}, None, 0.0),
    ({"rounds": 3, "telemetry": False}, {"rounds": 3}, None, 0.0),
    ({}, {"serve": True}, None, 0.0),
    ({}, {"codec": "topk"}, None, 0.0),
    ({}, {}, _grouped, 0.0),                       # stacked_lowering
], ids=["seed", "lr", "attack", "defense", "fused_chunk", "telemetry",
        "serve", "codec", "stacked_lowering"])
def test_program_key_change_builds_anew(key_ds, monkeypatch, first, second,
                                        patch, expect):
    _reused(key_ds, **first)
    if patch is not None:
        patch(monkeypatch)
    assert _reused(key_ds, **second) == expect


def test_program_outlives_its_simulation(key_ds):
    sim = FederatedSimulation(FLConfig(**dict(BASE, rounds=1)), key_ds)
    sim.run()
    ref = weakref.ref(sim)
    del sim
    gc.collect()
    assert ref() is None
    assert _reused(key_ds, rounds=1, seed=12) == 1.0
