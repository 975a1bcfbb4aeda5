"""Scenario registry: spec validation, resolution to runnable configs,
the stable result-JSON schema, and the CI bench compare gate
(DESIGN.md §6-§7)."""
import json
import os
import sys

import numpy as np
import pytest

from repro.core import scenarios
from repro.core.fl_types import FLConfig
from repro.core.strategies import STRATEGY_REGISTRY_VERSION  # noqa: F401
from repro.core.simulation import FederatedSimulation

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.ci_bench import ASYNC_SPEEDUP_FLOOR, compare  # noqa: E402


# ---------------------------------------------------------------------------
# registry + spec validation
# ---------------------------------------------------------------------------

def test_registry_covers_every_axis():
    """The shipped registry spans the full evaluation space: every
    strategy, all three engines, both partitions, and every
    heterogeneity speed model appear in at least one named scenario."""
    specs = [scenarios.get(n) for n in scenarios.names()]
    assert {s.strategy for s in specs} == set(scenarios.TOPOLOGY_BY_STRATEGY)
    assert {s.engine for s in specs} == {"loop", "vectorized", "fused"}
    assert {s.partition for s in specs} == {"iid", "dirichlet"}
    assert {s.speed_model for s in specs if s.strategy == "async"} == {
        "uniform", "lognormal", "straggler"}


def test_every_spec_resolves_to_fl_config():
    for name in scenarios.names():
        fl = scenarios.get(name).to_fl_config()
        assert isinstance(fl, FLConfig)
        assert fl.num_clients % fl.num_groups == 0


def test_ci_smoke_grid_is_registered():
    assert len(scenarios.CI_SMOKE_GRID) == 9
    for name in scenarios.CI_SMOKE_GRID:
        assert name in scenarios.REGISTRY
    # the grid carries one adversarial scenario (ISSUE 3 satellite)
    assert any(scenarios.get(n).attack != "none"
               for n in scenarios.CI_SMOKE_GRID)
    # ... one scenario per PR 4 strategy-plugin family
    grid_strategies = {scenarios.get(n).strategy
                       for n in scenarios.CI_SMOKE_GRID}
    assert {"fedprox", "fedadam"} <= grid_strategies
    # ... one fused-executor scenario (ISSUE 5 satellite)
    assert any(scenarios.get(n).engine == "fused"
               for n in scenarios.CI_SMOKE_GRID)
    # ... and one upload-codec scenario (ISSUE 7 satellite)
    assert any(scenarios.get(n).codec != "none"
               for n in scenarios.CI_SMOKE_GRID)
    # ... and one serving scenario (ISSUE 9 satellite)
    assert any(scenarios.get(n).serve for n in scenarios.CI_SMOKE_GRID)


def test_spec_validation():
    with pytest.raises(ValueError, match="topology"):
        scenarios.ScenarioSpec("bad", "x", strategy="hfl", topology="ring")
    with pytest.raises(ValueError, match="strategy"):
        scenarios.ScenarioSpec("bad", "x", strategy="warp")
    with pytest.raises(ValueError, match="partition"):
        scenarios.ScenarioSpec("bad", "x", partition="sorted")
    with pytest.raises(ValueError, match="engine"):
        scenarios.ScenarioSpec("bad", "x", engine="warp")
    with pytest.raises(ValueError, match="duplicate"):
        scenarios.register(scenarios.get("iid-hfl-vec"))
    with pytest.raises(KeyError, match="unknown scenario"):
        scenarios.get("no-such-scenario")


def test_async_spec_maps_to_async_strategy():
    """Since PR 4 async is a first-class Strategy plugin: the spec's
    strategy name resolves 1:1 through the registry (no more cfl
    substrate indirection), carrying the heterogeneity knobs."""
    fl = scenarios.get("async-uniform-vec").to_fl_config()
    assert fl.strategy == "async" and fl.engine == "vectorized"
    assert fl.speed_model == "uniform" and fl.tick == 1.0
    fl = scenarios.get("ring-gossip-vec").to_fl_config()
    assert fl.afl_mode == "gossip"


# ---------------------------------------------------------------------------
# resolution + execution
# ---------------------------------------------------------------------------

def test_from_scenario_applies_dirichlet_partition():
    spec = scenarios.get("dirichlet-afl-loop")
    sim = FederatedSimulation.from_scenario(spec)
    sizes = [len(p) for p in sim.parts]
    assert sum(sizes) == spec.n_train
    assert max(sizes) != min(sizes)        # label skew -> uneven shards
    iid = FederatedSimulation.from_scenario(scenarios.get("iid-hfl-loop"))
    assert max(len(p) for p in iid.parts) - min(
        len(p) for p in iid.parts) <= 1


def test_run_scenario_result_schema():
    """One cheap async run end-to-end; the result document is the stable
    schema every consumer (example, benchmarks, CI) parses."""
    spec = scenarios.ScenarioSpec(
        "tiny-async", "schema smoke", strategy="async", topology="event",
        engine="loop", num_clients=4, n_train=128, n_test=64,
        speed_model="uniform", updates_per_client=1)
    res = scenarios.run_scenario(spec)
    assert res["schema_version"] == scenarios.RESULT_SCHEMA_VERSION
    assert res["scenario"] == "tiny-async"
    assert res["spec"]["strategy"] == "async"
    for k in ("test_accuracy", "train_accuracy", "precision", "recall",
              "f1", "balanced_accuracy"):
        assert 0.0 <= res["metrics"][k] <= 1.0
    assert res["timing"]["rounds_per_s"] > 0
    assert res["async"]["merges"] == 4 and res["async"]["batches"] == 1
    # v2.1: the strategy-plugin block (PR 4 satellite)
    assert res["strategy"] == {
        "plugin": "async",
        "registry_version": STRATEGY_REGISTRY_VERSION}
    # v2.4: serving off -> explicit null block
    assert res["serving"] is None
    json.dumps(res)                        # must be JSON-serializable


def test_result_schema_backward_compat_read():
    """Schema bump contract (DESIGN.md §6): v1 documents (no attack
    block), v2 documents (no strategy block), v2.1 documents (no
    communication block), v2.2 documents (no telemetry block), and v2.3
    documents (no serving block) normalize through `load_result` to the
    current version, so every consumer reads one shape."""
    v1 = {"schema_version": 1, "scenario": "legacy",
          "metrics": {"test_accuracy": 0.9}, "async": None}
    doc = scenarios.load_result(v1)
    assert doc["schema_version"] == scenarios.RESULT_SCHEMA_VERSION == 2.6
    assert doc["attack"] is None
    assert doc["strategy"] == {"plugin": None, "registry_version": None}
    assert doc["communication"] is None
    assert doc["telemetry"] is None
    assert doc["serving"] is None
    assert doc["metrics"]["test_accuracy"] == 0.9
    v2 = {"schema_version": 2, "scenario": "legacy2",
          "spec": {"strategy": "afl"}, "attack": None}
    doc = scenarios.load_result(v2)
    assert doc["schema_version"] == scenarios.RESULT_SCHEMA_VERSION
    assert doc["attack"] is None                  # v2 block preserved
    assert doc["strategy"]["plugin"] == "afl"
    assert doc["strategy"]["registry_version"] is None
    assert doc["communication"] is None
    assert doc["serving"] is None
    v21 = {"schema_version": 2.1, "scenario": "legacy21", "attack": None,
           "strategy": {"plugin": "hfl", "registry_version": 1}}
    doc = scenarios.load_result(v21)
    assert doc["schema_version"] == scenarios.RESULT_SCHEMA_VERSION
    assert doc["strategy"]["plugin"] == "hfl"     # v2.1 block preserved
    assert doc["communication"] is None
    assert doc["telemetry"] is None
    assert doc["serving"] is None
    v22 = {"schema_version": 2.2, "scenario": "legacy22", "attack": None,
           "strategy": {"plugin": "afl", "registry_version": 1},
           "communication": {"codec": "qsgd"}}
    doc = scenarios.load_result(v22)
    assert doc["schema_version"] == scenarios.RESULT_SCHEMA_VERSION
    assert doc["communication"] == {"codec": "qsgd"}  # v2.2 preserved
    assert doc["telemetry"] is None
    assert doc["serving"] is None
    v23 = {"schema_version": 2.3, "scenario": "legacy23", "attack": None,
           "strategy": {"plugin": "afl", "registry_version": 1},
           "communication": None, "telemetry": {"enabled": False}}
    doc = scenarios.load_result(v23)
    assert doc["schema_version"] == scenarios.RESULT_SCHEMA_VERSION
    assert doc["telemetry"] == {"enabled": False}  # v2.3 preserved
    assert doc["serving"] is None


def test_run_scenario_sync_has_null_async_block():
    spec = scenarios.ScenarioSpec(
        "tiny-cfl", "schema smoke", strategy="cfl", topology="sequential",
        engine="loop", num_clients=4, n_train=128, n_test=64, rounds=1)
    res = scenarios.run_scenario(spec)
    assert res["async"] is None
    assert res["attack"] is None          # clean run: v2 null attack block
    assert res["spec"]["rounds"] == 1
    json.dumps(res)


# ---------------------------------------------------------------------------
# CI bench gate
# ---------------------------------------------------------------------------

def _bench_doc(sync_speedup, async_speedup, scale="quick"):
    return {"schema_version": 1, "scale": scale, "clients": 64,
            "sync": {"speedup": sync_speedup},
            "async": {"speedup": async_speedup},
            "scenarios": {n: {} for n in scenarios.CI_SMOKE_GRID}}


def test_compare_passes_within_tolerance():
    base = _bench_doc(3.0, 2.8)
    assert compare(_bench_doc(3.0, 2.8), base) == []
    assert compare(_bench_doc(2.4, ASYNC_SPEEDUP_FLOOR + 0.2), base) == []


def test_compare_driver_overhead_gate():
    """The ISSUE 4 driver gate: absolute sync round throughput must stay
    within 5% of the baseline — but only when host core count and scale
    match (raw wall clock is not portable across hardware)."""
    base = _bench_doc(3.0, 2.8)
    base["host"] = {"cpus": 2}
    base["sync"].update(loop_rounds_per_s=0.10, vectorized_rounds_per_s=0.30)
    ok = _bench_doc(3.0, 2.8)
    ok["host"] = {"cpus": 2}
    ok["sync"].update(loop_rounds_per_s=0.099, vectorized_rounds_per_s=0.295)
    assert compare(ok, base) == []
    slow = _bench_doc(3.0, 2.8)
    slow["host"] = {"cpus": 2}
    slow["sync"].update(loop_rounds_per_s=0.10,
                        vectorized_rounds_per_s=0.25)
    fails = compare(slow, base)
    assert len(fails) == 1 and "driver overhead" in fails[0]
    # different host core count: the driver gate must NOT fire
    other_host = {**slow, "host": {"cpus": 8}}
    assert compare(other_host, base) == []


def test_compare_flags_regressions():
    base = _bench_doc(3.0, 2.8)
    fails = compare(_bench_doc(1.5, 2.8), base)
    assert len(fails) == 1 and "sync" in fails[0]
    fails = compare(_bench_doc(3.0, 1.2), base)
    assert any("async" in f for f in fails)
    assert any("floor" in f for f in fails)
    # floor only applies at quick scale
    assert compare(_bench_doc(3.0, 1.9, scale="smoke"),
                   _bench_doc(3.0, 1.9, scale="smoke")) == []
    fails = compare({**_bench_doc(3.0, 2.8), "scenarios": {}}, base)
    assert any("coverage" in f for f in fails)


def test_compare_obs_overhead_gate():
    """The ISSUE 8 telemetry budget: on-by-default tracing must cost
    <= 5% rounds/s under every engine. The gate reads only the new
    document (the overhead is a same-run on/off ratio, not a
    baseline-relative number) and stays silent for pre-ISSUE-8
    documents that carry no "obs" section."""
    from benchmarks.ci_bench import OBS_OVERHEAD_TOLERANCE

    def _obs(overhead):
        return {eng: {"overhead": overhead, "on_rounds_per_s": 1.0,
                      "off_rounds_per_s": 1.0 + overhead}
                for eng in ("loop", "vectorized", "fused")}

    base = _bench_doc(3.0, 2.8)
    ok = {**_bench_doc(3.0, 2.8), "obs": _obs(0.02)}
    assert compare(ok, base) == []
    bad = {**_bench_doc(3.0, 2.8),
           "obs": _obs(OBS_OVERHEAD_TOLERANCE + 0.03)}
    fails = compare(bad, base)
    assert len(fails) == 3                 # one per engine
    assert all("telemetry overhead" in f for f in fails)
    # smoke scale: informational only, like the other floors
    smoke = {**_bench_doc(3.0, 2.8, scale="smoke"), "obs": _obs(0.5)}
    assert compare(smoke, _bench_doc(3.0, 2.8, scale="smoke")) == []
    # absent section (old run): no gate
    assert compare(_bench_doc(3.0, 2.8), base) == []
