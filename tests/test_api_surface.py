"""`repro.api` public-surface snapshot + legacy-import deprecation shims
(PR 4 satellite): the stable surface must not silently shrink or drift,
and every pre-PR-4 import path must keep working while warning."""
import pytest

from repro import api
from repro.core.fl_types import FLConfig
from repro.data.synthetic import mnist_like

# THE snapshot: additions require updating this list consciously;
# removals/renames are breaking changes to the public surface.
API_SURFACE = sorted([
    # configuration
    "ATTACKS", "DEFENSES", "ENGINES", "STRATEGIES", "FLConfig",
    # strategy plugin protocol + registry
    "Strategy", "RoundPlan", "LocalSpec", "register_strategy",
    "get_strategy", "strategy_names", "STRATEGY_REGISTRY",
    "STRATEGY_REGISTRY_VERSION",
    # upload-codec protocol + registry
    "Codec", "register_codec", "get_codec", "codec_names",
    "CODEC_REGISTRY", "CODEC_REGISTRY_VERSION",
    # driver
    "FederatedSimulation", "FLResult",
    # scenarios + result schema
    "ScenarioSpec", "register_scenario", "get_scenario", "scenario_names",
    "run_scenario", "load_result", "RESULT_SCHEMA_VERSION",
    "CI_SMOKE_GRID", "output_path",
    # observability (DESIGN.md §13)
    "Telemetry", "write_chrome_trace", "validate_chrome_trace",
    # aggregation operator module
    "ops",
])


def test_api_surface_snapshot():
    assert api.__all__ == API_SURFACE
    for name in API_SURFACE:
        assert hasattr(api, name), f"repro.api lost {name}"


def test_api_registry_contents():
    """Every shipped strategy is reachable by name through the public
    registry, including the PR 4 plugins."""
    names = api.strategy_names()
    assert {"hfl", "afl", "cfl", "async", "fedprox", "fedavgm",
            "fedadam"} <= set(names)
    for name in names:
        cls = api.get_strategy(name)
        assert issubclass(cls, api.Strategy)
        assert cls.name == name
        assert cls.topologies            # every strategy declares graphs
        for topo in cls.topologies:      # ... and per-event defenses
            assert "none" in cls.defenses.get(topo, ("none",))


def test_api_schema_constants():
    assert api.RESULT_SCHEMA_VERSION == 2.6
    assert api.STRATEGY_REGISTRY_VERSION == 1
    assert api.CODEC_REGISTRY_VERSION == 1


def test_api_codec_registry_contents():
    """Every shipped codec is reachable by name through the public
    registry and declares its defense validity."""
    names = api.codec_names()
    assert {"none", "topk", "qsgd"} <= set(names)
    for name in names:
        cls = api.get_codec(name)
        assert issubclass(cls, api.Codec)
        assert cls.name == name
        assert cls.defenses  # every codec declares what it composes with


def test_legacy_simulation_import_is_canonical():
    """`repro.core.simulation.FederatedSimulation` is the same object the
    api exports — old imports keep working without indirection."""
    from repro.core.simulation import FederatedSimulation
    assert FederatedSimulation is api.FederatedSimulation


def test_legacy_strategies_operator_imports_warn():
    """The aggregation operators moved to `core/aggregation.py`; the old
    `core.strategies` names still resolve but warn."""
    import repro.core.strategies as legacy_strategies
    from repro.core import aggregation
    with pytest.warns(DeprecationWarning, match="moved to"):
        fn = legacy_strategies.fedavg
    assert fn is aggregation.fedavg
    with pytest.warns(DeprecationWarning):
        from repro.core.strategies import gossip_round  # noqa: F401
    with pytest.raises(AttributeError):
        legacy_strategies.no_such_operator


def test_legacy_defenses_by_event_warns():
    from repro.core import simulation
    with pytest.warns(DeprecationWarning, match="DEFENSES_BY_EVENT"):
        table = simulation.DEFENSES_BY_EVENT
    # the deprecated view mirrors the Strategy-declared tables
    assert table["cfl"] == ("none", "norm_clip")
    assert "krum" in table["hfl"]
    assert "krum" not in table["afl-gossip"]
    assert table["afl-fedavg"] == api.get_strategy("afl").defenses["star"]


def test_legacy_async_simulation_warns_and_still_runs():
    ds = mnist_like(seed=0, n_train=128, n_test=64)
    fl = FLConfig(strategy="cfl", num_clients=4, num_groups=2,
                  local_epochs=1, local_batch_size=32, lr=0.05, seed=0)
    sim = api.FederatedSimulation(fl, ds)
    from repro.core.async_agg import AsyncSimulation
    with pytest.warns(DeprecationWarning, match="AsyncSimulation"):
        legacy = AsyncSimulation(sim, updates_per_client=1,
                                 speed_model="uniform", tick=1.0,
                                 engine="vectorized")
    r = legacy.run()
    assert r.merges == 4 and r.batches == 1
    assert 0.0 <= r.test_accuracy <= 1.0
    # the wrapper's engine override must not leak into the wrapped sim
    assert sim.vec is None and sim.strategy.name == "cfl"


def test_unknown_strategy_name_fails_loud():
    ds = mnist_like(seed=0, n_train=128, n_test=64)
    with pytest.raises(ValueError, match="unknown strategy"):
        api.FederatedSimulation(FLConfig(strategy="warp", num_clients=4,
                                         num_groups=2), ds)
