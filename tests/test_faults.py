"""Churn-tolerant federation runtime (DESIGN.md §15): fault-schedule
compilation properties (heartbeat/rejoin invariants, masked mixing
matrices, bitwise regeneration), moving-target topology, engine parity
under an active fault profile (loop == vectorized == fused), profile
"none" inertness, the masked-gossip kernel path, and the result-schema
v2.5 `faults` block."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import faults, membership, scenarios, topology
from repro.core.fl_types import FLConfig
from repro.core.simulation import FederatedSimulation
from repro.data.synthetic import mnist_like
from repro.kernels import ops as kops
from repro.kernels.gossip_mix import gossip_mix_jnp

ACTIVE_PROFILES = [p for p in faults.FAULT_PROFILES if p != "none"]


def _schedule(profile="churn", seed=0, C=8, R=12, rate=0.4, quorum=0.5,
              timeout=1, mtd=False, k=8, degree=2):
    return faults.FaultSchedule(
        profile=profile, seed=seed, num_clients=C, n_events=R,
        churn_rate=rate, quorum_frac=quorum, heartbeat_timeout=timeout,
        mtd=mtd, event_size=k, gossip_degree=degree)


# ---------------------------------------------------------------------------
# quorum threshold
# ---------------------------------------------------------------------------

def test_quorum_threshold_floor_and_ceiling():
    assert faults.quorum_threshold(8, 0.5) == 4
    assert faults.quorum_threshold(8, 0.51) == 5      # ceil, not round
    assert faults.quorum_threshold(8, 1.0) == 8
    assert faults.quorum_threshold(8, 0.0) == 1       # floor: never 0
    assert faults.quorum_threshold(1, 0.0) == 1


# ---------------------------------------------------------------------------
# heartbeat / rejoin invariants (membership.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 7])
def test_heartbeat_ages_invariants(seed):
    """Ages are 0 while alive, and +1 monotone over every outage — no
    resets without a heartbeat, no resurrection mid-outage."""
    rng = np.random.default_rng(seed)
    alive = rng.random((20, 6)) >= 0.4
    ages = membership.heartbeat_ages(alive)
    assert (ages[alive] == 0).all()
    assert (ages[0][~alive[0]] == 1).all()
    prev = np.vstack([np.zeros((1, 6), np.int64), ages[:-1]])
    assert (ages[~alive] == prev[~alive] + 1).all()


@pytest.mark.parametrize("profile", ACTIVE_PROFILES)
def test_no_resurrection_before_scheduled_rejoin(profile):
    """A client is alive at round r iff the schedule says so — within any
    outage the ages count straight up and the rejoin marker only fires on
    the first alive round after it (never mid-outage)."""
    s = _schedule(profile=profile, R=24)
    rej, stale = membership.rejoin_events(s.alive, s.ages)
    assert not rej[0].any()                  # round 0 has no history
    # a rejoin is exactly an alive round preceded by a dead one
    np.testing.assert_array_equal(rej[1:], s.alive[1:] & ~s.alive[:-1])
    # mid-outage the client stays dead and its age keeps growing
    mid = ~s.alive[1:] & ~s.alive[:-1]
    assert (s.ages[1:][mid] == s.ages[:-1][mid] + 1).all()


def test_rejoin_staleness_equals_outage_length():
    alive = np.array([[1, 1], [0, 1], [0, 0], [0, 1], [1, 1]], bool)
    ages = membership.heartbeat_ages(alive)
    rej, stale = membership.rejoin_events(alive, ages)
    # client 0: dead rounds 1-3, rejoins at 4 with staleness 3
    assert rej[4, 0] and stale[4, 0] == 3
    # client 1: one-round outage at 2, rejoins at 3 with staleness 1
    assert rej[3, 1] and stale[3, 1] == 1
    assert stale[rej].sum() == stale.sum()   # staleness only at rejoins


def test_detected_failures_respect_timeout():
    ages = np.array([[0, 1, 2, 3]])
    np.testing.assert_array_equal(
        membership.detected_failures(ages, 2)[0], [False, False, True, True])
    # timeout floors at 1: any missed heartbeat is immediately detected
    np.testing.assert_array_equal(
        membership.detected_failures(ages, 0)[0], [False, True, True, True])


# ---------------------------------------------------------------------------
# masked mixing matrices / gather indices
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 3])
def test_masked_mix_row_stochastic_and_symmetric_support(seed):
    rng = np.random.default_rng(seed)
    k = 8
    alive = rng.random(k) >= 0.4
    detected = ~alive & (rng.random(k) >= 0.5)
    mix = membership.masked_mix_matrix(topology.ring_neighbors(k, 2),
                                       alive, detected)
    np.testing.assert_allclose(mix.sum(axis=1), 1.0, atol=1e-6)
    for p in np.flatnonzero(~alive):         # dead rows are identity
        row = np.zeros(k, np.float32)
        row[p] = 1.0
        np.testing.assert_array_equal(mix[p], row)
    off = mix.copy()
    np.fill_diagonal(off, 0.0)
    np.testing.assert_array_equal(off > 0, off.T > 0)   # symmetric support
    assert (off[:, ~alive] == 0).all()       # dead columns receive nothing


def test_masked_mix_undetected_share_falls_back_to_self():
    """Before the heartbeat timeout a dead neighbor keeps its slot in the
    support — its share returns to the mixing client (transient link
    loss); after detection the support shrinks and renormalizes."""
    nbrs = topology.ring_neighbors(4, 2)
    alive = np.array([True, False, True, True])
    undet = membership.masked_mix_matrix(nbrs, alive, np.zeros(4, bool))
    det = membership.masked_mix_matrix(nbrs, alive,
                                       np.array([False, True, False, False]))
    # undetected: client 0 keeps 1/3 support size, dead share to self
    np.testing.assert_allclose(undet[0], [2 / 3, 0, 0, 1 / 3], atol=1e-6)
    # detected: neighbor 1 pruned, remaining support {0, 3} renormalizes
    np.testing.assert_allclose(det[0], [0.5, 0, 0, 0.5], atol=1e-6)


def test_masked_gather_substitutes_self_for_dead_neighbors():
    nbrs = topology.ring_neighbors(4, 2)
    alive = np.array([True, False, True, True])
    idx = membership.masked_gather_indices(nbrs, alive, 3)
    np.testing.assert_array_equal(idx[1], [1, 1, 1])    # dead row: all self
    assert idx[0, 0] == 0 and 0 in idx[0, 1:]           # dead nbr 1 -> self
    assert idx.shape == (4, 3)
    assert ((idx >= 0) & (idx < 4)).all()


def test_moving_target_ring_degree_and_symmetry():
    rng = np.random.default_rng(0)
    rings = [membership.moving_target_ring(8, 2, rng) for _ in range(6)]
    for ring in rings:
        for p, nbrs in enumerate(ring):
            assert len(nbrs) == 2 and p not in nbrs
            for q in nbrs:
                assert p in ring[q]          # symmetric, like the static ring
    assert any(r != rings[0] for r in rings[1:])   # actually re-randomizes


# ---------------------------------------------------------------------------
# schedule compilation: bitwise regeneration, MTD, group quorum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("profile", ACTIVE_PROFILES)
def test_schedule_regenerates_bitwise(profile):
    a = _schedule(profile=profile, mtd=True, R=10)
    b = _schedule(profile=profile, mtd=True, R=10)
    np.testing.assert_array_equal(a.alive, b.alive)
    np.testing.assert_array_equal(a.ages, b.ages)
    np.testing.assert_array_equal(a.detected, b.detected)
    np.testing.assert_array_equal(a.rejoin_staleness, b.rejoin_staleness)
    assert a.rings == b.rings
    pids = np.arange(8)
    for ev in range(10):
        np.testing.assert_array_equal(a.gossip_mix(ev, pids),
                                      b.gossip_mix(ev, pids))
    assert a.schedule_stats() == b.schedule_stats()


def test_schedule_seed_and_profile_change_the_stream():
    base = _schedule(seed=0)
    assert not np.array_equal(base.alive, _schedule(seed=1).alive)
    assert not np.array_equal(base.alive,
                              _schedule(profile="dropout").alive)


def test_mtd_rerandomizes_per_round_static_does_not():
    mtd = _schedule(mtd=True, R=8)
    static = _schedule(mtd=False, R=8)
    rings = [mtd.neighbors_for(ev) for ev in range(8)]
    assert any(r != rings[0] for r in rings[1:])
    assert all(static.neighbors_for(ev) == topology.ring_neighbors(8, 2)
               for ev in range(8))


def test_group_qok_matches_contiguous_groups():
    s = _schedule(quorum=0.5)
    pids = np.arange(8)
    for ev in range(s.n_events):
        g = s.group_qok(ev, pids, 2)
        per = s.alive[ev].reshape(2, 4).sum(axis=1)
        np.testing.assert_array_equal(g, per >= 2)
        fe = s.event_view(ev, pids)
        assert fe.qok == (fe.n_alive >= 4)


def test_scan_xs_matches_event_views():
    """The fused executor's stacked scan inputs are exactly the per-round
    drivers' event views — the bitwise-parity contract's data side."""
    s = _schedule(mtd=True)
    pids_l = [np.arange(8)] * s.n_events
    xs = s.scan_xs(pids_l, num_groups=2, gossip=True)
    for ev in range(s.n_events):
        fe = s.event_view(ev, pids_l[ev])
        np.testing.assert_array_equal(xs["fault_alive"][ev], fe.alive)
        assert bool(xs["fault_qok"][ev]) == fe.qok
        np.testing.assert_array_equal(xs["fault_gqok"][ev],
                                      s.group_qok(ev, pids_l[ev], 2))
        np.testing.assert_array_equal(xs["fault_mix"][ev],
                                      s.gossip_mix(ev, pids_l[ev]))
    gidx = s.scan_xs(pids_l, gossip=True, gossip_defended=True,
                     gather_k=3)["fault_gidx"]
    assert gidx.shape == (s.n_events, 8, 3)


def test_compile_schedule_none_and_validation():
    fl = FLConfig(num_clients=4, num_groups=2)
    assert faults.compile_schedule(fl, n_events=3, event_size=4) is None
    with pytest.raises(ValueError, match="profile"):
        _schedule(profile="none")
    with pytest.raises(ValueError, match="quake"):
        _schedule(profile="quake")


# ---------------------------------------------------------------------------
# masked-gossip kernel path
# ---------------------------------------------------------------------------

def test_masked_gossip_kernel_matches_reference():
    rng = np.random.default_rng(0)
    stacked = jnp.asarray(rng.normal(size=(8, 130)).astype(np.float32))
    mix = membership.masked_mix_matrix(
        topology.ring_neighbors(8, 2), rng.random(8) >= 0.4)
    np.testing.assert_allclose(
        np.asarray(kops.masked_gossip_aggregate(stacked, jnp.asarray(mix),
                                                interpret=True)),
        np.asarray(gossip_mix_jnp(stacked, jnp.asarray(mix))), atol=1e-5)


# ---------------------------------------------------------------------------
# engine parity under an active fault profile (the tentpole pin)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_ds():
    return mnist_like(seed=0, n_train=256, n_test=128)


def _run(ds, engine, **kw):
    fl = FLConfig(num_clients=8, num_groups=2, rounds=2, local_epochs=1,
                  local_batch_size=16, lr=0.05, seed=0, participation=1.0,
                  engine=engine, **kw)
    return FederatedSimulation(fl, ds).run()


@pytest.mark.parametrize("label,kw", [
    ("hfl", dict(strategy="hfl")),
    ("afl-star", dict(strategy="afl", afl_mode="fedavg")),
    ("afl-gossip", dict(strategy="afl", afl_mode="gossip")),
    ("afl-gossip-median", dict(strategy="afl", afl_mode="gossip",
                               defense="median")),
])
def test_engine_parity_under_churn(small_ds, label, kw):
    """loop == vectorized == fused BITWISE under an active churn profile:
    the schedule is precomputed host numpy, the masking algebra is shared
    jnp operators, and the quorum hold is jnp.where in all engines."""
    res = {e: _run(small_ds, e, fault_profile="churn", churn_rate=0.4,
                   **kw)
           for e in ("loop", "vectorized", "fused")}
    accs = {e: r.test_accuracy for e, r in res.items()}
    assert accs["loop"] == accs["vectorized"] == accs["fused"], (label,
                                                                 accs)
    trains = {e: r.train_accuracy for e, r in res.items()}
    assert len(set(trains.values())) == 1, (label, trains)
    blocks = [r.extra["faults"] for r in res.values()]
    assert blocks[0] == blocks[1] == blocks[2]


def test_engine_parity_under_strict_quorum_holds(small_ds):
    """churn + quorum_frac high enough that rounds FAIL quorum: the hold
    path (host early-return vs fused tree_where) must also be bitwise."""
    res = {e: _run(small_ds, e, fault_profile="churn", churn_rate=0.6,
                   quorum_frac=0.95)
           for e in ("loop", "vectorized", "fused")}
    accs = {e: r.test_accuracy for e, r in res.items()}
    assert len(set(accs.values())) == 1, accs
    blk = res["fused"].extra["faults"]
    assert blk["quorum_failures"] >= 1
    assert np.isfinite(list(accs.values())[0])


def test_fault_profile_none_is_inert(small_ds):
    """profile="none" compiles no schedule: no `faults` result block and
    the run matches a default-config run bitwise (the structural
    inertness contract — every fault seam is a host-level `if`)."""
    plain = _run(small_ds, "fused")
    explicit = _run(small_ds, "fused", fault_profile="none",
                    churn_rate=0.7, quorum_frac=0.9, heartbeat_timeout=3)
    assert "faults" not in plain.extra and "faults" not in explicit.extra
    assert plain.test_accuracy == explicit.test_accuracy
    assert plain.train_accuracy == explicit.train_accuracy


def test_faults_block_contents(small_ds):
    r = _run(small_ds, "vectorized", fault_profile="churn", churn_rate=0.4)
    blk = r.extra["faults"]
    assert blk["profile"] == "churn"
    assert blk["events_logged"] == 2
    assert 0.0 < blk["mean_alive_frac"] <= 1.0
    assert blk["churn_events"] >= 0 and blk["rejoins"] >= 0
    assert isinstance(blk["quorum_failed_events"], list)
    assert blk["degraded_rounds"] >= blk["quorum_failures"] >= 0


# ---------------------------------------------------------------------------
# scenarios: churn registrations + schema v2.5 back-compat
# ---------------------------------------------------------------------------

def test_churn_scenarios_registered():
    names = [n for n in scenarios.names() if "churn" in n]
    assert {"churn-afl-gossip-mtd", "churn-hfl-quorum",
            "churn-signflip-median-mtd",
            "churn-signflip-median-static"} <= set(names)
    mtd = scenarios.get("churn-signflip-median-mtd")
    static = scenarios.get("churn-signflip-median-static")
    # the acceptance pair differs ONLY in the moving-target toggle
    assert dataclasses.replace(static, name=mtd.name,
                               description=mtd.description,
                               fault_mtd=True) == mtd
    assert mtd.attack_placement == "colluding"
    assert mtd.churn_rate == 0.3 and mtd.defense == "median"


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="fault"):
        scenarios.ScenarioSpec("bad", "x", fault_profile="quake")
    with pytest.raises(ValueError, match="ring"):
        scenarios.ScenarioSpec("bad", "x", fault_mtd=True)
    with pytest.raises(ValueError, match="placement"):
        scenarios.ScenarioSpec("bad", "x", attack_placement="everywhere")


def test_result_schema_v24_backward_compat_read():
    """v2.4 documents (pre-faults) normalize with a null faults block;
    older versions gain it too."""
    v24 = {"schema_version": 2.4, "scenario": "old", "serving": None}
    doc = scenarios.load_result(v24)
    assert doc["schema_version"] == scenarios.RESULT_SCHEMA_VERSION
    assert doc["faults"] is None and doc["serving"] is None
    for v in (1, 2, 2.1, 2.2, 2.3):
        assert scenarios.load_result(
            {"schema_version": v, "spec": {"strategy": "afl"}})["faults"] \
            is None


def test_result_schema_v25_faults_block(small_ds):
    spec = scenarios.ScenarioSpec(
        "tiny-churn", "schema smoke", strategy="afl", topology="star",
        engine="vectorized", num_clients=4, n_train=128, n_test=64,
        rounds=2, participation=1.0, fault_profile="dropout",
        churn_rate=0.5)
    res = scenarios.run_scenario(spec)
    assert res["schema_version"] == scenarios.RESULT_SCHEMA_VERSION == 2.6
    assert res["faults"]["profile"] == "dropout"
    import json
    json.dumps(res)
