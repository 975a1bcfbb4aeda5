"""Fused executor (DESIGN.md §10) + bitonic selection kernel (ISSUE 5).

Two invariants:

* the fused run — one `lax.scan` over all rounds, device-resident state,
  hoisted schedules/batch indices — equals the vectorized per-round
  driver to float tolerance for EVERY built-in sync strategy (curves AND
  final metrics), including attack + defense in-scan;
* the bitonic-sort selection kernel (Pallas interpret mode AND the jnp
  production CPU path) equals the sort-based oracle
  `ref.trimmed_mean_ref`, including ties, C=1, non-power-of-two C, and
  block-boundary edges.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fl_types import ENGINES, FLConfig
from repro.core.simulation import FederatedSimulation
from repro.data.synthetic import mnist_like
from repro.kernels import ref
from repro.kernels.robust_agg import (bitonic_sorted, median_agg,
                                      median_jnp, trimmed_mean_agg,
                                      trimmed_mean_jnp)


# ---------------------------------------------------------------------------
# bitonic selection kernel vs sort-based oracle
# ---------------------------------------------------------------------------

def _mat(C, N, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(C, N)).astype(np.float32))


@pytest.mark.parametrize("C,N,trim", [
    (4, 300, 1),            # even power-of-two C
    (5, 1000, 2),           # odd C (pad row), maximal trim (median)
    (8, 8192, 3),           # exact block boundary
    (8, 8192 + 7, 3),       # pad path
    (1, 64, 0),             # single client: no network stages at all
    (3, 129, 1),
    (33, 200, 7),           # just past a power of two: 31 pad rows
])
def test_bitonic_kernel_matches_oracle(C, N, trim):
    x = _mat(C, N)
    want = np.asarray(ref.trimmed_mean_ref(x, trim))
    np.testing.assert_allclose(
        np.asarray(trimmed_mean_agg(x, trim, interpret=True)), want,
        atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(trimmed_mean_jnp(x, trim)), want, atol=1e-6)


def test_bitonic_kernel_handles_ties():
    """Tied values are interchangeable across the trim boundary: any
    correct selection sums identically, so no index tie-break is
    needed."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(0, 3, size=(6, 500)).astype(np.float32))
    want = np.asarray(ref.trimmed_mean_ref(x, 2))
    np.testing.assert_allclose(
        np.asarray(trimmed_mean_agg(x, 2, interpret=True)), want,
        atol=1e-6)
    np.testing.assert_allclose(np.asarray(trimmed_mean_jnp(x, 2)), want,
                               atol=1e-6)


@pytest.mark.parametrize("C", [4, 5, 6, 7])
def test_bitonic_median_even_and_odd(C):
    x = _mat(C, 257, seed=C)
    want = np.median(np.asarray(x), axis=0)
    np.testing.assert_allclose(
        np.asarray(median_agg(x, interpret=True)), want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(median_jnp(x)), want, atol=1e-6)


@pytest.mark.parametrize("C", [1, 2, 3, 5, 8, 12, 33])
def test_bitonic_network_sorts(C):
    """The network itself: ascending along axis 0, +inf pad rows at the
    bottom, real rows a permutation of the input columns."""
    x = _mat(C, 97, seed=C)
    s = np.asarray(bitonic_sorted(x))
    assert s.shape[0] >= C and (s.shape[0] & (s.shape[0] - 1)) == 0
    np.testing.assert_allclose(s[:C], np.sort(np.asarray(x), axis=0),
                               atol=0)
    assert np.all(np.isinf(s[C:]))


def test_bitonic_rejects_bad_trim():
    with pytest.raises(ValueError, match="trim"):
        trimmed_mean_agg(_mat(4, 64), 2, interpret=True)
    with pytest.raises(ValueError, match="trim"):
        trimmed_mean_jnp(_mat(4, 64), 2)


# ---------------------------------------------------------------------------
# fused run == vectorized per-round run (curves + final metrics)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fused_ds():
    # 8 clients x 32 samples, shard-divisible (the §4 parity regime)
    return mnist_like(seed=0, n_train=256, n_test=128)


def _cfg(engine, **kw):
    base = dict(num_clients=8, num_groups=2, rounds=2, local_epochs=1,
                local_batch_size=16, lr=0.05, seed=0, participation=1.0)
    base.update(kw)
    return FLConfig(engine=engine, **base)


def _assert_fused_parity(ds, **kw):
    rv = FederatedSimulation(_cfg("vectorized", **kw), ds).run()
    rf = FederatedSimulation(_cfg("fused", **kw), ds).run()
    np.testing.assert_allclose(rf.round_train_acc, rv.round_train_acc,
                               atol=1e-5)
    np.testing.assert_allclose(rf.round_train_loss, rv.round_train_loss,
                               atol=1e-4)
    np.testing.assert_allclose(rf.round_test_acc, rv.round_test_acc,
                               atol=1e-5)
    assert abs(rf.train_accuracy - rv.train_accuracy) <= 1e-5
    assert abs(rf.test_accuracy - rv.test_accuracy) <= 1e-5
    assert abs(rf.f1 - rv.f1) <= 1e-5
    np.testing.assert_array_equal(rf.confusion, rv.confusion)
    return rv, rf


@pytest.mark.parametrize("strategy,kw", [
    # rounds=3 spans a full HFL dissemination cycle: refine-only round,
    # scheduled global round, forced final global round
    ("hfl", dict(rounds=3)),
    ("afl", dict(participation=0.5)),       # per-round participant gather
    ("cfl", dict()),                        # nested visit scan
    ("fedprox", dict(prox_mu=0.1)),         # extra="bases" proximal ref
    ("fedavgm", dict(server_lr=0.7, server_momentum=0.9)),
    ("fedadam", dict(server_lr=0.1)),       # Adam state rides the carry
])
def test_fused_matches_per_round(fused_ds, strategy, kw):
    _assert_fused_parity(fused_ds, strategy=strategy, **kw)


def test_fused_matches_per_round_gossip(fused_ds):
    _assert_fused_parity(fused_ds, strategy="afl", afl_mode="gossip")


def test_fused_matches_per_round_under_attack(fused_ds):
    """Attack + defense entirely in-scan: sign-flip corruption between
    training and the bitonic-median aggregation event."""
    _assert_fused_parity(fused_ds, strategy="afl", attack="sign_flip",
                         attack_scale=4.0, defense="median", rounds=3)


def test_fused_rng_stream_matches_per_round(fused_ds):
    """The hoisted precompute consumes the run rng exactly like the
    per-round driver (§4), so the post-run generator states coincide."""
    sv = FederatedSimulation(_cfg("vectorized", strategy="afl",
                                  participation=0.5), fused_ds)
    sf = FederatedSimulation(_cfg("fused", strategy="afl",
                                  participation=0.5), fused_ds)
    sv.run(), sf.run()
    assert (sv.rng.bit_generator.state["state"]
            == sf.rng.bit_generator.state["state"])


# ---------------------------------------------------------------------------
# surface / validation
# ---------------------------------------------------------------------------

def test_fused_engine_registered():
    assert "fused" in ENGINES


def test_fused_rejects_async(fused_ds):
    with pytest.raises(ValueError, match="fused"):
        FederatedSimulation(
            FLConfig(strategy="async", engine="fused", num_clients=4,
                     local_batch_size=16), mnist_like(n_train=64,
                                                      n_test=32)).run()


def test_fused_scenario_spec_rejects_async():
    from repro.core.scenarios import ScenarioSpec
    with pytest.raises(ValueError, match="fused"):
        ScenarioSpec("bad-fused", "async cannot fuse", strategy="async",
                     topology="event", engine="fused")


def test_fused_scenarios_registered_and_runnable():
    from repro.core import scenarios
    assert "iid-hfl-fused" in scenarios.names()
    assert "iid-hfl-fused" in scenarios.CI_SMOKE_GRID
    spec = scenarios.get("attack-signflip-median-fused")
    res = scenarios.run_scenario(spec)
    assert res["spec"]["engine"] == "fused"
    assert res["attack"]["defense"] == "median"
    assert res["timing"]["build_time_s"] > 0
    assert len(res["metrics"]) == 6


# ---------------------------------------------------------------------------
# memory-bounded chunked local training (ISSUE 6: FLConfig.fused_chunk)
# ---------------------------------------------------------------------------
# Clients are independent, so training the participant stack one
# sub-stack at a time (lax.map over chunks) computes the same math as the
# all-at-once stack — chunking only bounds activation memory. It is not
# bitwise: the chunked round is a different XLA program (a loop over
# (chunk, ...) operands), which XLA fuses and orders its float
# reductions differently — the installed XLA:CPU moves the round loss by
# ~2 ulp (2.4e-7 at loss 2.3). The pin is the fused-parity tolerance of
# this file.

@pytest.mark.parametrize("strategy,chunk,kw", [
    ("afl", 4, {}),
    ("afl", 2, {}),
    ("hfl", 4, dict(rounds=3)),
    ("fedprox", 4, dict(prox_mu=0.1)),      # extra="bases" chunks too
])
def test_fused_chunked_matches_unchunked(fused_ds, strategy, chunk, kw):
    whole = FederatedSimulation(_cfg("fused", **kw),
                                fused_ds, strategy=strategy).run()
    chunked = FederatedSimulation(_cfg("fused", fused_chunk=chunk, **kw),
                                  fused_ds, strategy=strategy).run()
    np.testing.assert_allclose(chunked.round_train_loss,
                               whole.round_train_loss, atol=1e-5)
    np.testing.assert_allclose(chunked.round_test_acc,
                               whole.round_test_acc, atol=1e-5)
    assert abs(chunked.test_accuracy - whole.test_accuracy) <= 1e-5


def test_fused_chunk_must_divide_stack(fused_ds):
    with pytest.raises(ValueError, match="fused_chunk"):
        FederatedSimulation(_cfg("fused", fused_chunk=3), fused_ds,
                            strategy="afl").run()


def test_fused_chunk_config_validation():
    with pytest.raises(AssertionError):
        FLConfig(fused_chunk=-1)
    assert FLConfig(engine="fused", fused_chunk=4).fused_chunk == 4
