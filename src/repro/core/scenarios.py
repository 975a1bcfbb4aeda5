"""Declarative scenario registry — one source of truth for experiments,
benchmarks, and CI.

A `ScenarioSpec` names a point in the evaluation space the paper (and its
future-work directions) spans:

    strategy x partition (iid / Dirichlet-alpha) x topology
             x heterogeneity (speed model, dropout, staleness decay)
             x adversary (attack type/fraction -> defense; DESIGN.md §8)
             x engine (loop / vectorized)

`strategy` may be ANY name in the Strategy plugin registry
(`core/strategies.py`): the paper's hfl/afl/cfl, the async runtime, the
PR 4 plugins (fedprox, fedavgm, fedadam), or a third-party plugin
registered before the spec is built — topology and defense validity are
read off the strategy class itself (DESIGN.md §9).

Every spec resolves to a runnable configuration (`resolve`) and every run
emits one stable result-JSON document (`run_scenario`, schema in
DESIGN.md §6) so `examples/`, `benchmarks/run.py`, and the CI bench-smoke
job all consume the same definitions instead of hand-rolled configs.

    PYTHONPATH=src python -m repro.core.scenarios --list
    PYTHONPATH=src python -m repro.core.scenarios --run iid-hfl-vec
    PYTHONPATH=src python -m repro.core.scenarios --grid ci --json out.json
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional, Tuple, Union

from repro.core.codecs import CODEC_REGISTRY_VERSION, codec_names, get_codec
from repro.core.faults import FAULT_PROFILES
from repro.core.fl_types import ARRIVALS, ATTACKS, DEFENSES
from repro.core.strategies import (STRATEGY_REGISTRY_VERSION, get_strategy,
                                   strategy_names)

# v2.6: the "telemetry" block loses the per-round phase proxy and the
# trace-entry dispatch counters, its counters gain the fused executor's
# "compile.requests" / "compile.cache_hits", and its "run" spans gain
# construct / lower / compile (DESIGN.md §13). v2.5 added the "faults" block (churn-tolerant
# runtime — DESIGN.md §15:
# fault profile + schedule statistics, churn/rejoin counts, quorum
# failures, degraded rounds; null when fault_profile="none"). v2.4
# added the "serving" block (federation-in-the-loop serving —
# DESIGN.md §14: virtual-clock qps, latency percentiles, shed rate,
# batch occupancy, hot-swap count, served-staleness histogram; null
# when serving is off). v2.3 added the "telemetry" block (per-phase
# span totals, run-level spans, counters/series, dispatch deltas, peak
# RSS — DESIGN.md §13; {"enabled": false} when telemetry is off) and
# the warmup/steady timing split (timing.warmup_time_s /
# timing.steady_time_s); v2.2 added the "communication" block
# (per-round uplink/downlink bytes, compression ratio, codec name +
# registry version; null for dense runs); v2.1 added the "strategy"
# block (plugin name + registry version); v2 added the "attack" block.
# Older documents are still readable through `load_result`.
RESULT_SCHEMA_VERSION = 2.6

# One output-dir convention for every result/curve writer: the example
# CLI's curves, `--json` grid dumps, and experiment artifacts all land
# under this root (env-overridable), so nothing strays into the repo
# root anymore.
OUTPUT_DIR = os.environ.get("REPRO_OUTPUT_DIR", "experiments")


def output_path(*parts: str) -> str:
    """Join under the shared output root, creating directories."""
    path = os.path.join(OUTPUT_DIR, *parts)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    return path


PARTITIONS = ("iid", "dirichlet")


def _topologies(strategy: str) -> Tuple[str, ...]:
    """Valid communication graphs, read off the registered Strategy."""
    return get_strategy(strategy).topologies


def _defenses(strategy: str, topology: str) -> Tuple[str, ...]:
    """Valid defenses at the strategy/topology aggregation event
    (declared on the Strategy class — DESIGN.md §8/§9)."""
    return get_strategy(strategy).defenses.get(topology, ("none",))


# Static snapshots of the shipped strategies' declarations (backwards-
# compatible view; plugin strategies registered later are validated
# against the registry directly, not these tables).
TOPOLOGY_BY_STRATEGY = {name: _topologies(name) for name in strategy_names()}
DEFENSES_BY_STRATEGY = {
    (name, topo): _defenses(name, topo)
    for name in strategy_names() for topo in _topologies(name)}


@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """One named, fully-specified federated run."""
    name: str
    description: str
    strategy: str = "afl"            # any registered Strategy plugin
    topology: str = "star"           # see Strategy.topologies
    engine: str = "vectorized"       # loop | vectorized
    # data
    dataset: str = "mnist"           # mnist | fashion
    partition: str = "iid"           # iid | dirichlet
    dirichlet_alpha: float = 0.5
    n_train: int = 512
    n_test: int = 256
    # federation shape / schedule
    num_clients: int = 8
    num_groups: int = 2
    rounds: int = 2
    local_epochs: int = 1
    local_batch_size: int = 32
    lr: float = 0.05
    momentum: float = 0.9
    participation: float = 1.0
    gossip_neighbors: int = 2
    merge_alpha: float = 0.5
    # heterogeneity (async strategy only)
    speed_model: str = "uniform"     # uniform | lognormal | straggler
    dropout: float = 0.0
    staleness_alpha: float = 0.6
    staleness_decay: float = 0.5
    updates_per_client: int = 2
    tick: float = 1.0
    # strategy-plugin knobs (fedprox / server-optimizer family)
    prox_mu: float = 0.01
    server_lr: float = 1.0
    server_momentum: float = 0.9
    # adversarial clients + robust aggregation (DESIGN.md §8)
    attack: str = "none"             # core/attacks.py
    attack_fraction: float = 0.25
    attack_scale: float = 1.0
    attack_placement: str = "random"  # random | colluding (DESIGN.md §15)
    defense: str = "none"            # core/robust.py
    defense_f: int = 0               # 0 = derive from attack_fraction
    clip_tau: float = 10.0
    # fault injection / dynamic membership (DESIGN.md §15): named
    # profiles compiled from the seed into per-round schedules;
    # "none" is structurally inert (bitwise the pre-fault run)
    fault_profile: str = "none"      # core/faults.py FAULT_PROFILES
    churn_rate: float = 0.3
    quorum_frac: float = 0.5
    heartbeat_timeout: int = 1
    fault_mtd: bool = False          # per-round gossip-ring re-random.
    # upload codec (DESIGN.md §12)
    codec: str = "none"              # core/codecs.py registry
    topk_frac: float = 0.1           # topk: fraction of coords shipped
    quant_bits: int = 8              # qsgd: 8 (int8+scale) | 16 (bf16)
    # observability (DESIGN.md §13): on-by-default tracer; results are
    # bitwise identical either way
    telemetry: bool = True
    # federation-in-the-loop serving (DESIGN.md §14): virtual-clock
    # request serving with round-boundary hot-swap; training results
    # are bitwise identical with serving on or off
    serve: bool = False
    serve_qps: float = 64.0
    serve_arrival: str = "poisson"   # poisson | burst | diurnal
    serve_batch: int = 8
    serve_max_wait: float = 0.05
    serve_queue: int = 64
    serve_round_duration: float = 1.0
    seed: int = 0

    def __post_init__(self):
        try:
            allowed = _topologies(self.strategy)
        except KeyError:
            raise ValueError(f"unknown strategy {self.strategy!r} "
                             f"(registered: {strategy_names()})") from None
        if self.topology not in allowed:
            raise ValueError(
                f"{self.name}: topology {self.topology!r} is invalid for "
                f"strategy {self.strategy!r} (expected one of {allowed})")
        if self.partition not in PARTITIONS:
            raise ValueError(f"unknown partition {self.partition!r}")
        if self.engine not in ("loop", "vectorized", "fused"):
            raise ValueError(f"unknown engine {self.engine!r}")
        if self.engine == "fused" and not getattr(
                get_strategy(self.strategy), "supports_fused", False):
            raise ValueError(
                f"{self.name}: strategy {self.strategy!r} does not "
                f"support the fused executor (DESIGN.md §10)")
        if self.attack not in ATTACKS:
            raise ValueError(f"unknown attack {self.attack!r} "
                             f"(expected one of {ATTACKS})")
        allowed_d = _defenses(self.strategy, self.topology)
        if self.defense not in allowed_d:
            raise ValueError(
                f"{self.name}: defense {self.defense!r} does not apply to "
                f"the {self.strategy}/{self.topology} aggregation event "
                f"(expected one of {allowed_d}; DESIGN.md §8)")
        if self.codec not in codec_names():
            raise ValueError(
                f"{self.name}: unknown codec {self.codec!r} "
                f"(registered: {codec_names()})")
        if self.codec != "none":
            cls = get_codec(self.codec)
            if self.defense not in cls.defenses:
                raise ValueError(
                    f"{self.name}: codec {self.codec!r} does not support "
                    f"defense {self.defense!r} (declared: {cls.defenses}; "
                    f"DESIGN.md §12)")
            if cls.stateful and getattr(get_strategy(self.strategy),
                                        "codec_seam", "driver") != "driver":
                raise ValueError(
                    f"{self.name}: stateful codec {self.codec!r} needs the "
                    f"stacked driver upload seam, which strategy "
                    f"{self.strategy!r} does not use (DESIGN.md §12)")
        if self.serve and self.serve_arrival not in ARRIVALS:
            raise ValueError(
                f"{self.name}: unknown arrival process "
                f"{self.serve_arrival!r} (expected one of {ARRIVALS})")
        if self.fault_profile not in FAULT_PROFILES:
            raise ValueError(
                f"{self.name}: unknown fault profile "
                f"{self.fault_profile!r} (expected one of "
                f"{FAULT_PROFILES})")
        if self.fault_mtd and self.topology != "ring":
            raise ValueError(
                f"{self.name}: fault_mtd re-randomizes the GOSSIP ring "
                f"per round — it needs topology='ring' (DESIGN.md §15)")
        if self.attack_placement not in ("random", "colluding"):
            raise ValueError(
                f"{self.name}: unknown attack placement "
                f"{self.attack_placement!r} (expected random|colluding)")

    def to_fl_config(self):
        """The underlying FLConfig: `strategy` resolves 1:1 through the
        plugin registry; an AFL ring topology selects gossip mode."""
        from repro.core.fl_types import FLConfig
        return FLConfig(
            strategy=self.strategy,
            num_clients=self.num_clients, num_groups=self.num_groups,
            rounds=self.rounds, local_epochs=self.local_epochs,
            local_batch_size=self.local_batch_size, lr=self.lr,
            momentum=self.momentum, participation=self.participation,
            afl_mode="gossip" if self.topology == "ring" else "fedavg",
            gossip_neighbors=self.gossip_neighbors,
            merge_alpha=self.merge_alpha, seed=self.seed,
            staleness_alpha=self.staleness_alpha,
            staleness_decay=self.staleness_decay,
            updates_per_client=self.updates_per_client,
            speed_model=self.speed_model, dropout=self.dropout,
            tick=self.tick, prox_mu=self.prox_mu,
            server_lr=self.server_lr,
            server_momentum=self.server_momentum,
            attack=self.attack, attack_fraction=self.attack_fraction,
            attack_scale=self.attack_scale,
            attack_placement=self.attack_placement,
            defense=self.defense,
            defense_f=self.defense_f, clip_tau=self.clip_tau,
            fault_profile=self.fault_profile,
            churn_rate=self.churn_rate, quorum_frac=self.quorum_frac,
            heartbeat_timeout=self.heartbeat_timeout,
            fault_mtd=self.fault_mtd,
            codec=self.codec, topk_frac=self.topk_frac,
            quant_bits=self.quant_bits, telemetry=self.telemetry,
            serve=self.serve, serve_qps=self.serve_qps,
            serve_arrival=self.serve_arrival,
            serve_batch=self.serve_batch,
            serve_max_wait=self.serve_max_wait,
            serve_queue=self.serve_queue,
            serve_round_duration=self.serve_round_duration,
            engine=self.engine)

    def asdict(self) -> Dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

REGISTRY: Dict[str, ScenarioSpec] = {}


def register(spec: ScenarioSpec) -> ScenarioSpec:
    if spec.name in REGISTRY:
        raise ValueError(f"duplicate scenario name {spec.name!r}")
    REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> ScenarioSpec:
    if name not in REGISTRY:
        known = ", ".join(sorted(REGISTRY))
        raise KeyError(f"unknown scenario {name!r} (known: {known})")
    return REGISTRY[name]


def names() -> List[str]:
    return sorted(REGISTRY)


# strategy x engine coverage on the paper's IID setting
register(ScenarioSpec(
    "iid-hfl-vec", "centralized two-tier HFL, IID shards, stacked engine",
    strategy="hfl", topology="hierarchical", local_epochs=2))
register(ScenarioSpec(
    "iid-hfl-loop", "loop-engine twin of iid-hfl-vec (paper-faithful "
    "per-client dispatch timing)",
    strategy="hfl", topology="hierarchical", local_epochs=2, engine="loop"))
register(ScenarioSpec(
    "iid-afl-vec", "decentralized AFL, 50% participation, masked FedAvg",
    strategy="afl", topology="star", participation=0.5, local_epochs=2))
register(ScenarioSpec(
    "iid-cfl-vec", "decentralized continual CFL, sequential client pass",
    strategy="cfl", topology="sequential"))
register(ScenarioSpec(
    "ring-gossip-vec", "AFL in gossip mode: ring-neighbor averaging, full "
    "participation",
    strategy="afl", topology="ring", participation=1.0))
# fused-executor twins (DESIGN.md §10): the whole run as one compiled
# lax.scan with device-resident state — same schedule/rng/curves as the
# vectorized per-round driver to float tolerance (tests/test_fused.py)
register(ScenarioSpec(
    "iid-hfl-fused", "fused-executor twin of iid-hfl-vec: all rounds in "
    "one lax.scan, device-resident group/global state, in-scan "
    "dissemination schedule",
    strategy="hfl", topology="hierarchical", local_epochs=2,
    engine="fused"))
register(ScenarioSpec(
    "attack-signflip-median-fused", "sign-flip attackers vs the bitonic "
    "median kernel, corrupted and defended entirely inside the fused "
    "round scan",
    strategy="afl", topology="star", participation=1.0, engine="fused",
    attack="sign_flip", attack_scale=4.0, defense="median"))
# non-IID Dirichlet label skew — loop engine (uneven shards are the loop
# engine's territory: the stacked engine truncates to the federation-min
# batch count)
register(ScenarioSpec(
    "dirichlet-afl-loop", "AFL under Dirichlet(0.3) label skew",
    strategy="afl", topology="star", engine="loop", partition="dirichlet",
    dirichlet_alpha=0.3, participation=0.5, n_train=768))
register(ScenarioSpec(
    "dirichlet-hfl-loop", "HFL under mild Dirichlet(1.0) label skew",
    strategy="hfl", topology="hierarchical", engine="loop",
    partition="dirichlet", dirichlet_alpha=1.0, n_train=768))
# heterogeneous async runtime — the PR 2 tentpole axis, now a plugin
register(ScenarioSpec(
    "async-uniform-vec", "async staleness-aware merge, homogeneous "
    "clients (full-federation tick batches)",
    strategy="async", topology="event", speed_model="uniform"))
register(ScenarioSpec(
    "async-straggler-vec", "async with one 4x straggler: fast clients "
    "keep merging while the straggler's updates arrive stale",
    strategy="async", topology="event", speed_model="straggler"))
register(ScenarioSpec(
    "async-dropout-vec", "async where half the participants fail "
    "mid-run; the survivors' merges carry the model",
    strategy="async", topology="event", speed_model="uniform", dropout=0.5,
    updates_per_client=3))
register(ScenarioSpec(
    "async-lognormal-loop", "async under continuous LogNormal speeds "
    "(singleton batches — the loop engine's regime)",
    strategy="async", topology="event", engine="loop",
    speed_model="lognormal", tick=0.0))

# PR 4 strategy plugins, shipped through the public API alone: FedProx
# (proximal local objective under label skew — its home turf) and the
# server-optimizer family (FedAvgM / FedAdam over the kernel-backed
# aggregate)
register(ScenarioSpec(
    "fedprox-dirichlet-vec", "FedProx (mu=0.1) under Dirichlet(0.5) "
    "label skew: the proximal pull bounds client drift",
    strategy="fedprox", topology="star", partition="dirichlet",
    dirichlet_alpha=0.5, n_train=768, prox_mu=0.1, local_epochs=2))
register(ScenarioSpec(
    "fedprox-iid-loop", "FedProx on IID shards under the loop engine "
    "(mu=0.01 barely perturbs FedAvg — the sanity point)",
    strategy="fedprox", topology="star", engine="loop", prox_mu=0.01))
register(ScenarioSpec(
    "fedavgm-iid-vec", "FedAvgM: server momentum (0.9) over the round "
    "pseudo-gradient, kernel-backed aggregate",
    strategy="fedavgm", topology="star", local_epochs=2,
    server_lr=0.7, server_momentum=0.9))
register(ScenarioSpec(
    "fedadam-iid-vec", "FedAdam: server Adam over the round "
    "pseudo-gradient",
    strategy="fedadam", topology="star", local_epochs=2, server_lr=0.1))
register(ScenarioSpec(
    "fedadam-signflip-median-vec", "FedAdam composed with the "
    "adversarial axis: sign-flip attackers, median aggregate feeding "
    "the server optimizer",
    strategy="fedadam", topology="star", local_epochs=2, server_lr=0.1,
    attack="sign_flip", attack_scale=4.0, defense="median"))

# adversarial axis — attack x defense x architecture (DESIGN.md §8).
# The 32-client sign-flip family is the ISSUE 3 acceptance measurement:
# same data/schedule/seed, only the attack/defense toggles differ, so the
# macro-F1 deltas isolate the aggregation rule (recovery run checked into
# experiments/attacks/).
# plain SGD (no momentum) at a larger step: momentum + tiny shards makes
# even the CLEAN 32-client run unstable past ~10 rounds, and robust
# aggregation's quantile bias shrinks the effective step (the larger lr
# compensates — calibrated so defended runs recover the no-attack F1)
_ACC32 = dict(strategy="afl", topology="star", participation=1.0,
              num_clients=32, n_train=3072, n_test=512, rounds=10,
              local_epochs=2, lr=0.08, momentum=0.0)
register(ScenarioSpec(
    "attack-none-32c-vec", "32-client no-attack baseline of the "
    "acceptance family (recovery reference)", **_ACC32))
register(ScenarioSpec(
    "attack-signflip-fedavg-32c-vec", "25% sign-flip attackers vs PLAIN "
    "FedAvg — demonstrates the degradation robust aggregation prevents",
    attack="sign_flip", attack_scale=4.0, **_ACC32))
register(ScenarioSpec(
    "attack-signflip-median-32c-vec", "25% sign-flip attackers vs "
    "coordinate-wise median (robust_agg kernel)",
    attack="sign_flip", attack_scale=4.0, defense="median", **_ACC32))
register(ScenarioSpec(
    "attack-signflip-trimmed-32c-vec", "25% sign-flip attackers vs "
    "trimmed mean (robust_agg kernel, f from attack fraction)",
    attack="sign_flip", attack_scale=4.0, defense="trimmed_mean",
    **_ACC32))
# defense coverage across the other architectures / aggregation events
register(ScenarioSpec(
    "attack-gauss-hfl-krum-vec", "centralized HFL with Gaussian-noise "
    "attackers; Krum selection at each group server (tier 1)",
    strategy="hfl", topology="hierarchical", num_clients=16, n_train=1024,
    local_epochs=2, attack="gauss", attack_scale=3.0, defense="krum"))
register(ScenarioSpec(
    "attack-replace-cfl-clip-vec", "sequential CFL with a boosted "
    "model-replacement attacker; norm-clipped continual merges",
    strategy="cfl", topology="sequential", attack="model_replace",
    attack_fraction=0.15, attack_scale=10.0, defense="norm_clip",
    clip_tau=3.0))
register(ScenarioSpec(
    "attack-labelflip-afl-trimmed-loop", "data-layer label-flip "
    "poisoning under the loop engine; trimmed-mean aggregation",
    strategy="afl", topology="star", engine="loop", participation=1.0,
    attack="label_flip", defense="trimmed_mean"))
register(ScenarioSpec(
    "attack-signflip-gossip-median-vec", "decentralized ring gossip "
    "where each node median-mixes its neighborhood (Byzantine neighbors "
    "bounded without any server)",
    strategy="afl", topology="ring", participation=1.0,
    attack="sign_flip", attack_scale=4.0, defense="median"))
register(ScenarioSpec(
    "attack-gauss-async-clip-vec", "async staleness merges under "
    "Gaussian attackers; every arriving delta norm-clipped",
    strategy="async", topology="event", speed_model="uniform",
    attack="gauss", attack_scale=3.0, defense="norm_clip", clip_tau=3.0))

# communication axis — upload codecs on the wire (DESIGN.md §12). The
# acceptance pair is `comm-qsgd-accept-32c-vec` vs `attack-none-32c-vec`
# (same data/schedule/seed, only the codec toggles): ISSUE 7 requires
# >= 3.5x uplink compression with macro-F1 within 0.02 of the dense run.
register(ScenarioSpec(
    "comm-topk-afl-vec", "top-k sparsification (10% of coordinates) with "
    "error-feedback residuals on the AFL star",
    strategy="afl", topology="star", participation=1.0, local_epochs=2,
    codec="topk", topk_frac=0.1))
register(ScenarioSpec(
    "comm-qsgd-hfl-fused", "int8 stochastic quantization under the fused "
    "executor: dequantize-and-aggregate inside the round scan",
    strategy="hfl", topology="hierarchical", engine="fused",
    local_epochs=2, codec="qsgd"))
register(ScenarioSpec(
    "comm-qsgd-signflip-median-vec", "the codec x adversary crossing: "
    "sign-flip attackers quantized on the wire, median aggregation over "
    "the dequantized coordinates",
    strategy="afl", topology="star", participation=1.0, codec="qsgd",
    attack="sign_flip", attack_scale=4.0, defense="median"))
register(ScenarioSpec(
    "comm-topk-async-loop", "top-k + error feedback riding the async "
    "merge batches under the loop engine",
    strategy="async", topology="event", engine="loop",
    speed_model="uniform", codec="topk", topk_frac=0.25))
# the acceptance pair runs the 32-client basis for 12 rounds (vs the
# attack family's 10): both runs converge there, so the measurement
# isolates the quantization noise floor instead of mid-training
# variance (at 10 rounds the runs sit on the steep part of the curve
# and seed-level noise alone moves macro-F1 by more than the 0.02 bar)
_COMM32 = dict(_ACC32, rounds=12)
register(ScenarioSpec(
    "comm-dense-accept-32c-vec", "32-client dense reference of the "
    "codec acceptance pair (the macro-F1 baseline qsgd is held to)",
    **_COMM32))
register(ScenarioSpec(
    "comm-qsgd-accept-32c-vec", "32-client qsgd acceptance run: the "
    "dense twin with int8 uploads (~4x uplink compression at matched "
    "macro-F1)",
    codec="qsgd", **_COMM32))

# observability (DESIGN.md §13): the trace-demo / CI trace-artifact
# scenario — fused executor (exercising the in-scan counters AND the
# per-phase proxy), sign-flip attackers under median defense so the
# corrupt/defense phases show up in the per-phase breakdown
register(ScenarioSpec(
    "obs-trace-fused-16c", "16-client fused sign-flip/median run for "
    "the telemetry trace demo (make trace-demo / the CI trace artifact)",
    strategy="afl", topology="star", engine="fused", participation=1.0,
    num_clients=16, rounds=4, n_train=1024, attack="sign_flip",
    attack_scale=4.0, defense="median"))

# federation-in-the-loop serving (DESIGN.md §14): train+serve scenarios
# exercising each arrival shape. The fused twin is the acceptance run
# (hot-swap replay of the in-scan model stack); the burst scenario is
# sized to overflow the bounded queue so shedding shows up in the
# block; the codec x adversary crossing serves the model the defended
# quantized aggregation actually produces.
register(ScenarioSpec(
    "serve-iid-fused", "fused-executor HFL with the serving side-car: "
    "per-round global models stacked in-scan, hot-swap replayed at "
    "round boundaries, Poisson traffic",
    strategy="hfl", topology="hierarchical", local_epochs=2,
    engine="fused", serve=True))
register(ScenarioSpec(
    "serve-hfl-burst", "centralized HFL under on/off burst traffic: "
    "3x-rate bursts against the bounded queue — occupancy high, "
    "overflow shed and accounted",
    strategy="hfl", topology="hierarchical", local_epochs=2, serve=True,
    serve_arrival="burst", serve_qps=256.0, serve_batch=4,
    serve_queue=8, serve_max_wait=0.02))
register(ScenarioSpec(
    "serve-qsgd-signflip-median", "the full-stack crossing: sign-flip "
    "attackers quantized on the wire, median-defended aggregation, and "
    "the surviving global model served under diurnal traffic",
    strategy="afl", topology="star", participation=1.0, codec="qsgd",
    attack="sign_flip", attack_scale=4.0, defense="median", serve=True,
    serve_arrival="diurnal"))

# churn-tolerant runtime (DESIGN.md §15): dynamic-membership scenarios.
# The acceptance PAIR is `churn-signflip-median-mtd` vs its `-static`
# twin — identical data/schedule/seed/churn, only the per-round
# moving-target ring re-randomization toggles, so the macro-F1 delta
# isolates what MTD buys against a COLLUDING sign-flip neighborhood
# (attackers placed to sandwich every other ring position; median over
# a degree-2 neighborhood breaks when 2 of 3 members collude, and the
# re-randomized ring makes that sandwich a transient instead of a
# permanent fixture). ISSUE 10 acceptance: 30% churn, no NaN, MTD
# recovers a positive macro-F1 margin over the static ring.
register(ScenarioSpec(
    "churn-afl-gossip-mtd", "clean gossip ring under 30% crash/rejoin "
    "churn with per-round moving-target re-randomization, fused "
    "executor (fault schedule as precomputed scan inputs)",
    strategy="afl", topology="ring", engine="fused", participation=1.0,
    fault_profile="churn", churn_rate=0.3, fault_mtd=True))
register(ScenarioSpec(
    "churn-hfl-quorum", "centralized HFL under mid-severity faults with "
    "a strict quorum: below-quorum groups hold their round-start model, "
    "below-quorum rounds hold the hierarchy",
    strategy="hfl", topology="hierarchical", local_epochs=2,
    fault_profile="mid", quorum_frac=0.6))
# acceptance-pair base (DESIGN.md §15): degree-4 ring + scale 1.5 is
# the tuned operating point. At degree 4 with colluding even-id
# placement every attacker row carries self + two attacker neighbors =
# 3 corrupt of 5 gather slots — exactly saturating the median window
# deterministically on the static ring — while per-round re-
# randomization (fault_mtd) drops attacker neighborhoods below the
# threshold most rounds. Scale 1.5 sits past the static ring's
# destruction cliff but inside the MTD ring's recovery region
# (observed: mtd f1 0.277 vs static 0.071; at degree 2 the two arms
# are nearly indistinguishable because dead-neighbor self-substitution
# keeps ~as many attacker rows corrupt either way).
_CHURN32 = dict(_ACC32, topology="ring", attack="sign_flip",
                attack_scale=1.5, attack_placement="colluding",
                defense="median", gossip_neighbors=4,
                fault_profile="churn", churn_rate=0.3)
register(ScenarioSpec(
    "churn-signflip-median-mtd", "32-client acceptance run: colluding "
    "sign-flip neighborhoods on the gossip ring under 30% churn, median "
    "defense, WITH per-round moving-target re-randomization",
    fault_mtd=True, **_CHURN32))
register(ScenarioSpec(
    "churn-signflip-median-static", "static-ring twin of "
    "churn-signflip-median-mtd (the colluding sandwich persists every "
    "round — the baseline MTD is measured against)",
    fault_mtd=False, **_CHURN32))

# the CI bench-smoke grid: one sync-centralized, one sync-decentralized,
# one async-heterogeneous, one adversarial scenario, one scenario per
# PR 4 strategy plugin family, one fused-executor scenario, one
# upload-codec scenario, plus one train+serve scenario
# (see .github/workflows/ci.yml)
CI_SMOKE_GRID: Tuple[str, ...] = (
    "iid-hfl-vec", "ring-gossip-vec", "async-straggler-vec",
    "attack-replace-cfl-clip-vec", "fedprox-dirichlet-vec",
    "fedadam-iid-vec", "iid-hfl-fused", "comm-qsgd-signflip-median-vec",
    "serve-iid-fused")


# ---------------------------------------------------------------------------
# resolution + execution
# ---------------------------------------------------------------------------

def resolve(spec: ScenarioSpec):
    """Spec -> (FederatedSimulation, spec) with dataset built, partition
    applied, strategy plugin resolved, and engine state ready."""
    from repro.core.simulation import FederatedSimulation
    return FederatedSimulation.from_scenario(spec), spec


def run_scenario(scenario: Union[str, ScenarioSpec],
                 trace_out: Optional[str] = None) -> Dict:
    """Run one scenario and return the stable result document
    (DESIGN.md §6). `rounds_per_s` is the round-throughput number the CI
    regression gate tracks: sync rounds (or async merge-batches) per
    second of build time. `trace_out` additionally writes the run's
    Chrome-trace JSON there (open in Perfetto / chrome://tracing)."""
    spec = get(scenario) if isinstance(scenario, str) else scenario
    sim, _ = resolve(spec)
    r = sim.run()
    if trace_out:
        from repro.obs import write_chrome_trace
        write_chrome_trace(sim.telemetry, trace_out)
    async_block = None
    units = spec.rounds
    if getattr(sim.strategy, "timeline_result", False):
        # the strategy DECLARES the timeline measurement contract
        # (Strategy.timeline_result) — no key sniffing on extras
        async_block = {k: r.extra.get(k) for k in
                       ("merges", "batches", "mean_staleness", "makespan",
                        "dropped_clients", "participants")}
        units = r.extra.get("batches", spec.rounds)
    attack_block = None
    if spec.attack != "none" or spec.defense != "none":
        # the Byzantine allowance actually applied at the aggregation
        # event, not the federation-level resolution: HFL defends per
        # group, AFL per sampled participant set — the strategy declares
        # its own event size
        attack_block = {
            "attack": spec.attack,
            "fraction": spec.attack_fraction,
            "scale": spec.attack_scale,
            "attacked_clients": [int(c) for c in sim.attackers],
            "defense": spec.defense,
            "defense_f": sim.fl.resolved_defense_f(
                sim.strategy.event_size()),
            "clip_tau": spec.clip_tau,
        }
    comm_block = r.extra.get("communication")
    if comm_block is not None:
        comm_block = {**comm_block,
                      "registry_version": CODEC_REGISTRY_VERSION}
    return {
        "schema_version": RESULT_SCHEMA_VERSION,
        "scenario": spec.name,
        "spec": spec.asdict(),
        "strategy": {
            "plugin": sim.strategy.name,
            "registry_version": STRATEGY_REGISTRY_VERSION,
        },
        "metrics": {
            "test_accuracy": r.test_accuracy,
            "train_accuracy": r.train_accuracy,
            "precision": r.precision, "recall": r.recall, "f1": r.f1,
            "balanced_accuracy": r.balanced_accuracy,
        },
        "timing": {
            "build_time_s": r.build_time_s,
            "warmup_time_s": r.warmup_time_s,
            "steady_time_s": r.steady_time_s,
            "classification_time_s": r.classification_time_s,
            "rounds_per_s": (units / r.build_time_s
                             if r.build_time_s > 0 else 0.0),
        },
        "async": async_block,
        "attack": attack_block,
        "communication": comm_block,
        "telemetry": r.extra.get("telemetry"),
        "serving": r.extra.get("serving"),
        "faults": r.extra.get("faults"),
    }


def load_result(doc: Dict) -> Dict:
    """Normalize a result document to the CURRENT schema so consumers
    (CI baseline compare, experiments tooling) never branch on
    schema_version themselves. v1 documents (pre-adversarial) carry no
    "attack" key — they read as unattacked documents; v2 documents
    (pre-plugin) carry no "strategy" block — the plugin name falls back
    to the spec's strategy field with a null registry version; v2.1
    documents (pre-codec) carry no "communication" block — they read as
    dense (uncompressed) runs; v2.2 documents (pre-observability) carry
    no "telemetry" block — they read as untraced runs; v2.3 documents
    (pre-serving) carry no "serving" block — they read as train-only
    runs; v2.4 documents (pre-faults) carry no "faults" block — they
    read as fault-free runs; v2.3-v2.5 telemetry blocks keep the two
    keys v2.6 no longer writes, which no consumer reads."""
    v = doc.get("schema_version")
    if v == RESULT_SCHEMA_VERSION:
        return doc
    if v == 2.5:
        return {**doc, "schema_version": RESULT_SCHEMA_VERSION}
    if v == 2.4:
        return {**doc, "schema_version": RESULT_SCHEMA_VERSION,
                "faults": None}
    if v == 2.3:
        return {**doc, "schema_version": RESULT_SCHEMA_VERSION,
                "serving": None, "faults": None}
    if v == 2.2:
        return {**doc, "schema_version": RESULT_SCHEMA_VERSION,
                "telemetry": None, "serving": None, "faults": None}
    if v == 2.1:
        return {**doc, "schema_version": RESULT_SCHEMA_VERSION,
                "communication": None, "telemetry": None, "serving": None,
                "faults": None}
    if v == 2:
        plugin = (doc.get("spec") or {}).get("strategy")
        return {**doc, "schema_version": RESULT_SCHEMA_VERSION,
                "strategy": {"plugin": plugin, "registry_version": None},
                "communication": None, "telemetry": None, "serving": None,
                "faults": None}
    if v == 1:
        plugin = (doc.get("spec") or {}).get("strategy")
        return {**doc, "schema_version": RESULT_SCHEMA_VERSION,
                "attack": None,
                "strategy": {"plugin": plugin, "registry_version": None},
                "communication": None, "telemetry": None, "serving": None,
                "faults": None}
    raise ValueError(f"unknown result schema_version {v!r}")


def main(argv: Optional[List[str]] = None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--list", action="store_true",
                    help="print the registry and exit")
    ap.add_argument("--run", nargs="+", metavar="NAME",
                    help="run the named scenario(s)")
    ap.add_argument("--grid", choices=["ci"],
                    help="run a predefined grid (ci = the bench-smoke set)")
    ap.add_argument("--json", metavar="PATH",
                    help="also write results as a JSON list (bare "
                         f"filenames land under {OUTPUT_DIR}/results/)")
    ap.add_argument("--trace-out", metavar="PATH",
                    help="write the run's Chrome-trace JSON (single "
                         "--run scenario only; open in Perfetto)")
    ap.add_argument("--fault-profile", choices=FAULT_PROFILES,
                    help="override every selected scenario's fault "
                         "profile (DESIGN.md §15; the chaos CI job runs "
                         "the smoke grid with 'mid')")
    ap.add_argument("--churn-rate", type=float,
                    help="override the fault schedule's churn/severity "
                         "rate (fraction in [0,1])")
    ap.add_argument("--quorum-frac", type=float,
                    help="override the quorum threshold fraction an "
                         "aggregation event needs to proceed")
    args = ap.parse_args(argv)
    overrides = {k: v for k, v in (("fault_profile", args.fault_profile),
                                   ("churn_rate", args.churn_rate),
                                   ("quorum_frac", args.quorum_frac))
                 if v is not None}
    if args.trace_out and not (args.run and len(args.run) == 1
                               and not args.grid):
        ap.error("--trace-out needs exactly one --run scenario")

    if args.list or not (args.run or args.grid):
        for n in names():
            s = REGISTRY[n]
            adv = ("clean" if s.attack == "none" and s.defense == "none"
                   else f"{s.attack}->{s.defense}")
            print(f"{n:34s} {s.strategy}/{s.topology}/{s.engine:10s} "
                  f"partition={s.partition:9s} clients={s.num_clients:<3d} "
                  f"{adv:24s} {s.description}")
        return

    todo = list(args.run or []) + (list(CI_SMOKE_GRID) if args.grid else [])
    results = []
    for name in todo:
        spec = get(name)
        if overrides:
            # dataclasses.replace re-runs __post_init__, so an invalid
            # override combination fails loudly before any training
            spec = dataclasses.replace(spec, **overrides)
        res = run_scenario(spec, trace_out=args.trace_out)
        results.append(res)
        m, t = res["metrics"], res["timing"]
        print(f"{name}: test_acc={m['test_accuracy']:.3f} "
              f"f1={m['f1']:.3f} build={t['build_time_s']:.2f}s "
              f"rounds_per_s={t['rounds_per_s']:.3f}")
    if args.trace_out:
        print(f"trace -> {args.trace_out}")
    if args.json:
        path = (args.json if os.path.dirname(args.json)
                else output_path("results", args.json))
        with open(path, "w") as f:
            json.dump(results, f, indent=1)
        print(f"results -> {path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
