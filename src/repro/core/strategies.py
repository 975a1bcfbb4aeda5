"""Strategy plugin API — every FL architecture as one pluggable object.

PRs 1-3 encoded each architecture in duplicated per-engine runners
(`FederatedSimulation._run_{hfl,afl,cfl}` + `_vec` twins, plus
`AsyncSimulation`'s own dispatch), so every new axis (heterogeneity,
attacks, defenses) had to be threaded through six paths by hand. This
module replaces that with a small lifecycle protocol driven by ONE
generic round driver (`core/simulation.py`):

    init_state           -> the strategy's mutable round state
    select_participants  -> RoundPlan: who trains this event, from which
                            base models (async consumes its tick-batch
                            timeline here)
    local_spec           -> LocalSpec: the local objective (FedProx adds
                            its proximal term here)
    aggregate_event      -> fold the (possibly corrupted) uploads into
                            the state through the kernel-backed stacked
                            operators (`core/aggregation.py`), applying
                            the per-event defense
    round_model / served_fn / extra_result -> metric + serving surface

The driver owns everything strategy-independent: engine dispatch (loop
per-client jits vs the vectorized stacked scan), rng-parity bookkeeping
(DESIGN.md §4), attack corruption between training and aggregation
(DESIGN.md §8), defense-argument resolution, curve tracking, and the
paper's timing protocol. A strategy therefore states only its schedule
and its aggregation math — and is automatically available under both
engines, the attack axis, and `run_scenario`.

Since PR 5 a strategy may additionally opt into the FUSED executor
(`engine="fused"`, DESIGN.md §10): the whole run compiles into one
`jax.lax.scan` whose carry is the strategy state. The traceable half of
the protocol — `scan_round` (default wraps the lifecycle pieces),
`scan_bases`, `scan_aggregate`, `scan_carry`/`scan_uncarry`,
`scan_extra_xs` — lives on the Strategy too; `supports_fused` declares
the opt-in (async cannot fuse: its tick batches are data-dependent).

Strategies register by name (`@register_strategy`); `get_strategy`
resolves names for `FLConfig.strategy` and the scenario registry.
Third-party plugins subclass `Strategy` and register from their own
code — no core edits (tests/test_plugin_strategy.py proves this).

Which defenses are valid at a strategy's aggregation event is declared
ON the strategy (`defenses`, per topology) — the old
`simulation.DEFENSES_BY_EVENT` / `scenarios.DEFENSES_BY_STRATEGY`
tables are now deprecated views of these declarations (DESIGN.md §9).

Deprecation: the aggregation OPERATORS that used to live here moved to
`core/aggregation.py`; module-level `__getattr__` keeps the old names
importable with a DeprecationWarning.
"""
from __future__ import annotations

import dataclasses
import importlib
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.core import engine as engine_mod
from repro.core import topology
from repro.core.fl_types import DEFENSES
from repro.models import cnn as cnn_mod
from repro.optim import optimizers

Params = Any

# Bump when the Strategy protocol / registry semantics change in a way
# result-document consumers can observe (recorded in every run_scenario
# document since result-schema v2.1).
STRATEGY_REGISTRY_VERSION = 1


# ---------------------------------------------------------------------------
# plan / local-objective descriptors
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RoundPlan:
    """One aggregation event's schedule, as the strategy declared it.

    participants — absolute client ids in TRAINING ORDER (the order the
        rng-parity contract consumes batch permutations in).
    bases        — one round-start model per participant (the attack
        base and norm_clip center; repeat a shared model per slot).
    event        — the aggregation-event index (attack noise keying).
    alphas       — per-participant merge rates (async staleness).
    meta         — strategy-private scratch carried to aggregate_event.
    """
    participants: List[int]
    bases: List[Params]
    event: int
    alphas: Optional[Sequence[float]] = None
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class LocalSpec:
    """The local objective one event trains.

    `loss_fn(params, batch[, extra])` is the single-model loss (loop
    engine and the CFL scan); `stacked_loss_fn` its leading-client-axis
    twin. `extra="bases"` passes each participant's round-start model as
    the third argument (FedProx's proximal reference) — the function
    objects MUST be stable across events (they key the jit cache)."""
    loss_fn: Callable = cnn_mod.cnn_loss
    stacked_loss_fn: Callable = cnn_mod.cnn_loss_stacked
    extra: Optional[str] = None           # None | "bases"


# ---------------------------------------------------------------------------
# the Strategy protocol
# ---------------------------------------------------------------------------

class Strategy:
    """Base class of the plugin protocol (see module docstring).

    Class attributes (the declarative half):
      name        — registry key (`FLConfig.strategy` / ScenarioSpec).
      topologies  — communication graphs the strategy supports.
      defenses    — {topology: valid defense names} at this strategy's
                    aggregation event (DESIGN.md §8/§9).
      centralized — True: the served model lives at a central server and
                    classification scores the full test set (paper
                    §1.2.7); False: on-device 1/N-shard classification.
      track_curves — False disables per-event curve tracking (async:
                    per-batch test-set evals would distort makespan).
      mean_train_acc_over_events — True reports the mean local accuracy
                    over ALL events (async); False the last event's.
      timeline_result — True declares that `extra_result` carries the
                    timeline measurement contract (merges / batches /
                    mean_staleness / makespan / dropped_clients /
                    participants) consumed by `run_scenario`'s async
                    block; per-second throughput then counts batches,
                    not configured rounds.
    """

    name: str = ""
    topologies: Tuple[str, ...] = ("star",)
    defenses: Dict[str, Tuple[str, ...]] = {"star": DEFENSES}
    centralized = False
    track_curves = True
    mean_train_acc_over_events = False
    timeline_result = False
    # Where upload codecs attach (DESIGN.md §12): "driver" = the generic
    # corrupt->transport->aggregate seam over the stacked upload matrix
    # (everything that uses the default run_event / scan_round, async
    # included); "sequential" = per-visit merging (CFL) where only
    # STATELESS codecs apply — error-feedback state needs the stacked
    # seam, and the driver validates that composition at build time.
    codec_seam = "driver"

    def __init__(self, fl):
        self.fl = fl

    # -- validation ---------------------------------------------------------
    def active_topology(self) -> str:
        return self.topologies[0]

    def validate(self):
        """Raise if the config selects a topology this strategy does not
        declare, or a defense invalid at its aggregation event (per-event
        validity lives on the strategy)."""
        fl = self.fl
        topo = self.active_topology()
        if topo not in self.topologies:
            raise ValueError(
                f"topology {topo!r} is invalid for strategy "
                f"{self.name!r} (expected one of {self.topologies})")
        allowed = self.defenses.get(topo, ("none",))
        if fl.defense not in allowed:
            raise ValueError(
                f"defense {fl.defense!r} does not apply to the "
                f"{self.name}/{topo} aggregation event "
                f"(valid: {allowed}; DESIGN.md §8)")

    def event_size(self) -> int:
        """Client count of one aggregation event — the basis for the
        Byzantine allowance `FLConfig.resolved_defense_f`."""
        return self.fl.num_clients

    # -- lifecycle (override these) -----------------------------------------
    def init_state(self, sim) -> Any:
        raise NotImplementedError

    def num_events(self, sim) -> int:
        return self.fl.rounds

    def select_participants(self, sim, state, event: int,
                            rng: np.random.Generator) -> RoundPlan:
        raise NotImplementedError

    def local_spec(self, sim, state, plan) -> LocalSpec:
        return LocalSpec()

    def aggregate_event(self, sim, state, plan, uploads) -> Any:
        raise NotImplementedError

    def round_model(self, state) -> Params:
        raise NotImplementedError

    def served_fn(self, sim, state) -> Callable[[], Params]:
        state_ = state
        return lambda: self.round_model(state_)

    def extra_result(self, sim, state) -> Dict[str, Any]:
        return {}

    # -- default event driver (one generic synchronous round) ---------------
    def run_event(self, sim, state, event: int, rng=None):
        """plan -> local training (engine dispatch in the driver) ->
        attack corruption -> defended aggregation. Returns
        (state, per-client accs, per-client losses). Every lifecycle
        phase is wrapped in a telemetry span (DESIGN.md §13); async-style
        strategies set `timeline_result` and their rounds chain into one
        trace flow."""
        rng = sim.rng if rng is None else rng
        tel = sim.telemetry
        flow = {"flow": "rounds"} if self.timeline_result else {}
        with tel.span("round", cat="run", event=event, **flow):
            with tel.span("select", event=event):
                plan = self.select_participants(sim, state, event, rng)
                spec = self.local_spec(sim, state, plan)
            tel.append_series("participants", len(plan.participants))
            fargs = self._fault_telemetry(sim, plan)
            uploads, losses, accs = sim.local_train(plan, spec, rng)
            uploads = sim.corrupt(uploads, plan)
            uploads = sim.transport(uploads, plan)
            with tel.span("aggregate", event=event, **fargs):
                state = self.aggregate_event(sim, state, plan, uploads)
        return state, accs, losses

    def _fault_telemetry(self, sim, plan) -> Dict[str, Any]:
        """Record the event's fault view in telemetry (DESIGN.md §15):
        churn/quorum counters plus the span annotations returned for the
        aggregate span. No-op ({}) when fault injection is off."""
        fe = sim.fault_view(plan)
        if fe is None:
            return {}
        tel = sim.telemetry
        tel.append_series("alive_clients", fe.n_alive)
        dead = len(plan.participants) - fe.n_alive
        if dead:
            tel.counter("faults.lost_uploads", dead)
        if fe.rejoined:
            tel.counter("faults.rejoins", fe.rejoined)
        if not fe.qok:
            tel.counter("faults.quorum_failures", 1)
        return {"alive": fe.n_alive, "qok": fe.qok}

    def warmup(self, sim):
        """Compile every program the timed driver loop will dispatch
        (outside the build timer — DESIGN.md §3). The default dry-runs
        one FINAL event with a throwaway rng (shapes are identical; the
        sim's own rng is untouched)."""
        sim.warmup_default(self)

    def warmup_aggregate(self, sim):
        """Loop-engine half of the warmup: dry-run one aggregation event
        on dummy uploads so the stacked-operator programs (stack/ravel,
        kernels, corruption, serving) compile outside the build timer —
        the loop engine's training path compiles elsewhere, but since
        PR 4 its aggregation runs the same kernel-backed stacked path as
        the vectorized engine and needs the same warmup."""
        rng = np.random.default_rng(self.fl.seed)
        state = self.init_state(sim)
        plan = self.select_participants(sim, state,
                                        self.num_events(sim) - 1, rng)
        # the round-trip through unstack/stack also compiles the eager
        # per-leaf jnp.stack the loop engine's upload stacking dispatches
        uploads = engine_mod.stack_forest(engine_mod.unstack_forest(
            engine_mod.replicate_tree(sim.init_params,
                                      len(plan.participants))))
        state = self.aggregate_event(
            sim, state, plan,
            sim.transport(sim.corrupt(uploads, plan), plan))
        self.served_fn(sim, state)()

    # -- fused executor (DESIGN.md §10) -------------------------------------
    # `engine="fused"` compiles the ENTIRE run into one `jax.lax.scan`
    # whose carry is the strategy state, device-resident end to end. The
    # driver (`FederatedSimulation.run_fused`) hoists everything the
    # per-round path does on the host — participant schedules, the
    # (rounds, k, epochs*nb, B) batch-index tensor (consuming the run
    # rng in the per-round order, so §4 parity is bitwise), attack
    # flags/keys — into per-round scan inputs (`xs`), and `scan_round`
    # executes one round in-trace. The default wraps the same lifecycle
    # pieces the per-round driver dispatches (stacked train -> local
    # accs -> corruption -> aggregation), with the two strategy-shaped
    # holes expressed as traceable hooks: `scan_bases` (the round-start
    # base stack from the carried state) and `scan_aggregate` (the
    # aggregation event; the per-round `aggregate_event` is NOT reused
    # verbatim because it indexes host arrays with concrete participant
    # lists — each built-in's scan_aggregate funnels through the SAME
    # `core.aggregation` operators instead). `scan_carry`/`scan_uncarry`
    # bound the carry to array-only pytrees (server optimizers re-attach
    # their Optimizer closures on the way out).
    #
    # CONTRACT for declaring `supports_fused = True`: besides the hooks
    # below being traceable, `select_participants` must derive its
    # schedule from (event, rng) alone — the fused precompute calls it
    # once per round with the INITIAL state (the evolving state lives on
    # device inside the scan and is not available to host scheduling).
    # A strategy whose participant choice reads evolving state (e.g.
    # loss-ranked sampling) cannot fuse; leave the flag False and it
    # runs on the per-round drivers.

    supports_fused = False      # opt-in: see the contract above

    # -- mesh-sharded fused executor (DESIGN.md §11) ------------------------
    # `FLConfig.mesh_devices > 1` runs the fused scan under shard_map
    # with the stacked client axis partitioned over a "data" mesh. A
    # strategy opts in with `supports_mesh = True` when its scan hooks
    # are collective-correct: `scan_bases`/local training/corruption are
    # already per-client (embarrassingly parallel per shard), so the one
    # extra obligation is `scan_aggregate` lowering its event to mesh
    # collectives when `fx.mesh_axis` is set (the mesh-sharded stacked
    # operators in core/aggregation.py). `scan_carry_sharding` declares,
    # per top-level carry key, whether that subtree carries the client
    # axis ("client": leading dim sharded over the mesh) or is
    # federation-global ("replicated"). The driver validates the mesh
    # preconditions (full participation, shard divisibility,
    # defense="none") before compiling.

    supports_mesh = False

    def scan_carry_sharding(self, sim) -> Dict[str, str]:
        """Top-level scan-carry key -> "client" | "replicated"."""
        raise NotImplementedError

    def validate_mesh(self, sim, ndev: int) -> None:
        """Strategy-specific mesh preconditions, raised before compile
        (HFL: group/shard alignment). The driver has already checked the
        generic ones (full participation, C % ndev, defense="none")."""

    def scan_carry(self, sim, state):
        """Strategy state -> the array-only pytree carried by the scan."""
        return state

    def scan_uncarry(self, sim, carry):
        """Final scan carry -> full strategy state (for `round_model` /
        `served_fn` / `extra_result`)."""
        return carry

    def scan_extra_xs(self, sim, n_events: int) -> Dict[str, Any]:
        """Additional per-round scan inputs, each with leading dim
        n_events (e.g. HFL's dissemination flag)."""
        return {}

    def fault_scan_kwargs(self) -> Dict[str, Any]:
        """`FaultSchedule.scan_xs` kwargs for the fused precompute
        (DESIGN.md §15): which per-round fault arrays this strategy's
        `scan_aggregate` consumes beyond the universal alive-mask and
        quorum flag (HFL adds the per-group quorum flags, gossip AFL the
        per-round mixing matrices / gather indices)."""
        return {}

    def scan_bases(self, fx, carry, xs) -> Params:
        """The (k, ...) stacked round-start models for this round's
        participants, from the carried state (traceable)."""
        raise NotImplementedError

    def scan_aggregate(self, fx, carry, xs, uploads):
        """Fold the (possibly corrupted) uploads into the carry —
        the traceable twin of `aggregate_event`, built from the same
        `core.aggregation` operators."""
        raise NotImplementedError

    def scan_round(self, fx, carry, xs):
        """One round inside the fused scan: gather this round's batches
        from the device-resident federation dataset, train every
        participant, evaluate the paper's local-shard training accuracy,
        corrupt attacker uploads, aggregate. Returns
        (carry, (train_acc, train_loss, test_acc)) — test_acc is NaN
        when curve tracking is off.

        Under the mesh path every per-client input (`bases`, batches,
        flags/keys, eval shards) is the shard's LOCAL sub-stack —
        `fx.local_pids` maps the absolute participant ids to local rows,
        training/corruption run unchanged per shard, and the per-round
        scalar metrics are pmean'd so every shard reports the federation
        mean (equal shard sizes make the mean of shard means exact).

        Each phase runs under a `jax.named_scope` named after the
        per-round driver's phase span (`local_train`, `local_eval`,
        `corrupt`, `encode_decode`, `aggregate`, `eval`), so its ops
        carry the phase in their `op_name` metadata and a profiler trace
        can attribute device time to it. Scopes are metadata only.
        Training and local evaluation trace inside `fx.lowering`, so a
        mesh shard lowers the stacked CNN as the single-device run does;
        `fx.trained_stacked` tells `run_fused` that training ran one (it
        sets counter `local_train.grouped_conv` from the lowering it
        chose). `local_spec` gets the fused context in place of the
        simulation: the program outlives the simulation that built it."""
        fl = fx.fl
        bases = self.scan_bases(fx, carry, xs)
        pids = fx.local_pids(xs["pids"])
        spec = self.local_spec(fx, None, None)
        extra = bases if spec.extra == "bases" else None
        n = jax.tree.leaves(bases)[0].shape[0]
        with jax.named_scope("local_train"), \
                fx.lowering(n, fl.fused_chunk) as lowered:
            batch = engine_mod.gather_batches(fx.data_x, fx.data_y,
                                              pids, xs["idx"])
            params, losses, _ = engine_mod._train_clients_chunked_impl(
                bases, batch, stacked_loss_fn=spec.stacked_loss_fn,
                lr=fl.lr, momentum=fl.momentum, extra=extra,
                chunk=fl.fused_chunk)
        fx.trained_stacked = fx.trained_stacked or bool(lowered)
        with jax.named_scope("local_eval"), fx.lowering(n):
            accs = fx.local_accs(params, pids)
        with jax.named_scope("corrupt"):
            uploads = fx.corrupt(params, bases, xs)
        with jax.named_scope("encode_decode"):
            uploads = fx.transport(uploads, bases, xs)
        with jax.named_scope("aggregate"):
            carry = self.scan_aggregate(fx, carry, xs, uploads)
        with jax.named_scope("eval"):
            test_acc = fx.test_acc(self.round_model(carry))
        return carry, (fx.pmean(jnp.mean(accs)),
                       fx.pmean(jnp.mean(losses[:, -fx.nb:])), test_acc)

    def scan_telemetry(self, fx, carry, new_carry, xs) -> Dict[str, Any]:
        """Strategy-specific in-scan per-round counters (traceable;
        DESIGN.md §13): {name: scalar} computed from the pre/post-round
        scan carries, stacked by the fused driver next to the metric
        outputs and transferred once at run end. The default reports the
        L2 norm of the round's global-model step — a convergence-health
        series every fused strategy gets for free. Must not change any
        carried value: counters are read-only consumers, which is what
        keeps fused results bitwise identical telemetry on/off."""
        prev = self.round_model(carry)
        new = self.round_model(new_carry)
        d2 = sum(jnp.sum(jnp.square(b.astype(jnp.float32)
                                    - a.astype(jnp.float32)))
                 for a, b in zip(jax.tree.leaves(prev),
                                 jax.tree.leaves(new)))
        return {"model_delta_l2": jnp.sqrt(d2)}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

STRATEGY_REGISTRY: Dict[str, Type[Strategy]] = {}

# built-in strategies living in other modules, loaded on first lookup
# (async_agg imports this module, so it cannot be imported at top level)
_BUILTIN_MODULES = ("repro.core.async_agg",)
_builtins_loaded = False


def register_strategy(cls: Type[Strategy]) -> Type[Strategy]:
    """Class decorator: register a Strategy subclass under `cls.name`."""
    if not cls.name:
        raise ValueError(f"{cls.__name__} needs a non-empty `name`")
    if cls.name in STRATEGY_REGISTRY:
        raise ValueError(f"duplicate strategy name {cls.name!r}")
    STRATEGY_REGISTRY[cls.name] = cls
    return cls


def _load_builtins():
    global _builtins_loaded
    if not _builtins_loaded:
        for mod in _BUILTIN_MODULES:
            importlib.import_module(mod)
        _builtins_loaded = True


def get_strategy(name: str) -> Type[Strategy]:
    _load_builtins()
    if name not in STRATEGY_REGISTRY:
        known = ", ".join(sorted(STRATEGY_REGISTRY))
        raise KeyError(f"unknown strategy {name!r} (known: {known})")
    return STRATEGY_REGISTRY[name]


def strategy_names() -> List[str]:
    _load_builtins()
    return sorted(STRATEGY_REGISTRY)


# ---------------------------------------------------------------------------
# built-in strategies: the paper's three architectures
# ---------------------------------------------------------------------------

@register_strategy
class HFLStrategy(Strategy):
    """Centralized two-tier hierarchy (paper §2.1): every round all
    clients refine their group model; group servers aggregate (tier 1 —
    the defense boundary); the global server aggregates group models and
    disseminates every `hfl_global_every` rounds."""

    name = "hfl"
    topologies = ("hierarchical",)
    defenses = {"hierarchical": DEFENSES}
    centralized = True

    def event_size(self) -> int:
        return self.fl.clients_per_group

    def init_state(self, sim):
        return {"groups": engine_mod.replicate_tree(sim.init_params,
                                                    self.fl.num_groups),
                "global": sim.init_params, "last": None}

    def select_participants(self, sim, state, event, rng):
        fl = self.fl
        per = fl.clients_per_group
        group_models = engine_mod.unstack_forest(state["groups"])
        plan = RoundPlan(list(range(fl.num_clients)),
                         [group_models[c // per]
                          for c in range(fl.num_clients)], event)
        plan.meta["start_groups"] = state["groups"]   # (G, ...) centers
        # stacked bases (vectorized engine / corruption) without a
        # per-client jnp.stack: one repeat per leaf — built lazily so
        # the loop engine without an attack never pays for it
        groups = state["groups"]
        plan.meta["bases_stacked_fn"] = (
            lambda: engine_mod.repeat_groups(groups, per))
        return plan

    def aggregate_event(self, sim, state, plan, uploads):
        fl = self.fl
        fe = sim.fault_view(plan)
        if fe is not None and not fe.qok:
            # below-quorum round (DESIGN.md §15): the declared degraded
            # action holds the whole hierarchy — groups, global AND the
            # serving state — at its round-start values, bitwise what the
            # fused scan's tree_where(qok, ...) keeps
            return {"groups": state["groups"], "global": state["global"],
                    "last": self._held_last(sim, state)}
        w = np.asarray(sim.weights, np.float32)
        defkw = sim.defense_kwargs(self.event_size())
        alive = None if fe is None else fe.alive
        groups, gw = agg.hfl_tier1_stacked(
            uploads, fl.num_groups, w, centers=plan.meta["start_groups"],
            alive=alive, **defkw)
        if fe is not None:
            # per-group quorum: a below-quorum group server holds its
            # round-start model (it still enters tier 2 at full weight —
            # group totals are population sizes, not survivor counts)
            gqok = sim.faults.group_qok(plan.event, plan.participants,
                                        fl.num_groups)
            groups = agg.tree_where_rows(gqok, groups,
                                         plan.meta["start_groups"])
        global_model = state["global"]
        if ((plan.event + 1) % fl.hfl_global_every == 0
                or plan.event == fl.rounds - 1):
            global_model = agg.fedavg_stacked(groups, gw)
            groups = engine_mod.replicate_tree(global_model, fl.num_groups)
        last = ((uploads, plan.meta["start_groups"]) if fe is None
                else (uploads, plan.meta["start_groups"], fe.alive))
        return {"groups": groups, "global": global_model, "last": last}

    def _held_last(self, sim, state):
        """The serving tuple a quorum-failed round holds: the previous
        event's, or — when round 0 itself fails quorum — the same init
        values the fused carry starts from (uniform init uploads re-
        aggregate to the init model, so serving stays well-defined)."""
        if state["last"] is not None:
            return state["last"]
        fl = self.fl
        return (engine_mod.replicate_tree(sim.init_params, fl.num_clients),
                engine_mod.replicate_tree(sim.init_params, fl.num_groups),
                np.ones((fl.num_clients,), np.float32))

    def round_model(self, state):
        return state["global"]

    def served_fn(self, sim, state):
        # the global server re-aggregates at classification time
        fl = self.fl
        w = np.asarray(sim.weights, np.float32)
        defkw = sim.defense_kwargs(self.event_size())
        last = state["last"]
        if len(last) == 2:
            uploads, starts = last
            return lambda: agg.hfl_aggregate_stacked(
                uploads, fl.num_groups, w, centers=starts, **defkw)
        # fault injection active: re-run the degraded tiers exactly as
        # the round did — alive-masked tier 1, per-group quorum holds,
        # full-weight tier 2 (DESIGN.md §15)
        from repro.core import faults as faults_mod
        uploads, starts, alive = last
        per = fl.num_clients // fl.num_groups
        thr = faults_mod.quorum_threshold(per, fl.quorum_frac)
        gqok = (np.asarray(alive, np.float32).reshape(fl.num_groups, per)
                .sum(axis=1) >= thr)

        def serve():
            groups, gw = agg.hfl_tier1_stacked(
                uploads, fl.num_groups, w, centers=starts, alive=alive,
                **defkw)
            groups = agg.tree_where_rows(jnp.asarray(gqok), groups, starts)
            return agg.fedavg_stacked(groups, gw)
        return serve

    # -- fused executor -----------------------------------------------------
    supports_fused = True
    # mesh path: groups align to shards (num_groups % mesh_devices == 0,
    # validated by the driver), so tier 1 is the LOCAL reshape — no
    # cross-shard collective in the tier-1 event — and only tier 2 psums
    supports_mesh = True

    def scan_carry_sharding(self, sim):
        sharding = {"groups": "client", "global": "replicated",
                    "up": "client", "start": "client"}
        if sim.faults is not None:
            sharding["alive"] = "client"
        return sharding

    def validate_mesh(self, sim, ndev):
        fl = self.fl
        if fl.num_groups % ndev:
            raise ValueError(
                f"HFL mesh path needs groups aligned to shards: "
                f"num_groups={fl.num_groups} must be a multiple of "
                f"mesh_devices={ndev} so tier 1 never crosses a shard "
                f"boundary (DESIGN.md §11)")

    def scan_carry(self, sim, state):
        carry = {"groups": state["groups"], "global": state["global"],
                 "up": engine_mod.replicate_tree(sim.init_params,
                                                 self.fl.num_clients),
                 "start": state["groups"]}
        if sim.faults is not None:
            # last event's alive-mask rides the carry so the serving
            # tuple re-aggregates with the same degraded masking
            carry["alive"] = jnp.ones((self.fl.num_clients,), jnp.float32)
        return carry

    def scan_uncarry(self, sim, carry):
        last = (carry["up"], carry["start"])
        if "alive" in carry:
            last = last + (np.asarray(carry["alive"]),)
        return {"groups": carry["groups"], "global": carry["global"],
                "last": last}

    def scan_extra_xs(self, sim, n_events):
        fl = self.fl
        # the per-round driver's dissemination schedule, as a hoisted
        # boolean input (a Python `if` there, a `tree_where` in-scan)
        return {"hfl_global": np.array(
            [((ev + 1) % fl.hfl_global_every == 0 or ev == fl.rounds - 1)
             for ev in range(n_events)], bool)}

    def fault_scan_kwargs(self):
        return {"num_groups": self.fl.num_groups}

    def scan_bases(self, fx, carry, xs):
        # participants are always 0..C-1 in id order (select_participants)
        return engine_mod.repeat_groups(carry["groups"],
                                        self.fl.clients_per_group)

    def scan_aggregate(self, fx, carry, xs, uploads):
        fl = self.fl
        start_groups = carry["groups"]
        alive = xs.get("fault_alive")
        if fx.mesh_axis is not None:
            # tier 1 nests in the shard (driver-validated alignment):
            # pure local math, no collective; tier 2 is ONE weighted
            # psum over the local group models (defense="none" on the
            # mesh path — also driver-validated)
            per = fl.clients_per_group
            c_loc = fx.weights.shape[0]
            g_loc = c_loc // per
            groups, gw = agg.hfl_tier1_local(uploads, fx.weights, g_loc,
                                             alive=alive)
            if alive is not None:
                # the shard's slice of the per-group quorum flags
                i = jax.lax.axis_index(fx.mesh_axis)
                gqok = jax.lax.dynamic_slice_in_dim(
                    jnp.asarray(xs["fault_gqok"]), i * g_loc, g_loc)
                groups = agg.tree_where_rows(gqok, groups, start_groups)
            new_global = agg.mesh_fedavg_stacked(groups, gw,
                                                 axis=fx.mesh_axis)
        else:
            defkw = fx.defense_kwargs(self.event_size())
            groups, gw = agg.hfl_tier1_stacked(
                uploads, fl.num_groups, fx.weights, centers=start_groups,
                alive=alive, **defkw)
            if alive is not None:
                groups = agg.tree_where_rows(xs["fault_gqok"], groups,
                                             start_groups)
            # global aggregation + dissemination on the schedule flag;
            # the tier-2 reduction is over G tiny group models, so
            # computing it every round costs less than a scan-level
            # cond would
            new_global = agg.fedavg_stacked(groups, gw)
        disseminate = xs["hfl_global"]
        global_model = agg.tree_where(disseminate, new_global,
                                      carry["global"])
        n_groups_here = jax.tree.leaves(groups)[0].shape[0]
        groups = agg.tree_where(
            disseminate,
            engine_mod.replicate_tree(new_global, n_groups_here), groups)
        out = {"groups": groups, "global": global_model,
               "up": uploads, "start": start_groups}
        if alive is not None:
            # below-quorum round: hold every carried value — bitwise
            # what the per-round driver's host `if` keeps unchanged
            qok = xs["fault_qok"]
            out = {"groups": agg.tree_where(qok, groups, carry["groups"]),
                   "global": agg.tree_where(qok, global_model,
                                            carry["global"]),
                   "up": agg.tree_where(qok, uploads, carry["up"]),
                   "start": agg.tree_where(qok, start_groups,
                                           carry["start"]),
                   "alive": jnp.where(qok,
                                      jnp.asarray(alive, jnp.float32),
                                      carry["alive"])}
        return out

    def scan_telemetry(self, fx, carry, new_carry, xs):
        # the hierarchy's dissemination lag, as a per-round series: L2
        # spread of the group models around their mean (collapses to 0
        # on global-dissemination rounds)
        out = super().scan_telemetry(fx, carry, new_carry, xs)
        groups = new_carry["groups"]
        d2 = sum(jnp.sum(jnp.square(
                     g.astype(jnp.float32)
                     - jnp.mean(g.astype(jnp.float32), axis=0,
                                keepdims=True)))
                 for g in jax.tree.leaves(groups))
        out["group_spread_l2"] = jnp.sqrt(d2)
        return out


@register_strategy
class AFLStrategy(Strategy):
    """Decentralized aggregated FL (paper §2.2): sample a participant
    subset, train locally, aggregate directly — masked FedAvg (star) or
    ring-neighbor gossip mixing (`afl_mode="gossip"`)."""

    name = "afl"
    topologies = ("star", "ring")
    defenses = {"star": DEFENSES,
                "ring": ("none", "median", "trimmed_mean")}

    def active_topology(self) -> str:
        return "ring" if self.fl.afl_mode == "gossip" else "star"

    def event_size(self) -> int:
        fl = self.fl
        return max(1, int(round(fl.participation * fl.num_clients)))

    def init_state(self, sim):
        return {"global": sim.init_params, "last": None}

    def select_participants(self, sim, state, event, rng):
        fl = self.fl
        parts = topology.sample_participants(rng, fl.num_clients,
                                             fl.participation)
        parts = [int(c) for c in parts]
        plan = RoundPlan(parts, [state["global"]] * len(parts), event)
        start, k = state["global"], len(parts)
        plan.meta["bases_stacked_fn"] = (
            lambda: engine_mod.replicate_tree(start, k))
        return plan

    def aggregate_event(self, sim, state, plan, uploads):
        fl = self.fl
        k = len(plan.participants)
        fe = sim.fault_view(plan)
        if fe is not None and not fe.qok:
            # below-quorum round: hold the global model and serving
            # tuple (DESIGN.md §15)
            return {"global": state["global"],
                    "last": self._held_last(sim, state)}
        defkw = sim.defense_kwargs(k)
        pw = np.asarray(sim.weights, np.float64)[plan.participants]
        start = plan.bases[0]
        alive = None if fe is None else fe.alive
        if fl.afl_mode == "gossip":
            if fe is None:
                # defended mixing bounds Byzantine neighbors; the final
                # consensus average over mixed models stays plain
                nbrs = topology.ring_neighbors(k, fl.gossip_neighbors)
                uploads = agg.gossip_stacked(uploads, nbrs,
                                             defense=fl.defense,
                                             f=defkw["f"])
            elif fl.defense == "none":
                # dynamic membership: the schedule's per-round masked
                # (and, under MTD, re-randomized) mixing matrix
                uploads = agg.masked_gossip_stacked(
                    uploads, mix=sim.faults.gossip_mix(
                        plan.event, plan.participants))
            else:
                uploads = agg.masked_gossip_stacked(
                    uploads, gather_idx=sim.faults.gossip_gather(
                        plan.event, plan.participants,
                        fl.gossip_neighbors + 1),
                    defense=fl.defense, f=defkw["f"])
            global_model = agg.afl_aggregate_stacked(uploads, pw,
                                                     alive=alive)
        else:
            global_model = agg.defended_aggregate_stacked(
                uploads, pw, center=start, alive=alive, **defkw)
        last = ((uploads, pw, start, k) if fe is None
                else (uploads, pw, start, k, fe.alive))
        return {"global": global_model, "last": last}

    def _held_last(self, sim, state):
        """Serving tuple held by a quorum-failed round (round-0 failure
        falls back to the fused carry's init values)."""
        if state["last"] is not None:
            return state["last"]
        k = self.event_size()
        return (engine_mod.replicate_tree(sim.init_params, k),
                np.ones((k,), np.float32), sim.init_params, k,
                np.ones((k,), np.float32))

    def round_model(self, state):
        return state["global"]

    def served_fn(self, sim, state):
        fl = self.fl
        uploads, pw, start, k, *rest = state["last"]
        alive = rest[0] if rest else None
        defkw = sim.defense_kwargs(k)
        if fl.afl_mode == "gossip":
            return lambda: agg.afl_aggregate_stacked(uploads, pw,
                                                     alive=alive)
        return lambda: agg.defended_aggregate_stacked(
            uploads, pw, center=start, alive=alive, **defkw)

    # -- fused executor -----------------------------------------------------
    supports_fused = True
    # mesh path: star is one weighted psum; gossip is the masked
    # all-to-all mix (neighbor models DO cross shard boundaries)
    supports_mesh = True

    def scan_carry_sharding(self, sim):
        sharding = {"global": "replicated", "up": "client",
                    "pw": "client", "start": "replicated"}
        if sim.faults is not None:
            sharding["alive"] = "client"
        return sharding

    def scan_carry(self, sim, state):
        k = self.event_size()
        carry = {"global": state["global"],
                 "up": engine_mod.replicate_tree(sim.init_params, k),
                 "pw": jnp.ones((k,), jnp.float32),
                 "start": state["global"]}
        if sim.faults is not None:
            carry["alive"] = jnp.ones((k,), jnp.float32)
        return carry

    def scan_uncarry(self, sim, carry):
        last = (carry["up"], carry["pw"], carry["start"],
                self.event_size())
        if "alive" in carry:
            last = last + (np.asarray(carry["alive"]),)
        return {"global": carry["global"], "last": last}

    def fault_scan_kwargs(self):
        fl = self.fl
        if fl.afl_mode != "gossip":
            return {}
        if fl.defense == "none":
            return {"gossip": True}
        return {"gossip": True, "gossip_defended": True,
                "gather_k": fl.gossip_neighbors + 1}

    def scan_bases(self, fx, carry, xs):
        return engine_mod.replicate_tree(carry["global"],
                                         xs["pids"].shape[0])

    def scan_aggregate(self, fx, carry, xs, uploads):
        fl = self.fl
        k = xs["pids"].shape[0]
        pw = fx.weights[fx.local_pids(xs["pids"])]
        start = carry["global"]
        alive = xs.get("fault_alive")
        if fx.mesh_axis is not None:
            # defense="none" on the mesh path (driver-validated); the
            # ring spans the GLOBAL client ids, so the mix matrix is
            # built at federation size and applied as one collective
            # (under faults the precomputed per-round masked mix —
            # positions == ids under the mesh's full participation)
            if fl.afl_mode == "gossip":
                mix = (xs["fault_mix"] if alive is not None
                       else agg.gossip_mix_matrix(topology.ring_neighbors(
                           fl.num_clients, fl.gossip_neighbors)))
                uploads = agg.mesh_gossip_stacked(uploads, mix,
                                                  axis=fx.mesh_axis)
            pw_eff = pw if alive is None else pw * alive
            global_model = agg.mesh_fedavg_stacked(uploads, pw_eff,
                                                   axis=fx.mesh_axis)
            out = {"global": global_model, "up": uploads, "pw": pw,
                   "start": start}
            return self._fault_hold(carry, xs, out, alive)
        defkw = fx.defense_kwargs(k)
        if fl.afl_mode == "gossip":
            if alive is None:
                nbrs = topology.ring_neighbors(k, fl.gossip_neighbors)
                uploads = agg.gossip_stacked(uploads, nbrs,
                                             defense=fl.defense,
                                             f=defkw["f"])
            elif fl.defense == "none":
                uploads = agg.masked_gossip_stacked(uploads,
                                                    mix=xs["fault_mix"])
            else:
                uploads = agg.masked_gossip_stacked(
                    uploads, gather_idx=xs["fault_gidx"],
                    defense=fl.defense, f=defkw["f"])
            global_model = agg.afl_aggregate_stacked(uploads, pw,
                                                     alive=alive)
        else:
            global_model = agg.defended_aggregate_stacked(
                uploads, pw, center=start, alive=alive, **defkw)
        out = {"global": global_model, "up": uploads, "pw": pw,
               "start": start}
        return self._fault_hold(carry, xs, out, alive)

    def _fault_hold(self, carry, xs, out, alive):
        """Quorum gate for the scan step: a below-quorum round keeps the
        carried values (bitwise the per-round driver's host `if`)."""
        if alive is None:
            return out
        qok = xs["fault_qok"]
        held = {key: agg.tree_where(qok, out[key], carry[key])
                for key in out}
        held["alive"] = jnp.where(qok, jnp.asarray(alive, jnp.float32),
                                  carry["alive"])
        return held


@register_strategy
class CFLStrategy(Strategy):
    """Decentralized continual FL (paper §2.3): the model passes client
    to client in an rng-permuted visit order; each local update merges
    into the evolving global parameters. The sequential data dependence
    means training and aggregation fuse — the event runs through the
    driver's `sequential_round` (loop: per-visit host merges;
    vectorized: one `lax.scan` over visits with the kernel-backed merge
    and in-scan corruption)."""

    name = "cfl"
    topologies = ("sequential",)
    defenses = {"sequential": ("none", "norm_clip")}
    codec_seam = "sequential"   # per-visit wire: stateless codecs only

    def init_state(self, sim):
        return {"model": sim.init_params}

    def select_participants(self, sim, state, event, rng):
        order = [int(c) for c in rng.permutation(self.fl.num_clients)]
        return RoundPlan(order, [state["model"]] * len(order), event)

    def run_event(self, sim, state, event, rng=None):
        rng = sim.rng if rng is None else rng
        tel = sim.telemetry
        with tel.span("round", cat="run", event=event):
            with tel.span("select", event=event):
                plan = self.select_participants(sim, state, event, rng)
            tel.append_series("participants", len(plan.participants))
            # logs the fault view for this event (serve gating + result
            # block); sequential_round re-derives the same view for the
            # per-visit merge masking
            self._fault_telemetry(sim, plan)
            # training + merge fuse in sequential_round, which records
            # its own phase span
            model, losses, accs = sim.sequential_round(
                state["model"], plan.participants, plan.event,
                self.fl.merge_alpha, self.local_spec(sim, state, plan),
                rng)
        return {"model": model}, accs, losses

    def aggregate_event(self, sim, state, plan, uploads):
        raise NotImplementedError(       # pragma: no cover
            "CFL fuses training and aggregation in sequential_round")

    def warmup_aggregate(self, sim):
        """Nothing to warm: the loop-engine CFL pass merges through
        eager host ops (compiled pieces are covered by warmup_loop)."""

    def round_model(self, state):
        return state["model"]

    # -- fused executor -----------------------------------------------------
    # CFL's training and aggregation already fuse in `cfl_round_scan`
    # (one lax.scan over the visit order, corruption and kernel-backed
    # merge in-scan), so the fused round is that scan nested inside the
    # outer round scan — `scan_round` is overridden whole, like
    # `run_event` is for the per-round driver.
    supports_fused = True

    def scan_round(self, fx, carry, xs):
        # named scopes as in `Strategy.scan_round`; `cfl_round_scan`
        # scopes each visit's phases itself
        fl = self.fl
        with jax.named_scope("local_train"):
            batch = engine_mod.gather_batches(fx.data_x, fx.data_y,
                                              xs["pids"], xs["idx"])
        model, losses, accs = engine_mod.cfl_round_scan(
            carry["model"], batch, fx.eval_x[xs["pids"]],
            fx.eval_y[xs["pids"]], fl.merge_alpha,
            loss_fn=fx.loss_fn, apply_fn=fx.apply_fn,
            lr=fl.lr, momentum=fl.momentum, attack=fl.attack,
            attack_scale=fl.attack_scale, attack_flags=xs["flags"],
            attack_keys=xs["keys"], defense=fl.defense,
            clip_tau=fl.clip_tau, codec=fx.codec,
            codec_keys=xs.get("ckeys"),
            fault_alive=xs.get("fault_alive"),
            fault_qok=xs.get("fault_qok"))
        with jax.named_scope("eval"):
            test_acc = fx.test_acc(model)
        return {"model": model}, (jnp.mean(accs),
                                  jnp.mean(losses[:, -fx.nb:]), test_acc)


# ---------------------------------------------------------------------------
# new strategies, shipped through the plugin API alone (PR 4 proof)
# ---------------------------------------------------------------------------

@register_strategy
class FedProxStrategy(AFLStrategy):
    """FedProx (Li et al. 2020): AFL's schedule and aggregation with a
    proximal local objective — each client minimizes

        F_c(w) + (mu/2) ||w - w_base||^2

    where w_base is the model it pulled at round start. The proximal
    pull bounds client drift under heterogeneity. Implemented PURELY
    through the plugin surface: `local_spec` returns a prox-augmented
    loss with `extra="bases"`; schedule, engines, attacks and defenses
    are inherited."""

    name = "fedprox"
    topologies = ("star",)
    defenses = {"star": DEFENSES}

    def __init__(self, fl):
        super().__init__(fl)
        mu = float(fl.prox_mu)

        def _sq(p, r):
            d = p.astype(jnp.float32) - r.astype(jnp.float32)
            return jnp.square(d)

        def prox_loss(params, batch, ref):
            loss, acc = cnn_mod.cnn_loss(params, batch)
            sq = sum(jnp.sum(_sq(p, r)) for p, r in
                     zip(jax.tree.leaves(params), jax.tree.leaves(ref)))
            return loss + 0.5 * mu * sq, acc

        def prox_loss_stacked(params, batch, ref):
            loss_c, acc_c = cnn_mod.cnn_loss_stacked(params, batch)
            sq = sum(jnp.sum(_sq(p, r).reshape(p.shape[0], -1), axis=1)
                     for p, r in zip(jax.tree.leaves(params),
                                     jax.tree.leaves(ref)))
            return loss_c + 0.5 * mu * sq, acc_c

        # one stable spec per run: the function objects key the jit
        # cache, so they must not be rebuilt per event
        self._spec = LocalSpec(prox_loss, prox_loss_stacked, extra="bases")

    def local_spec(self, sim, state, plan):
        return self._spec


class ServerOptStrategy(AFLStrategy):
    """Server-optimizer family (Reddi et al. 2021, "Adaptive Federated
    Optimization"): the round's (defended, kernel-backed) aggregate is
    treated as a pseudo-gradient step

        g_t = w_t - aggregate_t

    and a SERVER optimizer applies it: FedAvgM (momentum SGD) or FedAdam
    (Adam). With server_lr=1 and no momentum this degenerates exactly to
    FedAvg (pinned in tests). Only `init_state`/`aggregate_event` differ
    from AFL — the plugin API's second extensibility proof."""

    topologies = ("star",)
    defenses = {"star": DEFENSES}
    centralized = True

    def make_opt(self):
        raise NotImplementedError

    def init_state(self, sim):
        opt = self.make_opt()
        return {"global": sim.init_params, "opt": opt,
                "opt_state": opt.init(sim.init_params), "last": None}

    def aggregate_event(self, sim, state, plan, uploads):
        fl = self.fl
        k = len(plan.participants)
        fe = sim.fault_view(plan)
        if fe is not None and not fe.qok:
            # below-quorum round: no pseudo-gradient step — the server
            # optimizer state holds along with the model (DESIGN.md §15)
            return {"global": state["global"], "opt": state["opt"],
                    "opt_state": state["opt_state"],
                    "last": self._held_last(sim, state)}
        defkw = sim.defense_kwargs(k)
        pw = np.asarray(sim.weights, np.float64)[plan.participants]
        g = state["global"]
        alive = None if fe is None else fe.alive
        aggregate = agg.defended_aggregate_stacked(uploads, pw, center=g,
                                                   alive=alive, **defkw)
        pseudo_grad = jax.tree.map(
            lambda a, b: (a - b).astype(jnp.float32), g, aggregate)
        updates, opt_state = state["opt"].update(pseudo_grad,
                                                 state["opt_state"], g)
        last = ((uploads, pw, g, k) if fe is None
                else (uploads, pw, g, k, fe.alive))
        return {"global": optimizers.apply_updates(g, updates),
                "opt": state["opt"], "opt_state": opt_state,
                "last": last}

    def served_fn(self, sim, state):
        # the server optimizer's state lives server-side: serve its model
        model = state["global"]
        return lambda: model

    # -- fused executor -----------------------------------------------------
    # The server optimizer's state is a pytree of arrays — it rides the
    # scan carry like the model does; only the Optimizer closures are
    # re-attached on the way out.

    def scan_carry_sharding(self, sim):
        # the server optimizer steps the REPLICATED global model with a
        # replicated pseudo-gradient — its state is identical per shard
        sharding = super().scan_carry_sharding(sim)
        sharding["opt_state"] = "replicated"
        return sharding

    def scan_carry(self, sim, state):
        carry = super().scan_carry(sim, state)
        carry["opt_state"] = state["opt_state"]
        return carry

    def scan_uncarry(self, sim, carry):
        state = super().scan_uncarry(sim, carry)
        state["opt"] = self.make_opt()
        state["opt_state"] = carry["opt_state"]
        return state

    def scan_aggregate(self, fx, carry, xs, uploads):
        fl = self.fl
        k = xs["pids"].shape[0]
        pw = fx.weights[fx.local_pids(xs["pids"])]
        g = carry["global"]
        alive = xs.get("fault_alive")
        if fx.mesh_axis is not None:
            pw_eff = pw if alive is None else pw * alive
            aggregate = agg.mesh_fedavg_stacked(uploads, pw_eff,
                                                axis=fx.mesh_axis)
        else:
            defkw = fx.defense_kwargs(k)
            aggregate = agg.defended_aggregate_stacked(
                uploads, pw, center=g, alive=alive, **defkw)
        pseudo_grad = jax.tree.map(
            lambda a, b: (a - b).astype(jnp.float32), g, aggregate)
        opt = self.make_opt()
        updates, opt_state = opt.update(pseudo_grad, carry["opt_state"], g)
        out = {"global": optimizers.apply_updates(g, updates),
               "opt_state": opt_state, "up": uploads, "pw": pw,
               "start": g}
        return self._fault_hold(carry, xs, out, alive)


@register_strategy
class FedAvgMStrategy(ServerOptStrategy):
    """FedAvgM: server momentum-SGD over the round pseudo-gradient."""
    name = "fedavgm"

    def make_opt(self):
        return optimizers.sgd(self.fl.server_lr,
                              momentum=self.fl.server_momentum)


@register_strategy
class FedAdamStrategy(ServerOptStrategy):
    """FedAdam: server Adam over the round pseudo-gradient."""
    name = "fedadam"

    def make_opt(self):
        return optimizers.adam(self.fl.server_lr)


# ---------------------------------------------------------------------------
# deprecation shims: the aggregation operators formerly defined here
# ---------------------------------------------------------------------------

def __getattr__(name):  # noqa: N807
    if hasattr(agg, name) and not name.startswith("_"):
        warnings.warn(
            f"repro.core.strategies.{name} moved to "
            f"repro.core.aggregation.{name} (the strategies module now "
            f"hosts the Strategy plugin API; import via repro.api)",
            DeprecationWarning, stacklevel=2)
        return getattr(agg, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
