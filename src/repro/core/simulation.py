"""Host-level federated-learning simulation — the generic round driver.

Runs the paper's CNN on client-partitioned data under ANY registered
Strategy plugin (`core/strategies.py`: hfl / afl / cfl / async /
fedprox / fedavgm / fedadam / third-party) and reports exactly the
paper's measurement suite (Tables 1-2): training / testing accuracy,
build time, classification time, precision, recall, F1, balanced
accuracy, confusion matrix, and per-round accuracy/loss curves
(Figures 9/11).

The driver owns everything strategy-independent (DESIGN.md §9):

* engine dispatch — `FLConfig.engine` selects how one event's local
  training executes:
    "loop"       — per-client Python loop, one jit dispatch per client
                   (the paper-faithful timing surface).
    "vectorized" — the federation as one stacked pytree; local training
                   is a single compiled scan and aggregation goes
                   through the kernel-backed stacked operators
                   (core/engine.py + core/aggregation.py). Same results
                   to float tolerance (tests/test_engine.py).
    "fused"      — the ENTIRE run as one compiled `lax.scan` over
                   rounds (`run_fused`, DESIGN.md §10): strategy state,
                   optimizer state and the stacked federation stay on
                   device end to end; schedules, batch indices and
                   attack inputs are hoisted out of the loop (same rng
                   order, so §4 parity is bitwise); metrics accumulate
                   in-scan with ONE device->host transfer at run end.
                   Same results again (tests/test_fused.py).
* rng-parity bookkeeping — batch construction consumes the run rng in
  one canonical order (client-major, epoch-minor) under both engines
  (DESIGN.md §4).
* attack corruption — uploads are corrupted between local training and
  the strategy's aggregation event, keyed by (seed, event, absolute
  client id) (DESIGN.md §8); defense arguments are resolved per event
  via the strategy's declared event size.
* metric tracking + the paper's timing protocol (DESIGN.md §3): build
  time excludes compilation (strategy-directed warmup), classification
  time is min-of-3 on the served model — full test set for centralized
  strategies, one 1/N shard for decentralized on-device serving.

Strategies contribute only their schedule and aggregation math through
the `Strategy` lifecycle protocol; sequential (CFL-style) strategies
use `sequential_round`, the one driver primitive where training and
merging fuse.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import attacks
from repro.core import codecs as codecs_mod
from repro.core import engine as engine_mod
from repro.core import faults as faults_mod
from repro.core import strategies as strat_mod
from repro.core import aggregation
from repro.kernels import ops
from repro.core.fl_types import FLConfig
from repro.core.metrics import Timer, classification_metrics
from repro.obs import collectors as obs_collectors
from repro.obs import export as obs_export
from repro.obs.telemetry import Telemetry
from repro.data.partition import iid_partition
from repro.models import cnn as cnn_mod
from repro.optim import optimizers


@dataclasses.dataclass
class FLResult:
    strategy: str
    dataset: str
    train_accuracy: float
    test_accuracy: float
    build_time_s: float
    classification_time_s: float
    precision: float
    recall: float
    f1: float
    balanced_accuracy: float
    confusion: np.ndarray
    round_train_acc: List[float]
    round_train_loss: List[float]
    round_test_acc: List[float]
    # DESIGN.md §3 timing split: `build_time_s` is the steady-state
    # measured window (compilation excluded, identical meaning under
    # every engine); `warmup_time_s` is the warmup/compile window that
    # precedes it; `steady_time_s` aliases build_time_s under the
    # explicit name
    warmup_time_s: float = 0.0
    steady_time_s: float = 0.0
    # strategy-specific extras (async: merges/batches/staleness/makespan;
    # always: the schema-v2.3 "telemetry" block)
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def row(self) -> Dict[str, float]:
        return {k: getattr(self, k) for k in
                ("strategy", "dataset", "train_accuracy", "test_accuracy",
                 "build_time_s", "classification_time_s", "precision",
                 "recall", "f1", "balanced_accuracy")}


# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("lr_momentum", "loss_fn"),
                   donate_argnums=(1,))
def _sgd_epoch(params, opt_state, data, lr_momentum, *,
               loss_fn=cnn_mod.cnn_loss, extra=None):
    """One local epoch over pre-batched data: (nb, B, 28,28,1)/(nb, B).
    `loss_fn`/`extra` come from the strategy's LocalSpec (FedProx passes
    the round-start model as `extra`).

    `opt_state` is DONATED: it is freshly initialized per client and
    threaded epoch-to-epoch, so its buffers (the momentum slot is
    model-sized) are reused for the returned state instead of copied.
    `params` is NOT donatable here — the first epoch receives the
    client's round-start base, which aliases a shared model (the plan's
    bases, the aggregate center) that the driver still reads."""
    lr, momentum = lr_momentum
    opt = optimizers.sgd(lr, momentum=momentum)

    def step(carry, batch):
        params, opt_state = carry
        if extra is None:
            (loss, acc), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            (loss, acc), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, extra)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optimizers.apply_updates(params, updates)
        return (params, opt_state), (loss, acc)

    (params, opt_state), (losses, accs) = jax.lax.scan(
        step, (params, opt_state), data)
    return params, opt_state, jnp.mean(losses), jnp.mean(accs)


@jax.jit
def _predict(params, images):
    return jnp.argmax(cnn_mod.cnn_apply(params, images), axis=-1)


def _batched(x, y, batch_size, rng):
    order = rng.permutation(len(x))
    nb = len(x) // batch_size
    sel = order[: nb * batch_size]
    return {"image": jnp.asarray(x[sel].reshape(nb, batch_size, *x.shape[1:])),
            "label": jnp.asarray(y[sel].reshape(nb, batch_size))}


@dataclasses.dataclass(frozen=True)
class FusedStatic:
    """Every Python value the fused scan's trace reads, and so the key of
    its compiled program (DESIGN.md §10): value-compared and hashable.
    Arrays reach the scan as arguments (`_fused_consts`, carry, `xs`),
    and JAX keys their shapes and shardings itself, so two simulations
    with equal `FusedStatic`s run one program. It holds no simulation
    and no device array. The run seed is not in it (`fl` has it zeroed):
    a seed sweep of one shape shares one program.

    strategy         — a registered Strategy class (rebuilt in-trace from
                       `fl`), or a plugin instance, keyed by identity.
    fl               — the run's `FLConfig` with `seed=0`.
    track, nb        — the strategy's `track_curves`; whole batches per
                       client epoch.
    loss_fn, apply_fn, stacked_apply_fn — the engine's model functions.
    lowerings        — ((stack size, stacked-CNN lowering), ...) for the
                       stacks the round trains and evaluates, as
                       `models.cnn.stacked_lowering` picks them on the host.
    attack_any       — some client of the federation is an attacker.
    carry_sharding   — mesh path only: sorted (carry key, "client" |
                       "replicated") pairs (`Strategy.scan_carry_sharding`).
    """
    strategy: Any
    fl: FLConfig
    track: bool
    nb: int
    loss_fn: Any
    apply_fn: Any
    stacked_apply_fn: Any
    lowerings: Tuple[Tuple[int, str], ...]
    attack_any: bool
    carry_sharding: Optional[Tuple[Tuple[str, str], ...]] = None

    def make_strategy(self):
        """The strategy the trace runs."""
        if isinstance(self.strategy, type):
            return self.strategy(self.fl)
        return self.strategy

    def lowering(self, stack):
        """The stacked-CNN lowering of a `stack`-client stack: the key's,
        or for a stack the default round does not train (a plugin's
        own `scan_round`), `stacked_lowering`'s."""
        return dict(self.lowerings).get(stack) \
            or cnn_mod.stacked_lowering(stack)


class FusedContext:
    """What one fused-scan round sees (DESIGN.md §10): the device-resident
    run state — stacked federation dataset, per-client eval shards,
    client weights, test split — plus the static config. Built INSIDE the
    jitted scan from the program key (`FusedStatic`) and the explicitly
    passed arrays (`_fused_consts`), so the data arrives as program
    inputs rather than baked-in constants and the program outlives the
    simulation that built it. `Strategy.scan_round`/`scan_bases`/
    `scan_aggregate` receive this as their first argument.

    Under the mesh-sharded path (DESIGN.md §11) the scan body runs
    inside shard_map and every client-axis array here is the shard's
    LOCAL sub-stack; `mesh_axis` names the mesh axis, `local_pids` maps
    absolute participant ids to local rows (the client axis is sharded
    contiguously, so local id = absolute id - shard offset), and
    `pmean` averages per-round scalars across shards. All three are
    identity when `mesh_axis` is None, so strategy code is written once."""

    def __init__(self, static, consts):
        fl = static.fl
        self.static, self.fl, self.nb = static, fl, static.nb
        self.loss_fn, self.apply_fn = static.loss_fn, static.apply_fn
        self.stacked_apply_fn = static.stacked_apply_fn
        self.codec = (codecs_mod.get_codec(fl.codec)(fl)
                      if fl.codec != "none" else None)
        self.data_x = consts["data_x"]
        self.data_y = consts["data_y"]
        self.eval_x = consts["eval_x"]
        self.eval_y = consts["eval_y"]
        self.weights = consts["weights"]          # (C,) float32 [local]
        self.x_test = consts["x_test"]
        self.y_test = consts["y_test"]
        self.track = static.track
        self.mesh_axis = "data" if fl.mesh_devices > 1 else None
        # per-client codec state for the CURRENT scan step (error-
        # feedback residuals): the executor threads it through the scan
        # carry and parks it here across the strategy's scan_round call
        # (None when the codec is stateless or inactive)
        self._codec_carry = None
        # set by `Strategy.scan_round` when local training traced a
        # stacked CNN call (the `local_train.grouped_conv` counter)
        self.trained_stacked = False

    def local_pids(self, pids):
        """Absolute participant ids -> rows of this shard's sub-stack
        (identity off-mesh). Only valid under the driver-validated
        full-participation regime, where shard s holds exactly ids
        [s*C_loc, (s+1)*C_loc)."""
        if self.mesh_axis is None:
            return pids
        c_loc = self.data_x.shape[0]
        return pids - jax.lax.axis_index(self.mesh_axis) * c_loc

    def lowering(self, num_clients, chunk=0):
        """`models.cnn.lowering_scope` for this shard's `num_clients`
        stack, trained `chunk` clients at a time: the lowering the host
        picked (`FusedStatic.lowerings`) for the stack the single-device
        run sees (all `num_clients` x shards of them on a mesh), so a
        shard computes what one device computes."""
        if self.mesh_axis is not None:
            num_clients *= self.fl.mesh_devices
        stack = engine_mod.train_stack_size(num_clients, chunk)
        return cnn_mod.lowering_scope(self.static.lowering(stack))

    def pmean(self, x):
        """Cross-shard mean of a per-shard scalar metric (identity
        off-mesh; shards are equal-size, so the mean of shard means is
        the exact federation mean)."""
        if self.mesh_axis is None:
            return x
        return jax.lax.pmean(x, self.mesh_axis)

    def defense_kwargs(self, event_size=None):
        return _defense_kwargs(self.fl, event_size)

    def local_accs(self, params, pids):
        """The paper's post-training local-shard accuracy, in-trace —
        the same math as `VectorizedClientEngine.local_accs`."""
        preds = jnp.argmax(
            self.stacked_apply_fn(params, self.eval_x[pids]), axis=-1)
        return jnp.mean((preds == self.eval_y[pids]).astype(jnp.float32),
                        axis=1)

    def corrupt(self, uploads, bases, xs):
        """In-scan attack corruption: same per-round operator
        (`attacks.corrupt_stacked`), flags/keys hoisted into scan inputs
        — honest rows pass through bitwise unchanged (DESIGN.md §8)."""
        fl = self.fl
        if fl.attack in ("none", "label_flip") or not self.static.attack_any:
            return uploads
        return attacks.corrupt_stacked(uploads, bases, xs["flags"],
                                       xs["keys"], kind=fl.attack,
                                       scale=fl.attack_scale)

    def transport(self, uploads, bases, xs):
        """In-scan codec round-trip — the fused twin of
        `FederatedSimulation.transport` (DESIGN.md §12): encode -> decode
        the (corrupted) upload stack with keys hoisted into
        `xs['ckeys']`; error-feedback rows ride the scan carry via
        `_codec_carry`. Identity when codec='none' (bitwise degeneracy:
        the traced program is unchanged)."""
        codec = self.codec
        if codec is None:
            return uploads
        mat = ops.stacked_ravel(uploads)
        base = ops.stacked_ravel(bases) if codec.needs_bases else None
        if codec.stateful:
            pids = self.local_pids(xs["pids"])
            rows = jax.tree.map(lambda a: a[pids], self._codec_carry)
            dec, new_rows = codec.scan_encode_decode(
                mat, xs["ckeys"], base=base, rows=rows)
            self._codec_carry = jax.tree.map(
                lambda a, r: a.at[pids].set(r), self._codec_carry,
                new_rows)
        else:
            dec, _ = codec.scan_encode_decode(mat, xs["ckeys"],
                                              base=base, rows=None)
        return ops.stacked_unravel(uploads, dec)

    def test_acc(self, model):
        """Per-round curve point on the full test split (one in-scan
        forward — accumulated on device, transferred once at run end)."""
        if not self.track:
            return jnp.float32(jnp.nan)
        preds = jnp.argmax(cnn_mod.cnn_apply(model, self.x_test), axis=-1)
        return jnp.mean((preds == self.y_test).astype(jnp.float32))


def _defense_kwargs(fl, event_size=None):
    """kwargs for the defended aggregation operators, with the Byzantine
    allowance resolved for an event of `event_size` clients."""
    return {"defense": fl.defense,
            "f": fl.resolved_defense_f(event_size),
            "tau": fl.clip_tau}


def _mesh_specs(carry_sharding, carry, xs, consts):
    """PartitionSpecs of the mesh path's three scan inputs (DESIGN.md
    §11): carry subtrees by `carry_sharding` ("client": leading dim over
    "data"); `run_fused`'s four client-axis per-round inputs shard dim
    1, strategy extra xs (per-round scalars) replicate; every const but
    the test split shards its client axis."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding import specs as specs_mod
    carry_specs = {
        k: (specs_mod.client_stack_specs(carry[k])
            if carry_sharding[k] == "client"
            else specs_mod.replicated_specs(carry[k]))
        for k in carry}
    xs_specs = {k: (P(None, "data")
                    if k in ("pids", "idx", "flags", "keys", "fault_alive")
                    else P())
                for k in xs}
    consts_specs = {k: (P() if k in ("x_test", "y_test") else P("data"))
                    for k in consts}
    return carry_specs, xs_specs, consts_specs


class _FusedProgram:
    """The one jitted fused scan of a `FusedStatic`. JAX's own trace,
    lowering and executable caches, keyed by this object's function and
    by the arguments' avals and shardings, do the rest: a second
    simulation of the key lowers and compiles from them in
    milliseconds. `jax.clear_caches()` empties those caches, and the
    next use traces and compiles anew. `traces` counts traces (a
    simulation that adds none reused the program); `trained_stacked`
    says whether the last trace's local training ran a stacked CNN."""

    def __init__(self, static):
        self.static = static
        self.traces = 0
        self.trained_stacked = False
        self.jitted = jax.jit(self._run, donate_argnums=(0,))

    def _run(self, carry, xs, consts):
        self.traces += 1
        st = self.static
        if st.fl.mesh_devices <= 1:
            return self._scan(carry, xs, consts)
        from jax.sharding import PartitionSpec as P
        from repro.launch import mesh as mesh_launch
        specs = _mesh_specs(dict(st.carry_sharding), carry, xs, consts)
        # replication checking is off: local client ids come from
        # `axis_index` arithmetic, which check_vma cannot type through
        # `lax.scan` (the §11 parity tests pin correctness instead)
        return jax.shard_map(
            self._scan, mesh=mesh_launch.make_client_mesh(
                st.fl.mesh_devices),
            in_specs=specs, out_specs=(specs[0], (P(), P(), P())),
            check_vma=False)(carry, xs, consts)

    def _scan(self, carry, xs, consts):
        st = self.static
        fl, strat = st.fl, st.make_strategy()
        fx = FusedContext(st, consts)
        stateful = fx.codec is not None and fx.codec.stateful
        # in-scan per-round counters (DESIGN.md §13) ride the stacked
        # outputs next to the metric curves, one transfer at run end;
        # so does the per-round global model when serving (DESIGN.md
        # §14: the rounds live inside one scan, so `run_fused` replays
        # the publishes after it). Both are off under the mesh: the
        # shard_map out_specs describe the bare metric triple.
        scan_tel = fl.telemetry and fx.mesh_axis is None

        def body(c, x):
            if stateful:
                sc, cc = c
                fx._codec_carry = cc
                sc_new, out = strat.scan_round(fx, sc, x)
                c_new = (sc_new, fx._codec_carry)
            else:
                sc = c
                sc_new, out = strat.scan_round(fx, sc, x)
                c_new = sc_new
            if fl.serve:
                out = (out, strat.round_model(sc_new))
            if scan_tel:
                out = (out, obs_collectors.round_counters(
                    strat, fx, sc, sc_new, x))
            return c_new, out

        out = jax.lax.scan(body, carry, xs)
        self.trained_stacked = fx.trained_stacked
        return out


# the fused programs of the last few keys, most recent last
_PROGRAMS: "collections.OrderedDict[FusedStatic, _FusedProgram]" = \
    collections.OrderedDict()
_PROGRAMS_MAX = 8
_programs_lock = threading.Lock()


def _fused_program(static):
    """The memoised `_FusedProgram` of `static` (a bounded LRU)."""
    with _programs_lock:
        prog = _PROGRAMS.pop(static, None) or _FusedProgram(static)
        _PROGRAMS[static] = prog
        while len(_PROGRAMS) > _PROGRAMS_MAX:
            _PROGRAMS.popitem(last=False)
        return prog


def _fused_consts(sim):
    """The device arrays a fused run passes into its compiled scan. The
    test images are the classification phase's cached full-split head,
    so a centralized strategy holds them on the device once."""
    eng = sim.vec
    data_x, data_y = eng.stacked_dataset()
    x_test, y_test = sim.dataset["test"]
    return {"data_x": data_x, "data_y": data_y,
            "eval_x": eng.eval_x, "eval_y": eng.eval_y,
            "weights": jnp.asarray(np.asarray(sim.weights, np.float64),
                                   jnp.float32),
            "x_test": sim._test_head_dev(len(x_test)),
            "y_test": jnp.asarray(y_test)}


class FederatedSimulation:
    """Python-level multi-client FL simulation on a single host: the
    generic round driver plus the engine/attack/metric machinery the
    Strategy protocol builds on (`repro.api` documents the plugin-facing
    surface)."""

    def __init__(self, fl: FLConfig, dataset: Dict[str, Any],
                 model_init=None, strategy=None):
        self.fl = fl
        self.dataset = dataset
        self.rng = np.random.default_rng(fl.seed)
        # per-run tracer (DESIGN.md §13); its spans also enter
        # `prog.<name>` annotations, so they land in any jax.profiler
        # trace beside the device ops
        self.telemetry = Telemetry(enabled=fl.telemetry,
                                   annotate=jax.profiler.TraceAnnotation)
        with self.telemetry.span("construct", cat="run"):
            self._construct(model_init, strategy)

    def _construct(self, model_init, strategy):
        """Everything `__init__` builds after the tracer: strategy,
        codec, fault schedule, attackers, partition and client shards."""
        fl, dataset = self.fl, self.dataset
        key = jax.random.PRNGKey(fl.seed)
        self.init_params = (model_init or cnn_mod.init_cnn)(key)
        # resolve the strategy plugin: an instance is used as-is (plugin
        # escape hatch), a name resolves through the registry
        self._strategy_plugin = isinstance(strategy, strat_mod.Strategy)
        if self._strategy_plugin:
            self.strategy = strategy
        else:
            try:
                cls = strat_mod.get_strategy(strategy or fl.strategy)
            except KeyError as e:
                raise ValueError(str(e)) from None
            self.strategy = cls(fl)
        self.strategy.validate()
        # resolve the upload codec (DESIGN.md §12). codec="none" leaves
        # `self.codec` as None and every transport seam is an identity
        # early-return — the exact pre-codec code path, bitwise.
        self.model_dim = sum(
            int(np.prod(l.shape, dtype=np.int64))
            for l in jax.tree.leaves(self.init_params))
        self.codec = None
        self.codec_state = {}
        self._comm_log: List[int] = []   # participants per logged event
        if fl.codec != "none":
            self.codec = codecs_mod.get_codec(fl.codec)(fl)
            self.codec.validate(fl)
            if (self.codec.stateful
                    and self.strategy.codec_seam != "driver"):
                raise ValueError(
                    f"codec {fl.codec!r} carries per-client state "
                    f"(error feedback), which needs the stacked driver "
                    f"upload seam; strategy {self.strategy.name!r} "
                    f"aggregates sequentially "
                    f"(codec_seam={self.strategy.codec_seam!r}) — use a "
                    f"stateless codec or a stacked strategy")
            if fl.engine == "fused" and not self.codec.supports_fused:
                raise ValueError(
                    f"codec {fl.codec!r} does not support the fused "
                    f"executor (Codec.supports_fused)")
            self.codec_state = self.codec.init_state(fl.num_clients,
                                                     self.model_dim)
            # one jitted round-trip shared by all per-round events
            self._codec_apply = jax.jit(self.codec.scan_encode_decode)
        # fault-injection schedule (DESIGN.md §15). fault_profile="none"
        # leaves `self.faults` as None and every fault seam is a
        # host-level `if` — the exact pre-fault code path, bitwise
        # (mirrors the codec gate above). The schedule derives from its
        # own salted generator, so the run rng never shifts.
        if fl.fault_profile not in faults_mod.FAULT_PROFILES:
            raise ValueError(
                f"unknown fault profile {fl.fault_profile!r} "
                f"(expected one of {faults_mod.FAULT_PROFILES})")
        self.faults = faults_mod.compile_schedule(
            fl, n_events=self.strategy.num_events(self),
            event_size=self.strategy.event_size())
        self._fault_log: Dict[int, Any] = {}
        # Byzantine subset: drawn from a dedicated generator (never the
        # schedule rng) so the attack axis leaves the DESIGN.md §4 parity
        # contract intact
        self.attack_mask = (
            attacks.attacker_mask(fl.num_clients, fl.attack_fraction,
                                  fl.seed, placement=fl.attack_placement)
            if fl.attack != "none" else np.zeros(fl.num_clients, bool))
        self.attackers = np.flatnonzero(self.attack_mask)
        self.opt = optimizers.sgd(fl.lr, momentum=fl.momentum)
        xtr, ytr = dataset["train"]
        self._install_clients(iid_partition(ytr, fl.num_clients,
                                            seed=fl.seed))

    # -- local work ---------------------------------------------------------
    def _local_train(self, params, cid, spec=None):
        """Returns (params, last-epoch loss, POST-training local accuracy).

        "Training accuracy" follows the paper's protocol: the client's
        local model evaluated on its own shard after local training — this
        is what makes HFL's train/test gap visible (local models fit local
        data; the aggregated global model generalizes worse)."""
        x, y = self.client_data[cid]
        loss_fn = spec.loss_fn if spec is not None else cnn_mod.cnn_loss
        extra = params if (spec is not None and spec.extra == "bases") \
            else None
        opt_state = self.opt.init(params)
        loss = 0.0
        for _ in range(self.fl.local_epochs):
            data = _batched(x, y, self.fl.local_batch_size, self.rng)
            params, opt_state, loss, _ = _sgd_epoch(
                params, opt_state, data, (self.fl.lr, self.fl.momentum),
                loss_fn=loss_fn, extra=extra)
        n_eval = min(len(x), 512)
        preds = np.asarray(_predict(params, self._client_eval_dev(cid)))
        acc = float(np.mean(preds == y[:n_eval]))
        return params, float(loss), acc

    # -- device-resident eval arrays (built once per run, not per call) -----
    def _client_eval_dev(self, cid):
        """Client `cid`'s local eval shard on device — the loop engine's
        post-training accuracy reads it every round, so the transfer is
        paid once, not per (client, round)."""
        dev = self._eval_dev.get(cid)
        if dev is None:
            x, _ = self.client_data[cid]
            dev = self._eval_dev[cid] = jnp.asarray(x[: min(len(x), 512)])
        return dev

    def _split_dev(self, split, batch):
        """The split's images as device-resident `batch`-sized chunks
        (cached — `_eval` is called per round for curve tracking and
        re-transferred the whole split each time before PR 5)."""
        key = (split, batch)
        chunks = self._split_cache.get(key)
        if chunks is None:
            x = self.dataset[split][0]
            chunks = [jnp.asarray(x[i:i + batch])
                      for i in range(0, len(x), batch)]
            self._split_cache[key] = chunks
        return chunks

    def _eval(self, params, split="test", batch=500):
        return np.concatenate(
            [np.asarray(_predict(params, xb))
             for xb in self._split_dev(split, batch)])

    @classmethod
    def from_scenario(cls, spec) -> "FederatedSimulation":
        """Build a simulation from a `core.scenarios.ScenarioSpec` (duck-
        typed: any object with the spec's fields works): dataset
        constructed, partition applied, strategy resolved from the
        registry, engine state ready."""
        from repro.data.synthetic import DATASETS
        ds = DATASETS[spec.dataset](seed=spec.seed, n_train=spec.n_train,
                                    n_test=spec.n_test)
        sim = cls(spec.to_fl_config(), ds)
        if spec.partition == "dirichlet":
            from repro.data.partition import dirichlet_partition
            _, ytr = ds["train"]
            # every client must fill at least one local batch — with the
            # default floor (8) a heavily-skewed shard can fall below the
            # batch size and the loop engine would train it on ZERO
            # batches (NaN loss, untrained params)
            sim.set_partition(dirichlet_partition(
                ytr, spec.num_clients, alpha=spec.dirichlet_alpha,
                seed=spec.seed, min_per_client=spec.local_batch_size))
        return sim

    def set_partition(self, parts):
        """Re-partition the train split (e.g. Dirichlet non-IID) after
        construction; rebuilds the vectorized engine state if active."""
        self._install_clients(parts)

    def _install_clients(self, parts):
        """Materialize per-client shards from a partition: label_flip
        poisons attacker shards HERE (data-layer attack — the poisoned
        shard is what both engines batch from, so parity is structural),
        and the vectorized engine state is (re)built on the final data.
        The fused engine shares the vectorized engine's stacked state
        (its scan adds the device-resident dataset on top)."""
        xtr, ytr = self.dataset["train"]
        self.parts = parts
        self.client_data = []
        for c, p in enumerate(parts):
            y = ytr[p]
            if self.fl.attack == "label_flip" and self.attack_mask[c]:
                y = attacks.flip_labels(y)
            self.client_data.append((xtr[p], y))
        self.weights = [len(p) for p in parts]
        self._eval_dev = {}              # per-client device eval shards
        self._split_cache = {}           # device test/train eval chunks
        self.vec = (engine_mod.VectorizedClientEngine(
                        self.fl, self.client_data, self.weights)
                    if self.fl.engine in ("vectorized", "fused") else None)

    # -- driver primitives (the plugin-facing surface) ----------------------
    def defense_kwargs(self, event_size=None) -> Dict[str, Any]:
        """kwargs for the defended aggregation operators, with the
        Byzantine allowance resolved for this event's client count."""
        return _defense_kwargs(self.fl, event_size)

    def _build_bases_stacked(self, plan):
        """One FRESH stacked round-start-bases tree (uncached): from the
        strategy's lazy `bases_stacked_fn` if declared, else by stacking
        the list."""
        fn = plan.meta.get("bases_stacked_fn")
        return (fn() if fn is not None
                else engine_mod.stack_forest(plan.bases))

    def _bases_stacked(self, plan):
        """The plan's round-start bases as ONE stacked tree, built at
        most once per plan and only when a consumer (corruption, the
        FedProx proximal reference) actually needs it. The stacked TRAIN
        input is deliberately NOT this instance — the train dispatch
        donates its base-stack argument (`train_clients_donated`), so it
        gets a private fresh build while later consumers share this
        cache."""
        bases = plan.meta.get("bases_stacked")
        if bases is None:
            bases = plan.meta["bases_stacked"] = \
                self._build_bases_stacked(plan)
        return bases

    def local_train(self, plan, spec, rng):
        """One event's local training under the active engine. Consumes
        `rng` in the canonical client-major, epoch-minor order (§4) and
        returns (stacked uploads, per-client losses, per-client accs) —
        the uploads carry a leading participant axis under BOTH engines,
        so strategies aggregate through one stacked-operator path."""
        fl = self.fl
        with self.telemetry.span("local_train", k=len(plan.participants)):
            if self.vec is not None:
                eng = self.vec
                data = eng.batched_clients(rng, plan.participants,
                                           fl.local_epochs)
                # the train dispatch donates its base stack (buffer reuse
                # for the trained params), so it receives a private fresh
                # build; corruption / FedProx share the cached instance
                bases = self._build_bases_stacked(plan)
                extra = (self._bases_stacked(plan) if spec.extra == "bases"
                         else None)
                params, losses, _ = eng.train(
                    bases, data, stacked_loss_fn=spec.stacked_loss_fn,
                    extra=extra)
                accs = eng.local_accs(params, plan.participants)
                out = (params,
                       np.asarray(losses[:, -eng.nb:]).mean(axis=1), accs)
            else:
                locals_, losses, accs = [], [], []
                for c, base in zip(plan.participants, plan.bases):
                    p, loss, acc = self._local_train(base, c, spec=spec)
                    locals_.append(p)
                    losses.append(loss)
                    accs.append(acc)
                out = (engine_mod.stack_forest(locals_), losses, accs)
        return out

    def corrupt(self, uploads, plan):
        """Corrupt attacker rows of the trained upload stack against the
        plan's round-start bases; noise keys derive from (seed, event,
        absolute client id) — bitwise identical under both engines
        (DESIGN.md §8)."""
        fl = self.fl
        flags = self.attack_mask[np.asarray(plan.participants, int)]
        if fl.attack in ("none", "label_flip") or not flags.any():
            return uploads
        with self.telemetry.span("corrupt",
                                 attackers=int(flags.sum())):
            bases = self._bases_stacked(plan)
            keys = attacks.client_keys(
                attacks.event_key(fl.seed, plan.event), plan.participants)
            out = attacks.corrupt_stacked(uploads, bases, flags, keys,
                                          kind=fl.attack,
                                          scale=fl.attack_scale)
        return out

    def transport(self, uploads, plan):
        """Ship one event's upload stack through the active codec:
        encode -> decode on the raveled (k, N) matrix, error-feedback
        rows gathered/scattered against the per-client codec state, and
        the event's analytic wire bytes logged (DESIGN.md §12). Identity
        when codec='none' — the exact pre-codec path. Runs AFTER
        `corrupt` (the wire carries the corrupted encoded update) and
        BEFORE aggregation (defenses see dequantized coordinates)."""
        codec = self.codec
        if codec is None:
            return uploads
        fl = self.fl
        with self.telemetry.span("encode_decode", codec=codec.name):
            mat = ops.stacked_ravel(uploads)
            keys = codecs_mod.upload_keys(fl.seed, plan.event,
                                          np.asarray(plan.participants,
                                                     np.int32))
            base = (ops.stacked_ravel(self._bases_stacked(plan))
                    if codec.needs_bases else None)
            if codec.stateful:
                pids = jnp.asarray(np.asarray(plan.participants, np.int32))
                rows = jax.tree.map(lambda a: a[pids], self.codec_state)
                dec, new_rows = self._codec_apply(mat, keys, base=base,
                                                  rows=rows)
                self.codec_state = jax.tree.map(
                    lambda a, r: a.at[pids].set(r), self.codec_state,
                    new_rows)
            else:
                dec, _ = self._codec_apply(mat, keys, base=base, rows=None)
            self._comm_log.append(len(plan.participants))
            self.telemetry.counter(
                "codec.uplink_bytes",
                len(plan.participants) * codec.bytes_on_wire(self.model_dim))
            out = ops.stacked_unravel(uploads, dec)
        return out

    def fault_view(self, plan):
        """The plan's event-level fault view (DESIGN.md §15), or None
        when fault injection is off. Pure precomputed-numpy indexing, so
        strategies may call it from aggregation events and warmup
        dry-runs alike; every call logs the view into `_fault_log`
        (idempotently — the schedule is immutable), which feeds the
        result document's `faults` block and the serving quorum gate."""
        if self.faults is None:
            return None
        fe = self.faults.event_view(plan.event, plan.participants)
        self._fault_log[plan.event] = fe
        return fe

    def _reset_codec(self):
        """Re-zero codec state + wire log (warmups dry-run the transport
        to compile it, which must not leak residuals/bytes into the
        measured run)."""
        if self.codec is not None:
            self.codec_state = self.codec.init_state(self.fl.num_clients,
                                                     self.model_dim)
            self._comm_log = []

    def sequential_round(self, model, order, event, alpha, spec, rng):
        """One continual (CFL-style) pass: clients train in visit order,
        each (possibly corrupted, possibly norm-clipped) update merging
        into the carried model. Loop engine: per-visit dispatch + host
        merges; vectorized: one `lax.scan` with in-scan corruption (the
        visit base is the carried state). Returns (model, losses, accs)."""
        with self.telemetry.span("sequential_round", k=len(order)):
            out = self._sequential_round(model, order, event, alpha,
                                         spec, rng)
        return out

    def _sequential_round(self, model, order, event, alpha, spec, rng):
        fl = self.fl
        codec = self.codec
        # faults in the sequential pass (DESIGN.md §15): a dead visitor
        # still trains (rng parity) but its merge is discarded — the
        # carried model passes through unchanged; a below-quorum round
        # reverts to its start model
        fe = (self.faults.event_view(event, order)
              if self.faults is not None else None)
        if fe is not None:
            self._fault_log[event] = fe
        ckeys = (codecs_mod.upload_keys(fl.seed, event,
                                        np.asarray(order, np.int32))
                 if codec is not None else None)
        if codec is not None:
            self._comm_log.append(len(order))
            self.telemetry.counter(
                "codec.uplink_bytes",
                len(order) * codec.bytes_on_wire(self.model_dim))
        if self.vec is not None:
            eng = self.vec
            data = eng.batched_clients(rng, order, fl.local_epochs)
            # per-visit attack inputs, permuted into visit order; keys
            # derive from absolute ids so they match the loop engine
            keys = attacks.client_keys(attacks.event_key(fl.seed, event),
                                       order)
            model, losses, accs = eng.cfl_round(
                model, order, data, alpha, attack=fl.attack,
                attack_scale=fl.attack_scale,
                attack_flags=self.attack_mask[np.asarray(order, int)],
                attack_keys=keys, defense=fl.defense,
                clip_tau=fl.clip_tau, codec=codec, codec_keys=ckeys,
                fault_alive=None if fe is None else fe.alive,
                fault_qok=None if fe is None else np.bool_(fe.qok))
            return (model, np.asarray(losses[:, -eng.nb:]).mean(axis=1),
                    np.asarray(accs))
        attacking = fl.attack not in ("none", "label_flip")
        key = attacks.event_key(fl.seed, event)
        losses, accs = [], []
        model0 = model
        for i, c in enumerate(order):
            local, loss, acc = self._local_train(model, c, spec=spec)
            if fe is None or fe.alive_b[i]:
                if attacking and self.attack_mask[c]:
                    # base = the model this visit pulled (the carried
                    # state), exactly the in-scan base of the vectorized
                    # pass
                    local = attacks.corrupt_tree(
                        local, model, True,
                        jax.random.fold_in(key, int(c)),
                        kind=fl.attack, scale=fl.attack_scale)
                if codec is not None:
                    # wire seam per visit: the merged update is the
                    # decoded encoding of the (corrupted) local model,
                    # keyed like the vectorized pass (absolute client id)
                    local = codecs_mod.roundtrip_tree(
                        codec, local, ckeys[i][None], base_tree=model)
                if fl.defense == "norm_clip":
                    from repro.core import robust
                    local = robust.clip_update(model, local, fl.clip_tau)
                model = aggregation.cfl_merge(model, local, alpha)
            losses.append(loss)
            accs.append(acc)
        if fe is not None and not fe.qok:
            model = model0
        return model, losses, accs

    # -- warmup (DESIGN.md §3: compilation stays out of the timers) ---------
    def warmup_default(self, strategy):
        """Engine-appropriate default warmup for a strategy: loop
        compiles the local-train/predict/attack programs; vectorized
        dry-runs one FINAL event (tier-2 paths included) plus the served
        model with a throwaway rng — shapes are identical, `self.rng` is
        untouched."""
        if self.vec is None:
            self.warmup_loop(strategy)
            strategy.warmup_aggregate(self)
            return
        self._warmup_predicts()
        rng = np.random.default_rng(self.fl.seed)
        state = strategy.init_state(self)
        state, _, _ = strategy.run_event(
            self, state, strategy.num_events(self) - 1, rng=rng)
        strategy.served_fn(self, state)()

    def warmup_loop(self, strategy):
        """Compile the loop engine's jits outside the measured windows so
        build/classification timers compare strategies, not XLA caching."""
        spec = strategy.local_spec(
            self, None, strat_mod.RoundPlan([0], [self.init_params], 0))
        x, y = self.client_data[0]
        data = _batched(x[: 2 * self.fl.local_batch_size],
                        y[: 2 * self.fl.local_batch_size],
                        self.fl.local_batch_size, np.random.default_rng(0))
        extra = self.init_params if spec.extra == "bases" else None
        _sgd_epoch(self.init_params, self.opt.init(self.init_params), data,
                   (self.fl.lr, self.fl.momentum), loss_fn=spec.loss_fn,
                   extra=extra)
        self._warmup_predicts()
        self._warmup_attack()
        # local-shard train-accuracy eval shape
        n_eval = min(len(x), 512)
        _predict(self.init_params, jnp.asarray(x[:n_eval]))

    def _warmup_attack(self):
        """Compile the loop engine's per-client corruption / clip programs
        (jitted on shapes + attack kind) outside the build window."""
        fl = self.fl
        if fl.attack not in ("none", "label_flip") and len(self.attackers):
            attacks.corrupt_tree(self.init_params, self.init_params, True,
                                 attacks.event_key(fl.seed, 0),
                                 kind=fl.attack, scale=fl.attack_scale)
        if fl.defense == "norm_clip":
            from repro.core import robust
            robust.clip_update(self.init_params, self.init_params,
                               fl.clip_tau)

    def _warmup_predicts(self, full=None):
        """Compile the classification/eval `_predict` shapes (shared by
        both engines). `full`: the whole test split on the device, where
        the caller holds it already."""
        x_test = self.dataset["test"][0]
        _predict(self.init_params, jnp.asarray(x_test[:500]))
        _predict(self.init_params,                                   # full
                 jnp.asarray(x_test) if full is None else full)
        shard = -(-len(x_test) // self.fl.num_clients)
        _predict(self.init_params, jnp.asarray(x_test[:shard]))     # shard
        # stragglers of the batched _eval: the final partial batch
        if len(x_test) % 500:
            _predict(self.init_params,
                     jnp.asarray(x_test[-(len(x_test) % 500):]))

    # -- the generic driver loop --------------------------------------------
    def run(self) -> FLResult:
        if self.fl.engine == "fused":
            return self.run_fused()
        fl, strat = self.fl, self.strategy
        tel = self.telemetry
        curves = {"train_acc": [], "train_loss": [], "test_acc": []}
        state = strat.init_state(self)
        # warmup dry-runs the lifecycle to compile it — suppressed so
        # compile time never pollutes the phase totals (DESIGN.md §13);
        # the warmup window is timed separately (§3 build/steady split)
        warmup_timer = Timer()
        with tel.span("warmup", cat="run"), warmup_timer, tel.suppress():
            strat.warmup(self)
        self._reset_codec()
        n_events = strat.num_events(self)
        # federation-in-the-loop serving (DESIGN.md §14): the session's
        # traffic draws from its own seed fold, and the publish hook
        # below only READS the round model — training is bitwise
        # identical with serving on or off
        serve_sess = self._make_serve_session(n_events)
        all_accs: List[float] = []
        train_acc = 0.0
        build_timer = Timer()

        with build_timer:
            for ev in range(n_events):
                state, accs, losses = strat.run_event(self, state, ev)
                train_acc = float(np.mean(np.asarray(accs)))
                all_accs.extend(float(a) for a in np.ravel(accs))
                if strat.track_curves:
                    self._track(curves, accs, losses,
                                strat.round_model(state))
                if serve_sess is not None:
                    # round boundary: serve the window's traffic on the
                    # old model, then hot-swap the fresh aggregate in —
                    # unless the round failed quorum, in which case
                    # NOTHING publishes and the staleness histogram
                    # reflects the held version (DESIGN.md §15)
                    fe = self._fault_log.get(ev)
                    if fe is not None and not fe.qok:
                        serve_sess.hold_round(ev + 1)
                    else:
                        serve_sess.publish_round(ev + 1,
                                                 strat.round_model(state))
        if strat.mean_train_acc_over_events:
            train_acc = float(np.mean(all_accs)) if all_accs else 0.0
        return self._classify_and_result(state, curves, train_acc,
                                         build_timer,
                                         warmup_timer=warmup_timer)

    # -- the fused executor (DESIGN.md §10) ---------------------------------
    def run_fused(self) -> FLResult:
        """The whole run as ONE compiled `lax.scan` over rounds: strategy
        state, optimizer state and the stacked federation live on device
        for the entire run, with per-round metrics accumulated in-scan
        and transferred once at the end.

        §4 rng parity with the per-round driver is preserved BITWISE:
        the host precompute below consumes `self.rng` in exactly the
        per-round order — per event, the strategy's participant schedule
        first (`select_participants`), then one batch-index permutation
        per (client, epoch) (`batch_indices`) — and hoists the results
        into the scan's per-round inputs. Warmup = AOT-compiling the
        scan (DESIGN.md §3: the build timer measures ONE steady-state
        execution of the compiled run), or finding the program an
        earlier simulation of the same key (`FusedStatic`: any seed,
        any data of the same shapes) built. The scan carry is donated,
        so round t+1's state reuses round t's buffers."""
        fl, strat = self.fl, self.strategy
        if self.vec is None:
            raise ValueError(
                "run_fused needs the stacked engine state "
                "(FLConfig.engine='fused', or 'vectorized' when calling "
                "run_fused directly)")
        if not strat.supports_fused:
            raise ValueError(
                f"strategy {strat.name!r} does not support the fused "
                f"executor (Strategy.supports_fused; async-style "
                f"data-dependent schedules cannot be hoisted into a scan)")
        tel = self.telemetry
        R = strat.num_events(self)
        state0 = strat.init_state(self)

        # host precompute (untimed): schedule + batch indices + attack
        # inputs for every round, in the per-round rng order. Schedules
        # are drawn against the INITIAL state — part of the
        # supports_fused contract (see strategies.py): a fused
        # strategy's participant choice depends on (event, rng) only.
        with tel.span("precompute", cat="run", rounds=R):
            pids_l, idx_l, keys_l = [], [], []
            for ev in range(R):
                plan = strat.select_participants(self, state0, ev,
                                                 self.rng)
                parts = np.asarray(plan.participants, np.int32)
                pids_l.append(parts)
                idx_l.append(self.vec.batch_indices(self.rng,
                                                    plan.participants,
                                                    fl.local_epochs))
                keys_l.append(np.asarray(attacks.client_keys(
                    attacks.event_key(fl.seed, ev), parts)))
            k = len(pids_l[0]) if R else strat.event_size()
            T = fl.local_epochs * self.vec.nb
            pids = (np.stack(pids_l) if R
                    else np.zeros((0, k), np.int32))
            idx = (np.stack(idx_l) if R
                   else np.zeros((0, k, T, fl.local_batch_size), np.int32))
            keys = (np.stack(keys_l) if R
                    else np.zeros((0, k, 2), np.uint32))
            xs = {"pids": jnp.asarray(pids), "idx": jnp.asarray(idx),
                  "flags": jnp.asarray(self.attack_mask[pids]),
                  "keys": jnp.asarray(keys),
                  "event": jnp.arange(R, dtype=jnp.int32)}
            for key, val in strat.scan_extra_xs(self, R).items():
                xs[key] = jnp.asarray(val)
            if self.faults is not None:
                # fault schedule as precomputed scan inputs (DESIGN.md
                # §15): alive masks, quorum flags and — per strategy —
                # group quorums / gossip mixing arrays, the SAME numpy
                # views the per-round drivers index, so loop == vec ==
                # fused stays bitwise under an active profile
                for key, val in self.faults.scan_xs(
                        pids_l, **strat.fault_scan_kwargs()).items():
                    xs[key] = jnp.asarray(val)
                for ev in range(R):
                    self._fault_log[ev] = self.faults.event_view(
                        ev, pids_l[ev])
            codec_state = None
            if self.codec is not None:
                # codec rng hoisted like the attack keys: one (k, 2) key
                # block per round, derived from (seed, event, client id)
                ckeys = ([np.asarray(codecs_mod.upload_keys(fl.seed, ev,
                                                            pids_l[ev]))
                          for ev in range(R)])
                xs["ckeys"] = jnp.asarray(
                    np.stack(ckeys) if R
                    else np.zeros((0, k, 2), np.uint32))
                if self.codec.stateful:
                    codec_state = self.codec.init_state(fl.num_clients,
                                                        self.model_dim)
            consts = _fused_consts(self)
        # private copy of the initial carry: the scan donates it, and
        # state0's leaves may alias long-lived arrays (init_params)
        carry0 = jax.tree.map(jnp.array, strat.scan_carry(self, state0))
        if codec_state is not None:
            # error-feedback residuals ride the scan carry next to the
            # strategy's state (device-resident for the whole run, same
            # donation discipline); carry0 stays untouched when the
            # codec is stateless or inactive — the compiled program is
            # the pre-codec one
            carry0 = (carry0, codec_state)

        if fl.mesh_devices > 1:
            self._mesh_validate(pids)
        static = self._fused_static(k)
        program = _fused_program(static)
        if fl.mesh_devices > 1:
            carry0, xs, consts = self._mesh_place(static, carry0, xs,
                                                  consts)

        # warmup = lower + compile the scan once (AOT, so the donated
        # carry is not consumed) + the classification-phase predict
        # shapes; lowering and compiling are spans of their own, which
        # count the run's compile requests and persistent-cache hits.
        # A program this process already built for the same key
        # (`FusedStatic`) comes back from JAX's caches: the two spans
        # then time that lookup, and `fused.program_reused` reads 1.
        warmup_timer = Timer()
        with tel.span("warmup", cat="run"), warmup_timer:
            traces = program.traces
            requests = tel.counters.get("compile.requests", 0.0)
            with obs_collectors.compile_span(tel, "lower"):
                lowered = program.jitted.lower(carry0, xs, consts)
            with obs_collectors.compile_span(tel, "compile"):
                compiled = lowered.compile()
            tel.set_counter("fused.program_reused", float(
                program.traces == traces
                and tel.counters.get("compile.requests", 0.0) == requests))
            with tel.suppress():
                n_test = len(self.dataset["test"][0])
                self._warmup_predicts(full=self._test_head_dev(n_test))
        if program.trained_stacked:
            # the lowering local training took: 1 grouped, 0 patch
            train_stack = engine_mod.train_stack_size(k, fl.fused_chunk)
            tel.set_counter("local_train.grouped_conv",
                            float(static.lowering(train_stack) == "grouped"))

        build_timer = Timer()
        with build_timer, tel.span("fused_scan", cat="run", rounds=R):
            carry, outs = compiled(carry0, xs, consts)
            jax.block_until_ready((carry, outs))
        if fl.telemetry and fl.mesh_devices <= 1:
            outs, scan_counters = outs
        else:
            scan_counters = {}
        round_models = None
        if fl.serve:
            outs, round_models = outs
        acc_r, loss_r, tacc_r = outs
        if fl.mesh_devices > 1:
            # the classification phase mixes this state with
            # single-device test shards — re-home the final carry so
            # those computations colocate (untimed, like the
            # single-device path's absent transfer)
            dev0 = jax.devices()[0]
            carry = jax.tree.map(lambda l: jax.device_put(l, dev0), carry)
        if codec_state is not None:
            carry, self.codec_state = carry
        if self.codec is not None:
            # analytic wire accounting, from the hoisted schedules
            self._comm_log = [len(p) for p in pids_l]
        # one bulk transfer of the in-scan counters + the host-known
        # per-round series (participants, codec wire bytes)
        for cname, vals in scan_counters.items():
            tel.record_series("scan." + cname, np.asarray(vals))
        tel.record_series("participants", [len(p) for p in pids_l])
        if self.codec is not None:
            bw = self.codec.bytes_on_wire(self.model_dim)
            tel.record_series("codec.uplink_bytes",
                              [len(p) * bw for p in pids_l])
            tel.counter("codec.uplink_bytes",
                        sum(len(p) * bw for p in pids_l))
        state = strat.scan_uncarry(self, carry)
        # kept for inspection: the compiled scan (HLO text, shardings,
        # memory analysis) and the final strategy state
        self.fused_program, self.final_state = compiled, state
        acc_r, loss_r, tacc_r = (np.asarray(acc_r), np.asarray(loss_r),
                                 np.asarray(tacc_r))
        curves = {"train_acc": [], "train_loss": [], "test_acc": []}
        if strat.track_curves:
            curves = {"train_acc": [float(a) for a in acc_r],
                      "train_loss": [float(x) for x in loss_r],
                      "test_acc": [float(a) for a in tacc_r]}
        train_acc = float(acc_r[-1]) if R else 0.0
        # warm the serving path outside the classification timer (the
        # per-round driver does this in warmup_default) — on the shard
        # shape the timed phase will use, which _warmup_predicts already
        # compiled
        x_test = self.dataset["test"][0]
        shard = (len(x_test) if strat.centralized
                 else -(-len(x_test) // fl.num_clients))
        _predict(strat.served_fn(self, state)(),
                 self._test_head_dev(shard))
        serve_sess = self._make_serve_session(R)
        if serve_sess is not None:
            # replay the publishes the per-round drivers perform live:
            # one hot-swap per round, in round order, at the same
            # virtual times — the serving block is engine-independent
            with tel.span("serve_replay", cat="serve", rounds=R):
                for ev in range(R):
                    fe = self._fault_log.get(ev)
                    if fe is not None and not fe.qok:
                        # quorum-failed round: nothing published live
                        # either — replay the hold (DESIGN.md §15)
                        serve_sess.hold_round(ev + 1)
                        continue
                    serve_sess.publish_round(
                        ev + 1,
                        jax.tree.map(lambda l, _e=ev: l[_e],
                                     round_models))
        return self._classify_and_result(state, curves, train_acc,
                                         build_timer,
                                         warmup_timer=warmup_timer)

    def _fused_static(self, k):
        """This run's `FusedStatic`: the program key, from host values
        alone. `k` clients train each round; the stacked-CNN lowering of
        each stack the default round traces (training in chunks of
        `fused_chunk`, local evaluation whole) is decided here."""
        fl, strat, eng = self.fl, self.strategy, self.vec
        stacks = {engine_mod.train_stack_size(k, fl.fused_chunk), k}
        carry_sharding = None
        if fl.mesh_devices > 1:
            carry_sharding = tuple(sorted(
                strat.scan_carry_sharding(self).items()))
        return FusedStatic(
            strategy=(strat if self._strategy_plugin else type(strat)),
            fl=dataclasses.replace(fl, seed=0),
            track=strat.track_curves, nb=eng.nb, loss_fn=eng.loss_fn,
            apply_fn=eng.apply_fn, stacked_apply_fn=eng.stacked_apply_fn,
            lowerings=tuple(sorted(
                (n, cnn_mod.stacked_lowering(n)) for n in stacks)),
            attack_any=bool(self.attack_mask.any()),
            carry_sharding=carry_sharding)

    def _mesh_validate(self, pids):
        """DESIGN.md §11: the fused scan under `shard_map`, the stacked
        CLIENT axis partitioned over a 1-D ("data",) mesh
        (`_FusedProgram._run` wraps the scan, `_mesh_specs` says how).

        Local training / corruption / eval are embarrassingly parallel
        per shard; each strategy's `scan_aggregate` lowers its event to
        mesh collectives (core/aggregation.py mesh-sharded operators).
        Validates the shardability preconditions — the client axis is
        partitioned POSITIONALLY, so every round must train every client
        (full participation), shards must be equal (C % ndev == 0), and
        in-scan defenses are off (they rank across the whole federation;
        scan-level robust aggregation on the mesh is future work)."""
        fl, strat = self.fl, self.strategy
        ndev, C = fl.mesh_devices, fl.num_clients
        if not strat.supports_mesh:
            raise ValueError(
                f"strategy {strat.name!r} does not support the "
                f"mesh-sharded fused executor (Strategy.supports_mesh; "
                f"sequential schedules cannot shard the client axis)")
        if fl.defense != "none":
            raise ValueError(
                f"mesh_devices={ndev} with defense={fl.defense!r}: "
                f"in-scan defenses rank uploads across the WHOLE "
                f"federation and do not lower to per-shard collectives "
                f"(run the single-device fused path instead)")
        if C % ndev:
            raise ValueError(
                f"mesh path needs equal shards: num_clients={C} must be "
                f"a multiple of mesh_devices={ndev}")
        if fl.fused_chunk and (C // ndev) % fl.fused_chunk:
            raise ValueError(
                f"fused_chunk={fl.fused_chunk} must divide the LOCAL "
                f"participant stack ({C // ndev} clients per shard)")
        strat.validate_mesh(self, ndev)
        want = np.arange(C, dtype=np.int32)
        if pids.size and (pids.shape[1] != C
                          or not np.array_equal(
                              pids, np.broadcast_to(want, pids.shape))):
            raise ValueError(
                "mesh path needs full participation (participation=1.0): "
                "the client axis is sharded positionally, so every round "
                "must train clients 0..C-1 in id order")

    def _mesh_place(self, static, carry0, xs, consts):
        """The mesh path's scan inputs, device_put onto their
        NamedShardings up front: the AOT call then needs no resharding,
        and the federation stack never materializes on a single
        device."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from repro.launch import mesh as mesh_launch
        mesh = mesh_launch.make_client_mesh(self.fl.mesh_devices)
        sharding = dict(static.carry_sharding)
        if set(sharding) != set(carry0):
            raise ValueError(
                f"scan_carry_sharding keys {sorted(sharding)} do not "
                f"match the scan carry {sorted(carry0)}")

        def _put(tree, specs):
            return jax.tree.map(
                lambda s, l: jax.device_put(l, NamedSharding(mesh, s)),
                specs, tree,
                is_leaf=lambda x: isinstance(x, P))

        return tuple(_put(tree, specs) for tree, specs in zip(
            (carry0, xs, consts),
            _mesh_specs(sharding, carry0, xs, consts)))

    def _test_head_dev(self, shard):
        """Cached device-resident head of the test split (the
        classification-phase input — satellite of the §10 rework: no
        re-transfer per run/call)."""
        key = ("test_head", shard)
        dev = self._split_cache.get(key)
        if dev is None:
            dev = self._split_cache[key] = jnp.asarray(
                self.dataset["test"][0][:shard])
        return dev

    def _classify_and_result(self, state, curves, train_acc,
                             build_timer, warmup_timer=None) -> FLResult:
        """The paper's classification-time protocol (§1.2.7) + result
        assembly, shared by the per-round and fused drivers: centralized
        strategies serve the full test set at the server (after
        materializing the served model); decentralized strategies
        classify on-device — every client scores its own 1/N test shard
        in parallel, so measured wall time is one shard pass (+ any
        pre-serving aggregation the strategy's served_fn performs)."""
        fl, strat = self.fl, self.strategy
        served_fn = strat.served_fn(self, state)
        x_test, y_true = self.dataset["test"]
        shard = (len(x_test) if strat.centralized
                 else -(-len(x_test) // fl.num_clients))
        xs = self._test_head_dev(shard)
        with self.telemetry.span("classify", cat="run"):
            best = None
            for _ in range(3):      # min-of-3: immune to scheduler noise
                t = Timer()
                with t:
                    served = served_fn()
                    pred_head = np.asarray(_predict(served, xs))
                best = t.elapsed if best is None else min(best, t.elapsed)
            class_timer = Timer()
            class_timer.elapsed = best
            pred_tail = (self._eval(served)[shard:] if shard < len(x_test)
                         else np.empty((0,), pred_head.dtype))
            y_pred = np.concatenate([pred_head, pred_tail])
            m = classification_metrics(y_true, y_pred, 10)

        extra = dict(strat.extra_result(self, state))
        if self.codec is not None:
            extra["communication"] = self._communication_block()
        if self.faults is not None:
            # schema-v2.5 faults block (DESIGN.md §15) — absent when
            # fault_profile="none", like the communication block above
            extra["faults"] = self._faults_block()
        serve_sess = getattr(self, "_serve_session", None)
        if serve_sess is not None:
            # drains the tail traffic + summarizes (DESIGN.md §14);
            # virtual-clock quantities — engine-independent by
            # construction
            extra["serving"] = serve_sess.result_block()
        if self.vec is not None and self.vec.dropped_samples:
            # the stacked engine trains every client for the federation-
            # minimum batch count (core/engine.py ShardTruncationWarning)
            # — surface the per-client per-epoch sample loss so result
            # consumers see the documented loop/vectorized divergence
            extra["truncated_samples_per_epoch"] = dict(
                self.vec.dropped_samples)
        # the telemetry block (schema v2.3, reshaped in v2.6; always
        # present; when disabled it is the single-key {"enabled": False}
        # stub)
        extra["telemetry"] = obs_export.result_block(self.telemetry)

        return FLResult(
            strategy=strat.name, dataset=self.dataset["name"],
            train_accuracy=train_acc, test_accuracy=m["accuracy"],
            build_time_s=build_timer.elapsed,
            classification_time_s=class_timer.elapsed,
            precision=m["precision"], recall=m["recall"], f1=m["f1"],
            balanced_accuracy=m["balanced_accuracy"], confusion=m["confusion"],
            round_train_acc=curves["train_acc"],
            round_train_loss=curves["train_loss"],
            round_test_acc=curves["test_acc"],
            warmup_time_s=(warmup_timer.elapsed
                           if warmup_timer is not None else 0.0),
            steady_time_s=build_timer.elapsed,
            extra=extra,
        )

    def _make_serve_session(self, n_events: int):
        """Build the DESIGN.md §14 serving side-car (None when serving
        is off). The dispatch seam pads every micro-batch to the
        `serve_batch` admission cap so the whole serving run is ONE
        compiled classify shape — compiled here, outside every timed
        window. Sets `self._serve_session` (consumed by
        `_classify_and_result` for the schema-v2.4 block)."""
        fl = self.fl
        self._serve_session = None
        if not fl.serve:
            return None
        from repro import serve as serve_mod
        x_test, y_test = self.dataset["test"]
        dispatch = None
        if fl.serve_dispatch:
            xj = jnp.asarray(x_test)
            yt = np.asarray(y_test)
            pad = fl.serve_batch

            def dispatch(params, example_idx):
                ei = np.asarray(example_idx, np.int64)
                idx = np.zeros(pad, np.int64)
                idx[: len(ei)] = ei
                preds = np.asarray(
                    _predict(params, xj[jnp.asarray(idx)]))
                return preds[: len(ei)] == yt[ei]

        self._serve_session = serve_mod.ServeSession(
            fl, n_events=n_events, n_test=len(x_test),
            init_params=self.init_params, dispatch_fn=dispatch,
            telemetry=self.telemetry)
        return self._serve_session

    def _faults_block(self) -> Dict[str, Any]:
        """The schema-v2.5 `faults` result block (DESIGN.md §15):
        schedule-level statistics (deterministic in (seed, profile)) plus
        the run's observed event log — quorum failures, degraded rounds
        and the mean alive fraction over the events actually driven."""
        block = self.faults.schedule_stats()
        log = self._fault_log
        fails = sorted(ev for ev, fe in log.items() if not fe.qok)
        degraded = sorted(ev for ev, fe in log.items()
                          if fe.n_alive < len(fe.alive))
        block["events_logged"] = len(log)
        block["quorum_failures"] = len(fails)
        block["quorum_failed_events"] = fails
        block["degraded_rounds"] = len(degraded)
        block["mean_event_alive_frac"] = (
            float(np.mean([fe.n_alive / max(1, len(fe.alive))
                           for fe in log.values()])) if log else 1.0)
        return block

    def _communication_block(self) -> Dict[str, Any]:
        """The byte-count cost model (DESIGN.md §12), assembled from the
        per-event participant log. Accounting is ANALYTIC — bytes follow
        from the wire format and the event's participant count, never
        from measuring device buffers — so it is engine-independent by
        construction. Uplink = what participants ship through the codec;
        downlink = the dense model broadcast each participant pulled
        (codecs compress the upload path only); the compression ratio is
        dense-f32 uplink over codec uplink."""
        codec, dim = self.codec, self.model_dim
        per_up = [k * codec.bytes_on_wire(dim) for k in self._comm_log]
        per_down = [k * 4 * dim for k in self._comm_log]
        up, dense = sum(per_up), sum(per_down)
        return {
            "codec": codec.name,
            "uplink_bytes_per_round": per_up,
            "downlink_bytes_per_round": per_down,
            "uplink_bytes": int(up),
            "downlink_bytes": int(sum(per_down)),
            "dense_uplink_bytes": int(dense),
            "compression_ratio": (dense / up) if up else 1.0,
        }

    def _track(self, curves, accs, losses, model_for_eval):
        curves["train_acc"].append(float(np.mean(np.asarray(accs))))
        curves["train_loss"].append(float(np.mean(np.asarray(losses))))
        with self.telemetry.span("eval"):
            preds = self._eval(model_for_eval)
        curves["test_acc"].append(
            float(np.mean(preds == self.dataset["test"][1])))


def __getattr__(name):  # noqa: N807
    if name == "DEFENSES_BY_EVENT":
        warnings.warn(
            "simulation.DEFENSES_BY_EVENT is deprecated: per-event "
            "defense validity is declared on each Strategy "
            "(Strategy.defenses; see repro.api)", DeprecationWarning,
            stacklevel=2)
        hfl = strat_mod.get_strategy("hfl")
        afl = strat_mod.get_strategy("afl")
        cfl = strat_mod.get_strategy("cfl")
        return {"hfl": hfl.defenses["hierarchical"],
                "afl-fedavg": afl.defenses["star"],
                "afl-gossip": afl.defenses["ring"],
                "cfl": cfl.defenses["sequential"]}
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
