"""Vectorized stacked-client engine.

The loop engine (`FederatedSimulation`'s original path) trains clients in
a Python loop — one jit dispatch per client per round — so measured build
times reflect host dispatch overhead, not aggregation architecture, and
client counts beyond a few dozen are infeasible. This module represents
the federation as ONE pytree whose leaves carry a leading client axis and
runs local training for all clients in a single `jit(vmap(lax.scan))`
program: one XLA dispatch per round, regardless of client count.

Pieces:

* stack/unstack utilities — list-of-pytrees <-> stacked pytree.
* `train_clients` — vmap-of-scan local SGD for every client at once
  (`train_clients_donated` is the driver's buffer-reusing twin).
* `predict_clients` — vmapped post-training local-shard evaluation.
* `cfl_round_scan` — the continual (sequential) strategy as one
  `lax.scan` over the client visit order, kernel-backed merge inside.
* `batch_indices` / `gather_batches` / `stacked_dataset` — the batch-
  construction primitive split so the per-round path gathers on the
  host while the fused executor (DESIGN.md §10) hoists the full
  (rounds, k, T, B) index tensor out of its scan and gathers from the
  device-resident federation dataset in-trace.
* `VectorizedClientEngine` — host-side driver state: per-client shards,
  stacked eval sets, and the rng-consumption protocol shared with the
  loop engine so both engines see identical batch orders (this is what
  makes loop/vectorized parity exact rather than statistical;
  DESIGN.md §4).

Aggregation itself lives in `core/aggregation.py` (stacked-array
section) and lowers onto the Pallas `fedavg_agg` kernel via the ravel path in
`kernels/ops.py`.

Consumers: `FederatedSimulation`'s vectorized runners (synchronous
rounds) and the heterogeneous async runtime (`core/async_agg.py`), whose
tick batches train through `batched_clients`/`train` with an arbitrary
client subset per dispatch and merge through the kernel-backed
`aggregation.async_batch_merge`.
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import cnn as cnn_mod
from repro.optim import optimizers

Params = Any


class ShardTruncationWarning(UserWarning):
    """The vectorized/fused engines truncated unequal client shards to
    the federation-minimum batch count (see VectorizedClientEngine).
    `dropped` maps absolute client id -> samples dropped PER EPOCH
    beyond what the loop engine's per-client flooring already drops —
    the documented loop-vs-vectorized divergence on skewed shards."""

    def __init__(self, msg: str, dropped: Dict[int, int]):
        super().__init__(msg)
        self.dropped = dropped


# ---------------------------------------------------------------------------
# stacking utilities
# ---------------------------------------------------------------------------

def stack_forest(trees: List[Params]) -> Params:
    """List of identically-shaped pytrees -> one pytree, leading client axis."""
    return jax.tree.map(lambda *ls: jnp.stack(ls), *trees)


def unstack_forest(stacked: Params) -> List[Params]:
    """Inverse of `stack_forest`."""
    n = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda l: l[i], stacked) for i in range(n)]


def replicate_tree(tree: Params, n: int) -> Params:
    """Broadcast one model to a stacked federation of `n` copies."""
    return jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), tree)


def repeat_groups(stacked_groups: Params, per: int) -> Params:
    """(G, ...) group models -> (G*per, ...) client stack, contiguous
    group blocks (matches `topology.hierarchical_groups` ordering)."""
    return jax.tree.map(lambda l: jnp.repeat(l, per, axis=0), stacked_groups)


# ---------------------------------------------------------------------------
# compiled training / evaluation programs
# ---------------------------------------------------------------------------

def _local_sgd_scan(params, data, opt, loss_fn):
    """Scan local SGD over pre-batched data (T, B, ...). Momentum state
    persists across the whole scan — epochs are concatenated along T, so
    this reproduces the loop engine's per-epoch `_sgd_epoch` sequence."""
    def step(carry, batch):
        params, opt_state = carry
        (loss, acc), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optimizers.apply_updates(params, updates)
        return (params, opt_state), (loss, acc)

    (params, _), (losses, accs) = jax.lax.scan(
        step, (params, opt.init(params)), data)
    return params, losses, accs


def _train_clients_impl(stacked_params, data, *, stacked_loss_fn, lr,
                        momentum, extra=None):
    """All clients' local training as ONE compiled scan over batches.

    data leaves: (C, T, B, ...) with T = local_epochs * batches_per_epoch.
    `stacked_loss_fn(stacked_params, batch)` returns per-client
    ((C,) losses, (C,) accs); differentiating their SUM yields exactly the
    per-client gradients (clients are independent), so one scan step
    updates every client's SGD state at once. This is semantically
    `vmap(scan(local_sgd))`, with the client axis run through the
    stacked forward path (`cnn_apply_stacked`), whose lowering
    `models.cnn.stacked_lowering` picks from the backend and the stack's
    size.

    `extra` (optional, traced) is passed through as the loss's third
    argument — a Strategy's per-client loss context with a leading client
    axis (FedProx: the (C, ...) round-start models its proximal term
    references). The loss function object itself must stay stable across
    rounds: it keys the jit cache.

    Returns (new stacked params, per-batch losses (C, T), accs (C, T))."""
    opt = optimizers.sgd(lr, momentum=momentum)

    def step(carry, batch):
        params, opt_state = carry

        def total_loss(p):
            if extra is None:
                loss_c, acc_c = stacked_loss_fn(p, batch)
            else:
                loss_c, acc_c = stacked_loss_fn(p, batch, extra)
            return jnp.sum(loss_c), (loss_c, acc_c)

        (_, (loss_c, acc_c)), grads = jax.value_and_grad(
            total_loss, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optimizers.apply_updates(params, updates)
        return (params, opt_state), (loss_c, acc_c)

    # scan consumes the leading axis: make the data time-major (T, C, B, ...)
    data = jax.tree.map(lambda l: jnp.moveaxis(l, 1, 0), data)
    (stacked_params, _), (losses, accs) = jax.lax.scan(
        step, (stacked_params, opt.init(stacked_params)), data)
    return stacked_params, losses.T, accs.T


def train_stack_size(num_clients, chunk):
    """Clients per training stack of `_train_clients_chunked_impl`."""
    return chunk if 0 < chunk < num_clients else num_clients


def _train_clients_chunked_impl(stacked_params, data, *, stacked_loss_fn,
                                lr, momentum, extra=None, chunk):
    """`_train_clients_impl` one participant SUB-STACK at a time
    (DESIGN.md §11 chunking fallback): the (C, ...) stacks are reshaped
    to (C//chunk, chunk, ...) and a `lax.map` trains one chunk per step,
    so peak training-activation memory scales with `chunk` rather than
    the federation size — what lifts the fused client sweep past the
    single-stack ceiling. Clients are independent, so this computes the
    same math as the unchunked path; inside the fused round it is a
    different XLA program, whose float reductions XLA may fuse and order
    differently, so the two agree to float rounding, not bitwise
    (tests/test_fused.py)."""
    C = jax.tree.leaves(stacked_params)[0].shape[0]
    if train_stack_size(C, chunk) == C:
        return _train_clients_impl(
            stacked_params, data, stacked_loss_fn=stacked_loss_fn, lr=lr,
            momentum=momentum, extra=extra)
    if C % chunk:
        raise ValueError(
            f"fused_chunk={chunk} must divide the participant stack "
            f"({C} clients)")
    n = C // chunk
    split = functools.partial(jax.tree.map,
                              lambda l: l.reshape((n, chunk) + l.shape[1:]))
    unsplit = functools.partial(jax.tree.map,
                                lambda l: l.reshape((C,) + l.shape[2:]))

    def one_chunk(args):
        params_c, data_c, extra_c = args
        return _train_clients_impl(
            params_c, data_c, stacked_loss_fn=stacked_loss_fn, lr=lr,
            momentum=momentum, extra=extra_c)

    params, losses, accs = jax.lax.map(
        one_chunk, (split(stacked_params), split(data),
                    None if extra is None else split(extra)))
    return unsplit(params), unsplit(losses), unsplit(accs)


# Two jit surfaces over the same training program: the plain wrapper for
# callers that keep referencing the stacked params they pass in (tests,
# ad-hoc use), and a donating wrapper for the round driver's hot path —
# the round-start base stack is consumed exactly once there, so donating
# it lets XLA write the trained parameters into the same buffers instead
# of allocating a second copy of the federation (the driver builds a
# FRESH base stack for this argument whenever the bases have another
# consumer — attack corruption, FedProx's proximal reference). Inside
# the fused executor the impl is traced directly into the round scan,
# where the scan's donated carry provides the same reuse.
train_clients = functools.partial(jax.jit, static_argnames=(
    "stacked_loss_fn", "lr", "momentum"))(_train_clients_impl)
train_clients_donated = functools.partial(jax.jit, static_argnames=(
    "stacked_loss_fn", "lr", "momentum"), donate_argnums=(0,))(
    _train_clients_impl)


def gather_batches(data_x, data_y, pids, idx):
    """Device-side batch construction for one fused-scan round: gather
    the event's participants' batches straight out of the stacked
    federation dataset (`stacked_dataset`). `pids`: (k,) absolute client
    ids; `idx`: (k, T, B) per-client shard indices (`batch_indices`).
    Returns {"image": (k, T, B, ...), "label": (k, T, B)} — the same
    values `batched_clients` materializes on the host, with zero host
    round-trips (traceable; one fused gather per leaf)."""
    k, T, B = idx.shape
    rows = idx.reshape(k, -1)
    pid_col = pids[:, None]
    img = data_x[pid_col, rows].reshape(
        (k, T, B) + data_x.shape[2:])
    lab = data_y[pid_col, rows].reshape(k, T, B)
    return {"image": img, "label": lab}


@functools.partial(jax.jit, static_argnames=("stacked_apply_fn",))
def predict_clients(stacked_params, images, *, stacked_apply_fn):
    """Per-client predictions on per-client eval shards: (C, n, ...) ->
    (C, n) int labels. One dispatch instead of C."""
    return jnp.argmax(stacked_apply_fn(stacked_params, images), axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("loss_fn", "apply_fn", "lr", "momentum",
                                    "attack", "defense", "clip_tau",
                                    "codec"))
def cfl_round_scan(model, data, eval_images, eval_labels, alpha, *,
                   loss_fn, apply_fn, lr, momentum, attack="none",
                   attack_scale=1.0, attack_flags=None, attack_keys=None,
                   defense="none", clip_tau=10.0, codec=None,
                   codec_keys=None, fault_alive=None, fault_qok=None):
    """One CFL round — the sequential client-to-client continual pass — as
    a single `lax.scan` over clients in visit order.

    data leaves: (C, T, B, ...) already permuted into visit order;
    eval_images/labels: (C, n, ...) in the same order. The merge is the
    kernel-backed `cfl_merge_stacked` (C=2 weighted reduction).

    Adversarial axis (DESIGN.md §8): each visit's base model is the
    carried scan state, so corruption MUST happen inside the scan —
    `attack_flags`/`attack_keys` are per-visit (visit-order-permuted)
    scan inputs, the upload is corrupted between local training and the
    merge, and `defense="norm_clip"` clips the (possibly corrupted)
    delta before folding it in. Local accuracy is evaluated on the
    honest local model — attackers train honestly and corrupt only the
    upload.

    Upload codecs (DESIGN.md §12): the per-visit wire seam sits between
    corruption and the merge — the merged update is the decoded encoding
    of the (corrupted) local model, each visit keyed by `codec_keys`
    (one key row per visit, derived from (seed, event, absolute client
    id) with the codec salt). Only stateless codecs reach here (the
    driver validates); with `codec=None` the traced program is exactly
    the pre-codec one.

    Fault injection (DESIGN.md §15): `fault_alive` is a per-visit (C,)
    0/1 scan input — a dead visitor trains (rng parity) but its merge is
    discarded (`tree_where` holds the carried model, matching the loop
    engine's skipped host merge bitwise); `fault_qok` is the round's
    quorum flag — False holds the whole round at its start model (the
    declared degraded action for the redundancy-1 sequential merge).
    Both None is the exact pre-fault traced program.

    Each visit's phases run under `jax.named_scope` (`local_train`,
    `local_eval`, `corrupt`, `encode_decode`, `aggregate`), the names
    of the per-round driver's phase spans; scopes are metadata only.

    Returns (final model, losses (C, T), post-train local accs (C,))."""
    from repro.core import aggregation, attacks, codecs  # deferred
    opt = optimizers.sgd(lr, momentum=momentum)
    C = jax.tree.leaves(data)[0].shape[0]
    if attack_flags is None:
        attack_flags = jnp.zeros((C,), bool)
    if attack_keys is None:
        if attack not in ("none", "label_flip"):
            # a PRNGKey(0) fallback here would make the corruption noise
            # identical across runs regardless of FLConfig.seed,
            # violating the DESIGN.md §4/§8 rng contract — the driver
            # must pass keys derived from (seed, event, client id)
            raise ValueError(
                f"cfl_round_scan: attack={attack!r} corrupts uploads "
                f"in-scan and needs per-visit attack_keys (derive them "
                f"from the run seed via attacks.client_keys)")
        # benign path: keys are threaded as scan inputs but never used
        attack_keys = jax.random.split(jax.random.PRNGKey(0), C)
    if codec is not None and codec_keys is None:
        # same contract as attack_keys: a constant-key fallback would
        # make quantization noise seed-independent
        raise ValueError(
            f"cfl_round_scan: codec={codec.name!r} needs per-visit "
            f"codec_keys (derive them via codecs.upload_keys)")

    def visit(model, inputs):
        inputs = list(inputs)
        cdata, ex, ey, flag, key = inputs[:5]
        off = 5
        ckey = av = None
        if codec is not None:
            ckey = inputs[off]
            off += 1
        if fault_alive is not None:
            av = inputs[off]
            off += 1
        with jax.named_scope("local_train"):
            local, losses, _ = _local_sgd_scan(model, cdata, opt, loss_fn)
        with jax.named_scope("local_eval"):
            preds = jnp.argmax(apply_fn(local, ex), axis=-1)
            acc = jnp.mean((preds == ey).astype(jnp.float32))
        if attack not in ("none", "label_flip"):
            with jax.named_scope("corrupt"):
                local = attacks.corrupt_tree(local, model, flag, key,
                                             kind=attack,
                                             scale=attack_scale)
        if codec is not None:
            with jax.named_scope("encode_decode"):
                local = codecs.roundtrip_tree(codec, local, ckey[None],
                                              base_tree=model)
        with jax.named_scope("aggregate"):
            if defense == "norm_clip":
                merged = aggregation.defended_cfl_merge(model, local,
                                                        alpha, clip_tau)
            else:
                merged = aggregation.cfl_merge_stacked(model, local, alpha)
            if fault_alive is not None:
                # a dead visitor's merge is discarded (upload lost on
                # the wire); the carried model passes through bitwise,
                # matching the loop engine's skipped host merge
                merged = aggregation.tree_where(av > 0, merged, model)
        return merged, (losses, acc)

    model0 = model
    xs = (data, eval_images, eval_labels,
          jnp.asarray(attack_flags, bool), attack_keys)
    if codec is not None:
        xs = xs + (jnp.asarray(codec_keys),)
    if fault_alive is not None:
        xs = xs + (jnp.asarray(fault_alive, jnp.float32),)
    model, (losses, accs) = jax.lax.scan(visit, model, xs)
    if fault_qok is not None:
        # below-quorum round: the declared degraded action holds the
        # whole round at its start model
        with jax.named_scope("aggregate"):
            model = aggregation.tree_where(jnp.asarray(fault_qok, bool),
                                           model, model0)
    return model, losses, accs


# ---------------------------------------------------------------------------
# host-side driver
# ---------------------------------------------------------------------------

class VectorizedClientEngine:
    """Host state for the vectorized engine.

    Owns the per-client shards, the stacked local eval sets, and the batch
    construction. Batching consumes the caller's numpy rng in exactly the
    loop engine's order (client-major, epoch-minor permutations), so the
    two engines run the same SGD sequence and agree up to float tolerance.

    Constraint: all clients must yield the same number of batches per
    epoch; with unequal shards the batch count is truncated to the
    federation minimum (the loop engine floors per client instead — use
    shard-divisible datasets when exact parity matters).
    """

    def __init__(self, fl, client_data: List[Tuple[np.ndarray, np.ndarray]],
                 weights: Sequence[float], *,
                 loss_fn=cnn_mod.cnn_loss, apply_fn=cnn_mod.cnn_apply,
                 stacked_loss_fn=cnn_mod.cnn_loss_stacked,
                 stacked_apply_fn=cnn_mod.cnn_apply_stacked):
        self.fl = fl
        self.client_data = client_data
        self.weights = np.asarray(weights, np.float64)
        self.loss_fn = loss_fn                    # single-model (CFL scan)
        self.apply_fn = apply_fn
        self.stacked_loss_fn = stacked_loss_fn    # leading-client-axis path
        self.stacked_apply_fn = stacked_apply_fn
        sizes = [len(x) for x, _ in client_data]
        self.nb = min(sizes) // fl.local_batch_size
        if self.nb == 0:
            raise ValueError(
                f"local_batch_size={fl.local_batch_size} exceeds the "
                f"smallest client shard ({min(sizes)} samples)")
        # unequal shards: every client is truncated to the federation-
        # minimum batch count, while the loop engine floors PER CLIENT —
        # the engines then silently train on different data and parity
        # becomes statistical. Record the per-client divergence (samples
        # the loop engine would train on per epoch beyond this engine's
        # nb*B) and warn once, structured, so drivers can surface it.
        B = fl.local_batch_size
        self.dropped_samples = {
            c: (n // B) * B - self.nb * B
            for c, n in enumerate(sizes) if (n // B) * B > self.nb * B}
        if self.dropped_samples:
            total = sum(self.dropped_samples.values())
            warnings.warn(ShardTruncationWarning(
                f"unequal client shards: the vectorized/fused engines "
                f"truncate every client to the federation-minimum "
                f"{self.nb} batch(es)/epoch, dropping {total} sample(s)/"
                f"epoch that the loop engine trains on (per-client: "
                f"{self.dropped_samples}); loop-vs-vectorized parity is "
                f"statistical on this partition",
                self.dropped_samples), stacklevel=2)
        self.n_eval = min(512, min(sizes))
        # stacked on the host, one device put each: a device copy per
        # client first would hold C small, tile-padded arrays beside the
        # stack (on a v5e about 1 GB at 1,024 clients of 58 samples)
        self.eval_x = jnp.asarray(
            np.stack([x[: self.n_eval] for x, _ in client_data]))
        self.eval_y = jnp.asarray(
            np.stack([y[: self.n_eval] for _, y in client_data]))

    # -- batching -----------------------------------------------------------
    def batch_indices(self, rng: np.random.Generator,
                      client_ids: Sequence[int], epochs: int) -> np.ndarray:
        """The (k, epochs*nb, B) int32 batch-index tensor for one event:
        per-client indices into the client's OWN shard, rng order
        identical to the loop engine — for each client (in the given
        order), one permutation per epoch (DESIGN.md §4). This is the
        single batch-construction primitive: the per-round path gathers
        it on the host (`batched_clients`), the fused executor hoists
        the full (rounds, k, T, B) tensor out of its scan and gathers on
        device (`gather_batches`)."""
        B = self.fl.local_batch_size
        nb, T = self.nb, epochs * self.nb
        idx = np.empty((len(client_ids), T, B), np.int32)
        for i, c in enumerate(client_ids):
            n = len(self.client_data[c][0])
            for e in range(epochs):
                sel = rng.permutation(n)[: nb * B]
                idx[i, e * nb:(e + 1) * nb] = sel.reshape(nb, B)
        return idx

    def batched_clients(self, rng: np.random.Generator,
                        client_ids: Sequence[int], epochs: int
                        ) -> Dict[str, jnp.ndarray]:
        """Stacked pre-batched data for `client_ids`: the `batch_indices`
        tensor gathered on the host. Leaves: (C, epochs*nb, B, ...)."""
        idx = self.batch_indices(rng, client_ids, epochs)
        T, B = idx.shape[1], idx.shape[2]
        x0 = self.client_data[0][0]
        imgs = np.empty((len(client_ids), T, B) + x0.shape[1:], x0.dtype)
        labs = np.empty((len(client_ids), T, B), np.int32)
        for i, c in enumerate(client_ids):
            x, y = self.client_data[c]
            imgs[i] = x[idx[i]]
            labs[i] = y[idx[i]]
        return {"image": jnp.asarray(imgs), "label": jnp.asarray(labs)}

    def stacked_dataset(self):
        """The whole federation's shards as ONE device-resident pair
        (images (C, n_max, ...), labels (C, n_max)), built once per run
        and cached — the fused executor's in-scan gather source. Shards
        shorter than n_max are zero-padded; batch indices never
        reference the pad (they are permutations of each client's own
        shard length)."""
        cached = getattr(self, "_stacked_dataset", None)
        if cached is None:
            n_max = max(len(x) for x, _ in self.client_data)
            x0 = self.client_data[0][0]
            imgs = np.zeros((len(self.client_data), n_max) + x0.shape[1:],
                            x0.dtype)
            labs = np.zeros((len(self.client_data), n_max), np.int32)
            for c, (x, y) in enumerate(self.client_data):
                imgs[c, :len(x)] = x
                labs[c, :len(y)] = y
            cached = (jnp.asarray(imgs), jnp.asarray(labs))
            self._stacked_dataset = cached
        return cached

    # -- compiled-program wrappers ------------------------------------------
    def train(self, stacked_params, data, *, stacked_loss_fn=None,
              extra=None):
        """One event's stacked training dispatch. DONATES
        `stacked_params`: the driver passes a base stack it owns
        exclusively (see `train_clients_donated`) so the trained
        parameters reuse those buffers instead of doubling the
        federation's peak memory."""
        return train_clients_donated(
            stacked_params, data,
            stacked_loss_fn=stacked_loss_fn or self.stacked_loss_fn,
            lr=self.fl.lr, momentum=self.fl.momentum, extra=extra)

    def local_accs(self, stacked_params, client_ids) -> np.ndarray:
        """Post-training local-shard accuracy per client — the paper's
        "training accuracy" protocol, one vmapped dispatch."""
        idx = jnp.asarray(np.asarray(client_ids))
        preds = predict_clients(stacked_params, self.eval_x[idx],
                                stacked_apply_fn=self.stacked_apply_fn)
        return np.asarray(jnp.mean(
            (preds == self.eval_y[idx]).astype(jnp.float32), axis=1))

    def cfl_round(self, model, order, data, alpha, *, attack="none",
                  attack_scale=1.0, attack_flags=None, attack_keys=None,
                  defense="none", clip_tau=10.0, codec=None,
                  codec_keys=None, fault_alive=None, fault_qok=None):
        idx = jnp.asarray(np.asarray(order))
        return cfl_round_scan(model, data, self.eval_x[idx], self.eval_y[idx],
                              alpha, loss_fn=self.loss_fn,
                              apply_fn=self.apply_fn, lr=self.fl.lr,
                              momentum=self.fl.momentum, attack=attack,
                              attack_scale=attack_scale,
                              attack_flags=attack_flags,
                              attack_keys=attack_keys, defense=defense,
                              clip_tau=clip_tau, codec=codec,
                              codec_keys=codec_keys,
                              fault_alive=fault_alive, fault_qok=fault_qok)
