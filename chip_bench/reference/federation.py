"""Plain reference of the federations the cells run, for any model.

Independent of the program: straight `jax.numpy` / `lax`, one model per
client (`vmap` over clients), float32 at HIGHEST matmul precision, no
kernels, no stacking tricks. The model comes from the configuration's
family (`families/<arch>.py`, `reference_model()`) as three functions:
`init(seed, model_spec)`, the program's initial weights for the seed;
`loss(params, x, y, precision)`, the mean loss of a batch; and
`accuracy(params, x, y, precision)`, the share of a batch's label
entries predicted right. Everything else is the federation as the
cell's configuration and traffic files state it:

* data: the set the family rendered, split IID — a seeded permutation
  of the train indices cut into C contiguous parts (`np.array_split`),
  each part sorted;
* per round, from `np.random.default_rng(seed)`: the participants (AFL:
  `rng.choice` without replacement, sorted; CFL: a permutation that is
  the visit order; HFL: every client), then per participant and epoch
  one permutation of its shard, cut to whole batches;
* local SGD with heavy-ball momentum (fresh per round) on the family's
  loss of each batch;
* Byzantine clients (AFL): `attack_fraction` of the federation drawn
  from `np.random.default_rng([seed, 0x5EEDA77C])`; sign-flip uploads
  `base - scale * (local - base)`;
* aggregation: sample-weighted mean (AFL), coordinate-wise median
  (defense "median"), two-tier HFL (groups of contiguous clients, the
  global tier weighted by group sizes, dissemination every
  `hfl_global_every` rounds and at the last), the CFL continual merge
  `(1 - alpha) model + alpha local` after each visit;
* per round: the mean over participants of the mean loss of the last
  epoch's batches, the mean accuracy of each trained local model on the
  first min(512, smallest shard) samples of its own shard, and the
  accuracy of the round model on the whole test set.

`dtype=jnp.bfloat16` computes everything (floating data, weights,
momentum, aggregation) in bfloat16 at the default precision: the
lower-precision control. Only floating arrays take `dtype`: integer
inputs (token ids, labels) and integer leaves reach the family's `loss`
and `accuracy` as they were rendered, in the reference and the control
alike, since bfloat16 would round every id above 256.

A round's AFL or HFL participants train by one of two paths, chosen
from bytes (`stacked`): side by side (`train_clients`, a `vmap` over
k copies of their bases) where k x (one client's bytes, from the
family's `init` by `jax.eval_shape`) x 4 (base, trained model, momentum,
gradient) fits in half of the device's `memory_stats()["bytes_limit"]`,
or where the backend reports no limit (the CPU); else streamed
(`stream_client`): one participant at a time from the round's model, in
participant order, its local accuracy taken as it finishes and its
upload (sign-flipped if it attacks) folded into a running weighted sum
in `dtype` that the total weight divides at the round's end, so that
the device holds the round's model, the sum and one client's training
state. The streamed path has no HFL (G group models and G sums) and no
median defense (it needs the whole stack): both raise
`NotImplementedError`. CFL visits one client at a time on either path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ATTACK_SALT = 0x5EEDA77C


def local_sgd(p, xb, yb, lr, mom, prec, loss):
    """One client's local training over batches (T, B, ...): returns the
    trained model and the loss of each batch before its step."""
    def step(carry, batch):
        q, mu = carry
        value, g = jax.value_and_grad(loss)(q, batch[0], batch[1], prec)
        mu = jax.tree.map(lambda m, gi: mom * m + gi, mu, g)
        q = jax.tree.map(lambda a, m: a - lr * m, q, mu)
        return (q, mu), value

    mu0 = jax.tree.map(jnp.zeros_like, p)
    (p, _), losses = lax.scan(step, (p, mu0), (xb, yb))
    return p, losses


# -- jitted round pieces ----------------------------------------------------

@partial(jax.jit, static_argnames=("loss", "prec", "block"))
def train_clients(bases, x_dev, y_dev, gidx, lr, mom, *, loss, prec, block):
    """Every participant from its own base: bases (k, ...) stacked,
    gidx (k, T, B) indices into the device train set. Runs `block`
    clients at a time so the reference fits beside nothing else."""
    k = gidx.shape[0]
    split = lambda a: a.reshape((k // block, block) + a.shape[1:])  # noqa

    def one_block(args):
        b, gi = args
        return jax.vmap(lambda q, g: local_sgd(q, x_dev[g], y_dev[g], lr,
                                               mom, prec, loss))(b, gi)

    params, losses = lax.map(one_block,
                             (jax.tree.map(split, bases), split(gidx)))
    merge = lambda a: a.reshape((k,) + a.shape[2:])  # noqa
    return jax.tree.map(merge, params), merge(losses)


@partial(jax.jit, static_argnames=("accuracy", "prec"))
def local_accuracy(params, x_dev, y_dev, eidx, *, accuracy, prec):
    """Each trained local model on its own eval shard: eidx (k, n)."""
    return jax.vmap(lambda q, e: accuracy(q, x_dev[e], y_dev[e], prec))(
        params, eidx)


@partial(jax.jit, static_argnames=("accuracy", "prec", "block"))
def test_accuracy(p, x, y, *, accuracy, prec, block):
    """Share of the test labels predicted right, `block` samples at a
    time: each block's count of hits is its accuracy times its number of
    label entries, rounded to the whole number it is."""
    def hits(xb, yb):
        return jnp.round(accuracy(p, xb, yb, prec) * yb.size).astype(
            jnp.int32)

    n = x.shape[0] // block * block
    xs = x[:n].reshape((-1, block) + x.shape[1:])
    ys = y[:n].reshape((-1, block) + y.shape[1:])
    total = jnp.sum(lax.map(lambda a: hits(*a), (xs, ys)))
    if n < x.shape[0]:
        total += hits(x[n:], y[n:])
    return total / y.size


@partial(jax.jit, static_argnames=("loss", "accuracy", "prec"),
         donate_argnums=(1,))
def stream_client(base, total, x_dev, y_dev, gi, ei, w, flip, scale, lr, mom,
                  *, loss, accuracy, prec):
    """One participant of a streamed round, trained from the round's
    model `base` over gi (T, B): its local accuracy on its eval rows ei,
    and `total` + w x its upload (`base - scale * (local - base)` where
    `flip`). Returns (total, the loss of each batch, the accuracy)."""
    local, losses = local_sgd(base, x_dev[gi], y_dev[gi], lr, mom, prec, loss)
    acc = accuracy(local, x_dev[ei], y_dev[ei], prec)
    total = jax.tree.map(
        lambda t, l, b: t + w * jnp.where(flip, b - scale * (l - b), l),
        total, local, base)
    return total, losses, acc


def weighted_mean(stack, w):
    w = w / jnp.sum(w)
    return jax.tree.map(lambda a: jnp.tensordot(w, a, axes=1), stack)


def coordinate_median(stack):
    return jax.tree.map(lambda a: jnp.median(a, axis=0).astype(a.dtype),
                        stack)


@partial(jax.jit, static_argnames=("loss", "accuracy", "prec"))
def cfl_round(model, x_dev, y_dev, gidx, eidx, lr, mom, alpha, *, loss,
              accuracy, prec):
    """One continual pass: visits in order, each trains from the carried
    model and merges into it."""
    def visit(m, args):
        gi, ei = args
        local, losses = local_sgd(m, x_dev[gi], y_dev[gi], lr, mom, prec,
                                  loss)
        acc = accuracy(local, x_dev[ei], y_dev[ei], prec)
        m = jax.tree.map(lambda a, b: (1 - alpha) * a + alpha * b, m, local)
        return m, (losses, acc)

    return lax.scan(visit, model, (gidx, eidx))


# -- schedule ----------------------------------------------------------------

def partition(n_train, C, seed):
    idx = np.random.default_rng(seed).permutation(n_train)
    return [np.sort(p) for p in np.array_split(idx, C)]


def attackers(C, fraction, seed):
    if fraction <= 0 or C <= 1:
        return np.zeros(C, bool)
    k = min(C - 1, max(1, int(round(fraction * C))))
    ids = np.random.default_rng([seed, ATTACK_SALT]).choice(C, size=k,
                                                            replace=False)
    mask = np.zeros(C, bool)
    mask[ids] = True
    return mask


def round_size(fed):
    """Participants a round trains: a share of the clients (AFL) or every
    client (HFL, and CFL one after another)."""
    C = fed["num_clients"]
    if fed["strategy"] == "afl":
        return max(1, int(round(fed.get("participation", 0.5) * C)))
    return C


def client_bytes(init_fn, seed, model_spec):
    """Bytes of one client's parameters as the family's `init` makes
    them, from their shapes alone (`jax.eval_shape`: nothing allocated)."""
    shapes = jax.eval_shape(lambda: init_fn(seed, model_spec))
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))


def stacked(spec, nbytes, bytes_limit):
    """Whether a round's participants train side by side: k of them, each
    `nbytes` x 4 (base, trained model, momentum, gradient), in half of the
    device's `bytes_limit`; with no limit known (the CPU), they do."""
    return (bytes_limit is None
            or 4 * round_size(spec["federation"]) * nbytes <= bytes_limit / 2)


def floating(a):
    return jnp.issubdtype(a.dtype, jnp.floating)


def cast(tree, dtype):
    """Floating leaves in `dtype`; integer leaves as they are."""
    return jax.tree.map(lambda a: a.astype(dtype) if floating(a) else a, tree)


def schedule(fed, parts, seed):
    """Per round: (participants in training order, (k, T, B) global train
    indices of their batches)."""
    rng = np.random.default_rng(seed)
    C, B, E = fed["num_clients"], fed["local_batch_size"], \
        fed.get("local_epochs", 1)
    nb = min(len(p) for p in parts) // B
    out = []
    for _ in range(fed["rounds"]):
        if fed["strategy"] == "afl":
            pids = np.sort(rng.choice(C, size=round_size(fed),
                                      replace=False))
        elif fed["strategy"] == "cfl":
            pids = rng.permutation(C)
        else:
            pids = np.arange(C)
        g = np.empty((len(pids), E * nb, B), np.int32)
        for i, c in enumerate(pids):
            for e in range(E):
                sel = rng.permutation(len(parts[c]))[: nb * B]
                g[i, e * nb:(e + 1) * nb] = parts[c][sel].reshape(nb, B)
        out.append((np.asarray(pids), g))
    return out, nb


# -- the federation ----------------------------------------------------------

# what the reference implements; a cell that sets anything else needs
# reference code of its own before it can be compared
SUPPORTED = {"strategy": ("hfl", "afl", "cfl"), "defense": ("none", "median"),
             "attack": ("none", "sign_flip"), "afl_mode": ("fedavg",),
             "codec": ("none",), "fault_profile": ("none",)}
DEFAULTS = {"defense": "none", "attack": "none", "afl_mode": "fedavg",
            "codec": "none", "fault_profile": "none"}


def run(spec, dataset, seed, model, *, dtype=jnp.float32, block=128,
        stream=False):
    """Run the cell's federation with `model`, the family's
    `(init, loss, accuracy)`. Returns numpy results: round_loss,
    round_train_acc, round_test_acc (R,), init and final global params
    ({"<layer>/<leaf>": array, ...}). `stream=True` takes the streamed
    path whatever the bytes (for tests)."""
    fed = spec["federation"]
    init_fn, loss, accuracy = model
    for key, allowed in SUPPORTED.items():
        val = fed.get(key, DEFAULTS.get(key))
        if val not in allowed or (fed["strategy"] == "cfl"
                                  and fed.get("attack", "none") != "none"):
            raise NotImplementedError(
                f"the reference has no {key}={val!r} for strategy "
                f"{fed['strategy']!r}")
    strategy = fed["strategy"]
    defense = fed.get("defense", "none")
    nbytes = client_bytes(init_fn, seed, spec["model"])
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    stream = strategy != "cfl" and (stream or not stacked(spec, nbytes,
                                                          limit))
    if stream and (strategy == "hfl" or defense == "median"):
        what = "strategy 'hfl'" if strategy == "hfl" else \
            "defense 'median'"
        raise NotImplementedError(
            f"the streamed reference (clients of {nbytes:,} bytes, "
            f"{round_size(fed)} a round, device limit {limit}) has no "
            f"{what}")
    prec = (lax.Precision.HIGHEST if dtype == jnp.float32
            else lax.Precision.DEFAULT)
    xtr, ytr = dataset["train"]
    xte, yte = dataset["test"]
    C = fed["num_clients"]
    parts = partition(len(ytr), C, seed)
    n_eval = min(512, min(len(p) for p in parts))
    eval_rows = np.stack([p[:n_eval] for p in parts])
    rounds, nb = schedule(fed, parts, seed)
    x_dev = jnp.asarray(xtr, dtype if floating(xtr) else None)
    y_dev = jnp.asarray(ytr)
    x_test = jnp.asarray(xte, dtype if floating(xte) else None)
    y_test = jnp.asarray(yte)
    init = init_fn(seed, spec["model"])
    glob = cast(init, dtype)
    init = flat(init)
    lr = jnp.asarray(fed["lr"], dtype)
    mom = jnp.asarray(fed["momentum"], dtype)
    weights = jnp.asarray([len(p) for p in parts], dtype)
    mask = attackers(C, fed.get("attack_fraction", 0.25), seed) \
        if fed.get("attack", "none") != "none" else np.zeros(C, bool)
    scale = jnp.asarray(fed.get("attack_scale", 1.0), dtype)
    G = fed.get("num_groups", 2)
    if strategy == "hfl":
        groups = jax.tree.map(lambda a: jnp.stack([a] * G), glob)
    losses, train_accs, test_accs = [], [], []
    for ev, (pids, gidx) in enumerate(rounds):
        k = len(pids)
        gi = jnp.asarray(gidx)
        ei = jnp.asarray(eval_rows[pids])
        w = weights[jnp.asarray(pids)]
        if strategy == "cfl":
            glob, (ls, accs) = cfl_round(
                glob, x_dev, y_dev, gi, ei, lr, mom,
                jnp.asarray(fed.get("merge_alpha", 0.5), dtype), loss=loss,
                accuracy=accuracy, prec=prec)
        elif stream:
            total = jax.tree.map(jnp.zeros_like, glob)
            ls, accs = [], []
            for i, c in enumerate(pids):
                total, li, ai = stream_client(
                    glob, total, x_dev, y_dev, gi[i], ei[i], w[i],
                    bool(mask[c]), scale, lr, mom, loss=loss,
                    accuracy=accuracy, prec=prec)
                ls.append(li)
                accs.append(ai)
            total_w = jnp.sum(w)
            glob = jax.tree.map(lambda t: t / total_w, total)
            del total
            ls, accs = jnp.stack(ls), jnp.stack(accs)
        else:
            if strategy == "hfl":
                per = C // G
                bases = jax.tree.map(lambda a: jnp.repeat(a, per, axis=0),
                                     groups)
            else:
                bases = jax.tree.map(lambda a: jnp.stack([a] * k), glob)
            blk = block if k % block == 0 else k
            params, ls = train_clients(bases, x_dev, y_dev, gi, lr, mom,
                                       loss=loss, prec=prec, block=blk)
            accs = local_accuracy(params, x_dev, y_dev, ei,
                                  accuracy=accuracy, prec=prec)
            if strategy == "hfl":
                tier1 = [weighted_mean(
                    jax.tree.map(lambda a: a[g * per:(g + 1) * per], params),
                    w[g * per:(g + 1) * per]) for g in range(G)]
                groups = jax.tree.map(lambda *a: jnp.stack(a), *tier1)
                gw = jnp.stack([jnp.sum(w[g * per:(g + 1) * per])
                                for g in range(G)])
                if ((ev + 1) % fed.get("hfl_global_every", 2) == 0
                        or ev == fed["rounds"] - 1):
                    glob = weighted_mean(groups, gw)
                    groups = jax.tree.map(lambda a: jnp.stack([a] * G), glob)
            else:
                flags = jnp.asarray(mask[pids])
                if fed.get("attack", "none") == "sign_flip":
                    params = jax.tree.map(
                        lambda l, b: jnp.where(
                            flags.reshape((k,) + (1,) * (l.ndim - 1)),
                            b - scale * (l - b), l), params, bases)
                if defense == "median":
                    glob = coordinate_median(params)
                else:
                    glob = weighted_mean(params, w)
        losses.append(float(jnp.mean(ls[:, -nb:].astype(jnp.float32))))
        train_accs.append(float(jnp.mean(accs)))
        test_accs.append(float(test_accuracy(
            glob, x_test, y_test, accuracy=accuracy, prec=prec,
            block=min(2000, len(yte)))))
    return {"round_loss": np.asarray(losses),
            "round_train_acc": np.asarray(train_accs),
            "round_test_acc": np.asarray(test_accs),
            "init": init, "final": flat(glob)}


def flat(tree):
    """{"<layer>/<leaf>": float32 numpy array, ...}: leaf paths joined by "/"."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        out[name] = np.asarray(jax.device_get(leaf), np.float32)
    return out
