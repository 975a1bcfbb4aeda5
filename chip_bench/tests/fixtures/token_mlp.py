"""A token model family, reference side only, for the tests of the plain
reference and for its wide check on the chip (`wide_token_check.py`).

Int32 ids in, at every position the id that follows out: an embedding,
`layers` residual ReLU blocks (hidden -> ffn -> hidden) applied to each
position, and a head back to the vocabulary. Every width comes from the
configuration's blocks, so one file is a family of a few thousand
parameters on the CPU and of ~500 M on the chip:

* `model`: {"vocab", "hidden", "ffn", "layers"};
* `data`: {"vocab", "seq_len", "n_train", "n_test"}: sequences of ids
  drawn uniformly, each labelled with `(7 * id + 3) % vocab`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def render(data_spec, seed):
    rng = np.random.default_rng(seed)
    V, L = data_spec["vocab"], data_spec["seq_len"]

    def part(n):
        x = rng.integers(0, V, size=(n, L), dtype=np.int32)
        return x, ((7 * x.astype(np.int64) + 3) % V).astype(np.int32)

    return {"train": part(data_spec["n_train"]),
            "test": part(data_spec["n_test"]), "name": "token_map"}


def init(seed, model):
    """Weights from `jax.random.PRNGKey(seed)`, made on the device in one
    jitted call: N(0, 1) embedding rows, N(0, 1)/sqrt(fan_in) kernels,
    the blocks' down projections also over sqrt(layers) so that the
    residual sum stays near unit scale, zero biases."""
    V, H, F, N = model["vocab"], model["hidden"], model["ffn"], \
        model["layers"]

    def make(key):
        ks = jax.random.split(key, 2 * N + 2)
        normal = lambda k, shape, fan: (  # noqa: E731
            jax.random.normal(k, shape) / math.sqrt(fan))
        p = {"embed": {"table": normal(ks[0], (V, H), 1)},
             "head": {"kernel": normal(ks[1], (H, V), H),
                      "bias": jnp.zeros((V,))}}
        for i in range(N):
            p[f"block{i:02d}"] = {
                "up": normal(ks[2 + 2 * i], (H, F), H),
                "up_bias": jnp.zeros((F,)),
                "down": normal(ks[3 + 2 * i], (F, H), F * N),
                "down_bias": jnp.zeros((H,))}
        return p

    return jax.jit(make)(jax.random.PRNGKey(seed))


def forward(p, x, prec):
    """x (B, L) int32 -> logits (B, L, vocab)."""
    h = jnp.take(p["embed"]["table"], x, axis=0)
    for name in sorted(k for k in p if k.startswith("block")):
        q = p[name]
        u = jax.nn.relu(jnp.dot(h, q["up"], precision=prec) + q["up_bias"])
        h = h + jnp.dot(u, q["down"], precision=prec) + q["down_bias"]
    return jnp.dot(h, p["head"]["kernel"], precision=prec) + p["head"]["bias"]


def loss_fn(p, x, y, prec):
    """Mean cross-entropy over every position of the batch."""
    logp = jax.nn.log_softmax(forward(p, x, prec))
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))


def accuracy(p, x, y, prec):
    """Share of the batch's positions whose next id the argmax predicts."""
    return jnp.mean((jnp.argmax(forward(p, x, prec), -1) == y)
                    .astype(jnp.float32))


def reference_model():
    return init, loss_fn, accuracy
