"""Plain reference of the paper CNN (arXiv:2512.10987 §2.4, Fig. 7).

Straight `jax.numpy` / `lax`, each convolution written out tap by tap,
at the matmul precision the caller passes: three SAME 3x3 convolutions
with ReLU, a 2x2 max-pool after the first two, and a dense head.
Initial weights from `jax.random.PRNGKey(seed)`: four split keys, the
conv kernels N(0, 1)/sqrt(fan_in) in HWIO, the dense kernel
N(0, 1)/sqrt(fan_in), zero biases — the program's initial weights for
the same seed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def init(seed, model):
    """Initial weights for a configuration's `model` block."""
    image, classes = model["image"], model["classes"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    p, cin = {}, image[2]
    for i, cout in enumerate(model["filters"]):
        k = jax.random.normal(ks[i], (3, 3, cin, cout)) / math.sqrt(9 * cin)
        p[f"conv{i + 1}"] = {"kernel": k, "bias": jnp.zeros((cout,))}
        cin = cout
    feat = (image[0] // 4) * (image[1] // 4) * cin
    p["head"] = {"kernel": (jax.random.normal(ks[3], (feat, classes))
                            / math.sqrt(feat)),
                 "bias": jnp.zeros((classes,))}
    return p


def conv_same(h, k, prec):
    """Stride-1 SAME convolution, NHWC by HWIO, written out as the sum over
    the kernel's taps of a shifted input times that tap's (cin, cout)
    matrix. Under `vmap` over clients each tap is one batched matmul,
    where a per-client `lax.conv` would become a grouped convolution."""
    kh, kw = k.shape[0], k.shape[1]
    H, W = h.shape[1], h.shape[2]
    hp = jnp.pad(h, ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2), (0, 0)))
    out = 0.0
    for i in range(kh):
        for j in range(kw):
            out = out + jnp.einsum("bhwc,co->bhwo",
                                   hp[:, i:i + H, j:j + W, :], k[i, j],
                                   precision=prec)
    return out


def forward(p, x, prec):
    """x (B, 28, 28, 1) -> logits (B, 10)."""
    def conv(q, h):
        return jax.nn.relu(conv_same(h, q["kernel"], prec) + q["bias"])

    def pool(h):
        return lax.reduce_window(h, np.array(-np.inf, h.dtype), lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    h = pool(conv(p["conv1"], x))
    h = pool(conv(p["conv2"], h))
    h = conv(p["conv3"], h)
    h = h.reshape(h.shape[0], -1)
    return jnp.dot(h, p["head"]["kernel"], precision=prec) + p["head"]["bias"]


def loss_fn(p, x, y, prec):
    """Mean cross-entropy of the batch."""
    logp = jax.nn.log_softmax(forward(p, x, prec))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def accuracy(p, x, y, prec):
    """Share of the batch's labels that the model's argmax predicts."""
    return jnp.mean((jnp.argmax(forward(p, x, prec), -1) == y)
                    .astype(jnp.float32))
