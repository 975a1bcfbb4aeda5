"""The paper's CNN (§2.4): three conv layers (16, 12, 10 filters, 3x3),
two max-pool layers, ReLU hidden activations — for 28x28 grayscale inputs
(MNIST / Fashion-MNIST), 10 classes.

Layout (faithful to Figure 7):
  conv1 16@3x3 -> ReLU -> maxpool 2x2
  conv2 12@3x3 -> ReLU -> maxpool 2x2
  conv3 10@3x3 -> ReLU -> flatten -> dense 10 (logits)
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

from repro.models.layers import init_dense, dense


def _init_conv(key, kh, kw, cin, cout, dtype=jnp.float32):
    fan_in = kh * kw * cin
    return {"kernel": (jax.random.normal(key, (kh, kw, cin, cout))
                       / math.sqrt(fan_in)).astype(dtype),
            "bias": jnp.zeros((cout,), dtype)}


def _conv(params, x, stride=1):
    y = jax.lax.conv_general_dilated(
        x, params["kernel"].astype(x.dtype),
        window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + params["bias"].astype(x.dtype)


def _maxpool(x, window=2):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max,
        (1, window, window, 1), (1, window, window, 1), "VALID")


def init_cnn(key, num_classes=10, in_channels=1, image_size=28,
             dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {
        "conv1": _init_conv(ks[0], 3, 3, in_channels, 16, dtype),
        "conv2": _init_conv(ks[1], 3, 3, 16, 12, dtype),
        "conv3": _init_conv(ks[2], 3, 3, 12, 10, dtype),
    }
    feat = image_size // 4              # two 2x2 pools
    p["head"] = init_dense(ks[3], feat * feat * 10, num_classes,
                           use_bias=True, dtype=dtype)
    return p


def cnn_apply(params, images):
    """images: (B, 28, 28, 1) float -> logits (B, 10)."""
    x = images
    x = jax.nn.relu(_conv(params["conv1"], x))
    x = _maxpool(x)
    x = jax.nn.relu(_conv(params["conv2"], x))
    x = _maxpool(x)
    x = jax.nn.relu(_conv(params["conv3"], x))
    x = x.reshape(x.shape[0], -1)
    return dense(params["head"], x).astype(jnp.float32)


def cnn_loss(params, batch):
    """batch: {'image': (B,28,28,1), 'label': (B,)} -> (loss, accuracy)."""
    logits = cnn_apply(params, batch["image"])
    labels = batch["label"]
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
    acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    return nll, acc


# ---------------------------------------------------------------------------
# stacked-federation forward path (vectorized and fused engines)
# ---------------------------------------------------------------------------
# Every parameter leaf carries a leading client axis C and every client has
# its OWN weights. Two lowerings compute the same per-client forward, and
# `stacked_lowering` picks one from the backend and C at trace time:
#
# * grouped (TPU, stacks of up to `_GROUPED_MAX_CLIENTS`): the client axis
#   folds into the channel (lane) axis, (C, B, H, W, ch) -> (B, H, W, C*ch).
#   Each layer is ONE convolution with feature_group_count=C (its kernel
#   gradient a batch_group_count=C convolution, what `jax.vmap(cnn_apply)`
#   lowers to) and each 2x2 pool a `reduce_window` on the same layout, so
#   nothing transposes between layers.
# * patch (CPU, and larger stacks on the TPU): weight-independent patch
#   extraction with the client axis in the batch, then one batched GEMM
#   per layer. On XLA:CPU a client's gradient under it does not depend on
#   how many clients share its stack; under the grouped lowering it does
#   (by ~1e-5), and the mesh path's shards then drift from the
#   single-device run. On the TPU its 9-16-wide minor dimensions fill 16
#   of 128 lanes, which costs little only once C*B is large.
#
# A mesh shard trains a slice of the federation's stack. The fused
# executor traces it inside `lowering_scope`, with the lowering the
# single-device run picks for the whole stack, so that both lower alike.

# Above ~256 MB the materialized patch tensor (C, B*H*W, kh*kw*cin) stops
# paying for its better GEMM shape: it blows past every cache level and,
# at federation scale (C*B in the tens of thousands), past host RAM.
_PATCH_BYTES_LIMIT = 256 * 1024 * 1024


# The largest stack the TPU trains grouped. On a v5e, against the patch
# GEMM, a grouped training step at batch 16 was 2.6x faster at C=16, as
# fast at C=32, and 30%, 54% and 7% slower at C=48, 64 and 128; at batch
# 32 it was faster at every C from 10 (15x) to 128 (1.5x). Above 32 the
# compiler's layout for a grouped convolution turns on the batch (the
# batch or a client's channels in the lanes), so C alone cannot say.
_GROUPED_MAX_CLIENTS = 32


def stacked_lowering(num_clients, backend=None):
    """The lowering of a `num_clients` stack on `backend` (default: JAX's
    default backend): "grouped" on the TPU up to `_GROUPED_MAX_CLIENTS`
    clients, "patch" otherwise."""
    backend = backend or jax.default_backend()
    if backend == "tpu" and num_clients <= _GROUPED_MAX_CLIENTS:
        return "grouped"
    return "patch"


# (lowering, calls) of each open `lowering_scope`, innermost last
_scopes = []


@contextlib.contextmanager
def lowering_scope(lowering):
    """Trace every `cnn_apply_stacked` call inside under `lowering`,
    whatever the size of the stack it sees. Yields a list that gets the
    lowering once for each such call. The scope acts while a function
    is traced: a jitted function traced before keeps its lowering."""
    calls = []
    _scopes.append((lowering, calls))
    try:
        yield calls
    finally:
        _scopes.pop()


def _conv_grouped(params, x):
    """x: (B, H, W, C*cin), client-major channels; params['kernel']:
    (C, kh, kw, cin, cout) -> (B, H, W, C*cout), client-major."""
    k = params["kernel"].astype(x.dtype)
    C, kh, kw, cin, cout = k.shape
    k = jnp.moveaxis(k, 0, 3).reshape(kh, kw, cin, C * cout)
    y = jax.lax.conv_general_dilated(
        x, k, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=C)
    return y + params["bias"].astype(x.dtype).reshape(C * cout)


def cnn_apply_grouped(params, images):
    """The grouped lowering of `cnn_apply_stacked`."""
    C, B, H, W, cin = images.shape
    x = jnp.moveaxis(images, 0, 3).reshape(B, H, W, C * cin)
    x = jax.nn.relu(_conv_grouped(params["conv1"], x))
    x = _maxpool(x)
    x = jax.nn.relu(_conv_grouped(params["conv2"], x))
    x = _maxpool(x)
    x = jax.nn.relu(_conv_grouped(params["conv3"], x))
    _, h, w, cc = x.shape
    # the dense head per client: (B, h, w, C*ch) -> (C, B, h*w*ch), the
    # (h, w, ch) flattening order of `cnn_apply`
    x = jnp.moveaxis(x.reshape(B, h, w, C, cc // C), 3, 0)
    return _dense_stacked(params["head"], x.reshape(C, B, -1))


def _conv_patch(params, x):
    """x: (C, B, H, W, cin); params['kernel']: (C, kh, kw, cin, cout).

    Patches come from kh*kw shifted slices (pure memory movement — NOT
    `conv_general_dilated_patches`, whose identity-kernel conv lowering
    costs kh*kw*cin more FLOPs than the convolution itself). Small
    problems materialize the full patch tensor and contract it with one
    batched GEMM per layer; above `_PATCH_BYTES_LIMIT` the kh*kw shifted
    contributions are accumulated as separate batched GEMMs instead, so
    peak memory stays O(C*B*H*W*cin) no matter the federation size.
    Assumes stride 1, SAME padding, odd kernel — the paper CNN's case."""
    C, B, H, W, cin = x.shape
    k = params["kernel"].astype(x.dtype)
    kh, kw, cout = k.shape[1], k.shape[2], k.shape[4]
    xp = jnp.pad(x, ((0, 0), (0, 0), (kh // 2, kh // 2),
                     (kw // 2, kw // 2), (0, 0)))
    patch_bytes = x.size * kh * kw * x.dtype.itemsize
    if patch_bytes <= _PATCH_BYTES_LIMIT:
        pat = jnp.stack([xp[:, :, i:i + H, j:j + W, :]
                         for i in range(kh) for j in range(kw)], axis=4)
        pat = pat.reshape(C, B * H * W, kh * kw * cin)
        kmat = k.reshape(C, kh * kw * cin, cout)
        out = jnp.einsum("cbp,cpo->cbo", pat, kmat)
    else:
        out = None
        for i in range(kh):
            for j in range(kw):
                s = xp[:, :, i:i + H, j:j + W, :].reshape(C, B * H * W, cin)
                o = jax.lax.dot_general(
                    s, k[:, i, j], (((2,), (1,)), ((0,), (0,))))
                out = o if out is None else out + o
    return (out.reshape(C, B, H, W, cout)
            + params["bias"].astype(x.dtype)[:, None, None, None, :])


def _maxpool_patch(x, window=2):
    """Non-overlapping window max via reshape (same result as a VALID
    `reduce_window`, whose select-and-scatter backward is ~6x slower on
    CPU)."""
    C, B, H, W, ch = x.shape
    return jnp.max(
        x.reshape(C, B, H // window, window, W // window, window, ch),
        axis=(3, 5))


def cnn_apply_patch(params, images):
    """The patch lowering of `cnn_apply_stacked`."""
    x = images
    x = jax.nn.relu(_conv_patch(params["conv1"], x))
    x = _maxpool_patch(x)
    x = jax.nn.relu(_conv_patch(params["conv2"], x))
    x = _maxpool_patch(x)
    x = jax.nn.relu(_conv_patch(params["conv3"], x))
    return _dense_stacked(params["head"], x.reshape(x.shape[0], x.shape[1],
                                                    -1))


def _dense_stacked(head, x):
    """Per-client dense head: x (C, B, F) -> float32 logits (C, B, K)."""
    y = jnp.einsum("cbf,cfk->cbk", x, head["kernel"].astype(x.dtype))
    return (y + head["bias"].astype(x.dtype)[:, None, :]).astype(jnp.float32)


def cnn_apply_stacked(params, images):
    """Per-client forward: (C, B, 28, 28, 1) -> logits (C, B, 10) under
    per-client parameters (leading C axis on every leaf). Matches
    `jax.vmap(cnn_apply)` up to float reassociation. The lowering runs
    under a named scope of its own (`conv_grouped` / `conv_patch`), so a
    profile's op names say which one ran. Inside `lowering_scope` its
    lowering is the scope's, else `stacked_lowering`'s for this stack."""
    if _scopes:
        lowering, calls = _scopes[-1]
        calls.append(lowering)
    else:
        lowering = stacked_lowering(images.shape[0])
    apply = cnn_apply_grouped if lowering == "grouped" else cnn_apply_patch
    with jax.named_scope("conv_" + lowering):
        return apply(params, images)


def cnn_loss_stacked(params, batch):
    """Per-client loss/accuracy: batch leaves (C, B, ...) -> ((C,), (C,)).
    Summing the returned losses and differentiating yields exactly the
    per-client gradients (clients are independent — no cross terms)."""
    logits = cnn_apply_stacked(params, batch["image"])
    labels = batch["label"]
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean(
        axis=(1, 2))
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32),
                   axis=1)
    return nll, acc
