"""The paper CNN family: the model of arXiv:2512.10987 §2.4 (Fig. 7) on
MNIST- and Fashion-MNIST-shaped image sets rendered from the seed.

What the harness asks of a model family (`cells.Cell.family`):

* `render(data_spec, seed)` — the configuration's data set,
  `{"train": (x, y), "test": (x, y), "name"}`, keyed by
  `data_spec["generator"]`;
* `forward_flops(model_spec)` — FLOPs of one sample's forward pass;
* `reference_model()` — `(init, loss, accuracy)` of the plain reference
  (`reference/paper_cnn.py`) that `reference/federation.py` trains;
* `program_kwargs(config)` — the program's `FLConfig` keyword arguments
  that select this model: none, the paper CNN is the program's default.

The image sets (28x28x1 float32 in [0, 1], int32 labels in [0, 10))
have the statistical character of the program's `repro.data.synthetic`
generators: one smooth prototype per class for `mnist_like`; two
prototypes per class mixed with a shared texture bank, stronger
contrast jitter and class overlap for `fashion_like`. The prototypes
are built the same way; rendering is vectorised over the whole set (one
gather for the shifts, one draw for the noise), so 70,000 images take a
fraction of a second rather than a Python loop per image. The images
differ from the program's own generator for the same seed; both the
program and the reference are fed these.
"""
from __future__ import annotations

import numpy as np

from chip_bench.reference import paper_cnn as reference

IMAGE = 28
CLASSES = 10


def _smooth_field(rng, size=IMAGE, low=7):
    coarse = rng.normal(size=(low, low))
    idx = np.linspace(0, low - 1, size)
    x0 = np.floor(idx).astype(int)
    x1 = np.minimum(x0 + 1, low - 1)
    wx = idx - x0
    rows = (coarse[x0][:, x0] * (1 - wx)[None, :]
            + coarse[x0][:, x1] * wx[None, :])
    rows2 = (coarse[x1][:, x0] * (1 - wx)[None, :]
             + coarse[x1][:, x1] * wx[None, :])
    img = rows * (1 - wx)[:, None] + rows2 * wx[:, None]
    return (img - img.min()) / (np.ptp(img) + 1e-9)


def _prototypes(seed, per_class, bank_size=0):
    rng = np.random.default_rng(seed)
    protos = np.zeros((CLASSES, per_class, IMAGE, IMAGE))
    bank = [_smooth_field(rng) for _ in range(bank_size)]
    for c in range(CLASSES):
        for p in range(per_class):
            base = _smooth_field(rng)
            if bank:
                base = 0.65 * base + 0.35 * bank[rng.integers(bank_size)]
            protos[c, p] = base
    return protos.astype(np.float32)


def _render(rng, protos, n, shift, noise, contrast_jitter):
    labels = rng.integers(0, CLASSES, size=n).astype(np.int32)
    pick = rng.integers(0, protos.shape[1], size=n)
    d = rng.integers(-shift, shift + 1, size=(n, 2))
    ar = np.arange(IMAGE)
    rows = (ar[None, :] - d[:, :1]) % IMAGE          # circular shift
    cols = (ar[None, :] - d[:, 1:]) % IMAGE
    imgs = protos[labels, pick][np.arange(n)[:, None, None],
                                rows[:, :, None], cols[:, None, :]]
    if contrast_jitter:
        g = 1.0 + contrast_jitter * rng.standard_normal(n, np.float32)
        imgs = np.clip(imgs * g[:, None, None], 0.0, 1.0)
    imgs += noise * rng.standard_normal(imgs.shape, np.float32)
    np.clip(imgs, 0.0, 1.0, out=imgs)
    return imgs[..., None], labels


# generator name -> (prototype args, seed offset, render args)
GENERATORS = {
    "mnist_like": (dict(seed=1234, per_class=1), 0,
                   dict(shift=3, noise=0.30, contrast_jitter=0.0)),
    "fashion_like": (dict(seed=5678, per_class=2, bank_size=4), 10_000,
                     dict(shift=3, noise=0.18, contrast_jitter=0.2)),
}


def render(data_spec, seed, generators=GENERATORS):
    """{"train": (x, y), "test": (x, y), "name": ...} for a config's
    `data` block ({"generator", "n_train", "n_test"}), the generator
    looked up in `generators`."""
    proto_kw, offset, render_kw = generators[data_spec["generator"]]
    protos = _prototypes(**proto_kw)
    rng = np.random.default_rng(seed + offset)
    train = _render(rng, protos, data_spec["n_train"], **render_kw)
    test = _render(rng, protos, data_spec["n_test"], **render_kw)
    return {"train": train, "test": test, "name": data_spec["generator"]}


def forward_macs(image=(28, 28, 1), filters=(16, 12, 10), kernel=3, pool=2,
                 classes=10):
    """Forward multiply-accumulates of one image: SAME 3x3 convs with a
    2x2 max-pool after each but the last, then a dense layer to
    `classes` (activations and pooling are not counted)."""
    h, w, cin = image
    macs = 0
    for i, cout in enumerate(filters):
        macs += h * w * cout * kernel * kernel * cin
        cin = cout
        if i < len(filters) - 1:
            h, w = h // pool, w // pool
    return macs + h * w * cin * classes


def param_count(image=(28, 28, 1), filters=(16, 12, 10), kernel=3, pool=2,
                classes=10):
    """Parameter count N of the paper CNN (the aggregation row width)."""
    h, w, cin = image
    n = 0
    for i, cout in enumerate(filters):
        n += kernel * kernel * cin * cout + cout
        cin = cout
        if i < len(filters) - 1:
            h, w = h // pool, w // pool
    return n + h * w * cin * classes + classes


def forward_flops(model):
    """FLOPs of one image's forward pass: two per multiply-accumulate."""
    return float(2 * forward_macs(tuple(model["image"]),
                                  tuple(model["filters"]), model["kernel"],
                                  model["pool"], model["classes"]))


def reference_model():
    return reference.init, reference.loss_fn, reference.accuracy


def program_kwargs(config):
    return {}
