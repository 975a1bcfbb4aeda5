"""The benchmark's own image sets, rendered from the seed.

MNIST- and Fashion-MNIST-shaped synthetic sets (28x28x1 float32 in
[0, 1], int32 labels in [0, 10)) with the statistical character of the
program's `repro.data.synthetic` generators: one smooth prototype per
class for `mnist_like`; two prototypes per class mixed with a shared
texture bank, stronger contrast jitter and class overlap for
`fashion_like`. The prototypes are built the same way; rendering is
vectorised over the whole set (one gather for the shifts, one draw for
the noise), so 70,000 images take a fraction of a second rather than a
Python loop per image. The images differ from the program's own
generator for the same seed; both the program and the reference are fed
these.
"""
from __future__ import annotations

import numpy as np

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


def render(data_spec, seed):
    """{"train": (x, y), "test": (x, y), "name": ...} for a config's
    `data` block ({"generator", "n_train", "n_test"})."""
    proto_kw, offset, render_kw = GENERATORS[data_spec["generator"]]
    protos = _prototypes(**proto_kw)
    rng = np.random.default_rng(seed + offset)
    train = _render(rng, protos, data_spec["n_train"], **render_kw)
    test = _render(rng, protos, data_spec["n_test"], **render_kw)
    return {"train": train, "test": test, "name": data_spec["generator"]}
