"""The stacked CNN's two lowerings compute `jax.vmap(cnn_apply)`.

`cnn_apply_stacked` trains every client of a stack at once, either as
grouped convolutions with the clients in the channel lanes or as a
patch-tensor GEMM batched over clients (`models/cnn.py`). Both are
called directly here, whatever the backend would pick, and compared
with the per-client model under `vmap`: logits and per-client gradients
in float32 at HIGHEST precision."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import cnn


def _stack(C, B, seed=0):
    k_init, k_bias, k_img, k_lab = jax.random.split(jax.random.PRNGKey(seed),
                                                    4)
    params = jax.vmap(cnn.init_cnn)(jax.random.split(k_init, C))
    # nonzero biases, so a bias laid out on the wrong client shows
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(k_bias, len(leaves))
    params = jax.tree.unflatten(tree, [
        l + 0.05 * jax.random.normal(k, l.shape)
        for l, k in zip(leaves, keys)])
    images = jax.random.normal(k_img, (C, B, 28, 28, 1))
    labels = jax.random.randint(k_lab, (C, B), 0, 10)
    return params, images, labels


def _logits_and_grads(apply, params, images, labels):
    def loss(p):
        logp = jax.nn.log_softmax(apply(p, images))
        nll = -jnp.take_along_axis(logp, labels[..., None], -1)
        return nll.mean(axis=(1, 2)).sum()   # per-client means, summed
    with jax.default_matmul_precision("highest"):
        return apply(params, images), jax.grad(loss)(params)


@pytest.mark.parametrize("C,B", [(3, 4), (8, 2)])
@pytest.mark.parametrize("lowering", ["grouped", "patch"])
def test_lowering_matches_vmapped_cnn(lowering, C, B):
    apply = {"grouped": cnn.cnn_apply_grouped,
             "patch": cnn.cnn_apply_patch}[lowering]
    params, images, labels = _stack(C, B)
    ref_logits, ref_grads = _logits_and_grads(jax.vmap(cnn.cnn_apply),
                                              params, images, labels)
    logits, grads = _logits_and_grads(apply, params, images, labels)
    assert logits.shape == (C, B, 10)
    np.testing.assert_allclose(logits, ref_logits, rtol=1e-5, atol=1e-5)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        ref = ref_grads
        for key in path:
            ref = ref[key.key]
        np.testing.assert_allclose(g, ref, rtol=1e-5, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("backend,C,expect", [
    ("cpu", 1, "patch"), ("cpu", 10, "patch"), ("cpu", 1024, "patch"),
    ("tpu", 1, "grouped"), ("tpu", 10, "grouped"), ("tpu", 32, "grouped"),
    ("tpu", 33, "patch"), ("tpu", 128, "patch"), ("tpu", 1024, "patch"),
])
def test_stacked_lowering_choice(backend, C, expect):
    """The lowering PERF.md section 3 documents for each backend and
    stack size: grouped on the TPU up to 32 clients, else the patch
    GEMM."""
    assert cnn.stacked_lowering(C, backend) == expect
    if backend == jax.default_backend():
        assert cnn.stacked_lowering(C) == expect


@pytest.mark.parametrize("lowering", ["grouped", "patch"])
def test_lowering_scope_overrides_choice(lowering):
    """Inside `lowering_scope` a stack lowers as the scope says, whatever
    `stacked_lowering` would pick for its size, and each call is
    recorded."""
    params, images, _ = _stack(3, 2)
    with cnn.lowering_scope(lowering) as calls:
        # a new function each time: jit would reuse an earlier trace
        hlo = jax.jit(lambda p, x: cnn.cnn_apply_stacked(p, x)).lower(
            params, images).as_text()
    assert calls == [lowering]
    assert ("feature_group_count = 3" in hlo) == (lowering == "grouped")
    calls.clear()
    cnn.cnn_apply_stacked(params, images)
    assert calls == []
