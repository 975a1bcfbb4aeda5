"""The federated-learning Pallas kernels, and the stacked CNN's training
step, compile for a TPU v5e.

Interpret mode cannot see what the chip's compiler refuses: a tile that
overflows VMEM, or a block that does not match XLA's layout. These tests
compile each kernel for a described (not attached) v5e chip at the
client counts the repository runs and at the paper CNN's width, so a
refusal shows here instead of on the chip. Nothing runs: a compile that
passes says nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file."""
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import comm_agg, fedavg_agg, gossip_mix, robust_agg
from repro.launch import compile_cache
from repro.models import cnn
from repro.models.cnn import init_cnn


@pytest.fixture(scope="module")
def topo():
    # the compiler writes log files unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cnn_dim():
    shapes = jax.eval_shape(init_cnn, jax.random.PRNGKey(0))
    return sum(x.size for x in jax.tree.leaves(shapes))


CNN_DIM = _cnn_dim()

KERNELS = {
    "fedavg_agg": (fedavg_agg.fedavg_agg,
                   lambda C, N: [((C, N), jnp.float32), ((C,), jnp.float32)]),
    "dequant_agg": (comm_agg.dequant_agg,
                    lambda C, N: [((C, N), jnp.int8), ((C,), jnp.float32),
                                  ((C,), jnp.float32)]),
    "median_agg": (robust_agg.median_agg,
                   lambda C, N: [((C, N), jnp.float32)]),
    "gossip_mix_agg": (gossip_mix.gossip_mix_agg,
                       lambda C, N: [((C, N), jnp.float32),
                                     ((C, C), jnp.float32)]),
}


@pytest.mark.parametrize("C,N", [
    (64, CNN_DIM),           # the paper-scale federation
    (1024, CNN_DIM),         # the chunked large federation
    (64, 10**6 + 7),         # a wide model whose width is no block multiple
])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, C, N):
    fn, arg_shapes = KERNELS[kernel]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_shapes(C, N)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# an operand of the patch lowering: (..., kh*kw, cin) stacked patches or
# their (C, B*H*W, kh*kw*cin) matrix, for cin of 1, 16 and 12
PATCH_OPERAND = re.compile(r"\[[0-9,]*,9,(1|16|12)\]|,(9|144|108)\]")


def _stacked_train_step(apply):
    def step(params, images, labels):
        def loss(p):
            logp = jax.nn.log_softmax(apply(p, images))
            nll = -jnp.take_along_axis(logp, labels[..., None], -1)
            return nll.mean(axis=(1, 2)).sum()
        return jax.value_and_grad(loss)(params)
    return step


@pytest.mark.parametrize("C,B", [
    (10, 32),            # the paper's HFL federation, one stack
    (128, 16),           # one chunk of the 1,024-client federation
])
@pytest.mark.parametrize("lowering", ["grouped", "patch"])
def test_stacked_cnn_step_compiles_for_v5e(one_chip, lowering, C, B):
    """The stacked value-and-grad step compiles for the chip under each
    lowering. Where `stacked_lowering` picks the grouped lowering for the
    chip, the step convolves with feature groups and builds no patch
    tensor."""
    apply = {"grouped": cnn.cnn_apply_grouped,
             "patch": cnn.cnn_apply_patch}[lowering]
    params = jax.eval_shape(
        lambda k: jax.vmap(init_cnn)(jax.random.split(k, C)),
        jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        params)
    images = jax.ShapeDtypeStruct((C, B, 28, 28, 1), jnp.float32,
                                  sharding=one_chip)
    labels = jax.ShapeDtypeStruct((C, B), jnp.int32, sharding=one_chip)
    lowered = jax.jit(_stacked_train_step(apply)).lower(params, images,
                                                        labels)
    hlo = lowered.compile().as_text()
    if lowering == "patch":
        assert PATCH_OPERAND.search(hlo)
    elif cnn.stacked_lowering(C, "tpu") == "grouped":
        assert f"feature_group_count = {C}" in lowered.as_text()
        assert not PATCH_OPERAND.search(hlo), \
            PATCH_OPERAND.search(hlo).group(0)


@pytest.mark.parametrize("env", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says, and only to
    the checkout's fixed `.jax_cache` when it is unset."""
    if env is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = str(pathlib.Path(__file__).resolve().parents[1]
                   / ".jax_cache")
    else:
        want = str(tmp_path / env)
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == want
        # with the variable set, JAX reads it itself: nothing set in code
        assert jax.config.jax_compilation_cache_dir == (
            want if env is None else before)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
