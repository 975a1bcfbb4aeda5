"""The federated-learning Pallas kernels compile for a TPU v5e.

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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import comm_agg, fedavg_agg, gossip_mix, robust_agg
from repro.launch import compile_cache
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
