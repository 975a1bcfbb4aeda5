"""The plain reference's two paths for a round's participants: side by
side (stacked) where k of them fit in half of the device's memory, else
one at a time (streamed). The streamed path gives the stacked path's
results at the tiny root's size, refuses what it cannot do, and the
byte rule keeps every committed cell stacked. Integer inputs reach the
family's `loss` and `accuracy` as rendered, in the bfloat16 control too."""
import json

import chip_bench_tiny
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import wide_token_check

from chip_bench import cells
from chip_bench.reference import federation as ref_mod

SEED = 2**31 + 77
GIB16 = 16 * 2**30
TOKENS = wide_token_check.token_family()
# ids over 1,000, most of which bfloat16's 8-bit significand rounds
TOKEN_SPEC = {
    "model": {"vocab": 1200, "hidden": 8, "ffn": 16, "layers": 1},
    "data": {"vocab": 1200, "seq_len": 8, "n_train": 64, "n_test": 16},
    "federation": {"strategy": "afl", "participation": 1.0,
                   "num_clients": 4, "local_batch_size": 4, "lr": 0.05,
                   "momentum": 0.9, "local_epochs": 1, "rounds": 2}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return chip_bench_tiny.make_root(tmp_path_factory.mktemp("tiny"))


def _relative(a, b):
    return float(np.linalg.norm(np.asarray(a, np.float64) - b)
                 / np.linalg.norm(np.asarray(b, np.float64)))


@pytest.mark.parametrize("name,over", [
    ("fmnist_c1024.afl_mesh4", {}),
    ("fmnist_c1024.afl_median", {"defense": "none"})],
    ids=["afl_fedavg", "afl_signflip"])
def test_streamed_matches_stacked(root, name, over):
    cell = cells.load(name, root=root)
    spec = cell.spec
    spec["federation"].update(over)
    fed = spec["federation"]
    if fed.get("attack") == "sign_flip":
        assert fed["attack_fraction"] == 0.25
        assert ref_mod.attackers(fed["num_clients"], 0.25, SEED).any()
    data = cell.family.render(cell.config["data"], SEED)
    model = cell.family.reference_model()
    stacked = ref_mod.run(spec, data, SEED, model)
    streamed = ref_mod.run(spec, data, SEED, model, stream=True)
    assert _relative(streamed["round_loss"], stacked["round_loss"]) <= 1e-6
    assert set(streamed["final"]) == set(stacked["final"])
    for k, v in stacked["final"].items():
        assert _relative(streamed["final"][k], v) <= 1e-6, k
    for k in ("round_train_acc", "round_test_acc"):
        np.testing.assert_array_equal(streamed[k], stacked[k])
    for k, v in stacked["init"].items():
        np.testing.assert_array_equal(streamed["init"][k], v)


@pytest.mark.parametrize("name,what", [
    ("mnist_c10.hfl", "strategy 'hfl'"),
    ("fmnist_c1024.afl_median", "defense 'median'")])
def test_streamed_path_refuses(root, name, what):
    cell = cells.load(name, root=root)
    data = cell.family.render(cell.config["data"], SEED)
    with pytest.raises(NotImplementedError) as err:
        ref_mod.run(cell.spec, data, SEED, cell.family.reference_model(),
                    stream=True)
    assert what in str(err.value) and "bytes" in str(err.value)


@pytest.mark.parametrize("name", sorted(
    w["name"] for w in json.loads(
        (chip_bench_tiny.ROOT / "BENCHMARK.json").read_text())["workloads"]))
def test_every_committed_cell_stays_stacked(name):
    cell = cells.load(name)
    nbytes = ref_mod.client_bytes(cell.family.reference_model()[0], SEED,
                                  cell.spec["model"])
    assert nbytes == 4 * cell.config["model"]["params"]
    assert ref_mod.stacked(cell.spec, nbytes, GIB16)


@pytest.mark.parametrize("init_fn,model", [
    (lambda seed, m: {"w": jnp.zeros((m["params"],), jnp.float32)},
     {"params": 535_000_000}),
    (TOKENS.init, wide_token_check.WIDE["model"])],
    ids=["535M", "wide_token_mlp"])
def test_four_large_clients_stream(init_fn, model):
    spec = {"model": model, "federation": {"strategy": "afl",
                                           "participation": 1.0,
                                           "num_clients": 4}}
    nbytes = ref_mod.client_bytes(init_fn, SEED, model)
    assert nbytes >= 4 * 500_000_000
    assert not ref_mod.stacked(spec, nbytes, GIB16)
    assert ref_mod.stacked(spec, nbytes, None)       # no limit known


def _recording(fn, seen):
    def wrapped(p, x, y, prec):
        assert jnp.issubdtype(x.dtype, jnp.integer)
        assert jnp.issubdtype(y.dtype, jnp.integer)
        jax.debug.callback(lambda a, b: seen.append((np.asarray(a),
                                                     np.asarray(b))), x, y)
        return fn(p, x, y, prec)
    return wrapped


@pytest.mark.parametrize("stream", [False, True], ids=["stacked", "streamed"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_integer_ids_reach_the_family_as_rendered(dtype, stream):
    data = TOKENS.render(TOKEN_SPEC["data"], SEED)
    xs = np.concatenate([data["train"][0], data["test"][0]])
    ys = np.concatenate([data["train"][1], data["test"][1]])
    # the check can see a cast: bfloat16 moves most of these ids
    assert np.mean(xs.astype(jnp.bfloat16).astype(np.int32) != xs) > 0.5
    rows = {(a.tobytes(), b.tobytes()) for a, b in zip(xs, ys)}
    seen = []
    init, loss, accuracy = TOKENS.reference_model()
    res = ref_mod.run(TOKEN_SPEC, data, SEED,
                      (init, _recording(loss, seen),
                       _recording(accuracy, seen)),
                      dtype=dtype, stream=stream)
    jax.effects_barrier()
    assert np.all(np.isfinite(res["round_loss"]))
    got = [(a.reshape(-1, a.shape[-1]), b.reshape(-1, b.shape[-1]))
           for a, b in seen]
    n = sum(len(a) for a, _ in got)
    # every training batch, every eval shard and the test set
    assert n >= 2 * (64 + 4 * 16 + 16)
    for a, b in got:
        assert a.dtype == np.int32 and b.dtype == np.int32
        for ra, rb in zip(a, b):
            assert (ra.tobytes(), rb.tobytes()) in rows


def test_cast_keeps_integer_leaves():
    tree = {"w": jnp.ones((3,), jnp.float32), "ids": jnp.arange(3)}
    out = ref_mod.cast(tree, jnp.bfloat16)
    assert out["w"].dtype == jnp.bfloat16
    assert out["ids"].dtype == tree["ids"].dtype
    np.testing.assert_array_equal(out["ids"], tree["ids"])
