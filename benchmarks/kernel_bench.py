"""Kernel micro-benchmarks.

On the CPU the Pallas kernels execute in interpret mode (not
representative of TPU), so wall-clock timings are taken on the jnp
REFERENCE paths (the computation the kernels implement; XLA:CPU
timings) and the derived column reports the analytic roofline time of
the same op on a TPU v5e (peaks from `benchmarks/peaks.json`) — the
number the BlockSpec tiling is designed against.

CSV: name,us_per_call,derived
"""
import time

import jax
import jax.numpy as jnp

from repro.launch.roofline import V5E, device_peaks

# the derived tpu_roofline_us column is the TPU v5e target, whatever
# device the timings ran on
PEAK_FLOPS = device_peaks(V5E)["bf16_flops"]
HBM_BW = device_peaks(V5E)["hbm_bytes_per_s"]


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6   # us


def _time_min(fn, *args, iters=20):
    """Per-call MINIMUM latency in us. The mean-based `_time` is the
    trend number; gated RATIOS (robust retention, CI floors) use the
    minimum instead — on a preemptible CI runner the mean of a
    sub-10ms kernel call is dominated by scheduler evictions, and a
    floor gate on it flaps (the min is the clean-machine latency both
    sides of a ratio can be held to)."""
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    best = None
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best * 1e6   # us


def bench_fedavg():
    from repro.kernels import ref
    C, N = 16, 2_000_000
    stacked = jax.random.normal(jax.random.PRNGKey(0), (C, N))
    w = jnp.full((C,), 1.0 / C)
    f = jax.jit(ref.fedavg_agg_ref)
    us = _time(f, stacked, w)
    hbm_bytes = (C * N + N) * 4
    derived = f"tpu_roofline_us={hbm_bytes / HBM_BW * 1e6:.1f}"
    return [("fedavg_agg_C16_N2M", us, derived)]


def bench_attention():
    from repro.kernels import ref
    rows = []
    for S in (512, 1024):
        BH, d = 8, 128
        q = jax.random.normal(jax.random.PRNGKey(0), (BH, S, d), jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(1), (BH, S, d), jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(2), (BH, S, d), jnp.float32)
        f = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
        us = _time(f, q, k, v)
        flops = 4 * BH * S * S * d
        derived = f"tpu_roofline_us={flops / PEAK_FLOPS * 1e6:.1f}"
        rows.append((f"flash_attention_S{S}_d{d}", us, derived))
    return rows


def bench_ssm():
    from repro.models.ssm import ssd_chunked
    B, S, H, dh, N = 2, 2048, 8, 64, 64
    xh = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, dh))
    a = -jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (B, S, H)))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(2), (B, S, H)))
    Bm = jax.random.normal(jax.random.PRNGKey(3), (B, S, N))
    Cm = jax.random.normal(jax.random.PRNGKey(4), (B, S, N))
    f = jax.jit(lambda *a_: ssd_chunked(*a_, chunk=128))
    us = _time(f, xh, a, dt, Bm, Cm)
    Q = 128
    flops = B * H * (S // Q) * (2 * Q * Q * N + 2 * Q * Q * dh
                                + 4 * Q * N * dh)
    derived = f"tpu_roofline_us={flops / PEAK_FLOPS * 1e6:.2f}"
    return [(f"ssm_scan_S{S}_H{H}_N{N}", us, derived)]


def bench_aggregation_strategies():
    """Host-level aggregation operators at CNN scale (paper's hot ops)."""
    from repro.core import aggregation, topology
    from repro.models.cnn import init_cnn
    clients = [init_cnn(jax.random.PRNGKey(i)) for i in range(10)]
    groups = topology.hierarchical_groups(10, 2)
    nbrs = topology.ring_neighbors(10, 2)
    rows = []
    for name, fn in [
        ("fedavg_10c", lambda: aggregation.fedavg(clients)),
        ("hfl_two_tier_10c",
         lambda: aggregation.hfl_aggregate(clients, groups)),
        ("gossip_round_10c", lambda: aggregation.gossip_round(clients, nbrs)),
        ("cfl_merge",
         lambda: aggregation.cfl_merge(clients[0], clients[1], 0.5)),
    ]:
        fn()
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn()
            jax.tree.leaves(out)[0].block_until_ready()
        rows.append((name, (time.perf_counter() - t0) / 10 * 1e6,
                     "host_level"))
    return rows


def measure_robust(clients, iters=20):
    """Robust trimmed-mean aggregation vs the plain fedavg weighted
    reduction at paper-CNN scale, timed on the PRODUCTION entry points
    (`kops.trimmed_mean_aggregate` / `kops.fedavg_aggregate`, i.e.
    whatever the backend dispatch in kernels/ops.py actually routes to —
    so a dispatch regression, e.g. the CPU path falling back to XLA's
    ~8x-slower comparator sort or the interpret-mode grid loop, shows up
    here; the kernel's correctness is pinned in tests/test_fused.py and
    tests/test_attacks_robust.py).

    The reported `speedup` is fedavg_us / trimmed_us — the fraction of
    linear-aggregation throughput the robust path retains (selection
    costs a sort; the ratio is dimensionless, so the CI gate tracks the
    robustness OVERHEAD staying bounded across runner hardware). Shared
    with `ci_bench.bench_robust` like the sync/async helpers."""
    from repro.core.engine import stack_forest
    from repro.kernels import ops as kops
    from repro.models.cnn import init_cnn

    stacked = stack_forest([init_cnn(jax.random.PRNGKey(i))
                            for i in range(clients)])
    mat = kops.stacked_ravel(stacked)
    trim = max(1, clients // 4)
    w = jnp.full((clients,), 1.0 / clients)
    favg_us = _time_min(lambda m: kops.fedavg_aggregate(m, w), mat,
                        iters=iters)
    trimmed_us = _time_min(lambda m: kops.trimmed_mean_aggregate(m, trim),
                           mat, iters=iters)
    return {"fedavg_us": favg_us, "trimmed_us": trimmed_us,
            "trim": trim, "n_params": int(mat.shape[1]),
            "speedup": favg_us / trimmed_us}


def bench_robust_agg(client_counts=(8, 64, 256)):
    """Robust-kernel throughput sweep 8 -> 256 clients. The derived
    column is the TPU roofline of the kernel's HBM traffic — one (C, N)
    pass like fedavg_agg; the bitonic network's O(C log^2 C)
    compare-exchange stages ride the VPU under it (ISSUE 5: down from
    the PR 3 rank kernel's O(C^2))."""
    rows = []
    for C in client_counts:
        per = measure_robust(C)
        hbm_bytes = (C * per["n_params"] + per["n_params"]) * 4
        derived = f"tpu_roofline_us={hbm_bytes / HBM_BW * 1e6:.2f}"
        rows.append((f"robust_trimmed_c{C}", per["trimmed_us"], derived))
        rows.append((f"robust_trimmed_c{C}_vs_fedavg", per["speedup"],
                     f"fedavg/trimmed_{per['speedup']:.3f}x_(ratio,_not_us)"))
    return rows


def measure_comm(clients, iters=20):
    """Upload-codec section (DESIGN.md §12): the fused
    dequantize-and-aggregate reduce vs the plain fedavg weighted
    reduction at paper-CNN scale, timed on the PRODUCTION entry points
    (`kops.dequant_aggregate` / `kops.fedavg_aggregate` — whatever the
    backend dispatch in kernels/ops.py routes to, so a dispatch
    regression shows up here; kernel correctness is pinned in
    tests/test_codecs.py).

    `retention` is fedavg_us / dequant_us — the fraction of dense
    aggregation throughput the dequantizing reduce retains (it reads 4x
    fewer upload bytes but pays an int8->f32 cast + per-client scale
    multiply; dimensionless, so the CI floor holds across runner
    hardware). Compression ratios are ANALYTIC — dense f32 bytes over
    `Codec.bytes_on_wire` at this model dimension — because the wire
    cost is a shape property, not a timing. Shared with
    `ci_bench.bench_comm` like the other measure_* helpers."""
    from repro.core.codecs import get_codec
    from repro.core.engine import stack_forest
    from repro.core.fl_types import FLConfig
    from repro.kernels import ops as kops
    from repro.models.cnn import init_cnn

    stacked = stack_forest([init_cnn(jax.random.PRNGKey(i))
                            for i in range(clients)])
    mat = kops.stacked_ravel(stacked)
    n = int(mat.shape[1])
    w = jnp.full((clients,), 1.0 / clients)
    # an int8 payload of the right shape (values don't affect timing)
    scale = jnp.max(jnp.abs(mat), axis=1) / 127.0
    q = jnp.clip(jnp.round(mat / scale[:, None]), -127, 127).astype(jnp.int8)
    favg_us = _time_min(lambda m: kops.fedavg_aggregate(m, w), mat,
                        iters=iters)
    deq_us = _time_min(
        lambda qq: kops.dequant_aggregate(qq, scale, w), q, iters=iters)
    fl = FLConfig(strategy="afl", num_clients=clients, participation=1.0)
    dense_bytes = 4 * n
    ratios = {name: dense_bytes / get_codec(name)(fl).bytes_on_wire(n)
              for name in ("topk", "qsgd")}
    return {"fedavg_us": favg_us, "dequant_us": deq_us,
            "n_params": n, "retention": favg_us / deq_us,
            "topk_ratio": ratios["topk"], "qsgd_ratio": ratios["qsgd"],
            "topk_frac": fl.topk_frac, "quant_bits": fl.quant_bits}


def bench_comm_agg(client_counts=(8, 64)):
    """Dequantize-and-aggregate throughput sweep. The derived column is
    the TPU roofline of the kernel's HBM traffic — the int8 payload is
    a quarter of fedavg_agg's (C, N) f32 read."""
    rows = []
    for C in client_counts:
        per = measure_comm(C)
        hbm_bytes = C * per["n_params"] + 4 * per["n_params"] + 8 * C
        derived = f"tpu_roofline_us={hbm_bytes / HBM_BW * 1e6:.2f}"
        rows.append((f"dequant_agg_c{C}", per["dequant_us"], derived))
        rows.append((f"dequant_agg_c{C}_vs_fedavg", per["retention"],
                     f"fedavg/dequant_{per['retention']:.3f}x_"
                     f"(ratio,_not_us)"))
    return rows


ENGINE_SWEEPS = {
    "smoke": (8,),
    "quick": (8, 32, 64),
    "full": (8, 16, 32, 64, 128, 256),
}


def measure_sync_round(clients, rounds=2):
    """Seconds/round of the loop vs vectorized engines on the paper CNN
    under HFL (2 groups, 2 local epochs, 64-sample shards, batch 32) —
    THE synchronous protocol shape. The engine sweep below and the CI
    regression gate (benchmarks/ci_bench.py) both consume this helper so
    they can never measure different protocols. Compile time is excluded
    on both sides (the simulation warms up outside its build window)."""
    from repro.core.fl_types import FLConfig
    from repro.core.simulation import FederatedSimulation
    from repro.data.synthetic import mnist_like

    ds = mnist_like(n_train=clients * 64, n_test=128)
    per = {}
    for eng in ("loop", "vectorized"):
        fl = FLConfig(strategy="hfl", num_clients=clients, num_groups=2,
                      rounds=rounds, local_epochs=2, local_batch_size=32,
                      lr=0.05, seed=0, engine=eng)
        r = FederatedSimulation(fl, ds).run()
        per[eng] = r.build_time_s / rounds
    return per


def measure_async(clients, updates=2):
    """Loop vs vectorized results of the tick-batched async runtime
    under uniform speeds (full-federation arrival batches — the batched
    kernel merge's best case), run through the async Strategy plugin on
    the generic driver. THE async protocol shape, shared with the CI
    gate like `measure_sync_round`. Returns per-engine objects with
    `.merges`/`.batches`/`.build_time_s` (FLResult extras surfaced)."""
    import types

    from repro.core.fl_types import FLConfig
    from repro.core.simulation import FederatedSimulation
    from repro.data.synthetic import mnist_like

    ds = mnist_like(n_train=clients * 64, n_test=128)
    per = {}
    for eng in ("loop", "vectorized"):
        fl = FLConfig(strategy="async", num_clients=clients, num_groups=2,
                      local_epochs=1, local_batch_size=32, lr=0.05, seed=0,
                      participation=1.0, updates_per_client=updates,
                      speed_model="uniform", tick=1.0, engine=eng)
        r = FederatedSimulation(fl, ds).run()
        per[eng] = types.SimpleNamespace(
            merges=r.extra["merges"], batches=r.extra["batches"],
            build_time_s=r.build_time_s)
    return per


def measure_fused(clients, rounds=8):
    """Fused-executor round throughput vs the vectorized per-round
    driver (ISSUE 5 acceptance; shared with `ci_bench.bench_fused`).

    Protocol shape: AFL full participation, 1 local epoch, 8-sample
    shards / batch 8 — deliberately LIGHT local compute, because the
    fused executor optimizes the EXECUTOR (per-round dispatch, host
    rebatching, device->host metric syncs), not the GEMMs: at
    compute-heavy shapes (e.g. the sync section's HFL 2-epoch 64-sample
    rounds) both drivers converge on identical GEMM time and the
    measurement loses resolution on the thing this section tracks
    (DESIGN.md §10). Each engine's build is measured best-of-3
    (scheduler-eviction noise on CI runners; same rationale as
    `_time_min`). Both runs share one dataset/config and differ only in
    `FLConfig.engine`; parity of their outputs is pinned in
    tests/test_fused.py."""
    from repro.core.fl_types import FLConfig
    from repro.core.simulation import FederatedSimulation
    from repro.data.synthetic import mnist_like

    ds = mnist_like(n_train=clients * 8, n_test=128)
    per = {}
    for eng in ("vectorized", "fused"):
        fl = FLConfig(strategy="afl", num_clients=clients,
                      participation=1.0, rounds=rounds, local_epochs=1,
                      local_batch_size=8, lr=0.05, seed=0, engine=eng)
        per[eng] = min(FederatedSimulation(fl, ds).run().build_time_s
                       for _ in range(3)) / rounds
    return {"per_round_s": per["vectorized"], "fused_round_s": per["fused"],
            "per_round_rounds_per_s": 1.0 / per["vectorized"],
            "fused_rounds_per_s": 1.0 / per["fused"],
            "speedup": per["vectorized"] / per["fused"]}


def bench_fused(client_counts=(8, 64)):
    """Fused-vs-per-round sweep (the ISSUE 5 tentpole measurement)."""
    rows = []
    for C in client_counts:
        per = measure_fused(C)
        rows.append((f"fl_fused_round_c{C}", per["fused_round_s"] * 1e6,
                     "engine=one_round"))
        rows.append((f"fl_fused_round_c{C}_speedup", per["speedup"],
                     f"fused_{per['speedup']:.2f}x_(ratio,_not_us)"))
    return rows


def measure_obs(clients=16, rounds=4, reps=5):
    """Telemetry overhead per engine (ISSUE 8 acceptance): the same
    light AFL protocol shape as `measure_fused`, each engine run with
    `FLConfig.telemetry` on and off. `overhead` is on/off - 1 — the
    number `ci_bench.compare` holds to the ≤5% budget (DESIGN.md §13).
    Results are bitwise identical across the toggle (tests/test_obs.py
    pins it); this measures only the rounds/s cost of the spans +
    in-scan counters.

    The true span cost is microseconds against ~100ms rounds, so the
    measurement protocol is built to not flap on host noise: the
    on/off settings run INTERLEAVED (each rep times one on run
    immediately followed by one off run, so load drift hits both
    sides of the ratio equally — two back-to-back best-of-N groups
    showed ±6% swings in either direction from scheduler noise alone)
    and each side takes its best-of-`reps` floor."""
    from repro.core.fl_types import FLConfig
    from repro.core.simulation import FederatedSimulation
    from repro.data.synthetic import mnist_like

    ds = mnist_like(n_train=clients * 8, n_test=128)

    def _one(eng, tel):
        fl = FLConfig(strategy="afl", num_clients=clients,
                      participation=1.0, rounds=rounds,
                      local_epochs=1, local_batch_size=8, lr=0.05,
                      seed=0, engine=eng, telemetry=tel)
        return FederatedSimulation(fl, ds).run().build_time_s

    out = {}
    for eng in ("loop", "vectorized", "fused"):
        per = {True: [], False: []}
        for _ in range(reps):
            for tel in (True, False):
                per[tel].append(_one(eng, tel))
        on, off = min(per[True]) / rounds, min(per[False]) / rounds
        out[eng] = {"on_round_s": on, "off_round_s": off,
                    "on_rounds_per_s": 1.0 / on,
                    "off_rounds_per_s": 1.0 / off,
                    "overhead": on / off - 1.0}
    return out


def measure_churn(clients, rounds=8, reps=3):
    """Fault-plumbing cost under the fused executor (ISSUE 10): the same
    light AFL protocol shape as `measure_fused`, run with
    `fault_profile="none"` and with an active 30% churn profile,
    interleaved best-of-`reps` like `measure_obs`.

    The "none" arm is the gated number: profile="none" compiles no
    schedule and every fault seam is a host-level `if`, so the traced
    fused program is identical to a pre-fault build — `ci_bench.compare`
    holds its ABSOLUTE rounds/s to within 5% of the committed baseline's
    fused throughput (same protocol, same host). The churn arm is
    recorded for trend only: an active profile legitimately pays for the
    per-round alive/mix scan inputs and the quorum tree_where holds."""
    from repro.core.fl_types import FLConfig
    from repro.core.simulation import FederatedSimulation
    from repro.data.synthetic import mnist_like

    ds = mnist_like(n_train=clients * 8, n_test=128)

    def _one(profile):
        fl = FLConfig(strategy="afl", num_clients=clients,
                      participation=1.0, rounds=rounds, local_epochs=1,
                      local_batch_size=8, lr=0.05, seed=0, engine="fused",
                      fault_profile=profile, churn_rate=0.3)
        return FederatedSimulation(fl, ds).run().build_time_s

    per = {"none": [], "churn": []}
    for _ in range(reps):
        for profile in ("none", "churn"):
            per[profile].append(_one(profile))
    none_s = min(per["none"]) / rounds
    churn_s = min(per["churn"]) / rounds
    return {"none_round_s": none_s, "churn_round_s": churn_s,
            "none_rounds_per_s": 1.0 / none_s,
            "churn_rounds_per_s": 1.0 / churn_s,
            "active_overhead": churn_s / none_s - 1.0}


def measure_serve(clients=16, rounds=2, reps=20):
    """Serving section (ISSUE 9): the wall-clock steady-state throughput
    of the compiled padded-batch classify dispatch — the one model call
    per micro-batch, so `serve_batch / best_latency` is the requests/s
    the engine sustains at full occupancy — plus the VIRTUAL-clock
    serving block of a full serve-enabled run (p99/shed under the affine
    service-time model; deterministic in the config, so those numbers
    gate as exact ceilings, not host-tolerant ratios). Best-of-`reps`
    like the other gated numbers (DESIGN.md §14)."""
    import numpy as np
    from repro.core.fl_types import FLConfig
    from repro.core.simulation import FederatedSimulation
    from repro.data.synthetic import mnist_like

    ds = mnist_like(n_train=clients * 8, n_test=128)
    fl = FLConfig(strategy="hfl", num_clients=clients, rounds=rounds,
                  local_epochs=1, local_batch_size=8, lr=0.05, seed=0,
                  engine="vectorized", serve=True)
    sim = FederatedSimulation(fl, ds)
    blk = sim.run().extra["serving"]
    # steady-state wall clock: rebuild the run's dispatch closure (the
    # session warm-up compiles it) and time FULL admission-cap batches
    sess = sim._make_serve_session(rounds)
    dispatch = sess.batcher.dispatch_fn
    params = sim.init_params
    ei = np.arange(fl.serve_batch, dtype=np.int64)
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        dispatch(params, ei)        # returns host ndarray: synchronized
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return {
        "batch": fl.serve_batch,
        "dispatch_us": best * 1e6,
        "requests_per_s": fl.serve_batch / best,
        "virtual_p50_ms": blk["latency_ms"]["p50"],
        "virtual_p99_ms": blk["latency_ms"]["p99"],
        "shed_rate": blk["shed_rate"],
        "qps": blk["qps"],
        "served_accuracy": blk["served_accuracy"],
    }


FUSED_CHUNK = 128
FUSED_CHUNKED_SWEEPS = {
    "smoke": (),
    "quick": (1024,),
    "full": (1024, 2048),
}


def measure_fused_chunked(clients, rounds=2, chunk=FUSED_CHUNK):
    """Chunked fused-executor throughput past the vmap memory knee
    (ISSUE 6): `FLConfig.fused_chunk` trains the participant stack one
    sub-stack at a time (`lax.map` over chunks, core/engine.py), which
    bounds the C-proportional live set of the all-at-once vmap. On the
    1-core reference container at C=1024 the chunked run holds ~1.3 GiB
    peak RSS against ~3.6 GiB unchunked AND runs ~3.9x faster (the
    unchunked program thrashes the allocator at that live-set size) —
    this is what lifts the client sweep from the PR 5 ceiling of 256 to
    1024+. Chunked results are BITWISE equal to unchunked (clients are
    independent; tests/test_fused.py pins it). Fused engine only: the
    per-round driver at C>=1024 adds minutes of wall clock without
    informing the chunking question. Shared with `ci_bench.run`, whose
    peak-RSS gate samples right after this measurement so the envelope
    covers the chunked stack."""
    from repro.core.fl_types import FLConfig
    from repro.core.simulation import FederatedSimulation
    from repro.data.synthetic import mnist_like

    ds = mnist_like(n_train=clients * 8, n_test=128)
    fl = FLConfig(strategy="afl", num_clients=clients, participation=1.0,
                  rounds=rounds, local_epochs=1, local_batch_size=8,
                  lr=0.05, seed=0, engine="fused", fused_chunk=chunk)
    s = min(FederatedSimulation(fl, ds).run().build_time_s
            for _ in range(2)) / rounds
    return {"clients": clients, "chunk": chunk, "fused_round_s": s,
            "fused_rounds_per_s": 1.0 / s}


def bench_fused_chunked(client_counts=FUSED_CHUNKED_SWEEPS["quick"]):
    """Memory-bounded client-scale sweep (the ISSUE 6 chunking
    satellite measurement)."""
    rows = []
    for C in client_counts:
        per = measure_fused_chunked(C)
        rows.append((f"fl_fused_round_c{C}_chunk{per['chunk']}",
                     per["fused_round_s"] * 1e6,
                     "engine=one_round_chunked"))
    return rows


def bench_engines(client_counts=(8, 32, 64), rounds=2):
    """Round-throughput sweep over client counts. The loop engine pays
    one jit dispatch + one small-batch XLA program per client per epoch;
    the vectorized engine runs the whole federation as one compiled scan
    with kernel-backed aggregation (core/engine.py), so the gap widens
    with the client count and with the host's core count."""
    rows = []
    for C in client_counts:
        per = measure_sync_round(C, rounds)
        for eng in ("loop", "vectorized"):
            rows.append((f"fl_round_hfl_c{C}_{eng}", per[eng] * 1e6,
                         "engine=one_round"))
        speedup = per["loop"] / per["vectorized"]
        rows.append((f"fl_round_hfl_c{C}_speedup", speedup,
                     f"vectorized_{speedup:.2f}x_(ratio,_not_us)"))
    return rows


def bench_async_engines(client_counts=(8, 64), updates=2):
    """Merge-throughput sweep of the tick-batched async runtime: the
    vectorized engine executes each arrival batch as one stacked
    training dispatch + one kernel-backed weighted merge while the loop
    engine pays per-client dispatch + per-arrival host merges."""
    rows = []
    for C in client_counts:
        res = measure_async(C, updates)
        per = {eng: r.build_time_s / r.batches for eng, r in res.items()}
        for eng in ("loop", "vectorized"):
            rows.append((f"fl_async_batch_c{C}_{eng}", per[eng] * 1e6,
                         "engine=one_merge_batch"))
        speedup = per["loop"] / per["vectorized"]
        rows.append((f"fl_async_batch_c{C}_speedup", speedup,
                     f"vectorized_{speedup:.2f}x_(ratio,_not_us)"))
    return rows


def main(scale="quick"):
    rows = (bench_fedavg() + bench_attention() + bench_ssm()
            + bench_aggregation_strategies()
            + bench_robust_agg((8,) if scale == "smoke"
                               else (8, 64, 256))
            + bench_comm_agg((8,) if scale == "smoke" else (8, 64))
            + bench_engines(ENGINE_SWEEPS[scale])
            + bench_async_engines(tuple(sorted({min(ENGINE_SWEEPS[scale]),
                                                max(ENGINE_SWEEPS[scale])})))
            + bench_fused(tuple(sorted({min(ENGINE_SWEEPS[scale]),
                                        max(ENGINE_SWEEPS[scale])})))
            + bench_fused_chunked(FUSED_CHUNKED_SWEEPS[scale]))
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="quick", choices=sorted(ENGINE_SWEEPS))
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(args.scale)
