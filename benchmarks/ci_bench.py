"""CI benchmark: round-throughput tracking + scenario smoke grid.

Measures the loop-vs-vectorized round throughput of BOTH runtimes (the
synchronous engine and the tick-batched async engine) at the target
client count, the robust-aggregation overhead ratio (trimmed-mean vs
plain fedavg, DESIGN.md §8), the fused-executor round throughput vs the
vectorized per-round driver (DESIGN.md §10), runs the registry's CI
smoke grid, and writes one `BENCH_ci.json` document (stable schema,
DESIGN.md §7).

With `--baseline` it gates: the regression signal is the vectorized/loop
SPEEDUP ratio (dimensionless, so portable across runner hardware — raw
wall-clock from a laptop baseline would flap on every CI machine change;
absolute throughputs are still recorded for trend tracking), failing when
a speedup falls more than `--tolerance` (default 25%) below the committed
baseline, when the async/fused speedups at quick scale drop below their
2x acceptance floors, when the robust path retains less than 10% of
fedavg throughput (the ISSUE 5 bitonic-kernel floor), when the generic
round driver's ABSOLUTE sync round throughput falls more than
`--driver-tolerance` (default 5%) below the baseline's (the ISSUE 4
driver-overhead gate; same host core count and scale only, so hardware
swaps don't trip it), when same-host peak RSS regresses past 20%
(the ISSUE 5 buffer-donation satellite — at quick scale the envelope
includes the chunked 1024-client fused round, the ISSUE 6 memory-bounded
path), when the mesh-sharded fused run at 8 forced host devices falls
below `MESH_RATIO_FLOOR` of single-device throughput (ISSUE 6), or when
the upload-codec section (ISSUE 7) regresses: qsgd uplink compression
below its 3.5x acceptance floor, topk compression below the configured
sparsity's analytic ratio, or the dequantize-and-aggregate reduce
retaining less than `DEQUANT_RETENTION_FLOOR` of fedavg throughput, or
when the on-by-default telemetry (ISSUE 8) costs more than
`OBS_OVERHEAD_TOLERANCE` rounds/s under any of the three engines, or
when the serving engine (ISSUE 9) drops below `SERVE_QPS_FLOOR`
steady-state requests/s (the padded-batch dispatch must stay one
compiled call) or its deterministic virtual-clock p99 exceeds
`SERVE_P99_CEILING_MS`, or when the fault-injection runtime (ISSUE 10)
regresses: the none-profile fused run losing more than
`CHURN_PLUMBING_TOLERANCE` of the baseline's fused rounds/s
(profile="none" must stay structurally inert), or the deterministic
30%-churn acceptance scenario's macro-F1 falling below
`CHURN_ACCEPT_F1_FLOOR`.

Besides the gated numbers, the document's `host` block carries
per-section peak-RSS attribution (`rss_sections`, ISSUE 8 satellite):
ru_maxrss sampled at every section boundary, so a memory regression
shows WHICH phase raised the high-water mark, not just that it moved.
The process-level `host.peak_rss_mb` keeps its original sampling point
(right after the fused/chunked sections) for baseline back-compat.

    PYTHONPATH=src python -m benchmarks.ci_bench --scale quick \
        --out BENCH_ci.json --baseline benchmarks/BENCH_baseline.json --check
"""
import argparse
import json
import os
import sys

SCHEMA_VERSION = 1

SCALES = {
    # clients, sync rounds, async updates/client, fused rounds
    "smoke": {"clients": 8, "sync_rounds": 2, "updates": 2,
              "fused_rounds": 4},
    "quick": {"clients": 64, "sync_rounds": 2, "updates": 2,
              "fused_rounds": 8},
}
ASYNC_SPEEDUP_FLOOR = 2.0        # ISSUE 2 acceptance, quick scale only
# ISSUE 5: the recorded acceptance artifact shows the fused executor at
# >= 2x the per-round driver's rounds/s (see BENCH_ci.json). The CI
# floor sits well below that: the ratio measures dispatch-overhead vs
# compute, and its host sensitivity is large (observed 1.3x-3.2x across
# load regimes of the same 2-vCPU container — XLA:CPU dispatch cost and
# GEMM throughput respond differently to contention) — so the floor
# guards the fused path KEEPING an advantage at all (a de-fused or
# donation-broken executor measures ~1.0x), not the artifact's exact
# figure (DESIGN.md §10).
FUSED_SPEEDUP_FLOOR = 1.2
# ISSUE 5: the bitonic selection kernel must keep the robust path within
# 10x of fedavg latency (speedup = fedavg/trimmed >= 0.1; was ~95x/0.0105
# with the PR 3 rank-select kernel). Quick scale only, like the floors.
ROBUST_RETENTION_FLOOR = 0.1
PEAK_RSS_TOLERANCE = 0.20        # same-host peak-memory regression gate
# ISSUE 6: sharded(8 forced host devices)/single fused throughput ratio.
# On CI the 8 fake devices share the same core(s), so the sharded run
# CANNOT be faster — the ratio measures shard_map partition overhead
# (collective dispatch, smaller fusion windows). Observed ~0.5x on a
# 1-vCPU container; the floor guards the mesh path staying within a
# constant factor of single-device (a broken path — e.g. per-round
# recompiles or host round-trips — measures ~0.05x), not a speedup.
# Quick scale only, floor-only, like the fused gate (DESIGN.md §11).
MESH_RATIO_FLOOR = 0.2
# ISSUE 7: the qsgd acceptance clause — int8 + one float32 scale per
# client must compress the uplink >= 3.5x vs dense float32 (analytic
# ratio from Codec.bytes_on_wire, so it never flaps with host load; the
# actual figure is ~3.998x at CNN scale and dips toward 3.5x only for
# tiny models where the scale amortizes worse). The topk gate has no
# constant floor: its analytic ratio is 0.5/topk_frac exactly, so the
# compare gates against the configured sparsity itself.
QSGD_RATIO_FLOOR = 3.5
# ISSUE 7: the dequantize-and-aggregate reduce must retain a bounded
# fraction of plain-fedavg throughput (retention = fedavg_us /
# dequant_us). Observed ~0.3x on the CPU container — XLA:CPU pays the
# int8->f32 cast + scale multiply without the 4x HBM-read saving the
# kernel banks on TPU — so the floor guards the dispatch staying on the
# jnp/kernel production path at all (routing through the interpret-mode
# grid loop measures ~0.01x), not the TPU roofline. Quick scale only.
DEQUANT_RETENTION_FLOOR = 0.1
# ISSUE 8: telemetry is on by default, so its cost IS the default cost
# of every run — the acceptance clause budgets it at <= 5% rounds/s
# under each engine. The measurement (`kernel_bench.measure_obs`) is
# best-of-3 per toggle, which strips most scheduler noise; the overhead
# itself is host dispatch (span bookkeeping) for loop/vectorized and
# the in-scan counter lanes for fused.
OBS_OVERHEAD_TOLERANCE = 0.05
# ISSUE 9: the serving engine's steady-state dispatch throughput
# (requests/s at full micro-batch occupancy, best-of-N wall clock).
# Observed ~4000/s on the CPU container; the floor guards the dispatch
# staying ONE compiled padded-batch call — a shape-unstable dispatch
# recompiling per batch measures ~10/s, interpret-mode fallback ~100/s —
# not the container's absolute figure. Quick scale only, like the
# other floors.
SERVE_QPS_FLOOR = 200.0
# ISSUE 9: virtual-clock tail latency of the default serve config
# (qps=64, batch=8, max_wait=50ms, affine service model). The number is
# DETERMINISTIC in (trace, config) — observed exactly 61.0ms — so
# unlike the wall-clock floors this ceiling cannot flap with host load;
# headroom covers intentional config retunes, while a batching-policy
# regression (e.g. a broken max_wait trigger parking requests until the
# batch fills) overshoots it by integer factors.
SERVE_P99_CEILING_MS = 100.0
# ISSUE 10: fault plumbing must be free when off. profile="none"
# compiles no schedule and every fault seam is a host-level `if`, so
# the fused traced program is bitwise-identical to a pre-fault build —
# the gate holds the none-profile fused rounds/s to within 5% of the
# committed baseline's fused throughput (same measure_fused protocol
# shape; same-host + same-scale only, like the driver-overhead gate).
CHURN_PLUMBING_TOLERANCE = 0.05
# ISSUE 10: the 30%-churn acceptance scenario (colluding sign-flip
# neighborhoods on the degree-4 gossip ring, median defense, moving-
# target re-randomization) must keep a macro-F1 floor. The scenario is
# fully deterministic in (seed, config) — observed 0.277
# (experiments/churn/) — so like the serve p99 ceiling this cannot
# flap with host load; the floor sits under the observed figure with
# headroom for cross-platform fp drift, while a broken degraded path
# (NaN holds, wrong quorum masking, MTD silently pinned to the static
# ring) lands far below it — the static twin measures 0.071.
CHURN_ACCEPT_F1_FLOOR = 0.2


def bench_sync(clients, rounds):
    """Seconds/round of the synchronous engines — the measurement is
    `kernel_bench.measure_sync_round`, shared with the engine sweep so
    the gate can never drift from the protocol it claims to track."""
    from benchmarks.kernel_bench import measure_sync_round
    per = measure_sync_round(clients, rounds)
    return {
        "loop_round_s": per["loop"],
        "vectorized_round_s": per["vectorized"],
        "loop_rounds_per_s": 1.0 / per["loop"],
        "vectorized_rounds_per_s": 1.0 / per["vectorized"],
        "speedup": per["loop"] / per["vectorized"],
    }


def bench_async(clients, updates):
    """Merge throughput of the tick-batched async runtime — the
    measurement is `kernel_bench.measure_async`, shared with the async
    engine sweep (and the 64-client acceptance measurement)."""
    from benchmarks.kernel_bench import measure_async
    per = measure_async(clients, updates)
    return {
        "merges": per["loop"].merges,
        "batches": per["loop"].batches,
        "loop_build_s": per["loop"].build_time_s,
        "vectorized_build_s": per["vectorized"].build_time_s,
        "loop_merges_per_s": per["loop"].merges / per["loop"].build_time_s,
        "vectorized_merges_per_s": (per["vectorized"].merges
                                    / per["vectorized"].build_time_s),
        "speedup": (per["loop"].build_time_s
                    / per["vectorized"].build_time_s),
    }


def bench_robust(clients):
    """Robust trimmed-mean vs plain fedavg aggregation throughput — the
    measurement is `kernel_bench.measure_robust` (ISSUE 3 sweep), shared
    like the other helpers. The gated `speedup` is fedavg/trimmed: the
    fraction of linear-aggregation throughput the robust path retains
    (guards against e.g. accidentally routing the CPU path through the
    interpret-mode selection kernel)."""
    from benchmarks.kernel_bench import measure_robust
    return measure_robust(clients)


def bench_comm(clients):
    """Upload-codec compression ratios (analytic, from
    `Codec.bytes_on_wire` at paper-CNN dimension) + the fused
    dequantize-and-aggregate reduce vs plain fedavg — the measurement is
    `kernel_bench.measure_comm`, shared like the other helpers
    (DESIGN.md §12)."""
    from benchmarks.kernel_bench import measure_comm
    return measure_comm(clients)


def bench_obs(clients, rounds):
    """Per-engine telemetry overhead (ISSUE 8): each engine run with
    `FLConfig.telemetry` on and off, best-of-3; `overhead` = on/off - 1
    is what `compare` holds to `OBS_OVERHEAD_TOLERANCE`. The measurement
    is `kernel_bench.measure_obs`, shared like the other helpers."""
    from benchmarks.kernel_bench import measure_obs
    return measure_obs(clients, rounds)


def bench_serve(clients):
    """Serving engine steady state (ISSUE 9): wall-clock requests/s of
    the compiled padded-batch dispatch + the deterministic virtual-clock
    p99/shed numbers — the measurement is `kernel_bench.measure_serve`,
    shared like the other helpers (DESIGN.md §14)."""
    from benchmarks.kernel_bench import measure_serve
    return measure_serve(min(clients, 16))


def bench_churn(clients, rounds):
    """Fault-injection section (ISSUE 10): the none-vs-churn fused
    round-throughput instrument (`kernel_bench.measure_churn`) plus the
    deterministic 30%-churn acceptance scenario's macro-F1 — the two
    numbers `compare` gates (plumbing-free-when-off, acceptance floor)."""
    from benchmarks.kernel_bench import measure_churn
    from repro.core import scenarios
    out = measure_churn(clients, rounds)
    res = scenarios.run_scenario("churn-signflip-median-mtd")
    out["accept_scenario"] = "churn-signflip-median-mtd"
    out["accept_f1"] = res["metrics"]["f1"]
    out["accept_test_accuracy"] = res["metrics"]["test_accuracy"]
    out["accept_faults"] = {k: res["faults"][k] for k in
                            ("quorum_failures", "degraded_rounds",
                             "rejoins", "mean_alive_frac")}
    return out


def bench_fused(clients, rounds):
    """Fused-executor vs vectorized per-round throughput at minimal
    local compute (the executor-overhead instrument — see
    `kernel_bench.measure_fused` for the protocol rationale), plus the
    robust-kernel latency references the ISSUE 5 acceptance tracks
    alongside it (fused rounds run defended aggregation in-scan, so the
    selection kernel's latency IS hot-path latency there)."""
    from benchmarks.kernel_bench import measure_fused
    return measure_fused(clients, rounds)


def bench_mesh(clients):
    """Sharded-vs-single fused round throughput at 8 forced host
    devices, measured by `benchmarks.mesh_bench` in a fresh subprocess
    (the forced-device-count XLA flag must precede the jax import, and
    this process imported jax long ago). Subprocess RSS does not count
    toward this process's ru_maxrss, so running it after the RSS sample
    changes nothing — but the fused sections stay adjacent on purpose.

    The child is pinned to the CPU (JAX_PLATFORMS=cpu): it is a
    forced-host-device check, and on a machine with an accelerator this
    process already holds the chip, so a child that reached for it
    would fail or hang."""
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.mesh_bench", "--devices", "8",
         "--clients", str(clients), "--rounds", "4"],
        capture_output=True, text=True, timeout=900, cwd=repo,
        env=dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
                 JAX_PLATFORMS="cpu"))
    if out.returncode != 0:
        raise RuntimeError(f"mesh_bench failed: {out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _peak_rss_mb():
    """Peak RSS of this process in MiB (ru_maxrss is KiB on Linux).
    Sampled immediately after the fused/vectorized bench phase so the
    high-water mark reflects the stacked-engine buffer discipline the
    donation gate guards, not whichever later phase allocates most."""
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def run(scale):
    from repro.core import scenarios
    cfg = SCALES[scale]
    C = cfg["clients"]
    print(f"ci_bench scale={scale} clients={C}", flush=True)
    # per-section peak-RSS attribution (ISSUE 8 satellite): ru_maxrss is
    # a monotone process high-water mark, so the DELTA at each section
    # boundary says how much that section raised the peak (0 = it fit
    # inside an earlier section's envelope). This localizes a memory
    # regression to a phase; the process-level `host.peak_rss_mb` below
    # keeps its original sampling point for baseline back-compat.
    rss_sections = {}
    _rss_prev = [_peak_rss_mb()]

    def _rss_mark(name):
        cur = _peak_rss_mb()
        rss_sections[name] = {"peak_rss_mb": round(cur, 3),
                              "delta_mb": round(cur - _rss_prev[0], 3)}
        _rss_prev[0] = cur

    # the fused section runs FIRST and peak RSS is sampled right after
    # it: the donation satellite guards the stacked-engine/fused buffer
    # discipline, and ru_maxrss is a whole-process high-water mark —
    # sampled at the end it would be set by whichever later phase (the
    # loop-engine benches, the scenario grid) allocates most, masking
    # exactly the regression this gate exists for
    fus = bench_fused(C, cfg["fused_rounds"])
    print(f"  fused c{C}: per-round {fus['per_round_s']:.2f}s/round, "
          f"fused {fus['fused_round_s']:.2f}s/round "
          f"({fus['speedup']:.2f}x)", flush=True)
    _rss_mark("fused")
    chunked = None
    if scale == "quick":
        # ISSUE 6 memory-bounded path: the chunked fused round at 1024
        # clients runs BEFORE the RSS sample so the same-host peak-memory
        # envelope covers the large-C stack (chunk=128 holds it at
        # ~1.3 GiB vs ~3.6 GiB unchunked — see measure_fused_chunked)
        from benchmarks.kernel_bench import measure_fused_chunked
        chunked = measure_fused_chunked(1024)
        print(f"  fused-chunked c{chunked['clients']} "
              f"chunk={chunked['chunk']}: "
              f"{chunked['fused_round_s']:.2f}s/round", flush=True)
        _rss_mark("fused_chunked")
    peak_rss_mb = _peak_rss_mb()
    mesh = bench_mesh(C) if scale == "quick" else None
    if mesh:
        print(f"  mesh  c{C}x8dev: single {mesh['single_round_s']:.2f}"
              f"s/round, sharded {mesh['sharded_round_s']:.2f}s/round "
              f"(ratio {mesh['sharded_single_ratio']:.2f}x)", flush=True)
        _rss_mark("mesh")
    sync = bench_sync(C, cfg["sync_rounds"])
    print(f"  sync  c{C}: loop {sync['loop_round_s']:.2f}s/round, "
          f"vectorized {sync['vectorized_round_s']:.2f}s/round "
          f"({sync['speedup']:.2f}x)", flush=True)
    _rss_mark("sync")
    asy = bench_async(C, cfg["updates"])
    print(f"  async c{C}: loop {asy['loop_build_s']:.2f}s, "
          f"vectorized {asy['vectorized_build_s']:.2f}s for "
          f"{asy['merges']} merges ({asy['speedup']:.2f}x)", flush=True)
    _rss_mark("async")
    rob = bench_robust(C)
    print(f"  robust c{C}: trimmed {rob['trimmed_us']:.0f}us vs fedavg "
          f"{rob['fedavg_us']:.0f}us ({rob['speedup']:.3f}x)", flush=True)
    _rss_mark("robust")
    fus["robust_trimmed_us"] = rob["trimmed_us"]
    fus["robust_fedavg_us"] = rob["fedavg_us"]
    comm = bench_comm(C)
    print(f"  comm  c{C}: dequant {comm['dequant_us']:.0f}us vs fedavg "
          f"{comm['fedavg_us']:.0f}us "
          f"(retention {comm['retention']:.3f}x); "
          f"qsgd {comm['qsgd_ratio']:.2f}x, "
          f"topk {comm['topk_ratio']:.2f}x uplink compression", flush=True)
    _rss_mark("comm")
    # the telemetry-overhead instrument runs at a fixed small shape (16
    # clients caps it even at quick scale): the overhead is a RATIO of
    # the same protocol with the toggle flipped, so the client count
    # only needs to be big enough for the span/counter cost to register
    # against real per-round work, not to match the headline scale
    obs = bench_obs(min(C, 16), 4)
    for eng in ("loop", "vectorized", "fused"):
        o = obs[eng]
        print(f"  obs   {eng}: on {o['on_rounds_per_s']:.2f} r/s, "
              f"off {o['off_rounds_per_s']:.2f} r/s "
              f"(overhead {o['overhead']:+.1%})", flush=True)
    _rss_mark("obs")
    # the serving instrument is fixed-shape like obs: the gated numbers
    # are a compiled-dispatch floor and a deterministic virtual p99,
    # neither of which sharpens with client count
    srv = bench_serve(C)
    print(f"  serve batch={srv['batch']}: "
          f"{srv['requests_per_s']:.0f} req/s wall-clock "
          f"({srv['dispatch_us']:.0f}us/dispatch), "
          f"virtual p99 {srv['virtual_p99_ms']:.1f}ms, "
          f"shed {srv['shed_rate']:.1%}", flush=True)
    _rss_mark("serve")
    # the churn section runs the acceptance scenario (32 clients, 10
    # rounds) besides the throughput instrument, so quick scale only —
    # mirroring the mesh/chunked sections
    churn = bench_churn(C, cfg["fused_rounds"]) if scale == "quick" \
        else None
    if churn:
        print(f"  churn c{C}: none {churn['none_round_s']:.2f}s/round, "
              f"churn {churn['churn_round_s']:.2f}s/round "
              f"(active overhead {churn['active_overhead']:+.1%}); "
              f"accept f1={churn['accept_f1']:.3f}", flush=True)
        _rss_mark("churn")
    grid = {}
    for name in scenarios.CI_SMOKE_GRID:
        res = scenarios.run_scenario(name)
        grid[name] = res
        print(f"  scenario {name}: "
              f"test_acc={res['metrics']['test_accuracy']:.3f} "
              f"rounds_per_s={res['timing']['rounds_per_s']:.3f}",
              flush=True)
    _rss_mark("scenarios")
    doc = {
        "schema_version": SCHEMA_VERSION,
        "scale": scale,
        "clients": C,
        "host": {"cpus": os.cpu_count(), "peak_rss_mb": peak_rss_mb,
                 "rss_sections": rss_sections},
        "sync": sync,
        "async": asy,
        "robust": rob,
        "fused": fus,
        "comm": comm,
        "obs": obs,
        "serve": srv,
        "scenarios": grid,
    }
    if chunked is not None:
        doc["fused_chunked"] = chunked
    if mesh is not None:
        doc["mesh"] = mesh
    if churn is not None:
        doc["churn"] = churn
    return doc


def compare(new, baseline, tolerance=0.25, driver_tolerance=0.05):
    """Gate the run against the committed baseline. Returns a list of
    failure strings (empty = pass). The "robust"/"fused" sections gate
    only when both documents carry them (older baselines don't)."""
    failures = []
    # "fused" is deliberately NOT in the baseline-relative ratio loop:
    # its ratio swings ~2x with host speed/load (see FUSED_SPEEDUP_FLOOR
    # note), so a baseline recorded near the top of that band would set
    # an unreachable effective bar; the floor below is its only gate.
    for section in ("sync", "async", "robust"):
        if section == "robust" and not (section in new
                                        and section in baseline):
            continue
        got = new[section]["speedup"]
        want = baseline[section]["speedup"]
        if got < want * (1.0 - tolerance):
            failures.append(
                f"{section} throughput regression: "
                f"speedup {got:.2f}x < baseline {want:.2f}x - {tolerance:.0%}")
    # driver-overhead gate (ISSUE 4): the generic round driver must keep
    # >=95% of the baseline's ABSOLUTE sync round throughput per engine.
    # Unlike the dimensionless speedup ratios above, this compares raw
    # throughput, so it only gates when both documents were measured at
    # the same scale on a host with the same core count (otherwise
    # hardware changes, not driver overhead, would trip it).
    same_host = (new.get("host", {}).get("cpus")
                 == baseline.get("host", {}).get("cpus")
                 and new.get("scale") == baseline.get("scale"))
    if same_host:
        for key in ("loop_rounds_per_s", "vectorized_rounds_per_s"):
            got = new["sync"].get(key)
            want = baseline["sync"].get(key)
            if got and want and got < want * (1.0 - driver_tolerance):
                failures.append(
                    f"driver overhead regression: sync {key} "
                    f"{got:.4f}/s < baseline {want:.4f}/s "
                    f"- {driver_tolerance:.0%}")
    if new["scale"] == "quick" and new["async"]["speedup"] < ASYNC_SPEEDUP_FLOOR:
        failures.append(
            f"async speedup {new['async']['speedup']:.2f}x below the "
            f"{ASYNC_SPEEDUP_FLOOR}x acceptance floor at 64 clients")
    if new["scale"] == "quick" and "fused" in new:
        if new["fused"]["speedup"] < FUSED_SPEEDUP_FLOOR:
            failures.append(
                f"fused speedup {new['fused']['speedup']:.2f}x below the "
                f"{FUSED_SPEEDUP_FLOOR}x floor at 64 clients")
    if new["scale"] == "quick" and "mesh" in new:
        ratio = new["mesh"]["sharded_single_ratio"]
        if ratio < MESH_RATIO_FLOOR:
            failures.append(
                f"mesh-sharded fused ratio {ratio:.2f}x below the "
                f"{MESH_RATIO_FLOOR}x floor (sharded run must stay "
                f"within a constant factor of single-device on forced "
                f"host devices)")
    if new["scale"] == "quick" and "robust" in new:
        if new["robust"]["speedup"] < ROBUST_RETENTION_FLOOR:
            failures.append(
                f"robust retention {new['robust']['speedup']:.3f}x below "
                f"the {ROBUST_RETENTION_FLOOR}x floor (trimmed-mean must "
                f"stay within 10x of fedavg latency)")
    if new["scale"] == "quick" and "comm" in new:
        comm = new["comm"]
        if comm["qsgd_ratio"] < QSGD_RATIO_FLOOR:
            failures.append(
                f"qsgd uplink compression {comm['qsgd_ratio']:.2f}x below "
                f"the {QSGD_RATIO_FLOOR}x acceptance floor")
        # topk's ratio is analytic (0.5/frac): anything under the
        # configured sparsity's own ratio means the wire-cost model broke
        want_topk = 0.5 / comm["topk_frac"]
        if comm["topk_ratio"] < want_topk * (1.0 - 1e-6):
            failures.append(
                f"topk uplink compression {comm['topk_ratio']:.2f}x below "
                f"the configured sparsity's {want_topk:.2f}x ratio")
        if comm["retention"] < DEQUANT_RETENTION_FLOOR:
            failures.append(
                f"dequant-aggregate retention {comm['retention']:.3f}x "
                f"below the {DEQUANT_RETENTION_FLOOR}x floor (fedavg/"
                f"dequant must stay on the production dispatch path)")
    # telemetry-overhead gate (ISSUE 8): on-by-default telemetry must
    # cost <= OBS_OVERHEAD_TOLERANCE rounds/s under every engine. The
    # overhead is a same-host same-run ratio (on/off of the identical
    # protocol, best-of-3 each), so it gates unconditionally at quick
    # scale — no baseline or same-host qualifier needed. Gated on the
    # section's presence so pre-ISSUE-8 baselines don't change behavior.
    if new["scale"] == "quick" and "obs" in new:
        for eng, o in sorted(new["obs"].items()):
            if o["overhead"] > OBS_OVERHEAD_TOLERANCE:
                failures.append(
                    f"telemetry overhead {o['overhead']:+.1%} under the "
                    f"{eng} engine exceeds the "
                    f"{OBS_OVERHEAD_TOLERANCE:.0%} budget "
                    f"(on {o['on_rounds_per_s']:.2f} r/s vs off "
                    f"{o['off_rounds_per_s']:.2f} r/s)")
    # serving gates (ISSUE 9): requests/s floor guards the dispatch
    # staying one compiled padded-batch call; the p99 ceiling is a
    # deterministic virtual-clock number, so it gates unconditionally at
    # quick scale with no baseline/same-host qualifier. Presence-gated
    # so pre-ISSUE-9 baselines don't change behavior.
    if new["scale"] == "quick" and "serve" in new:
        srv = new["serve"]
        if srv["requests_per_s"] < SERVE_QPS_FLOOR:
            failures.append(
                f"serving dispatch throughput {srv['requests_per_s']:.0f} "
                f"req/s below the {SERVE_QPS_FLOOR:.0f} req/s floor "
                f"(padded-batch dispatch must stay one compiled call)")
        if srv["virtual_p99_ms"] > SERVE_P99_CEILING_MS:
            failures.append(
                f"serving virtual p99 {srv['virtual_p99_ms']:.1f}ms above "
                f"the {SERVE_P99_CEILING_MS:.0f}ms ceiling (deterministic "
                f"batching-policy tail latency regressed)")
    # fault-injection gates (ISSUE 10): (a) the none-profile fused run
    # must keep >= 95% of the baseline fused throughput — profile="none"
    # is structurally inert, so any loss here is fault plumbing leaking
    # into the hot path. Baseline-relative ABSOLUTE throughput, so
    # same-host + same-scale only (driver-overhead gate pattern); a
    # pre-ISSUE-10 baseline's own "fused" section serves as the
    # reference, since measure_churn's none arm replays that protocol.
    # (b) the deterministic 30%-churn acceptance macro-F1 floor gates
    # unconditionally at quick scale when the section is present.
    if new["scale"] == "quick" and "churn" in new:
        if same_host:
            want = (baseline.get("churn", {}).get("none_rounds_per_s")
                    or baseline.get("fused", {}).get("fused_rounds_per_s"))
            got = new["churn"]["none_rounds_per_s"]
            if want and got < want * (1.0 - CHURN_PLUMBING_TOLERANCE):
                failures.append(
                    f"fault-plumbing overhead: none-profile fused "
                    f"{got:.4f} rounds/s < baseline {want:.4f} rounds/s "
                    f"- {CHURN_PLUMBING_TOLERANCE:.0%} (profile='none' "
                    f"must stay structurally inert)")
        if new["churn"]["accept_f1"] < CHURN_ACCEPT_F1_FLOOR:
            failures.append(
                f"churn acceptance macro-F1 "
                f"{new['churn']['accept_f1']:.3f} below the "
                f"{CHURN_ACCEPT_F1_FLOOR} floor "
                f"({new['churn']['accept_scenario']} at 30% churn with "
                f"moving-target re-randomization)")
    # peak-memory gate (ISSUE 5 donation satellite): raw RSS is not
    # portable across hardware/scale, so gate same-host only, like the
    # driver-overhead gate
    if same_host:
        got = new.get("host", {}).get("peak_rss_mb")
        want = baseline.get("host", {}).get("peak_rss_mb")
        if got and want and got > want * (1.0 + PEAK_RSS_TOLERANCE):
            failures.append(
                f"peak-memory regression: {got:.0f} MiB > baseline "
                f"{want:.0f} MiB + {PEAK_RSS_TOLERANCE:.0%}")
    missing = [n for n in baseline.get("scenarios", {})
               if n not in new["scenarios"]]
    if missing:
        failures.append(f"scenario grid lost coverage: {missing}")
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", default="quick", choices=sorted(SCALES))
    ap.add_argument("--out", default="BENCH_ci.json")
    ap.add_argument("--baseline", default=None,
                    help="committed baseline JSON to compare against")
    ap.add_argument("--tolerance", type=float, default=0.25)
    ap.add_argument("--driver-tolerance", type=float, default=0.05,
                    help="max generic-driver round-throughput loss vs "
                         "the baseline's absolute sync rounds/s (same "
                         "host + scale only)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on regression vs the baseline")
    args = ap.parse_args(argv)

    doc = run(args.scale)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {args.out}")

    if args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)
        failures = compare(doc, base, args.tolerance,
                           args.driver_tolerance)
        for msg in failures:
            print(f"REGRESSION: {msg}", file=sys.stderr)
        if failures:
            print(f"{len(failures)} regression(s) vs {args.baseline}",
                  file=sys.stderr)
            if args.check:
                return 1
        else:
            print(f"no regression vs {args.baseline} "
                  f"(tolerance {args.tolerance:.0%})")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
