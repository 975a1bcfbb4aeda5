"""Chip benchmark: one run of one cell of `BENCHMARK.json`.

    python3 chip_bench/run.py --workload mnist_c10.hfl --seed 7 \
        --seconds 20 --trace 0

Set-up renders the cell's data set from `--seed` (by the configuration's
model family, `families/<arch>.py`) and makes one whole
federation run of the cell (compiling it, or reading it from JAX's
persistent compilation cache). The measured window then makes whole
runs back to back through the program's public entry,
`repro.api.FederatedSimulation(FLConfig(engine="fused", ...), data)
.run()`, each a new simulation on the same config and seed, until
`--seconds` have passed; the run in flight is finished. Afterwards the
plain reference (`reference/federation.py`, with the family's reference
model) runs the same federation once, and every run of the window is
compared with it (`compare.py`).

The last line of standard output is one JSON object: `correct`,
`attempted` (runs in the window), `failed` (runs that broke a limit),
`metrics` (the cell's end-to-end metrics with `--trace 0`, its
per-layer metrics with `--trace 1`), `device`, with `--trace 1` a
`breakdown`, and last the compared numbers beside their limits
(`checks`), which also end standard error. Off a TPU, or with fewer
chips than the cell asks for, it exits with code 2 and prints no result.

A `--trace 1` run writes its profiler trace under `<checkout>/.bench_trace`
and deletes it once reduced; with `CHIP_BENCH_KEEP_TRACE=1` set it keeps
it (`tests/trim_trace.py` cuts test fixtures from such a trace).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chip_bench import cells as cells_mod  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
# a traced window stops after the run that passes this many seconds (or
# `--seconds`, if shorter): it holds at least one whole run, and the
# trace of a cell whose runs are short stays a few hundred MB at most
TRACE_WINDOW_S = 6.0
GIB = 2 ** 30


def enable_compile_cache():
    """JAX's persistent compilation cache at `$JAX_COMPILATION_CACHE_DIR`
    or the fixed `<checkout>/.jax_cache`, keeping every compiled program
    however quick its compile, so only a checkout's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Compiles requested and persistent-cache hits, from JAX's
    monitoring events; misses = requests - hits. JAX keeps listeners for
    the life of the process, so there is one counter per process."""

    _instance = None

    def __init__(self):
        import jax
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits


class _Both:
    def __init__(self, *cms):
        self.cms = cms

    def __enter__(self):
        for c in self.cms:
            c.__enter__()
        return self

    def __exit__(self, *exc):
        for c in reversed(self.cms):
            c.__exit__(*exc)
        return False


def _annotate_spans(tel):
    """Mirror the program's telemetry spans into the profiler trace as
    `prog.<name>` annotations (the instance is patched, not the class)."""
    import jax
    orig = tel.span

    def span(name, cat=None, **args):
        return _Both(orig(name, cat, **args),
                     jax.profiler.TraceAnnotation("prog." + name))
    tel.span = span


def one_run(cell, dataset, seed, trace):
    """One whole federation run through the public entry. Returns the
    host timings, the program's run-level spans and counters, and the
    results that the comparison reads (as numpy)."""
    import jax
    from repro import api
    from chip_bench.reference.federation import flat
    ann = (jax.profiler.TraceAnnotation if trace
           else (lambda _n: contextlib.nullcontext()))
    t0 = time.perf_counter()
    with ann("bench.construct"):
        sim = api.FederatedSimulation(api.FLConfig(**cell.fl_kwargs(seed)),
                                      dataset)
    if trace:
        _annotate_spans(sim.telemetry)
    t1 = time.perf_counter()
    with ann("bench.run"):
        res = sim.run()
    t2 = time.perf_counter()
    with ann("bench.collect"):
        final = sim.strategy.round_model(sim.final_state)
        tel = res.extra["telemetry"]
        out = {
            "construct_s": t1 - t0, "run_s": t2 - t1,
            "spans": {k: v["total_s"] for k, v in
                      tel.get("run", {}).items()},
            "counters": dict(tel.get("counters", {})),
            "round_loss": list(res.round_train_loss),
            "round_test_acc": list(res.round_test_acc),
            "final": flat(final),
        }
        del sim, res, final
        gc.collect()
    out["wall_s"] = time.perf_counter() - t0
    return out


def device_info(chips):
    import jax
    devs = jax.devices()
    peak = None
    stats = [d.memory_stats() for d in devs[:chips]]
    if all(s is not None for s in stats):
        peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(cell, seed, seconds, trace, *, t_start=T_START):
    """Set-up, the measured window, the comparison; returns the result
    object. Checks for no chip: `main` does that."""
    import jax
    from chip_bench import compare, costs
    from chip_bench.reference import federation as ref_mod

    counter = CompileCounter.get()
    prog_seed = seed & 0xFFFFFFFF
    dataset = cell.family.render(cell.config["data"], prog_seed)
    one_run(cell, dataset, prog_seed, trace=False)        # warm-up
    setup_s = time.perf_counter() - t_start

    req0, hit0 = counter.snapshot()
    logdir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        logdir = str(TRACE_DIR)
        jax.profiler.start_trace(logdir)
    runs = []
    target = min(seconds, TRACE_WINDOW_S) if trace else seconds
    with (jax.profiler.TraceAnnotation("bench.window") if trace
          else contextlib.nullcontext()):
        w0 = time.perf_counter()
        while True:
            runs.append(one_run(cell, dataset, prog_seed, trace))
            if time.perf_counter() - w0 >= target:
                break
        window_s = time.perf_counter() - w0
    if trace:
        jax.profiler.stop_trace()
    req1, hit1 = counter.snapshot()
    missed = (req1 - req0) - (hit1 - hit0)
    print(f"window: {len(runs)} runs in {window_s:.3f} s; compiles that "
          f"missed the cache: {missed}; run seconds "
          f"{[round(r['wall_s'], 3) for r in runs]}", file=sys.stderr,
          flush=True)
    device = device_info(cell.chips)

    # the reference, once the window has closed and the program's state
    # is freed
    gc.collect()
    ref = ref_mod.run(cell.spec, dataset, prog_seed,
                      cell.family.reference_model())
    run_gaps = [compare.gaps(r, ref) for r in runs]
    checks, failed = compare.judge(run_gaps, cell.limits)
    for n in compare.NUMBERS:
        print(f"reading {n}: {max(g[n] for g in run_gaps)!r}",
              file=sys.stderr)

    work = costs.run_work(cell.spec,
                          cell.family.forward_flops(cell.config["model"]))
    result = {"correct": failed == 0 and bool(runs),
              "attempted": len(runs), "failed": failed}
    if trace:
        from chip_bench import trace as trace_mod
        red = trace_mod.reduce(trace_mod.load(trace_mod.find_xplane(logdir)))
        if not os.environ.get("CHIP_BENCH_KEEP_TRACE"):
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = {"cell": cell, "spec": cell.spec, "runs": runs,
               "window_s": window_s, "chips": cell.chips, "work": work,
               "peaks": costs.peaks(device["kind"]), "trace": red}
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = trace_mod.breakdown(red)
    else:
        samples = work["client_samples"] * len(runs)
        values = {"setup_s": setup_s,
                  "client_samples_per_s": samples / window_s,
                  "peak_hbm_gib": (device["memory_peak_bytes"] / GIB
                                   if device["memory_peak_bytes"] else None)}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells_mod.load(args.workload)
    enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_bench needs a TPU; JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips; JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
