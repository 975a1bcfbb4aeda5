"""Observability subsystem (DESIGN.md §13).

Five invariant families:

* the tracer itself — span recording, categories, suppress scoping,
  profiler annotations, thread safety;
* the Chrome-trace exporter — every produced trace passes the format
  validator (matched B/E stacks, monotone per-track ts), and the
  validator actually rejects malformed documents;
* BITWISE result parity with telemetry on vs off, under all three
  engines — telemetry is on by default, so it must be a pure observer
  (the in-scan counters read existing scan values, never feed back);
* the result-document contract — schema v2.3's `telemetry` block, the
  warmup/steady timing split, and `load_result` back-compat for
  v1-v2.2 documents;
* what the fused executor shows a profiler — its run spans as
  `prog.<name>` host events, its round phases as named scopes in the
  compiled program, and compile counters credited to one run.
"""
import contextlib
import glob
import json
import re
import threading

import jax
import numpy as np
import pytest

from repro.core.fl_types import FLConfig
from repro.core.simulation import FederatedSimulation
from repro.data.synthetic import mnist_like
from repro.obs import (Telemetry, chrome_trace, profiler_trace,
                       result_block, validate_chrome_trace,
                       write_chrome_trace)


@pytest.fixture(scope="module")
def obs_ds():
    # 8 clients x 32 samples, shard-divisible (the §4 parity regime)
    return mnist_like(seed=0, n_train=256, n_test=128)


def _cfg(engine, **kw):
    base = dict(num_clients=8, num_groups=2, rounds=2, local_epochs=1,
                local_batch_size=8, lr=0.05, seed=0, participation=1.0)
    base.update(kw)
    return FLConfig(engine=engine, **base)


# ---------------------------------------------------------------------------
# tracer unit tests
# ---------------------------------------------------------------------------

def test_span_records_name_cat_duration():
    tel = Telemetry()
    with tel.span("local_train", k=4):
        pass
    with tel.span("warmup", cat="run"):
        pass
    assert [s["name"] for s in tel.spans] == ["local_train", "warmup"]
    assert tel.spans[0]["cat"] == "phase"       # default category
    assert tel.spans[0]["args"] == {"k": 4}
    assert tel.spans[1]["cat"] == "run"
    for s in tel.spans:
        assert s["dur_us"] >= 0.0 and s["ts_us"] >= 0.0


def test_disabled_telemetry_records_nothing():
    tel = Telemetry(enabled=False)
    with tel.span("x"):
        pass
    tel.counter("c", 3)
    tel.append_series("s", 1.0)
    tel.record_series("r", [1.0, 2.0])
    assert not tel.spans and not tel.counters and not tel.series
    assert not tel.active
    assert result_block(tel) == {"enabled": False}


def test_suppress_mutes_everything():
    tel = Telemetry()
    with tel.suppress():
        with tel.span("hidden"):
            pass
        tel.counter("c")
        tel.append_series("s", 1.0)
    assert not tel.spans and not tel.counters and not tel.series
    with tel.span("visible"):
        pass
    assert [s["name"] for s in tel.spans] == ["visible"]


class _Annotations:
    """A stand-in for `jax.profiler.TraceAnnotation` that logs entries
    and exits."""

    def __init__(self):
        self.log = []

    @contextlib.contextmanager
    def __call__(self, name):
        self.log.append(("enter", name))
        yield
        self.log.append(("exit", name))


def test_span_enters_prog_annotation():
    ann = _Annotations()
    tel = Telemetry(annotate=ann)
    with tel.span("warmup", cat="run"):
        with tel.span("lower", cat="run"):
            pass
    assert ann.log == [("enter", "prog.warmup"), ("enter", "prog.lower"),
                       ("exit", "prog.lower"), ("exit", "prog.warmup")]
    assert [s["name"] for s in tel.spans] == ["lower", "warmup"]


@pytest.mark.parametrize("mute", ["suppressed", "disabled"])
def test_muted_span_enters_no_annotation(mute):
    ann = _Annotations()
    tel = Telemetry(enabled=mute != "disabled", annotate=ann)
    if mute == "suppressed":
        with tel.suppress(), tel.span("hidden"):
            pass
    else:
        with tel.span("hidden"):
            pass
    assert ann.log == [] and tel.spans == []


def test_counters_and_series_accumulate():
    tel = Telemetry()
    tel.counter("codec.uplink_bytes", 100)
    tel.counter("codec.uplink_bytes", 50)
    tel.append_series("participants", 4)
    tel.append_series("participants", 6)
    tel.record_series("scan.attackers", np.float32([1, 2]))
    assert tel.counters == {"codec.uplink_bytes": 150.0}
    assert tel.series["participants"] == [4.0, 6.0]
    assert tel.series["scan.attackers"] == [1.0, 2.0]


def test_summary_groups_by_name_within_category():
    tel = Telemetry()
    for _ in range(3):
        with tel.span("eval"):
            pass
    with tel.span("classify", cat="run"):
        pass
    phases = tel.summary("phase")
    assert set(phases) == {"eval"}
    assert phases["eval"]["count"] == 3
    assert phases["eval"]["mean_s"] == pytest.approx(
        phases["eval"]["total_s"] / 3)
    assert set(tel.summary("run")) == {"classify"}


def test_tracer_is_thread_safe():
    tel = Telemetry()

    def work():
        for i in range(200):
            with tel.span("t"):
                pass
            tel.counter("n")
            tel.append_series("s", i)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tel.spans) == 800
    assert tel.counters["n"] == 800.0
    assert len(tel.series["s"]) == 800
    assert not validate_chrome_trace(chrome_trace(tel))


# ---------------------------------------------------------------------------
# chrome-trace exporter + validator
# ---------------------------------------------------------------------------

def test_chrome_trace_structure_and_flows():
    tel = Telemetry()
    with tel.span("round", cat="run", flow="rounds"):
        with tel.span("local_train"):
            pass
    with tel.span("round", cat="run", flow="rounds"):
        pass
    tel.append_series("participants", 4)
    doc = chrome_trace(tel)
    assert validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    phs = [e["ph"] for e in evs]
    # named process + one thread_name per track (run, local_train,
    # counters), B/E pairs, a 2-point flow (s then f), one counter sample
    assert phs.count("M") == 4
    assert phs.count("B") == 3 and phs.count("E") == 3
    assert phs.count("s") == 1 and phs.count("f") == 1
    assert phs.count("C") == 1
    # the flow arg is consumed by the exporter, not emitted as a span arg
    b_args = [e["args"] for e in evs if e["ph"] == "B"]
    assert all("flow" not in a for a in b_args)
    assert json.loads(json.dumps(doc)) == doc     # JSON-serializable


def test_chrome_trace_empty_run_is_valid():
    assert validate_chrome_trace(chrome_trace(Telemetry())) == []


def test_validator_rejects_malformed_documents():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": "no"}) != []
    base = {"pid": 1, "tid": 1}
    # ts goes backwards on one track
    doc = {"traceEvents": [
        {"name": "a", "ph": "B", "ts": 10.0, "args": {}, **base},
        {"name": "a", "ph": "E", "ts": 5.0, **base}]}
    assert any("backwards" in e for e in validate_chrome_trace(doc))
    # E without a matching open B
    doc = {"traceEvents": [{"name": "a", "ph": "E", "ts": 1.0, **base}]}
    assert any("no open B" in e for e in validate_chrome_trace(doc))
    # B/E name mismatch (stack discipline)
    doc = {"traceEvents": [
        {"name": "a", "ph": "B", "ts": 1.0, "args": {}, **base},
        {"name": "b", "ph": "E", "ts": 2.0, **base}]}
    assert any("does not match" in e for e in validate_chrome_trace(doc))
    # unclosed B
    doc = {"traceEvents": [
        {"name": "a", "ph": "B", "ts": 1.0, "args": {}, **base}]}
    assert any("unclosed" in e for e in validate_chrome_trace(doc))
    # unknown phase letter / missing keys
    doc = {"traceEvents": [{"name": "a", "ph": "Z", "ts": 1.0, **base}]}
    assert any("unknown ph" in e for e in validate_chrome_trace(doc))
    doc = {"traceEvents": [{"ph": "B", "args": {}}]}
    assert validate_chrome_trace(doc) != []


def test_write_chrome_trace_roundtrip(tmp_path):
    tel = Telemetry()
    with tel.span("eval"):
        pass
    path = write_chrome_trace(tel, str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert validate_chrome_trace(doc) == []


# ---------------------------------------------------------------------------
# engine integration: bitwise parity + recorded content
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["loop", "vectorized", "fused"])
def test_bitwise_parity_telemetry_on_off(obs_ds, engine):
    """Telemetry must be a pure observer: the EXACT same bits with the
    toggle flipped (the acceptance clause is bitwise, not allclose)."""
    kw = dict(strategy="afl", attack="sign_flip", defense="median",
              attack_scale=4.0)
    r_on = FederatedSimulation(
        _cfg(engine, telemetry=True, **kw), obs_ds).run()
    r_off = FederatedSimulation(
        _cfg(engine, telemetry=False, **kw), obs_ds).run()
    assert r_on.test_accuracy == r_off.test_accuracy
    assert r_on.train_accuracy == r_off.train_accuracy
    np.testing.assert_array_equal(np.asarray(r_on.round_train_loss),
                                  np.asarray(r_off.round_train_loss))
    np.testing.assert_array_equal(np.asarray(r_on.round_test_acc),
                                  np.asarray(r_off.round_test_acc))
    np.testing.assert_array_equal(r_on.confusion, r_off.confusion)


@pytest.mark.parametrize("engine", ["loop", "vectorized"])
def test_driver_records_lifecycle_phases(obs_ds, engine):
    sim = FederatedSimulation(
        _cfg(engine, strategy="afl", attack="sign_flip",
             defense="median"), obs_ds)
    sim.run()
    tel = sim.telemetry
    phases = tel.summary("phase")
    for name in ("select", "local_train", "corrupt", "aggregate", "eval"):
        assert name in phases, name
        assert phases[name]["count"] >= 2       # one per round
    run_spans = tel.summary("run")
    assert "warmup" in run_spans and "round" in run_spans
    assert "classify" in run_spans
    assert tel.series["participants"] == [8.0, 8.0]
    assert validate_chrome_trace(chrome_trace(tel)) == []


def test_fused_in_scan_counters_and_proxy(obs_ds):
    cfg = _cfg("fused", strategy="afl", attack="sign_flip",
               defense="median", rounds=3)
    sim = FederatedSimulation(cfg, obs_ds)
    r = sim.run()
    tel = sim.telemetry
    # in-scan counters ride the scan outputs: one value per round, and
    # the attacker count is a constant the host also knows
    assert len(tel.series["scan.attackers"]) == 3
    assert tel.series["scan.attackers"] == [float(len(sim.attackers))] * 3
    assert len(tel.series["scan.model_delta_l2"]) == 3
    assert all(v > 0 for v in tel.series["scan.model_delta_l2"])
    # run-level structure; no per-round replay of the scan's phases
    run_spans = tel.summary("run")
    for name in ("construct", "precompute", "warmup", "lower", "compile",
                 "fused_scan", "classify"):
        assert name in run_spans, name
    assert tel.summary("phase") == {}
    assert "fused_phase_proxy" not in r.extra["telemetry"]
    assert validate_chrome_trace(chrome_trace(tel)) == []


def test_fused_chunked_skips_proxy(obs_ds):
    cfg = _cfg("fused", strategy="afl", fused_chunk=4)
    sim = FederatedSimulation(cfg, obs_ds)
    sim.run()
    assert sim.telemetry.summary("phase") == {}
    assert len(sim.telemetry.series["scan.model_delta_l2"]) == 2


def test_hfl_fused_group_spread_series(obs_ds):
    cfg = _cfg("fused", strategy="hfl", rounds=3)
    sim = FederatedSimulation(cfg, obs_ds)
    sim.run()
    spread = sim.telemetry.series["scan.group_spread_l2"]
    assert len(spread) == 3
    assert all(v >= 0 for v in spread)


def test_async_counters_and_flow_trace(obs_ds):
    cfg = FLConfig(strategy="async", engine="vectorized", num_clients=8,
                   local_batch_size=8, seed=0, updates_per_client=2,
                   rounds=2)
    sim = FederatedSimulation(cfg, obs_ds)
    r = sim.run()
    tel = sim.telemetry
    assert tel.counters["async.merges"] == r.extra["merges"]
    assert tel.counters["async.batches"] == r.extra["batches"]
    assert len(tel.series["batch_size"]) == r.extra["batches"]
    doc = chrome_trace(tel)
    assert validate_chrome_trace(doc) == []
    # tick-batch rounds chain into one flow (s ... f)
    phs = [e["ph"] for e in doc["traceEvents"]]
    assert phs.count("s") == 1 and phs.count("f") == 1


@pytest.mark.parametrize("engine", ["vectorized", "fused"])
def test_dispatch_counters_per_engine(obs_ds, engine):
    """Compile counters: the fused executor counts the XLA compile of
    its scan inside its `compile` span (a fresh one: JAX's caches are
    cleared, so no earlier simulation's program is reused); the
    per-round engines compile lazily at dispatch, outside any compile
    span, and count none."""
    jax.clear_caches()
    sim = FederatedSimulation(_cfg(engine, strategy="afl"), obs_ds)
    r = sim.run()
    counters = r.extra["telemetry"]["counters"]
    assert "dispatch" not in r.extra["telemetry"]
    if engine == "fused":
        assert counters["compile.requests"] >= 1
        assert counters.get("compile.cache_hits", 0) <= \
            counters["compile.requests"]
    else:
        assert not any(k.startswith("compile.") for k in counters)


@pytest.mark.parametrize("strategy,chunk,expect", [
    ("afl", 0, 0.0),        # one stack of 8 clients: the patch lowering
    ("afl", 2, 1.0),        # chunks of 2 clients: grouped
    ("hfl", 0, 0.0),
    ("hfl", 2, 1.0),
    ("cfl", 0, None),       # sequential visits train no stack
])
def test_grouped_conv_counter(obs_ds, monkeypatch, strategy, chunk, expect):
    """`local_train.grouped_conv` records the lowering that the fused
    run's local training took, as `stacked_lowering` picks it (here
    grouped for stacks of at most 2 clients): the compiled scan holds
    grouped convolutions exactly when the counter reads 1."""
    from repro.models import cnn as cnn_mod
    monkeypatch.setattr(
        cnn_mod, "stacked_lowering",
        lambda n, backend=None: "grouped" if n <= 2 else "patch")
    sim = FederatedSimulation(_cfg("fused", strategy=strategy,
                                   fused_chunk=chunk), obs_ds)
    r = sim.run()
    assert r.extra["telemetry"]["counters"].get(
        "local_train.grouped_conv") == expect
    grouped = "feature_group_count" in sim.fused_program.as_text()
    assert grouped == (expect == 1.0)


def test_compile_counters_credit_each_run(obs_ds):
    """Two identical simulations one after the other in one process: the
    first builds its scan and counts the compile on its own Telemetry;
    the second reuses that program, compiles nothing and counts the
    reuse on its own."""
    jax.clear_caches()
    sims = [FederatedSimulation(_cfg("fused", strategy="hfl"), obs_ds)
            for _ in range(2)]
    for sim in sims:
        sim.run()
    first, second = (sim.telemetry.counters for sim in sims)
    assert first["compile.requests"] >= 1
    assert first["fused.program_reused"] == 0.0
    assert "compile.requests" not in second
    assert second["fused.program_reused"] == 1.0


def test_compile_cache_hit_credited_to_reading_run(obs_ds, tmp_path):
    """With a persistent cache directory, the second identical run reads
    its scan from the cache and counts the hit on its own Telemetry
    (JAX's in-memory caches are cleared before each run, so neither
    reuses the program of an earlier simulation)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path / "cache"))
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], -1)
    cc.reset_cache()
    try:
        tels = []
        for _ in range(2):
            jax.clear_caches()
            sim = FederatedSimulation(_cfg("fused", strategy="afl"), obs_ds)
            sim.run()
            tels.append(sim.telemetry)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert tels[0].counters.get("compile.cache_hits", 0) == 0
    assert tels[1].counters["compile.cache_hits"] >= 1


def test_construct_lower_compile_spans_nest_in_warmup(obs_ds):
    sim = FederatedSimulation(_cfg("fused", strategy="hfl"), obs_ds)
    sim.run()
    spans = {s["name"]: s for s in sim.telemetry.spans
             if s["cat"] == "run"}
    assert {"construct", "lower", "compile"} <= set(
        sim.telemetry.summary("run"))
    warm = spans["warmup"]
    w0, w1 = warm["ts_us"], warm["ts_us"] + warm["dur_us"]
    for name in ("lower", "compile"):
        s = spans[name]
        assert w0 <= s["ts_us"] and s["ts_us"] + s["dur_us"] <= w1, name
    assert spans["lower"]["ts_us"] + spans["lower"]["dur_us"] <= \
        spans["compile"]["ts_us"]
    # construction ends before the run starts
    c = spans["construct"]
    assert c["ts_us"] + c["dur_us"] <= spans["precompute"]["ts_us"]


SCOPES = {"local_train", "local_eval", "aggregate", "eval"}


@pytest.mark.parametrize("kw,want", [
    (dict(strategy="hfl"), SCOPES),
    (dict(strategy="afl", attack="sign_flip", defense="median"),
     SCOPES | {"corrupt"}),
    (dict(strategy="afl", codec="qsgd"), SCOPES | {"encode_decode"}),
    (dict(strategy="cfl"), SCOPES),
], ids=["hfl", "afl_signflip_median", "afl_qsgd", "cfl"])
def test_fused_round_phases_are_named_scopes(obs_ds, kw, want):
    """The fused round's phases carry the per-round driver's phase names
    in the `op_name` metadata of the compiled scan."""
    sim = FederatedSimulation(_cfg("fused", **kw), obs_ds)
    sim.run()
    text = sim.fused_program.as_text()
    found = {part for name in re.findall(r'op_name="([^"]*)"', text)
             for part in name.split("/")}
    assert want <= found, sorted(want - found)


def test_fused_spans_are_native_profiler_events(obs_ds, tmp_path):
    """A jax.profiler trace of one small fused run holds the program's
    own spans as `prog.<name>` host events, with nothing patched."""
    from jax.profiler import ProfileData
    with profiler_trace(str(tmp_path)):
        FederatedSimulation(_cfg("fused", strategy="hfl"), obs_ds).run()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    for name in ("construct", "lower", "compile", "fused_scan"):
        assert "prog." + name in names, name


# ---------------------------------------------------------------------------
# result-document contract (schema v2.3)
# ---------------------------------------------------------------------------

def test_result_block_and_timing_split(obs_ds):
    sim = FederatedSimulation(_cfg("vectorized", strategy="afl"), obs_ds)
    r = sim.run()
    # warmup/steady split (§3): build_time_s stays the steady-state
    # number the throughput gates track; warmup (compile) is separate
    assert r.warmup_time_s > 0.0
    assert r.steady_time_s == r.build_time_s
    block = r.extra["telemetry"]
    assert block["enabled"] is True
    assert "local_train" in block["phases"]
    assert "warmup" in block["run"]
    assert block["peak_rss_mb"] > 0
    assert block["series"]["participants"] == [8.0, 8.0]
    assert json.loads(json.dumps(block)) == block


def test_result_block_disabled(obs_ds):
    sim = FederatedSimulation(
        _cfg("vectorized", strategy="afl", telemetry=False), obs_ds)
    r = sim.run()
    assert r.extra["telemetry"] == {"enabled": False}


def test_run_scenario_trace_out_and_v23_schema(tmp_path):
    from repro.core import scenarios
    path = str(tmp_path / "t.json")
    doc = scenarios.run_scenario("iid-hfl-fused", trace_out=path)
    assert doc["schema_version"] == scenarios.RESULT_SCHEMA_VERSION == 2.6
    assert doc["telemetry"]["enabled"] is True
    assert "fused_scan" in doc["telemetry"]["run"]
    assert not {"fused_phase_proxy", "dispatch"} & set(doc["telemetry"])
    assert doc["timing"]["warmup_time_s"] > 0.0
    assert doc["timing"]["steady_time_s"] == doc["timing"]["build_time_s"]
    with open(path) as f:
        assert validate_chrome_trace(json.load(f)) == []
    # the document normalizes through load_result unchanged
    assert scenarios.load_result(json.loads(json.dumps(doc))) == \
        json.loads(json.dumps(doc))


def test_load_result_backcompat_v22_and_older():
    from repro.core.scenarios import RESULT_SCHEMA_VERSION, load_result
    v22 = {"schema_version": 2.2, "scenario": "x",
           "spec": {"strategy": "hfl"}, "strategy": {"plugin": "hfl"},
           "communication": None}
    up = load_result(v22)
    assert up["schema_version"] == RESULT_SCHEMA_VERSION
    assert up["telemetry"] is None
    assert up["strategy"] == {"plugin": "hfl"}
    v21 = {"schema_version": 2.1, "spec": {"strategy": "cfl"},
           "strategy": {"plugin": "cfl"}}
    up = load_result(v21)
    assert up["telemetry"] is None and up["communication"] is None
    v1 = {"schema_version": 1, "spec": {"strategy": "afl"}}
    up = load_result(v1)
    assert up["telemetry"] is None and up["attack"] is None
    assert up["strategy"]["plugin"] == "afl"


def test_load_result_upgrades_v25():
    from repro.core.scenarios import RESULT_SCHEMA_VERSION, load_result
    tel = {"enabled": True, "run": {}, "counters": {}}
    up = load_result({"schema_version": 2.5, "spec": {}, "telemetry": tel,
                      "faults": None})
    assert up["schema_version"] == RESULT_SCHEMA_VERSION
    assert up["telemetry"] == tel and up["faults"] is None


def test_profiler_trace_noop_and_real(tmp_path):
    with profiler_trace(None):          # falsy logdir: pure no-op
        x = 1
    assert x == 1
    with profiler_trace(str(tmp_path / "xla")):
        import jax.numpy as jnp
        jnp.zeros(4).block_until_ready()
    assert (tmp_path / "xla").exists()
