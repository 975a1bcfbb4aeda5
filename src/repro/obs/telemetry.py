"""Host-side tracer: the span/counter half of the telemetry subsystem
(DESIGN.md §13).

Zero-dep by design (stdlib `time` + `threading` only): this module must
never pull jax/numpy — the import edge points strictly outward from
here. What ties a span to the XLA profiler's clock is handed in: a
`Telemetry` built with `annotate=jax.profiler.TraceAnnotation` (the
simulation does this) also enters `annotate("prog.<name>")` around
every recorded span, so the program's own spans land in any
`jax.profiler` trace next to the device ops.

A per-run `Telemetry` holds spans (monotonic perf_counter_ns clock),
counters, and per-round series, recorded under a lock (the async tick
loop and any plugin thread may record concurrently). `span(...)` is a
context manager; when telemetry is disabled or suppressed it returns a
shared no-op object (no annotation either), so the off path costs one
attribute check.

Span CATEGORIES partition the trace into tracks (DESIGN.md §13):
  "phase" — the steady per-event lifecycle phases the driver wraps
            (select / local_train / corrupt / encode_decode /
            aggregate / eval / sequential_round).
  "run"   — run-level structure (construct / warmup / lower / compile /
            round / precompute / fused_scan / classify).

Steady-state spans deliberately do NOT block on device work: under
jax's async dispatch they measure host-side dispatch windows, which is
what keeps telemetry inside the ≤5% overhead budget. Device time comes
from the XLA profiler (`obs.export.profiler_trace`), where the fused
round's phases carry `jax.named_scope` names.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence


# -- spans -------------------------------------------------------------------

class _NullSpan:
    """Shared no-op span: the disabled/suppressed fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tel", "_name", "_cat", "_args", "_t0", "_ann")

    def __init__(self, tel: "Telemetry", name: str, cat: str,
                 args: Dict[str, Any]):
        self._tel, self._name, self._cat, self._args = tel, name, cat, args
        self._ann = None

    def __enter__(self):
        if self._tel._annotate is not None:
            self._ann = self._tel._annotate("prog." + self._name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        tel = self._tel
        with tel._lock:
            tel.spans.append({
                "name": self._name, "cat": self._cat,
                "ts_us": (self._t0 - tel._t0) / 1e3,
                "dur_us": (t1 - self._t0) / 1e3,
                "args": self._args,
            })
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class Telemetry:
    """One run's trace: spans + counters + per-round series.

    `annotate`, when given, is a context-manager factory called with
    `"prog.<span name>"` around every recorded span (the simulation
    passes `jax.profiler.TraceAnnotation`)."""

    def __init__(self, enabled: bool = True,
                 annotate: Optional[Callable[[str], Any]] = None):
        self.enabled = bool(enabled)
        self.spans: List[Dict[str, Any]] = []
        self.counters: Dict[str, float] = {}
        self.series: Dict[str, List[float]] = {}
        self._lock = threading.Lock()
        self._suppress = 0
        self._annotate = annotate
        self._t0 = time.perf_counter_ns()

    # -- recording ----------------------------------------------------------
    @property
    def active(self) -> bool:
        return self.enabled and not self._suppress

    def span(self, name: str, cat: Optional[str] = None, **args):
        """Context manager recording one timed span (category `cat`,
        default "phase")."""
        if not self.enabled or self._suppress:
            return _NULL_SPAN
        return _Span(self, name, cat or "phase", args)

    def counter(self, name: str, value: float = 1.0) -> None:
        """Accumulate into a named run-total counter."""
        if not self.enabled or self._suppress:
            return
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + float(value)

    def set_counter(self, name: str, value: float) -> None:
        """Set a named run-total counter, replacing any earlier value: a
        fact of the run that each trace of the same code restates."""
        if not self.enabled or self._suppress:
            return
        with self._lock:
            self.counters[name] = float(value)

    def append_series(self, name: str, value: float) -> None:
        """Append one per-round value to a named series."""
        if not self.enabled or self._suppress:
            return
        with self._lock:
            self.series.setdefault(name, []).append(float(value))

    def record_series(self, name: str, values: Sequence[float]) -> None:
        """Record a whole per-round series at once (the fused executor's
        end-of-run transfer of in-scan counters)."""
        if not self.enabled:
            return
        with self._lock:
            self.series[name] = [float(v) for v in values]

    # -- scoping ------------------------------------------------------------
    @contextlib.contextmanager
    def suppress(self):
        """Mute span/counter recording (warmup dry-runs the lifecycle to
        compile it; compile time must not pollute the phase totals)."""
        self._suppress += 1
        try:
            yield self
        finally:
            self._suppress -= 1

    # -- summaries -----------------------------------------------------------
    def summary(self, cat: str = "phase") -> Dict[str, Dict[str, float]]:
        """{span name: {count, total_s, mean_s}} over one category."""
        with self._lock:
            spans = list(self.spans)
        out: Dict[str, Dict[str, float]] = {}
        for s in spans:
            if s["cat"] != cat:
                continue
            e = out.setdefault(s["name"], {"count": 0, "total_s": 0.0})
            e["count"] += 1
            e["total_s"] += s["dur_us"] / 1e6
        for e in out.values():
            e["mean_s"] = e["total_s"] / e["count"]
        return out
