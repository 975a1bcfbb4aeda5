"""Telemetry subsystem (DESIGN.md §13): host-side lifecycle spans
(`obs.telemetry`, on the XLA profiler's clock when given an annotation
factory), device-resident in-scan counters for the fused executor and
per-run compile counters (`obs.collectors`), and exporters — Chrome-trace JSON, the
result-document telemetry block, and the `jax.profiler.trace` wrapper
(`obs.export`)."""
from repro.obs.telemetry import Telemetry
from repro.obs.export import (chrome_trace, peak_rss_mb, profiler_trace,
                              result_block, validate_chrome_trace,
                              write_chrome_trace)

__all__ = [
    "Telemetry", "chrome_trace", "peak_rss_mb", "profiler_trace",
    "result_block", "validate_chrome_trace", "write_chrome_trace",
]
