"""Device-side telemetry collectors (DESIGN.md §13): in-scan counters
for the fused executor, and compile counters credited to one run.

Host spans cannot see inside the fused executor's compiled R-round
`lax.scan` (DESIGN.md §10), so fused-engine telemetry adds:

* `round_counters` — per-round scalar accumulators traced INTO the scan
  body: they ride the scan's stacked outputs next to the metric curves
  and transfer once at run end, preserving the one-transfer contract.
  The driver-owned counter is the attacker count per round; strategies
  add their own through `Strategy.scan_telemetry` (model-delta L2 by
  default, HFL adds the group-spread L2).

* `compile_span` — a run-level span around lowering or compiling the
  scan, inside which JAX's compilation-cache monitoring events count
  into the run's `compile.requests` / `compile.cache_hits` counters
  (misses = requests - hits). JAX keeps event listeners for the life of
  the process, so one listener is registered per process; each event is
  credited to the `Telemetry` whose `compile_span` is open on the
  compiling thread. JAX records a request for every XLA compile while
  `jax_enable_compilation_cache` is on (its default), with or without a
  cache directory; a hit needs the directory.

Per-phase device time inside the scan is the XLA profiler's: the round's
phases run under `jax.named_scope` (`Strategy.scan_round`), so their ops
carry the phase name in their `op_name` metadata.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict

import jax
import jax.numpy as jnp

COMPILE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "compile.requests",
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
}

_open = threading.local()          # per thread: Telemetry stack
_listener_lock = threading.Lock()
_listening = False


def _on_event(event: str, **_) -> None:
    counter = COMPILE_EVENTS.get(event)
    stack = getattr(_open, "stack", None)
    if counter is not None and stack:
        stack[-1].counter(counter)


@contextlib.contextmanager
def compile_span(tel, name: str):
    """A "run" span `name` during which compile events count on `tel`."""
    global _listening
    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    stack = _open.__dict__.setdefault("stack", [])
    stack.append(tel)
    try:
        with tel.span(name, cat="run"):
            yield
    finally:
        stack.pop()


def round_counters(strat, fx, carry_prev, carry_new, xs
                   ) -> Dict[str, Any]:
    """The per-round in-scan counter dict for one scan step (traced).
    All values are cast to float32 scalars so the stacked outputs form
    one homogeneous (R,)-per-counter block."""
    out = {"attackers": jnp.sum(xs["flags"].astype(jnp.int32))}
    try:
        extra = strat.scan_telemetry(fx, carry_prev, carry_new, xs)
    except NotImplementedError:
        extra = {}
    for k, v in extra.items():
        out[k] = v
    return {k: jnp.asarray(v, jnp.float32) for k, v in out.items()}
