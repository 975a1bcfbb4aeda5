"""Reduction of a profiler trace (`.xplane.pb`) to the numbers the
per-layer metrics read.

The trace holds one plane per device (`/device:TPU:<i>`) whose op line
lists every operation that ran there with its start and duration, and
host planes whose lines hold the host's events, among them the
`TraceAnnotation`s the harness writes around its own calls (`bench.*`)
and around the program's telemetry spans (`prog.*`). The reduction:

* clips everything to the traced window, the `bench.window` annotation;
* per device: busy time (the union of its op intervals), idle gaps (the
  rest of the window), time per op name, and the time of collective
  ops during which no other op ran on that device (exposed);
* within the `prog.fused_scan` spans: the device's busy time there (the
  scan's device time) and its exposed collective time;
* the Pallas kernels' events (custom calls to `tpu_custom_call`), by
  the instruction name the trace gives them (`%fedavg_agg.3` ->
  `fedavg_agg`), each with its duration and operand shapes;
* each idle gap of the first device, labelled by the innermost harness
  or program annotation (`bench.*`, `prog.*`) and the innermost other
  host event (a Python function or runtime call) covering its midpoint.

On a TPU the op line names each event by its HLO instruction text
(`%fusion.12 = f32[...] fusion(...)`). Loop and call containers
(`while`, `conditional`, `call`) span their bodies and are left out of
the busy time and the op table; the busy time is the union of the ops
they contain.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
OP_LINES = ("XLA Ops",)
# asynchronous ops (copies, slices, collectives split into start/done)
# run beside the op line; only their collectives are read
ASYNC_LINE = "Async XLA Ops"
WINDOW = "bench.window"
OURS = ("bench.", "prog.")
SCAN = "prog.fused_scan"

Interval = Tuple[int, int]


def find_xplane(logdir: str) -> str:
    hits = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return hits[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(iv: List[Interval]) -> int:
    return sum(e - s for s, e in iv)


def clip(iv: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv
            if min(e, hi) > max(s, lo)]


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of sorted disjoint `a` not covered by sorted disjoint `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


CONTAINER = re.compile(r"^%?(while|conditional|call)(\.\d+)?( |$)")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")


COLLECTIVE_OP = re.compile(r"[ =](?:%s)(?:-start|-done)?\(" %
                           "|".join(COLLECTIVES))


def is_collective(name: str) -> bool:
    """An op event that is a collective, by its instruction name or by
    the HLO op it calls (`... = f32[10] all-reduce-start(...)`)."""
    head = name.split(" = ")[0].lower()
    return (any(c in head for c in COLLECTIVES)
            or COLLECTIVE_OP.search(name) is not None)


def instruction(name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `%fusion.12`."""
    return name.split(" = ")[0].strip()


def label(name: str, width: int = 90) -> str:
    """An op's name for the breakdown: its instruction and result type."""
    head, _, rest = name.partition(" = ")
    return (head.lstrip("%") + " " + rest.split("{")[0])[:width].strip()


def kernel_name(name: str):
    """The Pallas kernel an op event runs (`fedavg_agg`), or None."""
    if KERNEL_TARGET not in name:
        return None
    return re.sub(r"\.\d+$", "", instruction(name).lstrip("%"))


def operand_shapes(name: str):
    """Operand shapes of an op's text: [("f32", (2, 7900)), ...]."""
    bare = re.sub(r"\{[^{}]*\}", "", name)          # drop layouts
    args = bare.split("custom-call(", 1)[-1].split(")", 1)[0]
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in SHAPE.findall(args)]


def _norm(name: str) -> str:
    """A host event's name without instance numbers."""
    return re.sub(r"[.:#]\d+", "", name)[:80]


def reduce(pd) -> Dict:
    host_events: List[Tuple[int, int, str]] = []
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host_events.append((ev.start_ns, ev.end_ns, ev.name))
    wins = [(s, e) for s, e, n in host_events if n == WINDOW]
    if not wins:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    lo, hi = wins[0]
    scan = union(clip([(s, e) for s, e, n in host_events if n == SCAN],
                      lo, hi))
    devices = []
    ops: Dict[str, Dict] = {}
    kernels: Dict[str, List] = {}
    for plane in device_planes:
        iv, coll = [], []
        for line in plane.lines:
            if line.name == ASYNC_LINE:
                coll.extend((max(ev.start_ns, lo), min(ev.end_ns, hi))
                            for ev in line.events
                            if ev.end_ns > lo and ev.start_ns < hi
                            and is_collective(ev.name))
            if line.name not in OP_LINES:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.end_ns
                if e <= lo or s >= hi:
                    continue
                name = ev.name
                if CONTAINER.match(name):
                    continue
                s, e = max(s, lo), min(e, hi)
                rec = ops.get(name)        # instruction names repeat
                if rec is None:            # across programs; texts differ
                    rec = ops[name] = {"s": 0.0, "n": 0,
                                       "label": label(name)}
                    kname = kernel_name(name)
                    rec["kernel"] = kname
                    rec["operands"] = operand_shapes(name) if kname else []
                rec["s"] += (e - s) / 1e9
                rec["n"] += 1
                if rec["kernel"]:
                    kernels.setdefault(rec["kernel"], []).append(
                        ((e - s) / 1e9, rec["operands"]))
                (coll if is_collective(name) else iv).append((s, e))
        if not iv and not coll:
            continue
        busy = union(iv + coll)
        compute = union(iv)
        exposed = subtract(union(coll), compute)
        devices.append({
            "plane": plane.name, "busy_ns": total(busy),
            "gaps": subtract([(lo, hi)], busy),
            "scan_ns": total(intersect(busy, scan)),
            "collective_ns": total(union(coll)),
            "scan_exposed_ns": total(intersect(exposed, scan)),
        })
    n = max(1, len(devices))
    idle: Dict[str, float] = {}
    if devices:
        # sweep the gaps in time order, keeping the host events that have
        # started and not yet ended; the shortest of them is innermost
        host = sorted(host_events)
        active: List[Tuple[int, int, str]] = []
        i = 0
        for s, e in sorted(devices[0]["gaps"], key=lambda g: g[0] + g[1]):
            mid = (s + e) // 2
            while i < len(host) and host[i][0] <= mid:
                active.append(host[i])
                i += 1
            active = [h for h in active if h[1] >= mid]
            ours = [h for h in active if h[2].startswith(OURS)]
            other = [h for h in active if not h[2].startswith(OURS)]
            parts = [_norm(min(hs, key=lambda h: h[1] - h[0])[2])
                     for hs in (ours, other) if hs]
            key = " | ".join(parts) or "(no host event)"
            idle[key] = idle.get(key, 0.0) + (e - s) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": len(devices),
        "busy_s": sum(d["busy_ns"] for d in devices) / n / 1e9,
        "scan_s": sum(d["scan_ns"] for d in devices) / n / 1e9,
        "collective_s": sum(d["collective_ns"] for d in devices) / n / 1e9,
        "scan_exposed_s": sum(d["scan_exposed_ns"] for d in devices)
        / n / 1e9,
        "ops": {k: {"s": v["s"] / n, "n": v["n"], "label": v["label"]}
                for k, v in ops.items() if v["s"] > 0},
        "kernels": kernels,
        "idle_by_host": idle,
    }


def breakdown(red: Dict) -> Dict:
    """The result line's `breakdown`: the 10 device ops that took most
    time and the 10 host activities that the device waited on longest."""
    top = sorted(red["ops"].items(), key=lambda kv: -kv[1]["s"])[:10]
    gaps = sorted(red["idle_by_host"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[v["label"], v["s"]] for _, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}
