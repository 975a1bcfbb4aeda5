"""Cut a recorded profiler trace down to a small test fixture.

    python3 chip_bench/tests/trim_trace.py IN.xplane.pb OUT.xplane.pb \
        --ms 120

Keeps, from the first `prog.fused_scan` span of the trace's
`bench.window` on, `--ms` milliseconds: the device planes' `XLA Ops`
events, and the host events that overlap that range and are harness or
program annotations or lie on the Python thread, at most `--host`
of them. The `bench.window` annotation is cut to the kept range. Times,
names and planes are kept as recorded; the stats of the events are not.
"""
from __future__ import annotations

import argparse
import json


def _quote(s):
    return json.dumps(s)


def trim(pd, ms, host_cap):
    host, dev = [], {}
    win = scan = None
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench.window" and win is None:
                    win = (ev.start_ns, ev.end_ns)
                if ev.name == "prog.fused_scan" and scan is None:
                    scan = ev.start_ns
    lo = max(win[0], scan - 5_000_000)
    hi = min(win[1], lo + int(ms * 1e6))
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                dev.setdefault(plane.name, []).extend(
                    (ev.start_ns, ev.end_ns, ev.name) for ev in line.events
                    if ev.end_ns > lo and ev.start_ns < hi)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.end_ns <= lo or ev.start_ns >= hi:
                        continue
                    if (ev.name.startswith(("bench.", "prog."))
                            or line.name == "python3"):
                        host.append((ev.start_ns, ev.end_ns, ev.name))
    keep = [h for h in host if h[2].startswith(("bench.", "prog."))]
    rest = sorted((h for h in host if not h[2].startswith(("bench.",
                                                            "prog."))),
                  key=lambda h: h[0] - h[1])[:host_cap]
    host = [(lo, hi, "bench.window") if h[2] == "bench.window" else h
            for h in keep] + rest
    return {"/host:CPU": {"python3": host},
            **{p: {"XLA Ops": evs} for p, evs in dev.items()}}


def to_text_proto(planes):
    out = []
    for pid, (pname, lines) in enumerate(planes.items()):
        names = {}
        body = []
        for lid, (lname, evs) in enumerate(lines.items()):
            base = int(min((s for s, _, _ in evs), default=0))
            body.append(f"lines {{ id: {lid} display_id: {lid} "
                        f"name: {_quote(lname)} timestamp_ns: {base}")
            for s, e, n in sorted(evs):
                mid = names.setdefault(n, len(names) + 1)
                body.append(f"events {{ metadata_id: {mid} offset_ps: "
                            f"{round((s - base) * 1000)} duration_ps: "
                            f"{round((e - s) * 1000)} }}")
            body.append("}")
        meta = [f"event_metadata {{ key: {i} value {{ id: {i} name: "
                f"{_quote(n)} }} }}" for n, i in names.items()]
        out.append(f"planes {{ id: {pid} name: {_quote(pname)} "
                   + " ".join(body + meta) + " }")
    return "\n".join(out)


def main(argv=None):
    from jax.profiler import ProfileData
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--ms", type=float, default=120.0)
    ap.add_argument("--host", type=int, default=400)
    args = ap.parse_args(argv)
    planes = trim(ProfileData.from_file(args.src), args.ms, args.host)
    blob = ProfileData.text_proto_to_serialized_xspace(to_text_proto(planes))
    with open(args.dst, "wb") as f:
        f.write(blob)


if __name__ == "__main__":
    main()
