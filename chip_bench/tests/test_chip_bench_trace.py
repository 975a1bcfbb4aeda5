"""The trace reduction, on a small recorded trace.

`fixtures/tiny_hfl.xplane.pb` is 120 ms of a `--trace 1` run of a
4-client HFL federation on one TPU v5e, cut down by `trim_trace.py`:
the device's op line, the harness's and the program's annotations and
the longest Python events of that range.
"""
import pathlib

import chip_bench_tiny  # noqa: F401  (puts the checkout on sys.path)
import numpy as np
import pytest

from chip_bench import trace

FIXTURE = pathlib.Path(__file__).with_name("fixtures") / "tiny_hfl.xplane.pb"


@pytest.fixture(scope="module")
def pd():
    return trace.load(str(FIXTURE))


@pytest.fixture(scope="module")
def red(pd):
    return trace.reduce(pd)


def _naive(pd):
    """Busy time on a 1 us grid, computed independently of `trace`."""
    host = [ev for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for ev in ln.events]
    win = next(ev for ev in host if ev.name == "bench.window")
    lo, hi = win.start_ns, win.end_ns
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    kernels = 0
    for p in pd.planes:
        if not p.name.startswith("/device:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name.startswith(("%while", "%conditional", "%call")):
                    continue
                s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
                if e > s:
                    grid[int((s - lo) // 1000):int((e - lo) // 1000)] = True
                    kernels += "tpu_custom_call" in ev.name
    return (hi - lo) / 1e9, grid.sum() / 1e6, kernels


def test_window_busy_and_idle(pd, red):
    window_s, busy_s, _ = _naive(pd)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(window_s, abs=1e-9)
    # a 1 us grid rounds each interval by at most 1 us at either end
    assert red["busy_s"] == pytest.approx(busy_s, rel=0.05)
    assert 0 < red["busy_s"] < red["window_s"]
    idle = sum(red["idle_by_host"].values())
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-6)
    # the window holds a whole fused scan, whose device time is busy
    assert 0 < red["scan_s"] <= red["busy_s"]
    assert red["collective_s"] == 0.0


def test_kernel_events_and_shapes(pd, red):
    _, _, n = _naive(pd)
    events = red["kernels"]["fedavg_agg"]
    assert len(events) == n > 0
    for dur, operands in events:
        assert dur > 0
        # weights (C, 1) and the raveled stack (C, 7900) of the paper CNN
        assert operands[-1][1][1] == 7_900


def test_breakdown_shape(red):
    b = trace.breakdown(red)
    assert 0 < len(b["device_ops"]) <= 10
    assert 0 < len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert not any(name.startswith("while") for name, _ in b["device_ops"])


def test_interval_algebra():
    a = trace.union([(0, 5), (3, 8), (10, 12)])
    assert a == [(0, 8), (10, 12)]
    assert trace.subtract([(0, 12)], a) == [(8, 10)]
    assert trace.intersect(a, [(4, 11)]) == [(4, 8), (10, 11)]
    assert trace.total(a) == 10


def test_op_text_parsing():
    text = ('%fedavg_agg.1 = f32[1,7900]{1,0:T(1,128)S(1)} custom-call('
            'f32[2,1]{1,0:T(2,128)S(1)} %copy, f32[2,7900]{1,0:T(2,128)} '
            '%stacked.1), custom_call_target="tpu_custom_call"')
    assert trace.kernel_name(text) == "fedavg_agg"
    assert trace.operand_shapes(text) == [("f32", (2, 1)),
                                          ("f32", (2, 7900))]
    assert trace.is_collective("%all-reduce.3 = f32[10] all-reduce(...)")
    assert trace.is_collective("%ar = f32[10] all-reduce-start(f32[10] %x)")
    assert not trace.is_collective(
        "%fusion.2 = f32[4] fusion(f32[4] %all-reduce.1)")
