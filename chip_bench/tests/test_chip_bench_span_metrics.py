"""The per-layer metrics that read one program span each: the mean over
the window's runs of the span's host seconds, and nothing where no run
recorded the span (a program that predates the span)."""
import chip_bench_tiny
import pytest

from chip_bench import cells

SPAN_METRICS = {"driver.construct_s": "construct",
                "driver.lower_s": "lower",
                "driver.compile_s": "compile"}


def _reader(metric):
    return cells.load(chip_bench_tiny.SINGLE[0]).reader(metric)


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_span_metric_is_the_mean_over_runs(metric):
    span = SPAN_METRICS[metric]
    ctx = {"runs": [{"run_s": 5.0, "spans": {span: 1.0, "fused_scan": 3.0}},
                    {"run_s": 5.0, "spans": {span: 2.0}},
                    {"run_s": 5.0, "spans": {"fused_scan": 3.0}}]}
    assert _reader(metric)(ctx) == pytest.approx(1.5)


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_span_metric_is_none_without_the_span(metric):
    ctx = {"runs": [{"run_s": 5.0, "spans": {"fused_scan": 3.0,
                                             "warmup": 1.0}}]}
    assert _reader(metric)(ctx) is None
    assert _reader(metric)({"runs": []}) is None


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_span_metric_listed_for_every_single_chip_cell(metric):
    for name in chip_bench_tiny.SINGLE:
        assert metric in {m["name"] for m in cells.load(name).per_layer}
