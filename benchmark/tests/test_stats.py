"""The interval rate estimator, the due-time latency arithmetic and the
histogram delta, on synthetic stamps."""

import math

import pytest

from benchmark.harness import intervals as iv
from benchmark.harness import promtext, stats


def test_interval_rate_leaves_out_the_first_events_own_pods():
    # 1024 pods every 0.7 s; the window cuts a cycle at each edge
    batches = [(0.7 * k, 1024) for k in range(1, 20)]
    rate, pods, span = stats.interval_rate(batches, t0=1.0, t1=11.0)
    # events at 1.4 ... 10.5: 14 events, 13 intervals of 0.7 s
    assert pods == 13 * 1024
    assert span == pytest.approx(9.1)
    assert rate == pytest.approx(1024 / 0.7)


def test_interval_rate_does_not_quantise_with_the_window_edge():
    batches = [(0.7 * k, 1024) for k in range(1, 40)]
    rates = {round(stats.interval_rate(batches, 1.0, 1.0 + w)[0], 6)
             for w in (9.0, 9.3, 9.6, 9.9)}
    assert rates == {round(1024 / 0.7, 6)}


def test_interval_rate_needs_two_events():
    with pytest.raises(ValueError):
        stats.interval_rate([(1.0, 5)], 0.0, 2.0)


def bursts(period, phase, until):
    """Two cycles of 1024 back to back, each seen in two deliveries, then a
    pause: the pattern the chip showed (PR 22)."""
    out, x = [], phase
    while x < until:
        out += [(x, 176), (x + 0.05, 848), (x + 0.5, 500), (x + 0.55, 524)]
        x += period
    return out


def test_slope_rate_is_exact_on_regular_cycles_whatever_the_window():
    batches = [(0.7 * k, 1024) for k in range(1, 80)]
    for w in (20.0, 20.3, 20.6):
        rate, _pods, n = stats.slope_rate(batches, 1.0, 1.0 + w)
        assert rate == pytest.approx(1024 / 0.7, rel=1e-9)
        assert n >= 28


def test_slope_rate_holds_on_bursts_where_the_interval_rate_reads_high():
    true = 2048 / 4.5
    slope, interval = [], []
    for phase in (-4.4, -3.3, -2.2, -1.1, -0.1):
        batches = bursts(4.5, phase, 40.0)
        slope.append(stats.slope_rate(batches, 0.0, 30.0)[0])
        interval.append(stats.interval_rate(batches, 0.0, 30.0)[0])
    assert max(abs(r / true - 1) for r in slope) < 0.02
    assert min(r / true - 1 for r in interval) > 0.03    # always too high


def test_latency_runs_from_the_due_time_not_the_send():
    due = [1.0, 2.0, 3.0, 9.0]
    bound = [1.5, 2.25, None, 9.5]
    lat, missing = stats.due_latencies_ms(due, bound, t0=0.5, t1=5.0)
    assert lat == [500.0, 250.0]
    assert missing == 1          # due inside the window, never seen bound


def test_backlog_counts_sent_and_not_yet_bound():
    sent = [0.0, 1.0, 2.0, 3.0]
    bound = [0.5, 2.5, None, 3.5]
    assert stats.backlog_at(sent, bound, 2.2) == 2
    assert stats.backlog_at(sent, bound, 4.0) == 1


def test_quantile_and_tail_rule():
    xs = list(range(1, 102))
    assert stats.quantile(xs, 0.5) == 51
    assert stats.quantile(xs, 0.99) == 100
    assert stats.tail_is_resolved(1000, 0.99)
    assert not stats.tail_is_resolved(999, 0.99)


PAGE0 = """# HELP x_seconds x
x_seconds_bucket{stage="a",le="0.1"} 10
x_seconds_bucket{stage="a",le="0.2"} 10
x_seconds_bucket{stage="a",le="+Inf"} 10
x_seconds_sum{stage="a"} 0.5
x_seconds_count{stage="a"} 10
c_total{k="v"} 3
c_total{k="w"} 4
"""
PAGE1 = PAGE0.replace('le="0.2"} 10', 'le="0.2"} 30').replace(
    'le="+Inf"} 10', 'le="+Inf"} 30').replace("0.5", "3.5").replace(
    'x_seconds_count{stage="a"} 10', 'x_seconds_count{stage="a"} 30').replace(
    'c_total{k="w"} 4', 'c_total{k="w"} 9')


def test_delta_of_two_scrapes():
    d = promtext.Delta(promtext.Scrape(PAGE0), promtext.Scrape(PAGE1))
    assert d.total("c_total") == 5
    assert d.total("c_total", k="v") == 0
    assert d.by_labels("c_total", "k") == {"w": 5}
    assert d.total("x_seconds_sum", stage="a") == pytest.approx(3.0)
    # all 20 new observations fell in (0.1, 0.2]: the median is its middle
    assert d.histogram_quantile("x_seconds", 0.5, stage="a") == \
        pytest.approx(0.15)
    assert d.histogram_quantile("x_seconds", 0.5, stage="none") is None


def test_interval_sets():
    a = iv.union([(0, 2), (1, 3), (5, 6)])
    assert a == [(0, 3), (5, 6)]
    assert iv.length(a) == 4
    assert iv.intersect(a, [(2, 5.5)]) == [(2, 3), (5, 5.5)]
    assert iv.subtract(a, [(1, 2), (5.5, 7)]) == [(0, 1), (2, 3), (5, 5.5)]
    assert iv.gaps(a, -1, 7) == [(-1, 0), (3, 5), (6, 7)]
    assert math.isclose(iv.length(iv.gaps(a, 0, 6)), 2)
