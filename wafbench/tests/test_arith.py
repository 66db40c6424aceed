import pytest

from wafbench import arith


def test_percentile_is_a_value_of_the_sample():
    values = [float(v) for v in range(1, 101)]
    assert arith.percentile(values, 50) == 50.0
    assert arith.percentile(values, 95) == 95.0
    assert arith.percentile(values, 99) == 99.0
    assert arith.percentile(values, 100) == 100.0
    assert arith.percentile([7.0], 95) == 7.0
    assert arith.percentile([3.0, 1.0, 2.0], 50) == 2.0


def test_percentile_counts_a_failed_request_as_the_window():
    # 19 fast requests and one counted at the window's length: p95 is a
    # fast one, p99 the window.
    lat = [10.0] * 19 + [20000.0]
    assert arith.percentile(lat, 95) == 10.0
    assert arith.percentile(lat, 99) == 20000.0


@pytest.mark.parametrize("bad", [0, -1, 101])
def test_percentile_refuses_what_is_not_a_percentile(bad):
    with pytest.raises(ValueError):
        arith.percentile([1.0], bad)
    with pytest.raises(ValueError):
        arith.percentile([], 50)


def test_rate_is_over_the_whole_window():
    assert arith.rate(1800, 20.0) == 90.0
    with pytest.raises(ValueError):
        arith.rate(1, 0.0)


def test_spread_is_the_drivers():
    # statistics.quantiles(n=4) of 1..6: q1 1.75, q3 5.25, median 3.5
    assert arith.quartile_spread([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)
