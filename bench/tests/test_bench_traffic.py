import math

import numpy as np
import pytest

from bench import traffic as TR


@pytest.mark.parametrize("seed", [0, 2**31 + 17])
def test_poisson_schedule_fixed_count_sorted_in_window(seed):
    rng = np.random.default_rng(seed)
    t = TR.poisson_schedule(50.0, 4.0, rng)
    assert len(t) == 200
    assert np.all(np.diff(t) >= 0) and t[0] > 0 and t[-1] < 4.0
    again = TR.poisson_schedule(50.0, 4.0, np.random.default_rng(seed))
    np.testing.assert_array_equal(t, again)


def test_poisson_schedule_mean_gap_and_other_seed_differs():
    a = TR.poisson_schedule(100.0, 50.0, np.random.default_rng(1))
    b = TR.poisson_schedule(100.0, 50.0, np.random.default_rng(2))
    assert abs(np.diff(a).mean() - 0.01) < 0.001
    assert not np.array_equal(a, b)


def test_schedule_dispatches_on_arrivals():
    p = {"arrivals": "poisson", "rate_rps": 40.0}
    assert len(TR.schedule(p, 2.0, np.random.default_rng(0))) == 80
    with pytest.raises(ValueError):
        TR.schedule({"arrivals": "bursty", "rate_rps": 40.0}, 2.0, np.random.default_rng(0))


def test_latency_from_due_time_and_failures_infinite():
    due = [1.0, 2.0, 3.0]
    done = [1.5, None, 3.001]
    lat = TR.latencies_ms(due, done)
    assert lat[0] == pytest.approx(500.0) and math.isinf(lat[1])
    assert lat[2] == pytest.approx(1.0)


def test_percentile_interpolates_and_propagates_inf():
    xs = [float(i) for i in range(1, 101)]
    assert TR.percentile(xs, 50) == pytest.approx(50.5)
    assert TR.percentile(xs, 95) == pytest.approx(95.05)
    assert math.isinf(TR.percentile(xs[:-1] + [math.inf], 99.5))
    assert TR.percentile(xs[:-1] + [math.inf], 50) == pytest.approx(50.5)
    with pytest.raises(ValueError):
        TR.percentile([], 50)


def test_request_sizes_same_share_of_each_size_in_a_seed_order():
    p = {"size_min": 1, "size_max": 8}
    a = TR.request_sizes(p, 80, np.random.default_rng(1))
    b = TR.request_sizes(p, 80, np.random.default_rng(2 ** 31 + 5))
    assert sorted(a) == sorted(b) and not np.array_equal(a, b)
    assert np.bincount(a, minlength=9)[1:].tolist() == [10] * 8
    assert TR.request_sizes({"size_min": 8, "size_max": 8}, 5, np.random.default_rng(0)).tolist() == [8] * 5
