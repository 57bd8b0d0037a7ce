import statistics

import pytest

from perfbench.lib import stats


@pytest.mark.parametrize("values, p, want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10], 95, 10.0),
    (list(range(1, 101)), 95, 95.05),
    (list(range(1, 101)), 0, 1.0),
    (list(range(1, 101)), 100, 100.0),
    ([5, 1, 3], 50, 3.0),            # unsorted input
])
def test_percentile_is_linear_interpolation(values, p, want):
    assert stats.percentile(values, p) == pytest.approx(want)


def test_percentile_agrees_with_the_standard_library():
    v = [0.3, 9.1, 4.4, 2.2, 7.7, 5.0, 6.1, 1.9, 8.8, 3.3, 4.9]
    q = statistics.quantiles(v, n=100, method="inclusive")
    assert stats.percentile(v, 95) == pytest.approx(q[94])
    assert stats.median(v) == statistics.median(v)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_per_template_medians_and_the_slowest():
    samples = [{"template": "a", "ms": x} for x in (1, 2, 30)] + \
              [{"template": "b", "ms": x} for x in (10, 12)] + \
              [{"template": "c", "ms": 5}]
    assert stats.template_medians(samples) == {"a": 2, "b": 11.0, "c": 5}
    # the largest per-template MEDIAN, not the largest sample (30, in a)
    assert stats.slowest_template(samples) == ("b", 11.0)


def test_spread_is_the_interquartile_distance_over_the_median():
    v = [100, 101, 102, 103, 104, 105]
    q1, _q2, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / 102.5)


def test_round_end_traffic_does_whole_rounds():
    """The closed-loop plan marks the round's length, and every client's
    sequence is whole rounds of the same multiset in seeded orders."""
    from perfbench.lib import traffic
    t = {"loop": "closed", "clients": 2, "think_ms": 0, "templates": "all",
         "order": "round_permutation", "stop": "round_end"}
    names = ["a", "b", "c"]
    p = traffic.plan(t, names, 2_147_483_700, 1.0)
    assert p["round_len"] == 3 and len(p["sequences"]) == 2
    for seq in p["sequences"]:
        assert all(sorted(seq[i:i + 3]) == names
                   for i in range(0, len(seq) - 3, 3))
    assert p["sequences"][0] != p["sequences"][1]
    assert p == traffic.plan(t, names, 2_147_483_700, 1.0)
    assert traffic.plan(dict(t, stop="deadline"), names, 1, 1.0)[
        "round_len"] == 0


def test_open_loop_plan_is_fixed_by_the_seed_and_the_rate():
    from perfbench.lib import traffic
    t = {"loop": "open", "clients": 4, "templates": {"a": 3, "b": 1},
         "order": "weighted_draw", "rate_per_s": 200.0,
         "arrivals": "poisson"}
    p = traffic.plan(t, ["a", "b"], 5, 10.0)
    due = [d for d, _t in p["due"]]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 10.0
    assert 1700 < len(due) < 2300          # 200/s for 10 s
    share_a = sum(1 for _d, name in p["due"] if name == "a") / len(due)
    assert 0.70 < share_a < 0.80
    assert p == traffic.plan(t, ["a", "b"], 5, 10.0)
