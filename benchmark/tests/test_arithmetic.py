"""Percentile, rate, interval-union and least-bytes arithmetic on fixed
samples. Run by hand: ``python -m pytest benchmark/tests -q``."""

import os

import pytest

from benchmark.harness import pql, stats
from benchmark.harness.min_bytes import min_bytes, planes
from benchmark.harness.reduce_trace import reduce_planes, union_seconds

HERE = os.path.dirname(os.path.abspath(__file__))


def test_percentile_is_nearest_rank():
    values = [float(v) for v in range(1, 101)]  # 1..100
    assert stats.percentile(values, 0.50) == 50.0
    assert stats.percentile(values, 0.95) == 95.0
    assert stats.percentile([7.0], 0.95) == 7.0
    assert stats.percentile([3.0, 1.0, 2.0], 0.50) == 2.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def _rec(t0, t1, status=200):
    return (0, t0, t1, status, "Count(Row(f=1))", None)


def test_latency_counts_requests_sent_in_the_window_and_failures_as_slowest():
    records = [_rec(9.9, 10.1), _rec(10.0, 10.5), _rec(19.0, 21.0), _rec(12.0, 12.1, 503), _rec(20.0, 20.2)]
    lat = stats.latencies_ms(records, 10.0, 20.0)
    assert sorted(round(v, 6) for v in lat) == [500.0, 2000.0, stats.FAILED_MS]
    assert stats.percentile(lat, 0.95) == stats.FAILED_MS


def test_rate_counts_good_replies_completed_in_the_window():
    records = [_rec(9.9, 10.1), _rec(10.0, 10.5), _rec(19.0, 21.0), _rec(12.0, 12.1, 503)]
    assert stats.completed_rate(records, 10.0, 20.0) == pytest.approx(2 / 10.0)


def test_union_of_overlapping_intervals():
    ns = 1_000_000_000
    assert union_seconds([]) == 0.0
    assert union_seconds([(0, ns), (ns // 2, 2 * ns), (3 * ns, 4 * ns)]) == pytest.approx(3.0)
    assert union_seconds([(0, 4 * ns), (ns, 2 * ns)]) == pytest.approx(4.0)


def test_reduce_planes_takes_the_op_line_of_device_planes_only():
    planes_ = [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [("x", 0, 10**9)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "Steps", "events": [("step", 0, 10**10)]},
            {"name": "XLA Ops", "events": [("fusion.1", 0, 10**8), ("fusion.1", 5 * 10**7, 10**8),
                                           ("copy.2", 10**9, 10**8)]},
        ]},
    ]
    out = reduce_planes(planes_)
    assert out["busy_s"] == pytest.approx(0.25)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.2)]
    assert reduce_planes(planes_[:1])["busy_s"] is None
    planes_[1]["lines"].pop()  # a device plane without its op line is an error
    with pytest.raises(ValueError):
        reduce_planes(planes_)


SCHEMA = {"cab_type": {"rows": 3}, "passenger_count": {"rows": 10}, "pickup_year": {"rows": 8},
          "dist_miles": {"rows": 32}, "total_amount_dollars": {"bits": 17}}
PLANE = (1 << 20) // 8  # one shard's row plane in bytes

# (query, planes an exact answer reads): 18 = 17 bit slices + existence
CASES = [
    ("TopN(cab_type)", 3),
    ("Sum(Row(passenger_count=2), field=total_amount_dollars)", 18 + 1),
    ("Count(Intersect(Row(pickup_year=3), Row(passenger_count=1)))", 2),
    ("TopN(dist_miles, Intersect(Row(pickup_year=3), Row(passenger_count=1)))", 32 + 2),
    ("Count(Union(Row(cab_type=1), Row(cab_type=1), Row(passenger_count=0)))", 2),
    ("Count(Intersect(Row(cab_type=1), Not(Union(Row(cab_type=2), Row(cab_type=0)))))", 4),
    ("Count(Intersect(Row(total_amount_dollars > 50), Row(cab_type=1)))", 18 + 1),
    ("GroupBy(Rows(passenger_count), Rows(pickup_year), filter=Row(cab_type=0))", 18 + 1),
    ("GroupBy(Rows(passenger_count), aggregate=Sum(field=total_amount_dollars))", 10 + 18),
]


@pytest.mark.parametrize("text,n_planes", CASES)
def test_min_bytes_per_template(text, n_planes):
    call = pql.parse(text)
    assert len(planes(call, SCHEMA)) == n_planes
    assert min_bytes(call, SCHEMA, 4 << 20) == n_planes * 4 * PLANE


def test_recorded_device_trace_reduces_to_a_busy_time_inside_its_span():
    path = os.path.join(HERE, "tiny.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace beside the tests")
    from benchmark.harness.reduce_trace import read_xplane

    out = reduce_planes(read_xplane(path))
    assert len(out["devices"]) == 1
    # five jitted calls with 10 ms of sleep between them
    assert 0.0 < out["busy_s"] < out["device_span_s"]
    assert out["device_span_s"] > 0.04
    assert out["device_ops"]


def test_the_taxi_reference_answers_each_call_from_its_joint_table():
    import numpy as np

    from benchmark.datasets import taxi

    cfg = {"schema": {"a": {"type": "set", "rows": 2}, "b": {"type": "set", "rows": 3},
                      "v": {"type": "int", "min": 0, "max": 100, "bits": 7}}}
    count = np.array([[5, 0, 2], [1, 4, 0]])  # [a, b] -> columns
    total = np.array([[50, 0, 6], [7, 8, 0]])  # [a, b] -> sum of v
    ref = taxi.Reference(cfg, [{"count": count.ravel(), "total": total.ravel()}])

    def ask(text):
        return ref.answer(pql.parse(text))

    assert ask("Count(Intersect(Row(a=0), Row(b=2)))") == 2
    assert ask("Count(Union(Row(a=1), Row(b=0)))") == 10
    assert ask("Count(Not(Row(b=0)))") == 6
    assert ask("Sum(Row(b=0), field=v)") == {"value": 57, "count": 6}
    assert ask("TopN(b)") == [{"id": 0, "count": 6}, {"id": 1, "count": 4}, {"id": 2, "count": 2}]
    assert ask("TopN(b, Row(a=0), n=1)") == [{"id": 0, "count": 5}]
    assert ask("GroupBy(Rows(b), Rows(a), filter=Row(a=1), aggregate=Sum(field=v))") == [
        {"group": [{"field": "b", "rowID": 0}, {"field": "a", "rowID": 1}], "count": 1, "sum": 7},
        {"group": [{"field": "b", "rowID": 1}, {"field": "a", "rowID": 1}], "count": 4, "sum": 8},
    ]
    with pytest.raises(ValueError):
        ask("Count(Row(v > 3))")
