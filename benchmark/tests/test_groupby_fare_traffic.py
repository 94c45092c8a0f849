"""The traffic file ``groupby_fare`` under the general generator: one
pass of the four queries a deck, four shapes to warm, a fare floor in
every request, and a reference that answers every template from its text
(test_traffic.py's parametrised tests pick the file up by themselves for
the rest)."""

import collections

from benchmark.harness import pql, traffic
from benchmark.harness.min_bytes import min_bytes


def test_groupby_fare_is_one_pass_of_the_four_and_each_floor_is_its_own_text():
    spec = traffic.load("groupby_fare")
    assert [t["share"] for t in spec["templates"]] == [1, 1, 1, 1]
    assert spec["clients"] == 16 and spec["processes"] == 4 and spec["check_share"] == 1.0
    assert spec["domains"]["t"]["values"] == list(range(3, 259)) and not spec["domains"]["t"].get("compiled")
    gen = traffic.Generator(spec, [6, 2])
    assert [ti for ti, _ in gen.warmup()] == [0, 1, 2, 3]
    texts = [gen.draw()[1] for _ in range(4000)]
    calls = [pql.parse(text) for text in texts]
    assert all(c.name == "GroupBy" and c.kw["filter"].cond.op == ">" for c in calls)
    levels = collections.Counter(len(c.children) for c in calls)
    assert levels == {1: 2000, 2: 1000, 3: 1000}
    assert sum("aggregate" in c.kw for c in calls) == 1000
    assert len(set(texts)) > 950  # 4 x 256 texts: dedup answers next to nothing


def test_least_bytes_of_the_fourth_query_are_sixty_two_planes():
    """50 rows of the three grouped fields and the 12 planes of the
    filter's int field that hold data (``int_planes: filled``)."""
    spec = traffic.load("groupby_fare")
    text = spec["templates"][3]["pql"].replace("{t}", "40")
    schema = {"passenger_count": {"rows": 10}, "pickup_year": {"rows": 8}, "dist_miles": {"rows": 32},
              "total_amount_dollars": {"bits": 11}}
    assert min_bytes(pql.parse(text), schema, 8 * 64) == 62 * 64
