"""The general generator on the committed traffic files."""

import collections
import glob
import os

import pytest

from benchmark.harness import pql, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = sorted(os.path.basename(p)[:-5] for p in glob.glob(os.path.join(HERE, "..", "traffic", "*.json")))


@pytest.mark.parametrize("name", NAMES)
def test_every_query_parses_and_decks_hold_the_shares(name):
    spec = traffic.load(name)
    gen = traffic.Generator(spec, [5, 1])
    deck = sum(t["share"] for t in spec["templates"])
    seen = collections.Counter()
    for _ in range(3 * deck):
        ti, text = gen.draw()
        pql.parse(text)
        seen[ti] += 1
    assert [seen[i] for i in range(len(spec["templates"]))] == [3 * t["share"] for t in spec["templates"]]
    for _, text in gen.warmup():
        pql.parse(text)


def test_same_seed_same_requests_other_seed_other_order():
    spec = traffic.load("four_queries")
    g1, g2, g3 = (traffic.Generator(spec, s) for s in ([9, 3], [9, 3], [10, 3]))
    one = [g1.draw() for _ in range(50)]
    assert one == [g2.draw() for _ in range(50)]
    assert one != [g3.draw() for _ in range(50)]


def test_four_queries_is_one_pass_of_the_loops_and_warms_each_shape_once():
    spec = traffic.load("four_queries")
    assert [t["share"] for t in spec["templates"]] == [1, 10, 80, 80]
    warm = traffic.Generator(spec, [1]).warmup()
    assert [ti for ti, _ in warm] == [0, 1, 2, 3]


def test_a_zipf_choice_favours_the_first_values_and_warms_all():
    spec = {
        "clients": 1,
        "domains": {"panel": {"kind": "choice", "zipf": 1.2, "compiled": True,
                              "values": [f"Count(Row(cab_type={k}))" for k in range(5)]}},
        "templates": [{"name": "panel", "share": 1, "pql": "{q}", "params": {"q": "panel"}}],
    }
    gen = traffic.Generator(spec, [4])
    seen = collections.Counter(gen.draw()[1] for _ in range(2000))
    ranked = [seen[f"Count(Row(cab_type={k}))"] for k in range(5)]
    assert ranked == sorted(ranked, reverse=True) and ranked[4] > 0
    assert sorted(q for _, q in gen.warmup()) == sorted(spec["domains"]["panel"]["values"])
