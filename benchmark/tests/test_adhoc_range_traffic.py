"""The traffic file ``adhoc_range`` under the general generator: five
shapes to warm, a condition leaf in every request, and thresholds that
hardly repeat (test_traffic.py's parametrised tests pick the file up by
themselves for the rest)."""

from benchmark.harness import pql, traffic


def test_adhoc_range_warms_five_shapes_and_hardly_repeats_a_threshold():
    spec = traffic.load("adhoc_range")
    assert [t["share"] for t in spec["templates"]] == [3, 3, 2, 2, 2]
    assert spec["domains"]["t"]["values"] == list(range(3, 1027)) and not spec["domains"]["t"].get("compiled")
    assert spec["domains"]["lo"]["values"] == list(range(3, 64))
    assert spec["domains"]["hi"]["values"] == list(range(64, 1027))
    gen = traffic.Generator(spec, [6, 2])
    assert [ti for ti, _ in gen.warmup()] == [0, 1, 2, 3, 4]
    texts = [gen.draw()[1] for _ in range(10000)]
    conds = [c.cond for text in texts for c in _rows(pql.parse(text)) if c.cond is not None]
    assert len(conds) == len(texts)  # every request has one condition leaf
    assert len({c.value for c in conds if c.op != "between"}) > 900
    bands = [c.value for c in conds if c.op == "between"]
    assert bands and all(lo < hi and lo_op == hi_op == "<=" for lo_op, lo, hi_op, hi in bands)
    assert len(set(texts)) > 5000


def _rows(call):
    if call.name == "Row":
        yield call
    for child in call.children:
        yield from _rows(child)
