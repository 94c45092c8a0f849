"""The traffic file ``ssb_flights`` and the dataset ``ssb`` under the
general generator: one flight of the thirteen queries a deck, enough texts
a template that dedup answers next to nothing, the least bytes of each,
and a reference that answers every template from its text as a pass over
the generated columns does (test_traffic.py's parametrised tests pick the
file up by themselves for the rest; tests/test_ssb_deployment.py holds the
program to the same brute force over a small hierarchy)."""

import collections
import json
import os

import numpy as np
import pytest

from benchmark.datasets import ssb
from benchmark.harness import pql, traffic
from benchmark.harness.min_bytes import min_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
WIDTH = 1 << 16  # one shard of the configuration's hierarchy, narrowed
SEED = (1 << 31) + 39


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark", "configs", "ssb-24.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def shard(cfg):
    """Two shards' columns and the reference's cubes over them."""
    hier = ssb.Hierarchy(cfg)
    tabs = ssb.tables(SEED, hier)
    cubes = ssb.new_cubes(hier)
    parts = []
    for s in range(2):
        cols = ssb.gen_shard(SEED, s, WIDTH, hier, tabs)
        ssb.add_to_cubes(cubes, hier, cols)
        parts.append(cols)
    cols = {f: np.concatenate([p[f] for p in parts]) for f in ssb.SET_FIELDS + ssb.INT_FIELDS}
    return cols, ssb.Reference(cfg, [{"cubes": cubes, "shards": [0, 1]}])


def test_a_deck_is_one_flight_of_thirteen_and_each_template_has_many_texts():
    spec = traffic.load("ssb_flights")
    names = [t["name"] for t in spec["templates"]]
    assert names == ["q1_1", "q1_2", "q1_3", "q2_1", "q2_2", "q2_3", "q3_1", "q3_2", "q3_3", "q3_4",
                     "q4_1", "q4_2", "q4_3"]
    assert [t["share"] for t in spec["templates"]] == [1] * 13
    assert spec["clients"] == 16 and spec["processes"] == 4 and spec["check_share"] == 1.0
    gen = traffic.Generator(spec, [SEED, 2])
    assert [ti for ti, _ in gen.warmup()] == list(range(13))  # no compiled domain
    texts = collections.defaultdict(set)
    for _ in range(13 * 400):
        ti, text = gen.draw()
        texts[ti].add(text)
    assert min(len(t) for t in texts.values()) >= 25


def test_every_template_is_answered_as_a_pass_over_the_columns_answers_it(shard):
    cols, ref = shard
    gen = traffic.Generator(traffic.load("ssb_flights"), [SEED, 3])
    for _ in range(4 * 13):
        _, text = gen.draw()
        call = pql.parse(text)
        assert ref.answer(call) == _brute(cols, call), text


def test_least_bytes_count_every_plane_the_answer_reads(cfg):
    """Q4.3: 7 + 250 + 1,000 grouped rows; of the filter, the c_region,
    s_nation and p_category rows (its two years are grouped rows already);
    and lo_profit's 24 filled bits with the existence plane."""
    spec = traffic.load("ssb_flights")
    text = next(t["pql"] for t in spec["templates"] if t["name"] == "q4_3")
    text = text.replace("{cr}", "1").replace("{sn}", "24").replace("{c}", "3")
    schema = {f: ({"rows": s["rows"]} if "rows" in s else {"bits": s["bits_filled"]})
              for f, s in cfg["schema"].items()}
    assert min_bytes(pql.parse(text), schema, 8 * 64) == (7 + 250 + 1000 + 1 + 1 + 1 + 25) * 64


# ------------------------------------------------------------ brute force
_OPS = {"<": np.less, "<=": np.less_equal}


def _rows(call, cols) -> np.ndarray:
    if call.name == "Row":
        if call.cond is not None:
            c, v = call.cond, cols[call.cond.field]
            if c.op == "between":
                lo_op, lo, hi_op, hi = c.value
                return _OPS[lo_op](lo, v) & _OPS[hi_op](v, hi)
            return _OPS[c.op](v, c.value)
        ((fld, row),) = call.kw.items()
        return cols[fld] == row
    kids = [_rows(c, cols) for c in call.children]
    return (np.logical_and if call.name == "Intersect" else np.logical_or).reduce(kids)


def _brute(cols, call):
    if call.name == "Sum":
        keep = _rows(call.children[0], cols)
        return {"value": int(cols[call.kw["field"]][keep].sum()), "count": int(keep.sum())}
    fields = [c.pos[0] for c in call.children]
    keep = _rows(call.kw["filter"], cols)
    keys = np.stack([cols[f][keep] for f in fields], axis=1)
    measure = cols[call.kw["aggregate"].kw["field"]][keep]
    groups, inverse = np.unique(keys, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    count = np.bincount(inverse, minlength=len(groups))
    total = np.zeros(len(groups), dtype=np.int64)
    np.add.at(total, inverse, measure)
    return [{"group": [{"field": f, "rowID": int(r)} for f, r in zip(fields, g)],
             "count": int(n), "sum": int(s)}
            for g, n, s in zip(groups.tolist(), count.tolist(), total.tolist())]
