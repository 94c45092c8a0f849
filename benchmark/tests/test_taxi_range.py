"""The dataset ``taxi_range`` by hand: its reference against a brute-force
pass over two generated shards for every operator and both strictnesses
of a band, anywhere in a row tree, under ``Count``, ``TopN``, ``Sum`` and
``GroupBy``; the calls it shares with ``taxi`` against ``taxi``'s own
reference; and the control (the last part lost)."""

import json
import operator
import os

import numpy as np
import pytest

from benchmark.datasets import taxi, taxi_range
from benchmark.harness import pql

HERE = os.path.dirname(os.path.abspath(__file__))
SEED, WIDTH, SHARDS = 2800000037, 1 << 12, 2
OPS = {"<": operator.lt, "<=": operator.le, ">": operator.gt, ">=": operator.ge,
       "==": operator.eq, "!=": operator.ne}
F = "total_amount_dollars"


@pytest.fixture(scope="module")
def data():
    with open(os.path.join(HERE, "..", "configs", "taxi-128r.json")) as f:
        cfg = json.load(f)
    luts = taxi._luts(cfg)
    sets, counts, _ = taxi._fields(cfg)
    shards = [taxi.gen_shard(SEED, s, WIDTH, luts) for s in range(SHARDS)]
    cols = {f: np.concatenate([rows[k] for rows, _ in shards]) for k, f in enumerate(sets)}
    amount = np.concatenate([a for _, a in shards])
    states, old = [], []
    for (rows, a), shard in zip(shards, range(SHARDS)):
        cell = np.ravel_multi_index([r.astype(np.intp) for r in rows], counts)
        bins = int(np.prod(counts))
        states.append({"hist": np.bincount(cell * taxi_range.AMOUNTS + a - taxi_range.AMOUNT_MIN,
                                           minlength=bins * taxi_range.AMOUNTS), "shards": [shard]})
        old.append({"count": np.bincount(cell, minlength=bins), "shards": [shard],
                    "total": np.bincount(cell, weights=a, minlength=bins).astype(np.int64)})
    return {"cfg": cfg, "cols": cols, "amount": amount, "states": states,
            "ref": taxi_range.Reference(cfg, states), "taxi": taxi.Reference(cfg, old)}


def brute(data, call) -> np.ndarray:
    """The columns a row call holds, one bool a column."""
    if call.name == "Row":
        if call.cond is None:
            ((fld, row),) = call.kw.items()
            return data["cols"][fld] == row
        a, c = data["amount"], call.cond
        if c.op == "between":
            lo_op, lo, hi_op, hi = c.value
            return OPS[lo_op](lo, a) & OPS[hi_op](a, hi)
        return OPS[c.op](a, c.value)
    kids = [brute(data, k) for k in call.children]
    fold = {"Intersect": np.logical_and, "Union": np.logical_or, "Xor": np.logical_xor,
            "Difference": lambda x, y: x & ~y}
    if call.name == "Not":
        return ~kids[0]
    out = kids[0]
    for k in kids[1:]:
        out = fold[call.name](out, k)
    return out


def brute_answer(data, call):
    if call.name == "Count":
        return int(brute(data, call.children[0]).sum())
    held = brute(data, call.children[0]) if call.children else np.ones(len(data["amount"]), dtype=bool)
    if call.name == "Sum":
        return {"value": int(data["amount"][held].sum()), "count": int(held.sum())}
    if call.name == "TopN":
        counts = np.bincount(data["cols"][call.pos[0]][held])
        order = sorted(range(counts.size), key=lambda r: (-int(counts[r]), r))
        return [{"id": r, "count": int(counts[r])} for r in order if counts[r]]
    raise ValueError(call.name)


CONDS = [f"{F} {op} {v}" for op in OPS for v in (3, 4, 17, 200, 1026, 1027, 0, 100000, 2 ** 70)]
CONDS += [f"{lo} {a} {F} {b} {hi}" for a in ("<", "<=") for b in ("<", "<=")
          for lo, hi in ((3, 64), (10, 11), (10, 10), (63, 1026), (0, 5000), (90, 5))]
SHAPES = ["Count(Row({c}))",
          "Count(Intersect(Row(pickup_year=2), Row({c})))",
          "Count(Union(Row(cab_type=1), Difference(Row({c}), Row(passenger_count=1))))",
          "Count(Not(Xor(Row({c}), Row(dist_miles=2))))",
          "Sum(Row({c}), field=" + F + ")",
          "TopN(dist_miles, Row({c}))",
          "TopN(passenger_count, Intersect(Row(pickup_year=0), Row({c})))"]


@pytest.mark.parametrize("cond", CONDS)
def test_a_condition_anywhere_in_a_row_tree_equals_the_brute_force_pass(data, cond):
    for shape in SHAPES:
        call = pql.parse(shape.replace("{c}", cond))
        assert data["ref"].answer(call) == brute_answer(data, call), shape.replace("{c}", cond)


def test_two_conditions_in_one_tree_and_a_groupby_under_one(data):
    call = pql.parse(f"Count(Intersect(Row({F} > 30), Row({F} <= 35), Row(cab_type=0)))")
    assert data["ref"].answer(call) == brute_answer(data, call)
    call = pql.parse(f"GroupBy(Rows(cab_type), Rows(pickup_year), filter=Row({F} >= 50), aggregate=Sum(field={F}))")
    held = data["amount"] >= 50
    want = []
    for cab in range(3):
        for year in range(8):
            m = held & (data["cols"]["cab_type"] == cab) & (data["cols"]["pickup_year"] == year)
            if m.any():
                want.append({"group": [{"field": "cab_type", "rowID": cab}, {"field": "pickup_year", "rowID": year}],
                             "count": int(m.sum()), "sum": int(data["amount"][m].sum())})
    assert data["ref"].answer(call) == want


@pytest.mark.parametrize("text", [
    "TopN(cab_type)", "Sum(Row(passenger_count=2), field=" + F + ")",
    "Count(Intersect(Row(pickup_year=4), Row(passenger_count=1)))",
    "TopN(dist_miles, Intersect(Row(pickup_year=4), Row(passenger_count=1)))",
    "GroupBy(Rows(cab_type), Rows(passenger_count), aggregate=Sum(field=" + F + "))"])
def test_the_calls_taxi_answers_get_taxis_answers(data, text):
    assert data["ref"].answer(pql.parse(text)) == data["taxi"].answer(pql.parse(text))


def test_the_control_loses_the_last_part_and_answers_otherwise(data):
    kept = taxi_range.drop_last_part(data["states"])
    assert [s["shards"] for s in kept] == [[0]]
    call = pql.parse(f"Count(Row({F} > 3))")
    assert taxi_range.Reference(data["cfg"], kept).answer(call) < data["ref"].answer(call)


def test_an_amount_outside_the_axis_or_another_field_fails_loudly(data):
    with pytest.raises(ValueError):
        data["ref"].answer(pql.parse("Count(Row(dist_miles > 3))"))
    with pytest.raises(ValueError):
        data["ref"].answer(pql.parse(f"Count(Row(3 > {F} > 1))"))


def test_a_program_without_the_required_family_is_refused_before_loading(data, monkeypatch):
    """``taxi-128r`` requires the counter that came with the operands: this
    program has it; one that lacks it (PR 32's parent) ends the run."""
    from benchmark.harness.server import RunFailure
    from pilosa_tpu.utils import stats as program_stats

    cfg = data["cfg"]
    assert cfg["requires"]["metrics_families"] == ["bsi_condition_leaves_total"]
    assert taxi_range.schema(cfg) == taxi.schema(cfg)
    without = {k: v for k, v in program_stats._METRIC_HELP.items() if k != "bsi_condition_leaves_total"}
    monkeypatch.setattr(program_stats, "_METRIC_HELP", without)
    with pytest.raises(RunFailure, match="requires a program that registers 'bsi_condition_leaves_total'"):
        taxi_range.schema(cfg)
    assert taxi_range.schema({**cfg, "requires": {}}) == taxi.schema(cfg)
