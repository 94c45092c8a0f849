"""The least bytes an exact answer has to read from HBM, per query.

Counts the same work whatever implements it: each distinct stored row
plane the answer depends on is read once, ``columns / 8`` bytes a plane.

- a tree of k distinct rows: k planes, plus the existence plane under
  ``Not``;
- ``Row(field op v)`` / ``between`` over a BSI field: bit depth + 1
  planes (the bit slices and the existence slice);
- ``Count(tree)``: the tree's planes;
- ``TopN(field, tree)``: the field's rows plus the tree's;
- ``Sum``/``Min``/``Max(tree, field=f)``: f's depth + 1 plus the tree's;
- ``GroupBy(Rows(a), Rows(b), filter=tree)``: both fields' rows plus the
  tree's, plus depth + 1 with an aggregate.

``schema`` is {field: {"rows": n}} for set fields and {field: {"bits":
d}} for BSI fields. Bitmap algebra does no matrix work, so the bound is
these bytes over the HBM peak.
"""

from __future__ import annotations

EXISTENCE = ("_exists", 0)


def planes(call, schema: dict) -> set:
    """The distinct (field, row) planes an exact answer reads."""
    name = call.name
    if name == "Row":
        if call.cond is not None:
            return _bsi(call.cond.field, schema)
        ((fld, row),) = call.kw.items()
        return {(fld, row)}
    if name in ("Intersect", "Union", "Difference", "Xor"):
        return set().union(*(planes(c, schema) for c in call.children))
    if name == "Not":
        return planes(call.children[0], schema) | {EXISTENCE}
    if name == "Count":
        return planes(call.children[0], schema)
    if name == "TopN":
        out = _rows(call.pos[0], schema)
        return out.union(*(planes(c, schema) for c in call.children))
    if name in ("Sum", "Min", "Max"):
        out = _bsi(call.kw["field"], schema)
        return out.union(*(planes(c, schema) for c in call.children))
    if name == "GroupBy":
        out = set()
        for c in call.children:
            out |= _rows(c.pos[0], schema)
        if "filter" in call.kw:
            out |= planes(call.kw["filter"], schema)
        if "aggregate" in call.kw:
            out |= _bsi(call.kw["aggregate"].kw["field"], schema)
        return out
    raise ValueError(f"min_bytes: no rule for {name!r}")


def _rows(fld: str, schema: dict) -> set:
    return {(fld, r) for r in range(schema[fld]["rows"])}


def _bsi(fld: str, schema: dict) -> set:
    return {(fld, f"bit{b}") for b in range(schema[fld]["bits"])} | {(fld, "exists")}


def min_bytes(call, schema: dict, columns: int) -> int:
    return len(planes(call, schema)) * (columns // 8)
