"""Dataset ``ssb``: the Star Schema Benchmark's ``lineorder`` flattened
into one index, as a bitmap index runs it (O'Neil, O'Neil, Chen, "Star
Schema Benchmark", revision 3).

Every column is one lineorder row. Each dimension attribute that the
thirteen queries read is a set field whose row id is an integer standing
for the SSB string (the configuration's ``row_of``); each measure is a
BSI int field in cents. The generator follows SSB's ``dbgen``, seeded:
order dates uniform over the order-date range, quantity 1-50 and discount
0-10 uniform, customer, supplier and part keys uniform over the scale
factor's dimension sizes, each key's attributes from a seeded dimension
table (``tables``), TPC-H's retail price from the part key, and

    extendedprice = quantity * retailprice
    revenue       = extendedprice * (100 - discount) / 100
    supplycost    = 6 * retailprice / 10
    extprice_discount = extendedprice * discount   (Q1's measure)
    profit        = revenue - supplycost           (Q4's measure)

The configuration's ``hierarchy`` sizes it: the nation -> region table,
cities a nation, manufacturers, categories a manufacturer, brands a
category, the order-date range and the dimension sizes, so that a test
can run the same code over a small hierarchy.

The reference keeps, per query family, a CUBE of counts and of one
measure's totals over the finest keys that family reads, filled while
the shards are generated (``CUBES``): (date, discount, quantity) for Q1,
(year, brand, supplier region) for Q2, (customer city, supplier city,
month) for Q3, and three for Q4. A coarse attribute is a lookup table
over a cube's axis (a city's nation, a date's year), so a row call is a
mask along one axis, a condition over ``lo_quantity`` or ``lo_discount``
a mask along that axis, and a grouped field a relabelling of its axis.
It answers ``Sum`` and ``GroupBy(..., aggregate=Sum(...))`` from the
query TEXT, with nothing of the program's parser, planner, engines or ops
in it; answers are memoised by the parsed call.
"""

from __future__ import annotations

import json
import operator

import numpy as np

from benchmark.datasets.taxi import BSI_EXISTS, BSI_OFFSET, _pack, drop_last_part  # noqa: F401
from benchmark.harness.server import RunFailure

BSI_SIGN = 1
SET_FIELDS = (
    "d_year", "d_yearmonthnum", "d_weeknuminyear",
    "c_region", "c_nation", "c_city", "s_region", "s_nation", "s_city",
    "p_mfgr", "p_category", "p_brand1",
)
INT_FIELDS = (
    "lo_quantity", "lo_discount", "lo_revenue", "lo_supplycost",
    "lo_extprice_discount", "lo_profit",
)
DISCOUNTS = 11  # 0-10
QUANTITIES = 50  # 1-50
# per query family: the axes of its cube and the measure it totals
CUBES = (
    ("q1", ("date", "discount", "quantity"), "lo_extprice_discount"),
    ("q2", ("year", "brand", "s_region"), "lo_revenue"),
    ("q3", ("c_city", "s_city", "month"), "lo_revenue"),
    ("q41", ("year", "c_nation", "s_region", "mfgr"), "lo_profit"),
    ("q42", ("year", "c_region", "s_nation", "category"), "lo_profit"),
    ("q43", ("year", "c_region", "s_city", "brand"), "lo_profit"),
)
_OPS = {"<": operator.lt, "<=": operator.le, ">": operator.gt, ">=": operator.ge}


class Hierarchy:
    """The configuration's dimension hierarchy, and every lookup table
    from a cube's axis to a field's row id."""

    def __init__(self, cfg: dict):
        h = cfg["hierarchy"]
        self.nation_region = np.asarray(h["nation_region"], dtype=np.int64)
        self.cpn = int(h["cities_per_nation"])
        self.cpm = int(h["categories_per_mfgr"])
        self.bpc = int(h["brands_per_category"])
        self.customers, self.suppliers, self.parts = (
            int(h["customers"]), int(h["suppliers"]), int(h["parts"]))
        nations = self.nation_region.size
        regions = int(self.nation_region.max()) + 1
        cities, mfgrs = nations * self.cpn, int(h["mfgrs"])
        categories = mfgrs * self.cpm
        brands = categories * self.bpc
        days = np.datetime64(h["first_day"], "D") + np.arange(int(h["order_days"]))
        first_year = int(str(h["first_day"])[:4])
        year = days.astype("datetime64[Y]").astype(np.int64) + 1970 - first_year
        month = days.astype("datetime64[M]").astype(np.int64) - (first_year - 1970) * 12
        doy = (days - days.astype("datetime64[Y]")).astype(np.int64)
        years = int(year.max()) + 1
        self.days = days.size
        ident = np.arange
        # axis -> (length, {set field: row id of each axis index})
        self.axes = {
            "date": (days.size, {"d_year": year, "d_yearmonthnum": month,
                                 "d_weeknuminyear": doy // 7}),
            "month": (years * 12, {"d_year": ident(years * 12) // 12,
                                   "d_yearmonthnum": ident(years * 12)}),
            "year": (years, {"d_year": ident(years)}),
            "brand": (brands, {"p_brand1": ident(brands), "p_category": ident(brands) // self.bpc,
                               "p_mfgr": ident(brands) // (self.bpc * self.cpm)}),
            "category": (categories, {"p_category": ident(categories),
                                      "p_mfgr": ident(categories) // self.cpm}),
            "mfgr": (mfgrs, {"p_mfgr": ident(mfgrs)}),
        }
        for side in ("c", "s"):
            self.axes[f"{side}_city"] = (cities, {
                f"{side}_city": ident(cities), f"{side}_nation": ident(cities) // self.cpn,
                f"{side}_region": self.nation_region[ident(cities) // self.cpn]})
            self.axes[f"{side}_nation"] = (nations, {
                f"{side}_nation": ident(nations), f"{side}_region": self.nation_region})
            self.axes[f"{side}_region"] = (regions, {f"{side}_region": ident(regions)})
        # the BSI fields a cube holds as an axis: the value of each index
        self.values = {"discount": ("lo_discount", ident(DISCOUNTS)),
                       "quantity": ("lo_quantity", ident(QUANTITIES) + 1)}
        self.axes["discount"] = (DISCOUNTS, {})
        self.axes["quantity"] = (QUANTITIES, {})
        self.rows = {"d_year": years, "d_yearmonthnum": years * 12, "d_weeknuminyear": 53,
                     "p_mfgr": mfgrs, "p_category": categories, "p_brand1": brands}
        for side in ("c", "s"):
            self.rows.update({f"{side}_region": regions, f"{side}_nation": nations,
                              f"{side}_city": cities})

    def check(self, cfg: dict) -> None:
        """The configuration's schema is this dataset's: its fields, and
        each set field's rows as the hierarchy gives them."""
        schema = cfg["schema"]
        if set(schema) != set(SET_FIELDS) | set(INT_FIELDS):
            raise ValueError("dataset ssb: the schema must hold the SSB fields, and only them")
        for f in SET_FIELDS:
            if schema[f]["rows"] != self.rows[f]:
                raise ValueError(f"dataset ssb: {f} has {self.rows[f]} rows in the hierarchy")


# ------------------------------------------------------------------ generator
def schema(cfg: dict) -> list[tuple[str, bytes]]:
    """The fields' options, once the configuration is seen to be this
    dataset's and the program to register every ``/metrics`` family the
    configuration ``requires`` (``pilosa_tpu/utils/stats.py``'s table of
    families, read without jax): a program without one ends the run here,
    before a byte is loaded."""
    Hierarchy(cfg).check(cfg)
    from pilosa_tpu.utils import stats as program_stats

    known = getattr(program_stats, "_METRIC_HELP", {})
    for family in cfg.get("requires", {}).get("metrics_families", []):
        if family not in known:
            raise RunFailure(
                f"configuration {cfg['name']!r} requires a program that registers {family!r}: "
                + cfg["requires"]["why"]
            )
    out = []
    for f, s in cfg["schema"].items():
        opts = {"options": {"type": "int", "min": s["min"], "max": s["max"]}} if s["type"] == "int" else {}
        out.append((f, json.dumps(opts).encode()))
    return out


def parts(cfg: dict) -> int:
    return int(cfg["scale"]["shards"])


def tables(seed: int, hier: Hierarchy) -> dict:
    """The seeded dimension tables: each customer's and supplier's city
    (a nation uniform, a city digit uniform), each part's brand (a
    manufacturer, a category in it and a brand in that, each uniform)."""
    rng = np.random.default_rng([seed, 0x55B0])
    cities = hier.nation_region.size * hier.cpn
    return {
        "cust_city": rng.integers(0, cities, hier.customers).astype(np.int32),
        "supp_city": rng.integers(0, cities, hier.suppliers).astype(np.int32),
        "part_brand": rng.integers(0, hier.axes["brand"][0], hier.parts).astype(np.int32),
    }


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """TPC-H's P_RETAILPRICE of a 1-based part key, in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def gen_shard(seed: int, shard: int, width: int, hier: Hierarchy, tabs: dict) -> dict:
    """The seeded lineorder rows of one shard: every cube axis's index and
    every field's value, a column each."""
    rng = np.random.default_rng([seed, 0x55B1, shard])
    day = rng.integers(0, hier.days, width)
    qty = rng.integers(1, QUANTITIES + 1, width)
    disc = rng.integers(0, DISCOUNTS, width)
    c_city = tabs["cust_city"][rng.integers(0, hier.customers, width)].astype(np.int64)
    s_city = tabs["supp_city"][rng.integers(0, hier.suppliers, width)].astype(np.int64)
    partkey = rng.integers(0, hier.parts, width)
    brand = tabs["part_brand"][partkey].astype(np.int64)
    price = retail_price(partkey + 1)
    ext = qty * price
    revenue = ext * (100 - disc) // 100
    supplycost = 6 * price // 10
    cols = {"date": day, "discount": disc, "quantity": qty - 1, "c_city": c_city, "s_city": s_city,
            "brand": brand,
            "lo_quantity": qty, "lo_discount": disc, "lo_revenue": revenue,
            "lo_supplycost": supplycost, "lo_extprice_discount": ext * disc,
            "lo_profit": revenue - supplycost}
    # the coarse axes, and every set field, from the lookup tables
    a = hier.axes
    cols["month"] = a["date"][1]["d_yearmonthnum"][day]
    cols["year"] = a["date"][1]["d_year"][day]
    for side in ("c", "s"):
        cols[f"{side}_nation"] = cols[f"{side}_city"] // hier.cpn
        cols[f"{side}_region"] = hier.nation_region[cols[f"{side}_nation"]]
    cols["category"] = brand // hier.bpc
    cols["mfgr"] = cols["category"] // hier.cpm
    for axis in ("date", "c_city", "s_city", "brand"):
        for f, lut in a[axis][1].items():
            cols.setdefault(f, lut[cols[axis]])
    return cols


def shard_frames(cfg: dict, shard: int, width: int, cols: dict):
    """The import-roaring frames of one shard: a set field's one row a
    column as sorted positions, an int field's bit slices dense."""
    from pilosa_tpu import roaring
    from pilosa_tpu.roaring.build import bitmap_from_positions
    from pilosa_tpu.roaring.serialize import serialize

    for f in SET_FIELDS:
        row = cols[f]
        order = np.argsort(row, kind="stable")  # row-major, columns ascending
        pos = row[order].astype(np.uint64) * np.uint64(width) + order.astype(np.uint64)
        yield f, "standard", shard, serialize(bitmap_from_positions(pos, presorted=True)), width
    for f in INT_FIELDS:
        v = cols[f]
        slices = [(BSI_EXISTS, _pack(np.ones(width, dtype=bool)))]
        if (v < 0).any():
            slices.append((BSI_SIGN, _pack(v < 0)))
        mag = np.abs(v)
        for b in range(cfg["schema"][f]["bits"]):
            bit = ((mag >> b) & 1).astype(bool)
            if bit.any():  # an empty slice is no row
                slices.append((BSI_OFFSET + b, _pack(bit)))
        yield (f, "bsi", shard) + roaring.payload_from_rows(slices, width)


def new_cubes(hier: Hierarchy) -> dict:
    return {name: (np.zeros(_size(hier, axes), dtype=np.int64),
                   np.zeros(_size(hier, axes), dtype=np.int64))
            for name, axes, _measure in CUBES}


def add_to_cubes(cubes: dict, hier: Hierarchy, cols: dict) -> None:
    """Count the shard's rows into every cube: their number and the
    cube's measure, at each cell. float64 holds a shard's totals exactly
    (under 2**53)."""
    for name, axes, measure in CUBES:
        dims = [hier.axes[a][0] for a in axes]
        cell = np.ravel_multi_index([cols[a] for a in axes], dims)
        count, total = cubes[name]
        count += np.bincount(cell, minlength=count.size)
        total += np.bincount(cell, weights=cols[measure].astype(np.float64),
                             minlength=total.size).astype(np.int64)


def _size(hier: Hierarchy, axes) -> int:
    return int(np.prod([hier.axes[a][0] for a in axes]))


def load_part(base: str, index: str, seed: int, cfg: dict, mine: list[int]) -> dict:
    """Generate the shards in ``mine``, post them over the program's bulk
    route, and count them into the reference's cubes."""
    from pilosa_tpu import loader
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    hier = Hierarchy(cfg)
    tabs = tables(seed, hier)
    cubes = new_cubes(hier)

    def frames():
        for shard in mine:
            cols = gen_shard(seed, shard, SHARD_WIDTH, hier, tabs)
            add_to_cubes(cubes, hier, cols)
            yield from shard_frames(cfg, shard, SHARD_WIDTH, cols)

    stats = loader.stream_frames(base, index, frames(), pipeline=2, timeout=300)
    return {"cubes": cubes, "shards": list(mine), "stats": stats}


# --------------------------------------------------------------- reference
class _Cube:
    def __init__(self, hier: Hierarchy, axes, measure: str, count, total):
        self.axes, self.measure = axes, measure
        self.dims = [hier.axes[a][0] for a in axes]
        self.count, self.total = count.reshape(self.dims), total.reshape(self.dims)
        # field -> (axis position, row id of each index of that axis)
        self.source = {}
        for k, a in enumerate(axes):
            for f, lut in hier.axes[a][1].items():
                self.source.setdefault(f, (k, lut))
            if a in hier.values:
                fld, vals = hier.values[a]
                self.source[fld] = (k, vals)


class Reference:
    def __init__(self, cfg: dict, states: list[dict]):
        self.hier = Hierarchy(cfg)
        self.cubes = []
        for name, axes, measure in CUBES:
            count = sum(s["cubes"][name][0] for s in states)
            total = sum(s["cubes"][name][1] for s in states)
            self.cubes.append(_Cube(self.hier, axes, measure, count, total))
        self._memo: dict = {}

    def answer(self, call):
        """What ``results[0]`` of the program's JSON reply must equal."""
        key = repr(call)
        if key not in self._memo:
            self._memo[key] = self._answer(call)
        return self._memo[key]

    def _answer(self, call):
        if call.name == "Sum":
            filt = call.children[0]
            cube = self._cube(filt, [], call.kw["field"])
            count, total = self._reduce(cube, self._mask(cube, filt), [])
            return {"value": int(total), "count": int(count)}
        if call.name == "GroupBy":
            return self._groupby(call)
        raise ValueError(f"reference: no call {call.name!r}")

    def _groupby(self, call) -> list[dict]:
        fields = [c.pos[0] for c in call.children]
        filt = call.kw["filter"]
        cube = self._cube(filt, fields, call.kw["aggregate"].kw["field"])
        count, total = self._reduce(cube, self._mask(cube, filt), fields)
        return [{"group": [{"field": f, "rowID": r} for f, r in zip(fields, rows)],
                 "count": int(count[tuple(rows)]), "sum": int(total[tuple(rows)])}
                for rows in np.argwhere(count > 0).tolist()]  # row-major: nested ascending

    # ----------------------------------------------------------- the cube
    def _cube(self, filt, fields: list[str], measure: str) -> _Cube:
        """The first cube that totals ``measure`` and holds every field the
        query reads."""
        need = set(fields) | _fields(filt)
        for cube in self.cubes:
            if cube.measure == measure and need <= set(cube.source):
                return cube
        raise ValueError(f"reference: no cube holds {sorted(need)} with {measure}")

    def _mask(self, cube: _Cube, call) -> dict:
        """The cells of ``cube`` in the row ``call`` describes, as one bool
        vector an axis ({axis: vector}; an axis left out is every index):
        an ``Intersect`` of rows, conditions and ``Union``s of one field's
        rows, which is every filter the traffic draws."""
        if call.name == "Row":
            if call.cond is not None:
                k, vals = cube.source[call.cond.field]
                return {k: _met(call.cond, vals)}
            ((fld, row),) = call.kw.items()
            k, lut = cube.source[fld]
            return {k: lut == row}
        kids = [self._mask(cube, c) for c in call.children]
        if call.name == "Intersect":
            out: dict = {}
            for m in kids:
                for k, v in m.items():
                    out[k] = out[k] & v if k in out else v
            return out
        axes = {k for m in kids for k in m}
        if call.name == "Union" and len(axes) == 1:
            (k,) = axes
            return {k: np.logical_or.reduce([m[k] for m in kids])}
        raise ValueError(f"reference: no rule for {call.name} over {len(axes)} axes")

    def _reduce(self, cube: _Cube, mask: dict, fields: list[str]):
        """(counts, totals) over the masked cells, by the rows of
        ``fields`` (axes in the order of ``fields``): the selected indices
        of each axis, then a sum along every axis no field groups by."""
        idx = [np.flatnonzero(mask[k]) if k in mask else np.arange(n)
               for k, n in enumerate(cube.dims)]
        by = {cube.source[f][0]: f for f in fields}
        if len(by) != len(fields):
            raise ValueError(f"reference: {fields} share an axis")
        out = []
        for t in (cube.count, cube.total):
            t = t[np.ix_(*idx)]
            for k in range(len(cube.dims) - 1, -1, -1):
                if k in by:
                    f = by[k]
                    t = _relabel(t, k, cube.source[f][1][idx[k]], self.hier.rows[f])
                else:
                    t = t.sum(axis=k)
            # the grouped axes are left in axis order; put them in the fields'
            order = np.argsort([cube.source[f][0] for f in fields])
            out.append(np.transpose(t, np.argsort(order)) if len(fields) > 1 else t)
        return out


def _fields(call) -> set:
    """Every field a row tree reads."""
    if call.name == "Row":
        return {call.cond.field} if call.cond is not None else set(call.kw)
    return set().union(*(_fields(c) for c in call.children))


def _met(cond, vals: np.ndarray) -> np.ndarray:
    if cond.op == "between":
        lo_op, lo, hi_op, hi = cond.value
        return _OPS[lo_op](lo, vals) & _OPS[hi_op](vals, hi)
    return _OPS[cond.op](vals, cond.value)


def _relabel(t: np.ndarray, axis: int, labels: np.ndarray, rows: int) -> np.ndarray:
    """``t`` summed along ``axis`` by ``labels`` (a row id an index) into
    ``rows`` slots."""
    order = np.argsort(labels, kind="stable")
    lab = labels[order]
    starts = np.flatnonzero(np.r_[True, lab[1:] != lab[:-1]]) if lab.size else lab
    shape = list(t.shape)
    shape[axis] = rows
    out = np.zeros(shape, dtype=t.dtype)
    if lab.size:
        at = [slice(None)] * t.ndim
        at[axis] = lab[starts]
        out[tuple(at)] = np.add.reduceat(np.take(t, order, axis=axis), starts, axis=axis)
    return out
