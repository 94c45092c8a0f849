"""The deployment ``ssb-24`` at a CPU size: the Star Schema Benchmark's
thirteen queries over the flattened ``lineorder``, served through
``API.query`` and the wave scheduler on the device, host and mesh routes
and on the device under a budget that forces the level walk's first read
(after which most GroupBys fit the deferred walk on the rows the filter
holds, and Q3.2 and Q4.3 walk on), against a
brute-force numpy pass over the seeded columns written here (a mask a row
call, ``np.add.at`` into the joint table; nothing of the program's
planner or ops, nor of the benchmark's reference).

The columns are the benchmark's own generator's (``benchmark/datasets/
ssb.py``) over a small hierarchy: 2 regions of 2 nations of 3 cities,
2 manufacturers of 2 categories of 3 brands, three years of order
dates. The benchmark's reference, its cubes filled from the same
columns, must agree with the brute force too. And the counters the
cell's per-layer metrics read move as docs/observability.md says.
"""

import copy
import json
import os

import numpy as np
import pytest

import jax

from benchmark.datasets import ssb
from benchmark.harness import pql as bench_pql
from pilosa_tpu import ops
from pilosa_tpu.core import FieldOptions, Holder
from pilosa_tpu.executor.router import QueryRouter
from pilosa_tpu.parallel.mesh import MeshContext, make_mesh
from pilosa_tpu.server.api import API
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.utils import stats as stats_mod
from pilosa_tpu.utils.stats import StatsClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_SHARDS = 8  # one a virtual device on the mesh route
PLANE = N_SHARDS * WORDS_PER_SHARD * 4
CAP = 8  # masks a level under the pinned budget: every GroupBy but Q4.1 reads the filter's rows first
SEED = 3900
ROWS = {"d_year": 3, "d_yearmonthnum": 36, "d_weeknuminyear": 53, "c_region": 2, "c_nation": 4,
        "c_city": 12, "s_region": 2, "s_nation": 4, "s_city": 12, "p_mfgr": 2, "p_category": 4,
        "p_brand1": 12}
YEARS2 = "Union(Row(d_year=1), Row(d_year=2))"
YEARS = "Union(Row(d_year=0), Row(d_year=1))"


def _gb(groups, filt, measure):
    rows = ", ".join(f"Rows({g})" for g in groups)
    return f"GroupBy({rows}, filter=Intersect({filt}), aggregate=Sum(field={measure}))"


def _union(field, ids):
    return "Union(" + ", ".join(f"Row({field}={i})" for i in ids) + ")"


SUM1 = "Sum(Intersect({}), field=lo_extprice_discount)"
# the thirteen forms of benchmark/traffic/ssb_flights.json; constants drawn below
TEMPLATES = {
    "q1_1": lambda d: SUM1.format(f"Row(d_year={d.y}), Row({d.a} <= lo_discount <= {d.a + 2}), "
                                  "Row(lo_quantity < 25)"),
    "q1_2": lambda d: SUM1.format(f"Row(d_yearmonthnum={d.m}), Row({d.a} <= lo_discount <= {d.a + 2}), "
                                  "Row(26 <= lo_quantity <= 35)"),
    "q1_3": lambda d: SUM1.format(f"Row(d_weeknuminyear={d.w}), Row(d_year={d.y}), "
                                  f"Row({d.a} <= lo_discount <= {d.a + 2}), Row(26 <= lo_quantity <= 35)"),
    "q2_1": lambda d: _gb(["d_year", "p_brand1"], f"Row(p_category={d.c}), Row(s_region={d.r})", "lo_revenue"),
    "q2_2": lambda d: _gb(["d_year", "p_brand1"],
                          f"{_union('p_brand1', [d.c * 3, d.c * 3 + 1])}, Row(s_region={d.r})", "lo_revenue"),
    "q2_3": lambda d: _gb(["d_year", "p_brand1"], f"Row(p_brand1={d.b}), Row(s_region={d.r})", "lo_revenue"),
    "q3_1": lambda d: _gb(["c_nation", "s_nation", "d_year"],
                          f"Row(c_region={d.r}), Row(s_region={d.r2}), {YEARS}", "lo_revenue"),
    "q3_2": lambda d: _gb(["c_city", "s_city", "d_year"],
                          f"Row(c_nation={d.n}), Row(s_nation={d.n2}), {YEARS}", "lo_revenue"),
    "q3_3": lambda d: _gb(["c_city", "s_city", "d_year"],
                          f"{_union('c_city', [d.n * 3, d.n * 3 + 2])}, "
                          f"{_union('s_city', [d.n2 * 3, d.n2 * 3 + 1])}, {YEARS}", "lo_revenue"),
    "q3_4": lambda d: _gb(["c_city", "s_city", "d_year"],
                          f"{_union('c_city', [d.n * 3, d.n * 3 + 2])}, "
                          f"{_union('s_city', [d.n2 * 3, d.n2 * 3 + 1])}, Row(d_yearmonthnum={d.m})",
                          "lo_revenue"),
    "q4_1": lambda d: _gb(["d_year", "c_nation"],
                          f"Row(c_region={d.r}), Row(s_region={d.r2}), {_union('p_mfgr', [0, 1])}", "lo_profit"),
    "q4_2": lambda d: _gb(["d_year", "s_nation", "p_category"],
                          f"Row(c_region={d.r}), Row(s_region={d.r2}), {YEARS2}, {_union('p_mfgr', [0, 1])}",
                          "lo_profit"),
    "q4_3": lambda d: _gb(["d_year", "s_city", "p_brand1"],
                          f"Row(c_region={d.r}), Row(s_nation={d.n}), {YEARS2}, Row(p_category={d.c})",
                          "lo_profit"),
}
DRAWS = 3  # seeded constants a template


class _Draw:
    def __init__(self, rng):
        self.y, self.a, self.m = int(rng.integers(3)), int(rng.integers(9)), int(rng.integers(36))
        self.w, self.c, self.b = int(rng.integers(52)), int(rng.integers(4)), int(rng.integers(12))
        self.r, self.r2 = int(rng.integers(2)), int(rng.integers(2))
        self.n, self.n2 = int(rng.integers(4)), int(rng.integers(4))


def texts() -> list[tuple[str, str]]:
    rng = np.random.default_rng(SEED)
    return [(name, make(_Draw(rng))) for name, make in TEMPLATES.items() for _ in range(DRAWS)]


TEXTS = texts()


# ------------------------------------------------------------------- data
def small_config() -> dict:
    """``ssb-24``'s configuration over the small hierarchy."""
    with open(os.path.join(REPO, "benchmark", "configs", "ssb-24.json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg["hierarchy"].update(nation_region=[0, 0, 1, 1], cities_per_nation=3, mfgrs=2,
                            categories_per_mfgr=2, brands_per_category=3, order_days=1096,
                            customers=500, suppliers=100, parts=300)
    for f, n in ROWS.items():
        cfg["schema"][f]["rows"] = n
    cfg["scale"].update(shards=N_SHARDS, columns=N_SHARDS * SHARD_WIDTH)
    return cfg


@pytest.fixture(scope="module")
def cfg():
    return small_config()


@pytest.fixture(scope="module")
def lineorder(cfg):
    """Every column's value of every field, and the reference's cubes,
    from the benchmark's generator."""
    hier = ssb.Hierarchy(cfg)
    hier.check(cfg)
    tabs = ssb.tables(SEED, hier)
    cubes = ssb.new_cubes(hier)
    shards = []
    for shard in range(N_SHARDS):
        cols = ssb.gen_shard(SEED, shard, SHARD_WIDTH, hier, tabs)
        ssb.add_to_cubes(cubes, hier, cols)
        shards.append(cols)
    cols = {f: np.concatenate([s[f] for s in shards]) for f in ssb.SET_FIELDS + ssb.INT_FIELDS}
    return cols, {"cubes": cubes, "shards": list(range(N_SHARDS))}


@pytest.fixture(scope="module")
def holder(cfg, lineorder):
    cols, _ = lineorder
    h = Holder(None)
    idx = h.create_index("ssb")
    ids = np.arange(N_SHARDS * SHARD_WIDTH, dtype=np.uint64)
    for f in ssb.SET_FIELDS:
        idx.create_field(f).import_bulk(cols[f].astype(np.uint64), ids)
    for f in ssb.INT_FIELDS:
        s = cfg["schema"][f]
        idx.create_field(f, FieldOptions(field_type="int", min=s["min"], max=s["max"])).import_values(ids, cols[f])
    idx.mark_columns_exist(ids)
    return h


# ------------------------------------------------------------ brute force
def _rows(call, cols) -> np.ndarray:
    if call.name == "Row":
        if call.cond is not None:
            v, c = cols[call.cond.field], call.cond
            if c.op == "between":
                lo_op, lo, hi_op, hi = c.value
                assert (lo_op, hi_op) == ("<=", "<=")
                return (v >= lo) & (v <= hi)
            assert c.op == "<"
            return v < c.value
        ((fld, row),) = call.kw.items()
        return cols[fld] == row
    kids = [_rows(c, cols) for c in call.children]
    if call.name == "Intersect":
        return np.logical_and.reduce(kids)
    assert call.name == "Union"
    return np.logical_or.reduce(kids)


def brute(cols, text: str):
    """What ``results[0]`` must be, from one pass over the columns."""
    call = bench_pql.parse(text)
    if call.name == "Sum":
        keep = _rows(call.children[0], cols)
        return {"value": int(cols[call.kw["field"]][keep].sum()), "count": int(keep.sum())}
    fields = [c.pos[0] for c in call.children]
    keep = _rows(call.kw["filter"], cols)
    measure = call.kw["aggregate"].kw["field"]
    at = tuple(cols[f][keep] for f in fields)
    shape = tuple(ROWS[f] for f in fields)
    count = np.zeros(shape, dtype=np.int64)
    total = np.zeros(shape, dtype=np.int64)
    np.add.at(count, at, 1)
    np.add.at(total, at, cols[measure][keep])
    return [{"group": [{"field": f, "rowID": r} for f, r in zip(fields, cell)],
             "count": int(count[tuple(cell)]), "sum": int(total[tuple(cell)])}
            for cell in np.argwhere(count > 0).tolist()]


@pytest.fixture(scope="module")
def want(lineorder):
    cols, _ = lineorder
    return {text: brute(cols, text) for _, text in TEXTS}


# ----------------------------------------------------------------- servers
def pinned_budget(cap: int = CAP) -> int:
    """The filter's plane, three levels of ``cap`` masks and the
    temporaries: under ``CAP`` every three-level GroupBy's pairs need
    over two chunks before its first read."""
    return (1 + 3 * cap + ops.groupby.TEMP_PLANES) * PLANE


def _api(holder, route: str, stats=None) -> API:
    """``levels``: the device route under ``pinned_budget()``;
    ``mesh_levels``: the mesh route under the same planes, a device's
    share of each (the mesh ledger's unit), so that its walk runs too."""
    mesh_ctx = None
    if route.startswith("mesh"):
        assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
        mesh_ctx = MeshContext(make_mesh(jax.devices(), words_axis=1))
    mode = {"levels": "device", "mesh_levels": "mesh"}.get(route, route)
    api = API(holder, stats=stats, mesh_ctx=mesh_ctx, router=QueryRouter(mode=mode, stats=stats))
    if route == "levels":
        api.executor.GROUPBY_MASK_BUDGET = pinned_budget()
    elif route == "mesh_levels":
        api.executor.GROUPBY_MASK_BUDGET = pinned_budget() // len(jax.devices())
    return api


@pytest.fixture(scope="module")
def counted(holder):
    """A server a route, each with a registry of its own behind it."""
    out = {}
    for route in ("device", "levels", "host", "mesh", "mesh_levels"):
        client = StatsClient()
        out[route] = (_api(holder, route, stats=client), client)
    return out


def _family(client: StatsClient, name: str, **tags) -> float:
    want_tags = tuple(sorted(tags.items()))
    with client._lock:
        return sum(v for (n, t), v in client._counters.items()
                   if n == name and all(kv in t for kv in want_tags))


# ------------------------------------------------------------------- cases
@pytest.mark.parametrize("route", ["device", "levels", "host", "mesh", "mesh_levels"])
@pytest.mark.parametrize("name", list(TEMPLATES))
def test_served_reply_equals_the_brute_force_pass(counted, want, name, route):
    api, _client = counted[route]
    for tname, text in TEXTS:
        if tname == name:
            assert api.query("ssb", text)["results"][0] == want[text], text


@pytest.mark.parametrize("name", list(TEMPLATES))
def test_the_benchmarks_reference_agrees_with_the_brute_force(cfg, lineorder, want, name):
    ref = ssb.Reference(cfg, [lineorder[1]])
    for tname, text in TEXTS:
        if tname == name:
            assert ref.answer(bench_pql.parse(text)) == want[text], text


def test_the_reference_after_a_lost_part_mismatches_every_group_by(cfg, lineorder, want):
    """The control: ``drop_last_part`` leaves out a part's shards, and the
    reference then disagrees with every answer but an empty one."""
    cols, state = lineorder
    hier = ssb.Hierarchy(cfg)
    tabs = ssb.tables(SEED, hier)
    halves = []
    for shards in (range(0, N_SHARDS // 2), range(N_SHARDS // 2, N_SHARDS)):
        cubes = ssb.new_cubes(hier)
        for shard in shards:
            ssb.add_to_cubes(cubes, hier, ssb.gen_shard(SEED, shard, SHARD_WIDTH, hier, tabs))
        halves.append({"cubes": cubes, "shards": list(shards)})
    whole = ssb.Reference(cfg, halves)
    lost = ssb.Reference(cfg, ssb.drop_last_part(halves))
    for _, text in TEXTS:
        call = bench_pql.parse(text)
        assert whole.answer(call) == want[text]
        if want[text] not in ([], {"value": 0, "count": 0}):
            assert lost.answer(call) != want[text], text


def test_level_walk_counts_its_pairs_and_sums_its_groups(holder, lineorder):
    """docs/observability.md: on the level walk every counts launch adds
    its real (parent, row) pairs to ``counted`` and those with a count
    above zero to ``kept``, the first read's filter against the rows of
    the levels below the first included, and those levels count only
    the rows the filter holds; every sums launch adds its real groups.
    Under ``CAP`` the 2 x 2 x 4 pairs that Q4.2's filter holds fit two
    chunks (the deferred walk after the first read); under 4 masks a
    level they need four, and the walk goes on level by level."""
    client = StatsClient()
    api = _api(holder, "levels", stats=client)
    api.executor.GROUPBY_MASK_BUDGET = pinned_budget(4)
    assert "groupby_level_pairs_total" in stats_mod._METRIC_HELP
    assert "groupby_groups_summed_total" in stats_mod._METRIC_HELP
    text = TEMPLATES["q4_2"](_Draw(np.random.default_rng(5)))
    table = brute(lineorder[0], text)
    levels = [[] for _ in range(3)]
    for g in table:
        cells = tuple(c["rowID"] for c in g["group"])
        for k in range(3):
            if cells[: k + 1] not in levels[k]:
                levels[k].append(cells[: k + 1])
    rows = [ROWS["d_year"], ROWS["s_nation"], ROWS["p_category"]]
    # the rows of a level that the filter holds: a column has one of each
    held = [len({cells[k] for cells in levels[2]}) for k in range(3)]
    before = {s: _family(client, "groupby_level_pairs_total", stage=s) for s in ("counted", "kept")}
    summed = _family(client, "groupby_groups_summed_total")
    assert api.query("ssb", text)["results"][0] == table
    counted_pairs = sum(rows) + len(levels[0]) * held[1] + len(levels[1]) * held[2]
    assert held[1] < rows[1] or held[2] < rows[2]
    assert _family(client, "groupby_level_pairs_total", stage="counted") - before["counted"] == counted_pairs
    assert _family(client, "groupby_level_pairs_total", stage="kept") - before["kept"] \
        == held[1] + held[2] + sum(map(len, levels))
    assert _family(client, "groupby_groups_summed_total") - summed == len(table)
    assert _family(client, "groupby_queries_total", path="levels") == 1


def test_a_filter_that_empties_the_first_level_keeps_no_pair(counted):
    """A customer region and a nation of the other region: the filter
    is empty, so the first read counts the cities of level 0 and the
    rows of the levels below against it and keeps none, and the walk
    sums nothing and answers no group."""
    api, client = counted["levels"]
    text = _gb(["c_city", "s_city", "d_year"], f"Row(c_region=0), Row(c_nation=3), {YEARS}", "lo_revenue")
    before = {s: _family(client, "groupby_level_pairs_total", stage=s) for s in ("counted", "kept")}
    summed = _family(client, "groupby_groups_summed_total")
    assert api.query("ssb", text)["results"][0] == []
    assert _family(client, "groupby_level_pairs_total", stage="counted") - before["counted"] \
        == ROWS["c_city"] + ROWS["s_city"] + ROWS["d_year"]
    assert _family(client, "groupby_level_pairs_total", stage="kept") == before["kept"]
    assert _family(client, "groupby_groups_summed_total") == summed


def test_deferred_walk_sums_every_real_pair_and_counts_no_level(counted, lineorder):
    """Q4.1 fits one chunk under any budget here: the deferred walk, no
    level counted; its one sums launch covers every (year, nation) pair
    of real rows, empty ones included."""
    api, client = counted["levels"]
    text = TEMPLATES["q4_1"](_Draw(np.random.default_rng(5)))
    before = {s: _family(client, "groupby_level_pairs_total", stage=s) for s in ("counted", "kept")}
    summed = _family(client, "groupby_groups_summed_total")
    fused = _family(client, "groupby_queries_total", path="fused")
    assert api.query("ssb", text)["results"][0] == brute(lineorder[0], text)
    assert _family(client, "groupby_queries_total", path="fused") == fused + 1
    for s in ("counted", "kept"):
        assert _family(client, "groupby_level_pairs_total", stage=s) == before[s]
    assert _family(client, "groupby_groups_summed_total") - summed == ROWS["d_year"] * ROWS["c_nation"]


def test_kept_never_passes_counted_over_a_deck(holder, want):
    """One GroupBy of every template on a server of its own: the walk
    keeps pairs, and never more than it counted."""
    client = StatsClient()
    api = _api(holder, "levels", stats=client)
    for name in TEMPLATES:
        text = next(t for n, t in TEXTS if n == name)
        assert api.query("ssb", text)["results"][0] == want[text]
    kept = _family(client, "groupby_level_pairs_total", stage="kept")
    assert 0 < kept < _family(client, "groupby_level_pairs_total", stage="counted")


def test_whole_stack_launches_are_counted_by_their_arguments(counted, lineorder):
    """``groupby_streamed_launches_total`` counts the counts launches that
    ``ops.groupby.whole_stack`` makes one pass over the whole stack, from
    the launch's own arguments: by year and month (36 rows, 64 padded) on
    the level walk, the first read's filter against the months is one;
    the filter against the 3 years (4 padded, one tile) and the three
    years' masks against the months are not. A deck's templates, whose
    levels hold at most 12 rows here, launch none."""
    api, client = counted["levels"]
    assert "groupby_streamed_launches_total" in stats_mod._METRIC_HELP
    text = _gb(["d_year", "d_yearmonthnum"], "Row(c_region=0), Row(s_region=1)", "lo_revenue")
    before = _family(client, "groupby_streamed_launches_total")
    launches = _family(client, "groupby_launches_total")
    assert api.query("ssb", text)["results"][0] == brute(lineorder[0], text)
    assert _family(client, "groupby_streamed_launches_total") - before == 1
    assert _family(client, "groupby_launches_total") - launches > 2
    before = _family(client, "groupby_streamed_launches_total")
    for name in ("q2_1", "q3_2", "q4_3"):
        api.query("ssb", TEMPLATES[name](_Draw(np.random.default_rng(5))))
    assert _family(client, "groupby_streamed_launches_total") == before


def test_the_mesh_level_walk_runs_as_mesh_programs(holder, want):
    """Under the mesh route's pinned budget every GroupBy of a deck but
    Q4.1's takes the level walk's first read, each launch a mesh program,
    and none of the deck's reads leaves the mesh route. After it the
    pairs the filter holds fit two chunks but for Q3.2's and Q4.3's,
    which walk on level by level."""
    client = StatsClient()
    api = _api(holder, "mesh_levels", stats=client)
    for name in TEMPLATES:
        text = next(t for n, t in TEXTS if n == name)
        assert api.query("ssb", text)["results"][0] == want[text]
    assert _family(client, "groupby_queries_total", path="levels") == 2
    assert _family(client, "groupby_queries_total", path="pruned") == len(TEMPLATES) - 6
    assert _family(client, "groupby_queries_total", path="fused") == 1
    assert _family(client, "queries_routed", path="mesh") == len(TEMPLATES)
    assert _family(client, "mesh_fallbacks_total") == 0
    # every launch but each GroupBy's filter (a bitmap program) is a GroupBy program
    group_bys = len(TEMPLATES) - 3
    assert _family(client, "mesh_program_calls_total", program="groupby") + group_bys \
        == _family(client, "groupby_launches_total")


def test_the_mesh_ledger_reserves_a_devices_share_of_a_plane(holder, want):
    """Q2.1 (3 years by 12 brands) under ``pinned_budget()``: on the
    device route a plane is the whole plane, a level holds 16 masks and
    the 36 pairs take three chunks, so the GroupBy reads the filter's
    rows first, and the 3 x 3 pairs it holds then fit one chunk (pruned);
    on the mesh route a plane is a device's share of it, the 64 padded
    pairs fit one chunk and the same GroupBy is fused. The answer is the
    same, and the mesh ledger's mark is the filter's plane, the levels'
    masks and the temporaries in a device's share of a plane."""
    text = next(t for n, t in TEXTS if n == "q2_1")
    devices = len(jax.devices())
    paths, marks = {}, {}
    for route in ("levels", "mesh"):
        client = StatsClient()
        api = _api(holder, route, stats=client)
        api.executor.GROUPBY_MASK_BUDGET = pinned_budget()
        assert api.query("ssb", text)["results"][0] == want[text]
        paths[route] = {p: _family(client, "groupby_queries_total", path=p)
                        for p in ("fused", "levels", "pruned")}
        marks[route] = api.executor.gb_ledger.high_water
    assert paths == {"levels": {"fused": 0, "levels": 0, "pruned": 1},
                     "mesh": {"fused": 1, "levels": 0, "pruned": 0}}
    assert marks["mesh"] == (1 + 4 + 64 + ops.groupby.TEMP_PLANES) * (PLANE // devices)
    assert marks["levels"] <= pinned_budget() < marks["mesh"] * devices


@pytest.mark.parametrize(
    "groups,rows,consecutive",
    [(128, 64, False), (1, 256, False), (64, 64, False), (128, 32, False),
     (8, 64, False), (32, 64, False), (1, 128, True)],
)
def test_count_pass_in_tiles_equals_numpy(groups, rows, consecutive):
    """``ops.groupby.level_counts`` over more masks than ``MASK_BLOCK`` or
    more rows than ``ROW_BLOCK`` (Q2.x's 8 and Q4.3's 32 masks by 64
    brands) goes tile by tile, and one mask against more rows than a tile
    (the first read's filter against the cell's 1,024 brands and 256
    cities) is one pass over the whole stack: every (group, row) count as
    numpy counts it, padding rows (-1) and rows past the stack 0.
    ``consecutive``: ids 0, 1, ... as the level walk's first read gives
    them."""
    rng = np.random.default_rng(groups * 1000 + rows)
    w = WORDS_PER_SHARD
    masks = rng.integers(0, 1 << 32, (groups, N_SHARDS, w), dtype=np.uint32)
    stack = rng.integers(0, 1 << 32, (rows - 3, N_SHARDS, w), dtype=np.uint32)
    if consecutive:  # 0 .. rows - 3, the last past the stack, then -1 -1
        ids = np.where(np.arange(rows) < rows - 2, np.arange(rows), -1).astype(np.int32)
    else:  # -2, -1 and rows - 3 .. rows - 2: no row
        ids = rng.permutation(rows).astype(np.int32) - 2
    got = np.asarray(jax.jit(ops.groupby.level_counts)(masks[0] if groups == 1 else masks, stack, ids))
    want = np.zeros((groups, rows), dtype=np.int64)
    for k, r in enumerate(ids.tolist()):
        if 0 <= r < stack.shape[0]:
            want[:, k] = np.bitwise_count(masks & stack[r][None]).sum(axis=(1, 2))
    assert (groups > ops.groupby.MASK_BLOCK or rows > ops.groupby.ROW_BLOCK)
    assert ops.groupby.whole_stack(masks[0] if groups == 1 else masks, stack, ids) == (groups == 1)
    assert np.array_equal(got, want)



@pytest.mark.parametrize(
    "groups,depth,shards,signed",
    [(128, 24, 8, False), (16, 25, 8, True), (4, 40, 8, True), (8, 6, 3, True), (1, 17, 3, False)],
)
def test_grouped_sums_equal_numpy(groups, depth, shards, signed):
    """``ops.groupby.grouped_sums``, the count pass with the measure's
    planes for rows: every group's positive and negative per-plane counts
    and its count of values as numpy counts them, over blocks of shards
    and whole planes, a depth past ``ROW_BLOCK``, with and without a
    negative value in the stack (none: the negative pass is skipped and
    reads zeros)."""
    rng = np.random.default_rng(groups * 100 + depth)
    w = WORDS_PER_SHARD
    stack = rng.integers(0, 1 << 32, (2 + depth, shards, w), dtype=np.uint32)
    if not signed:
        stack[ops.bsi.SIGN_ROW] = 0
    masks = rng.integers(0, 1 << 32, (groups, shards, w), dtype=np.uint32)
    pos, neg, n = (np.asarray(x) for x in jax.jit(ops.groupby.grouped_sums)(stack, masks))
    exists, sign = stack[ops.bsi.EXISTS_ROW], stack[ops.bsi.SIGN_ROW]
    mag = stack[ops.bsi.OFFSET_ROW:]

    def counts(f):
        return np.bitwise_count(masks[:, None] & (mag & f)[None]).sum(axis=(2, 3))

    assert np.array_equal(pos, counts(exists & ~sign))
    assert np.array_equal(neg, counts(exists & sign))
    assert np.array_equal(n, np.bitwise_count(masks & exists[None]).sum(axis=(1, 2)))
    assert neg.any() == signed
