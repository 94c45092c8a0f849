"""The two device walks of a ``GroupBy`` hold each other to the same
answers where they differ most: on SPARSE data, where most (row, row)
pairs are empty. The deferred walk (``Executor._groupby_deferred``)
expands every pair and drops the empty groups when it resolves; the
level-synchronous one (``_groupby_levels``) reads each level back and
expands the survivors. Entry for entry, on the device and mesh routes,
against each other and against a brute-force table written here.

Also the rule that picks the walk (``DEFERRED_CHUNKS``): from the row
lists, the resident stacks and the chunk cap alone; and, of the deferred
``GroupBy``s, the ones of several levels without an aggregate as ONE
chain count (``ops.groupby.chain_counts``), held to the masks and counts
it replaces.
"""

import numpy as np
import pytest

import jax

from pilosa_tpu import ops
from pilosa_tpu.core import FieldOptions, Holder
from pilosa_tpu.executor.compile import StackCache
from pilosa_tpu.executor.router import QueryRouter
from pilosa_tpu.parallel.mesh import MeshContext, make_mesh
from pilosa_tpu.server.api import API
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.utils.stats import StatsClient

N_SHARDS = 8  # one a virtual device on the mesh route
PLANE = N_SHARDS * WORDS_PER_SHARD * 4
ROWS = {"a": 6, "b": 5, "c": 7, "wide": 40}  # padded to 8, 8, 8, 64


def budget(cap: int) -> int:
    """Under it ``GroupBy(Rows(a), Rows(b), ...)`` holds ``cap`` masks of
    its second level: the filter's plane, a's 8 padded masks, a chunk,
    the temporaries."""
    return (1 + 8 + cap + ops.groupby.TEMP_PLANES) * PLANE


# one chunk a level whatever the query (None: the default budget), the 30
# real (a, b) pairs in 16 + 14, and one mask at a time: over two chunks
# wherever a level has masks, so the level-synchronous walk
BUDGETS = {"one_chunk": None, "two_chunks": budget(16), "levels": 0}


@pytest.fixture(scope="module")
def table():
    """3,000 columns, one value of every field each. Of the 6 x 5 x 7
    (a, b, c) groups twelve are populated: b follows from a but for one
    bit, c from both. The int field runs negative."""
    rng = np.random.default_rng(35)
    cols = rng.choice(N_SHARDS * SHARD_WIDTH, 3000, replace=False).astype(np.uint64)
    r = rng.integers(0, 1 << 20, cols.size)
    a = r % 6
    b = (2 * a + (r >> 8) % 2) % 5
    return {"cols": cols, "a": a, "b": b, "c": (a + b) % 7, "wide": (r >> 4) % 40,
            "v": rng.integers(-500, 500, cols.size)}


@pytest.fixture(scope="module")
def holder(table):
    h = Holder(None)
    idx = h.create_index("s")
    for f in ROWS:
        idx.create_field(f).import_bulk(table[f].astype(np.uint64), table["cols"])
    v = idx.create_field("v", FieldOptions(field_type="int", min=-1000, max=1000))
    v.import_values(table["cols"], table["v"])
    idx.mark_columns_exist(table["cols"])
    return h


def _api(holder, route: str, pinned: int | None) -> tuple[API, StatsClient]:
    client = StatsClient()
    mesh_ctx = None
    if route == "mesh":
        assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
        mesh_ctx = MeshContext(make_mesh(jax.devices(), words_axis=1))
    api = API(holder, stats=client, mesh_ctx=mesh_ctx,
              router=QueryRouter(mode=route, stats=client))
    api.executor.GROUPBY_MASK_BUDGET = pinned
    return api, client


@pytest.fixture(scope="module")
def apis(holder):
    return {(route, name): _api(holder, route, pinned)
            for route in ("device", "mesh") for name, pinned in BUDGETS.items()}


def paths(client: StatsClient) -> dict:
    with client._lock:
        return {dict(tags)["path"]: v for (n, tags), v in client._counters.items()
                if n == "groupby_queries_total"}


def counter(client: StatsClient, name: str) -> float:
    with client._lock:
        return sum(v for (n, _tags), v in client._counters.items() if n == name)


def brute(table, fields, where=None, first=None, agg=False, limit=None) -> list[dict]:
    """The joint table of ``fields`` over the columns ``where`` keeps, a
    level's rows from ``first[field]`` on, cells in nested ascending order."""
    keep = np.ones(table["cols"].size, bool) if where is None else where
    for f, lo in (first or {}).items():
        keep = keep & (table[f] >= lo)
    at = tuple(table[f][keep] for f in fields)
    shape = tuple(ROWS[f] for f in fields)
    count = np.zeros(shape, np.int64)
    total = np.zeros(shape, np.int64)
    np.add.at(count, at, 1)
    np.add.at(total, at, table["v"][keep])
    out = []
    for cell in np.argwhere(count > 0).tolist():
        g = {"group": [{"field": f, "rowID": r} for f, r in zip(fields, cell)],
             "count": int(count[tuple(cell)])}
        if agg:
            g["sum"] = int(total[tuple(cell)])
        out.append(g)
    return out[:limit]


# (pql, how brute() answers it)
QUERIES = {
    "three_levels": ("GroupBy(Rows(a), Rows(b), Rows(c))",
                     lambda t: brute(t, ["a", "b", "c"])),
    "sum_negative": ("GroupBy(Rows(a), Rows(b), aggregate=Sum(field=v))",
                     lambda t: brute(t, ["a", "b"], agg=True)),
    "limit": ("GroupBy(Rows(a), Rows(b), Rows(c), limit=5)",
              lambda t: brute(t, ["a", "b", "c"], limit=5)),
    "previous": ("GroupBy(Rows(a, previous=1), Rows(b), Rows(c, previous=2))",
                 lambda t: brute(t, ["a", "b", "c"], first={"a": 2, "c": 3})),
    "sum_filter_previous": (
        "GroupBy(Rows(a), Rows(b, previous=0), filter=Row(c=3), aggregate=Sum(field=v))",
        lambda t: brute(t, ["a", "b"], where=t["c"] == 3, first={"b": 1}, agg=True)),
    "one_level_sum_limit": ("GroupBy(Rows(a), aggregate=Sum(field=v), limit=3)",
                            lambda t: brute(t, ["a"], agg=True, limit=3)),
    "wide_last_level": ("GroupBy(Rows(b), Rows(wide), filter=Row(a=2))",
                        lambda t: brute(t, ["b", "wide"], where=t["a"] == 2)),
}


@pytest.mark.parametrize("route", ["device", "mesh"])
@pytest.mark.parametrize("chunks", ["one_chunk", "two_chunks"])
@pytest.mark.parametrize("query", list(QUERIES))
def test_deferred_and_level_walks_agree_on_sparse_data(apis, table, query, chunks, route):
    pql, truth = QUERIES[query]
    walk, walk_stats = apis[(route, chunks)]
    levels, level_stats = apis[(route, "levels")]
    fused0 = paths(walk_stats).get("fused", 0)
    levels0 = paths(level_stats).get("levels", 0)
    got = walk.query("s", pql)["results"][0]
    assert got == levels.query("s", pql)["results"][0]
    assert got == truth(table)
    assert 0 < len(got) <= 80  # of 200 or 210 groups: most are empty, none is answered
    # the reference really was the other walk; under the default budget
    # every query here is one chunk a level, so deferred
    assert paths(level_stats).get("levels", 0) == levels0 + 1
    assert counter(level_stats, "groupby_level_readbacks_total") > 0
    if chunks == "one_chunk":
        assert paths(walk_stats).get("fused", 0) == fused0 + 1
        assert counter(walk_stats, "groupby_level_readbacks_total") == 0


# (pql, budget, stack budget in rows or None, path, chunk waits, chain
# counts): a deferred GroupBy of several levels without an aggregate is
# one chain count, so it never waits for a chunk of masks
RULE = {
    "one_chunk": ("GroupBy(Rows(a), Rows(b), Rows(c))", None, None, "fused", 0, 1),
    "two_chunks": ("GroupBy(Rows(a), Rows(b), Rows(c))", budget(16), None, "fused", 0, 1),
    "two_chunks_of_sums": ("GroupBy(Rows(a), Rows(b), aggregate=Sum(field=v))",
                           budget(16), None, "fused", 1, 0),
    "four_chunks": ("GroupBy(Rows(a), Rows(b), Rows(c))", budget(8), None, "levels", 0, 0),
    "limit_in_one_chunk": ("GroupBy(Rows(a), Rows(b), Rows(c), limit=4)", None, None,
                           "fused", 0, 1),
    "limit_in_two_chunks": ("GroupBy(Rows(a), Rows(b), Rows(c), limit=4)",
                            budget(16), None, "levels", 0, 0),
    "a_streamed_level": ("GroupBy(Rows(a), Rows(wide))", None, 16, "levels", 0, 0),
    "one_level": ("GroupBy(Rows(a), filter=Row(c=3))", None, None, "fused", 0, 0),
}


@pytest.mark.parametrize("case", list(RULE))
def test_the_walk_is_chosen_from_pairs_stacks_and_cap(holder, apis, monkeypatch, case):
    pql, pinned, stack_rows, path, waits, chained = RULE[case]
    if stack_rows is not None:  # wide's 64 padded rows no longer fit a stack
        monkeypatch.setattr(StackCache, "STACK_BYTES_BUDGET", stack_rows * PLANE)
    api, client = _api(holder, "device", pinned)
    got = api.query("s", pql)["results"][0]
    assert paths(client) == {path: 1}
    assert counter(client, "groupby_chunk_waits_total") == waits
    assert counter(client, "groupby_chain_queries_total") == chained
    assert (counter(client, "groupby_level_readbacks_total") > 0) == (path == "levels")
    if stack_rows is None:
        assert got == apis[("device", "levels")][0].query("s", pql)["results"][0]
    else:
        monkeypatch.undo()
        assert got == apis[("device", "one_chunk")][0].query("s", pql)["results"][0]
    ledger = api.executor.gb_ledger.snapshot()
    assert ledger["heldBytes"] == 0
    if pinned is not None:
        assert ledger["highWaterBytes"] <= pinned


def _pop(words: np.ndarray) -> np.ndarray:
    """Set bits of each ``[..., S, W]`` plane, counted bit by bit."""
    return np.unpackbits(words.view(np.uint8), axis=-1).sum(axis=(-1, -2)).astype(np.int64)


@pytest.mark.parametrize("shards", [8, 3], ids=["blocked", "whole_planes"])
@pytest.mark.parametrize("levels", [2, 3])
def test_chain_counts_equal_masks_then_counts(levels, shards):
    """One chain count against the masks of every level made and counted
    (``pair_masks`` level by level, then ``level_counts``) and against
    numpy: padded last-level rows (-1), padding chains past the real
    ones, an all-zero upper row and an all-zero filter word count 0."""
    rng = np.random.default_rng(38 * levels + shards)
    w = 64

    def bits(*shape):
        return rng.integers(0, 1 << 32, shape, dtype=np.uint32) & rng.integers(
            0, 1 << 32, shape, dtype=np.uint32)

    filt = bits(shards, w)
    filt[:, 5] = 0
    uppers = [bits(r, shards, w) for r in (5, 4)[: levels - 1]]
    uppers[0][3] = 0  # every chain through this row is all-zero
    last = bits(6, shards, w)
    # each upper level's candidate rows, -1 padded; the table holds places in them
    upper_rows = tuple(np.array(r, np.int32) for r in ([4, 0, 1, 3], [1, 3, 2, -1])[: levels - 1])
    real = [4, 3][: levels - 1]
    rows = np.array([4, 0, 5, 2, -1, -1, -1, -1], dtype=np.int32)
    n = int(np.prod(real))
    table = np.full((16, levels - 1), -1, dtype=np.int32)
    table[:n] = np.indices(real).reshape(levels - 1, -1).T
    ids = np.stack([np.where(table[:, lv] >= 0, upper_rows[lv][table[:, lv]], -1)
                    for lv in range(levels - 1)], axis=1)  # the chains as row ids

    got = np.asarray(ops.groupby.chain_counts(
        filt, tuple(uppers), upper_rows, table, np.int32(n), last, rows))
    assert got.shape == (16, 8) and got.dtype == np.int64

    masks = ops.groupby.pair_masks(filt, uppers[0], np.zeros(16, np.int32), ids[:, 0])
    for level in range(1, levels - 1):
        masks = ops.groupby.pair_masks(masks, uppers[level], np.arange(16, dtype=np.int32),
                                       ids[:, level])
    assert (got == np.asarray(ops.groupby.level_counts(masks, last, rows))).all()

    want = np.zeros((16, 8), np.int64)
    for c in range(n):
        m = filt.copy()
        for level, stack in enumerate(uppers):
            m &= stack[ids[c, level]]
        for k, r in enumerate(rows):
            if r >= 0:
                want[c, k] = _pop(m & last[r])
    assert (got == want).all()
    assert (got[ids[:, 0] == 3] == 0).all() and got[:n].sum() > 0

    # a real-chain count below the table's real rows: the rest are 0
    fewer = np.asarray(ops.groupby.chain_counts(
        filt, tuple(uppers), upper_rows, table, np.int32(n - 3), last, rows))
    assert (fewer[: n - 3] == want[: n - 3]).all() and not fewer[n - 3:].any()
