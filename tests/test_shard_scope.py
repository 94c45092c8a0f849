"""A query's host cost does not grow with the shard count (PR 31).

The index keeps ONE mutation stamp (``Index.stamp``, raised by every
``View._bump_version`` and by ``delete_field``).  The sorted tuple of
available shards is memoized against it (``Index.shard_scope``), a
``TopN`` field's row count against its view's version
(``View.max_rows``), and the dedup token is one read of the stamp.  So
after warm-up a read walks no fragment, at 8 shards and at 64 alike;
after a write the memos are rebuilt once and never serve state older
than the write.
"""

import collections
import os
import sys
import threading

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [p for p in (HERE, os.path.dirname(HERE)) if p not in sys.path]

from benchmark.harness import pql as bench_pql, traffic  # noqa: E402
from benchmark.harness.server import Client  # noqa: E402
from benchmark.readers import prom_family  # noqa: E402
from benchmark.run import HERE as BENCH_DIR, load_json  # noqa: E402
from pilosa_tpu.core import Holder  # noqa: E402
from pilosa_tpu.core.field import FIELD_INT, FieldOptions  # noqa: E402
from pilosa_tpu.core.fragment import Fragment  # noqa: E402
from pilosa_tpu.core.index import Index  # noqa: E402
from pilosa_tpu.executor import Executor  # noqa: E402
from pilosa_tpu.executor.router import estimate_words  # noqa: E402
from pilosa_tpu.pql import parse  # noqa: E402
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD  # noqa: E402
from pilosa_tpu.utils.stats import StatsClient  # noqa: E402
from test_taxi_mesh import SEED, SPEC, ask, boot, cell_config, load, metrics  # noqa: E402

REBUILDS = "shard_scope_rebuilds_total"


def small_index(stats=None):
    """Two shards, a set field, an int field, existence tracked."""
    h = Holder(None, stats=stats)
    idx = h.create_index("i")
    f = idx.create_field("f")
    v = idx.create_field("v", FieldOptions(field_type=FIELD_INT, min=0, max=1000))
    cols = np.array([1, 2, SHARD_WIDTH + 1, SHARD_WIDTH + 2], dtype=np.uint64)
    f.import_bulk(np.array([0, 1, 1, 2], dtype=np.uint64), cols)
    v.import_values(cols, np.array([5, 6, 7, 8], dtype=np.int64))
    idx.mark_columns_exist(cols)
    return h, idx, Executor(h, stats=stats, route_mode="device")


def pairs(topn) -> list:
    return [(p["id"], p["count"]) for p in topn]


def rebuilds(stats) -> float:
    return stats.expvar()["counters"].get(REBUILDS, 0)


# ------------------------------------------------------------ (a) the stamp
MUTATIONS = {
    "Set": lambda idx, e: e.execute("i", "Set(7, f=3)"),
    "Clear": lambda idx, e: e.execute("i", "Clear(1, f=0)"),
    "SetValue": lambda idx, e: e.execute("i", "Set(1, v=99)"),
    "bulk_import": lambda idx, e: idx.field("f").import_bulk(
        np.array([4], dtype=np.uint64), np.array([9], dtype=np.uint64)),
    "import_values": lambda idx, e: idx.field("v").import_values(
        np.array([2], dtype=np.uint64), np.array([11], dtype=np.int64)),
    "fragment_creation": lambda idx, e: idx.field("f").view(
        "standard").create_fragment_if_not_exists(5),
    "remove_fragment": lambda idx, e: idx.field("f").view("standard").remove_fragment(1),
    "delete_field": lambda idx, e: idx.delete_field("f"),
}


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_the_index_stamp_rises_on_every_mutation_and_on_nothing_else(mutation):
    _h, idx, e = small_index()
    before = idx.stamp.value
    e.execute("i", "Count(Row(f=1))")
    e.execute("i", "TopN(f)")
    idx.shard_scope()
    assert idx.stamp.value == before, "a read moved the stamp"
    MUTATIONS[mutation](idx, e)
    first = idx.stamp.value
    assert first > before
    if mutation != "delete_field":
        e.execute("i", "Set(3, f=1)")
        assert idx.stamp.value > first  # monotone


def test_a_field_created_gets_the_stamp_and_a_view_alone_changes_no_answer():
    _h, idx, _e = small_index()
    before = idx.stamp.value
    g = idx.create_field("g")
    view = g.create_view_if_not_exists("standard")
    # nothing a query can read has changed: no fragment, no shard
    assert idx.stamp.value == before and view.index_stamp is idx.stamp
    g.set_bit(0, 3)
    assert idx.stamp.value > before


def test_a_reloaded_index_wires_every_view_to_its_stamp(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    idx = h.create_index("i")
    idx.create_field("f").set_bit(1, SHARD_WIDTH + 3)
    h.close()
    stats = StatsClient()
    h2 = Holder(str(tmp_path), stats=stats)
    h2.open()
    idx2 = h2.index("i")
    views = [v for f in idx2.fields.values() for v in f.views.values()]
    assert views and all(v.index_stamp is idx2.stamp for v in views)
    assert idx2.shard_scope() == (1,) and rebuilds(stats) == 1
    before = idx2.stamp.value
    idx2.field("f").set_bit(1, 5)
    assert idx2.stamp.value > before and idx2.shard_scope() == (0, 1)
    h2.close()


# ------------------------------------- (b) + (e) the scope and its counter
def _add_shard(idx, e):
    e.execute("i", f"Set({3 * SHARD_WIDTH + 4}, f=1)")
    return (0, 1, 3)


def _remove_shard(idx, e):
    for field in list(idx.fields.values()):
        for view in list(field.views.values()):
            view.remove_fragment(1)
    return (0,)


@pytest.mark.parametrize("change", [_add_shard, _remove_shard], ids=["added", "removed"])
def test_shards_is_one_tuple_until_a_shard_is_added_or_removed(change):
    stats = StatsClient()
    _h, idx, e = small_index(stats)
    scope = e._shards(idx, None)
    assert scope == (0, 1) and isinstance(scope, tuple)
    at = rebuilds(stats)
    assert at >= 1
    for q in ("Count(Row(f=1))", "TopN(f)", "Sum(field=v)"):
        e.execute("i", q)
        assert e._shards(idx, None) is scope
    assert idx.shard_scope() is scope and rebuilds(stats) == at
    want = change(idx, e)
    bumped = e._shards(idx, None)
    assert bumped == want and bumped is not scope
    assert e._shards(idx, None) is bumped
    # (e) one count a rebuild, and a rebuild only where a bump came between
    assert rebuilds(stats) == at + 1
    # a write that changes no shard still bumps: rebuilt once, equal, not older
    e.execute("i", "Set(9, f=2)")
    again = e._shards(idx, None)
    assert again == want and rebuilds(stats) == at + 2
    assert e._shards(idx, None) is again and rebuilds(stats) == at + 2


def test_an_explicit_shard_list_still_overrides_the_scope():
    stats = StatsClient()
    _h, idx, e = small_index(stats)
    at = rebuilds(stats)
    assert e._shards(idx, [1, 0]) == (0, 1) and e._shards(idx, [1]) == (1,)
    assert e.execute("i", "Count(Row(f=1))")[0] == 2
    assert e.execute("i", "Count(Row(f=1))", shards=[1])[0] == 1
    assert e.execute("i", "Options(Count(Row(f=1)), shards=[0])")[0] == 1
    assert e.execute("i", "Options(Count(Row(f=1)), shards=[0])", shards=[1])[0] == 1
    assert pairs(e.execute("i", "Options(TopN(f), shards=[1])")[0]) == [(1, 1), (2, 1)]
    assert e._route(idx, parse("Options(Count(Row(f=1)), shards=[0])")[0], None)[1] == WORDS_PER_SHARD
    assert rebuilds(stats) - at <= 1  # the whole-index read alone built it


def test_an_index_without_a_fragment_reads_shard_zero():
    h = Holder(None)
    idx = h.create_index("empty")
    idx.create_field("f")
    e = Executor(h, route_mode="device")
    assert idx.shard_scope() == () and e._shards(idx, None) == (0,)
    assert e.execute("empty", "Count(Row(f=1))") == [0]


def test_the_scope_is_never_older_than_an_acknowledged_write_under_threads():
    """Writers add shards (and rows) while readers read the memos: what
    a reader gets holds every write acknowledged BEFORE it asked."""
    _h, idx, _e = small_index()
    view = idx.field("f").view("standard")
    acked: list[int] = []  # (shard == row) of every finished write
    stop = threading.Event()
    stale: list = []

    def writer(k: int) -> None:
        for i in range(40):
            n = 2 + k * 40 + i
            idx.field("f").set_bit(n, n * SHARD_WIDTH + 1)
            acked.append(n)

    def reader() -> None:
        while not stop.is_set():
            seen = acked[-1] if acked else None
            scope, rows = idx.shard_scope(), view.max_rows()
            if seen is not None and (seen not in scope or rows <= seen):
                stale.append((seen, len(scope), rows))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=reader, daemon=True) for _ in range(6)]
        writers = [threading.Thread(target=writer, args=(k,), daemon=True) for k in range(4)]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join(60)
        stop.set()
        for t in readers:
            t.join(10)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in readers + writers)
    assert not stale, stale[:3]
    assert idx.shard_scope() == tuple(sorted(idx.available_shards())) and len(idx.shard_scope()) == 162
    assert view.max_rows() == 162


# ------------------------------------------ (d) the row count follows a write
@pytest.mark.parametrize("route_mode", ["device", "auto"])
def test_a_topn_fields_row_count_follows_a_write(route_mode):
    h, idx, _e = small_index()
    e = Executor(h, route_mode=route_mode)
    topn = parse("TopN(f)")[0]
    unit = 2 * WORDS_PER_SHARD
    view = idx.field("f").view("standard")
    assert view.max_rows() == 3 and estimate_words(idx, topn, 2) == 3 * unit
    assert e._route(idx, topn, None)[1] == 3 * unit
    assert pairs(e.execute("i", "TopN(f)")[0]) == [(1, 2), (0, 1), (2, 1)]
    e.execute("i", f"Set({SHARD_WIDTH + 9}, f=7)")  # a row the field never had
    assert view.max_rows() == 8 and estimate_words(idx, topn, 2) == 8 * unit
    assert e._route(idx, topn, None)[1] == 8 * unit
    assert pairs(e.execute("i", "TopN(f)")[0]) == [(1, 2), (0, 1), (2, 1), (7, 1)]
    # the int field's depth is read from the field, not walked: it follows too
    total = parse("Sum(field=v)")[0]
    before = estimate_words(idx, total, 2)
    e.execute("i", "Set(2, v=1000)")
    assert estimate_words(idx, total, 2) >= before
    assert e.execute("i", "Sum(field=v)")[0] == {"value": 5 + 1000 + 7 + 8, "count": 4}


# ----------------- (c) the invariant: after warm-up a read walks no fragment
class CountedDict(dict):
    """``View.fragments`` with every whole-dict read counted."""

    counts: collections.Counter

    def _seen(self):
        self.counts["View.fragments"] += 1

    def values(self):
        self._seen()
        return super().values()

    def items(self):
        self._seen()
        return super().items()

    def keys(self):
        self._seen()
        return super().keys()

    def __iter__(self):
        self._seen()
        return super().__iter__()


@pytest.fixture(scope="module", params=[8, 64], ids=["8-shards", "64-shards"])
def served(request, tmp_path_factory):
    """The benchmark's taxi deployment at two shard counts, one server a
    route, with its plain reference."""
    assert jax.local_device_count() > 1, "conftest gives the suite its virtual devices"
    cfg = cell_config(request.param)
    servers = {r: boot(tmp_path_factory.mktemp(f"{r}{request.param}"), r) for r in ("device", "mesh")}
    refs = {r: load(s, cfg, cfg["index"]) for r, s in servers.items()}
    yield {"cfg": cfg, "servers": servers, "ref": refs["mesh"], "shards": request.param}
    for s in servers.values():
        s.close()


@pytest.fixture
def walks(monkeypatch):
    """Calls that walk an index's fragments, counted by name."""
    counts: collections.Counter = collections.Counter()

    def count(cls, name):
        inner = getattr(cls, name)

        def counted(self, *a, **k):
            counts[f"{cls.__name__}.{name}"] += 1
            return inner(self, *a, **k)

        monkeypatch.setattr(cls, name, counted)

    count(Index, "available_shards")
    count(Fragment, "n_rows")
    monkeypatch.setattr(CountedDict, "counts", counts, raising=False)
    return counts


@pytest.mark.parametrize("route", ["device", "mesh"])
def test_after_warm_up_a_read_walks_no_fragment(served, walks, monkeypatch, route):
    srv, index, ref = served["servers"][route], served["cfg"]["index"], served["ref"]
    idx = srv.holder.index(index)
    assert len(idx.shard_scope()) == served["shards"]
    c = Client(f"http://127.0.0.1:{srv.port}")
    gen = traffic.Generator(SPEC, [SEED, served["shards"]])
    for t in SPEC["templates"]:  # warm-up: stacks packed, programs compiled
        for _ in range(3):
            ask(c, index, gen.render(t, {}))
    for field in idx.fields.values():
        for view in field.views.values():
            monkeypatch.setattr(view, "fragments", CountedDict(view.fragments))
    walks.clear()
    before = metrics(srv)
    scope = idx.shard_scope()
    for t in SPEC["templates"]:
        for _ in range(50):
            text = gen.render(t, {})
            assert ask(c, index, text) == ref.answer(bench_pql.parse(text)), text
    c.close()
    after = metrics(srv)
    routed = sum(after["queries_routed"].values()) - sum(before["queries_routed"].values())
    assert routed + after.get("queries_deduped", {}).get("", 0) - before.get(
        "queries_deduped", {}).get("", 0) == 50 * len(SPEC["templates"])
    assert dict(walks) == {}, f"{served['shards']} shards, {route}: {dict(walks)}"
    assert idx.shard_scope() is scope
    # the benchmark's per-layer metric, by the reader its file names: no
    # rebuild in a window without a write
    spec = load_json(BENCH_DIR, "layer_metrics", "scope_rebuilds_per_query.json")
    ctx = {"scrapes": {"window_start": {"metrics": before}, "window_end": {"metrics": after}}}
    assert spec["reader"] == "prom_family" and prom_family.read(spec["params"], ctx) == 0.0


def test_scope_rebuilds_per_query_is_above_zero_when_writes_come_between_reads(tmp_path):
    srv = boot(tmp_path, "device")
    try:
        cfg = cell_config(2)
        index = cfg["index"]
        ref = load(srv, cfg, index)
        c = Client(f"http://127.0.0.1:{srv.port}")
        text = "Count(Intersect(Row(pickup_year=1), Row(passenger_count=1)))"
        want = ref.answer(bench_pql.parse(text))
        assert ask(c, index, text) == want
        before = metrics(srv)
        assert REBUILDS in before
        free = 2 * SHARD_WIDTH + 17  # a column, and a shard, the load never wrote
        for k in range(4):
            assert c.json(f"/index/{index}/query",
                          f"Set({free + k}, pickup_year=1) Set({free + k}, passenger_count=1)".encode()
                          )["results"] == [True, True]
            # acknowledged, so read back: the next read is never older
            assert ask(c, index, text) == want + k + 1
        c.close()
        after = metrics(srv)
        spec = load_json(BENCH_DIR, "layer_metrics", "scope_rebuilds_per_query.json")
        ctx = {"scrapes": {"window_start": {"metrics": before}, "window_end": {"metrics": after}}}
        per_query = prom_family.read(spec["params"], ctx)
        moved = after[REBUILDS][""] - before[REBUILDS][""]
        assert 4 <= moved <= 8 and per_query == moved / 4
    finally:
        srv.close()
