"""The deployment ``taxi-128g`` at a CPU size: the taxi benchmark's four
queries as ``GroupBy`` calls under a fare floor, served through
``API.query`` and the wave scheduler on the device, host and mesh
routes, against a brute-force numpy reference written here (one pass
over the seeded columns, ``np.add.at`` into the joint table; nothing of
the program's planner or ops).

Also what the deployment's guarantee rests on: the transient ledger
(``GroupByLedger``) never holds more than the budget it is given, under
sixteen threads, and the spans and counters that the benchmark's
per-layer metrics read move as docs/observability.md says.
"""

import threading

import numpy as np
import pytest

import jax

from pilosa_tpu import ops
from pilosa_tpu.core import FieldOptions, Holder
from pilosa_tpu.executor.router import QueryRouter
from pilosa_tpu.parallel.mesh import MeshContext, make_mesh
from pilosa_tpu.server.api import API
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.utils import stats as stats_mod, xlaevents
from pilosa_tpu.utils.stats import StatsClient
from pilosa_tpu.utils.tracing import GLOBAL_TRACER

N_SHARDS = 8  # one a virtual device on the mesh route
ROWS = {"cab_type": 3, "passenger_count": 10, "pickup_year": 8, "dist_miles": 32}
AMOUNT = "total_amount_dollars"
FLOOR = f"filter=Row({AMOUNT} > {{t}})"
TEMPLATES = {
    "g1_by_cab": f"GroupBy(Rows(cab_type), {FLOOR})",
    "g2_amount_by_passengers": (
        f"GroupBy(Rows(passenger_count), {FLOOR}, aggregate=Sum(field={AMOUNT}))"
    ),
    "g3_by_passengers_year": f"GroupBy(Rows(passenger_count), Rows(pickup_year), {FLOOR})",
    "g4_by_passengers_year_distance": (
        f"GroupBy(Rows(passenger_count), Rows(pickup_year), Rows(dist_miles), {FLOOR})"
    ),
}
GROUPED = {
    "g1_by_cab": ["cab_type"],
    "g2_amount_by_passengers": ["passenger_count"],
    "g3_by_passengers_year": ["passenger_count", "pickup_year"],
    "g4_by_passengers_year_distance": ["passenger_count", "pickup_year", "dist_miles"],
}
FLOORS = (3, 40, 258)  # every ride has over 3 dollars but the cheapest
PLANE = N_SHARDS * WORDS_PER_SHARD * 4
CAP = 4  # masks a level under the pinned budget
NEW_FAMILIES = (
    "groupby_queries_total", "groupby_launches_total", "groupby_level_readbacks_total",
    "groupby_mask_bytes_total", "groupby_chunks_total", "groupby_transient_high_water_bytes",
    "groupby_chunk_waits_total", "groupby_chain_queries_total",
)


# ------------------------------------------------------------------- data
@pytest.fixture(scope="module")
def rides():
    """One value of every field for every column, seeded: the set fields
    uniform over their rows, the amount 3-1026 dollars, skewed low."""
    rng = np.random.default_rng(3400)
    n = N_SHARDS * SHARD_WIDTH
    cols = {f: rng.integers(0, k, n) for f, k in ROWS.items()}
    v = (rng.integers(0, 1 << 16, n) * rng.integers(0, 1 << 16, n)) >> 16
    cols[AMOUNT] = 3 + ((v * v) >> 22)
    return cols


@pytest.fixture(scope="module")
def holder(rides):
    h = Holder(None)
    idx = h.create_index("taxi")
    ids = np.arange(N_SHARDS * SHARD_WIDTH, dtype=np.uint64)
    for f in ROWS:
        idx.create_field(f).import_bulk(rides[f].astype(np.uint64), ids)
    amount = idx.create_field(AMOUNT, FieldOptions(field_type="int", min=0, max=100000))
    amount.import_values(ids, rides[AMOUNT])
    idx.mark_columns_exist(ids)
    return h


def reference(rides, template: str, t: int) -> list[dict]:
    """What ``results[0]`` must be: the joint table of the grouped fields
    over the rides above the floor, its cells in nested ascending order."""
    return joint_table(rides, GROUPED[template], t, "aggregate=" in TEMPLATES[template])


def joint_table(rides, fields: list[str], t: int, agg: bool) -> list[dict]:
    keep = rides[AMOUNT] > t
    at = tuple(rides[f][keep] for f in fields)
    shape = tuple(ROWS[f] for f in fields)
    count = np.zeros(shape, dtype=np.int64)
    total = np.zeros(shape, dtype=np.int64)
    np.add.at(count, at, 1)
    np.add.at(total, at, rides[AMOUNT][keep])
    out = []
    for cell in np.argwhere(count > 0).tolist():
        g = {"group": [{"field": f, "rowID": r} for f, r in zip(fields, cell)],
             "count": int(count[tuple(cell)])}
        if agg:
            g["sum"] = int(total[tuple(cell)])
        out.append(g)
    return out


# ----------------------------------------------------------------- servers
def _api(holder, route: str, stats=None) -> API:
    mesh_ctx = None
    if route == "mesh":
        assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
        mesh_ctx = MeshContext(make_mesh(jax.devices(), words_axis=1))
    return API(holder, stats=stats, mesh_ctx=mesh_ctx, router=QueryRouter(mode=route, stats=stats))


@pytest.fixture(scope="module")
def apis(holder):
    return {route: _api(holder, route) for route in ("device", "host", "mesh")}


def pinned_budget() -> int:
    """The budget under which the fourth query holds ``CAP`` masks a
    level: the filter's plane, two levels of masks and the temporaries."""
    return (1 + 2 * CAP + ops.groupby.TEMP_PLANES) * PLANE


@pytest.fixture(scope="module")
def pinned(holder):
    """A device-route server of its own with the transient budget pinned
    and a registry of its own behind it."""
    client = StatsClient()
    api = _api(holder, "device", stats=client)
    api.executor.GROUPBY_MASK_BUDGET = pinned_budget()
    return api, client


def ask(api: API, template: str, t: int) -> list[dict]:
    return api.query("taxi", TEMPLATES[template].format(t=t))["results"][0]


# ------------------------------------------------------------------- cases
@pytest.mark.parametrize("route", ["device", "host", "mesh"])
@pytest.mark.parametrize("t", FLOORS)
@pytest.mark.parametrize("template", list(TEMPLATES))
def test_served_reply_equals_the_brute_force_table(apis, rides, template, t, route):
    got = ask(apis[route], template, t)
    assert got == reference(rides, template, t)
    assert got  # the floor leaves rides in every query


@pytest.mark.parametrize("t", FLOORS)
@pytest.mark.parametrize("template", list(TEMPLATES))
def test_same_reply_in_chunks_of_four(apis, pinned, template, t):
    api, _client = pinned
    assert ask(api, template, t) == ask(apis["device"], template, t)


@pytest.fixture(scope="module")
def counted(holder):
    """A server a route with a registry of its own behind it."""
    out = {}
    for route in ("device", "host", "mesh"):
        client = StatsClient()
        out[route] = (_api(holder, route, stats=client), client)
    return out


@pytest.mark.parametrize("limit", [None, 7, 100])
@pytest.mark.parametrize("route", ["device", "host", "mesh"])
@pytest.mark.parametrize("template", ["g3_by_passengers_year", "g4_by_passengers_year_distance"])
def test_chain_count_answers_with_and_without_limit(counted, rides, template, route, limit):
    """The g3 and g4 shapes, one chain count on the device and mesh
    routes and the numpy engine on the host route: the same table, cut
    by a ``limit`` where the query has one, and counted in
    ``groupby_chain_queries_total`` where a chain count answered."""
    api, client = counted[route]
    pql = TEMPLATES[template].format(t=40)
    if limit is not None:
        pql = pql[:-1] + f", limit={limit})"
    chained = _family(client, "groupby_chain_queries_total")
    got = api.query("taxi", pql)["results"][0]
    assert got == reference(rides, template, 40)[:limit]
    assert _family(client, "groupby_chain_queries_total") == chained + (route != "host")


def _family(client: StatsClient, name: str) -> float:
    with client._lock:
        return sum(v for (n, _tags), v in client._counters.items() if n == name)


def _compiles(client: StatsClient) -> int:
    with client._lock:
        return sum(h.count for (n, _tags), h in client._timings.items()
                   if n == "xla_compile_seconds")


def test_sixteen_threads_ten_decks_inside_the_budget(pinned, rides):
    """Every answer exact, none raises, nothing compiles in the second
    half, and the ledger's mark never passes the pinned budget."""
    api, client = pinned
    want = {(name, t): reference(rides, name, t) for name in TEMPLATES for t in FLOORS}
    wrong: list = []

    def decks(seed: int, n: int):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(n):
                for name in rng.permutation(list(TEMPLATES)):
                    t = FLOORS[int(rng.integers(len(FLOORS)))]
                    if ask(api, name, t) != want[(name, t)]:
                        wrong.append((name, t))
        except Exception as e:  # noqa: BLE001 — reported below, with its query
            wrong.append(repr(e))

    def half(base: int):
        threads = [threading.Thread(target=decks, args=(base + k, 5)) for k in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()

    xlaevents.set_stats(client)
    try:
        jax.jit(lambda x: x * 3 + 1)(np.arange(7))  # a program nobody has compiled
        assert _compiles(client) > 0  # the listener is live
        half(100)
        compiled = _compiles(client)
        half(200)
        assert _compiles(client) == compiled
    finally:
        xlaevents.set_stats(None)
    assert not wrong, wrong[:3]
    ledger = api.executor.gb_ledger.snapshot()
    assert 0 < ledger["highWaterBytes"] <= pinned_budget()
    assert ledger["heldBytes"] == 0
    gauge = client._gauges[("groupby_transient_high_water_bytes", ())]
    assert gauge == ledger["highWaterBytes"]


def _chunks(n: int) -> list[int]:
    return [min(CAP, n - lo) for lo in range(0, n, CAP)]


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def test_one_g4_moves_every_family_by_the_documented_amount(pinned, rides):
    """docs/observability.md: a level-synchronous GroupBy of three levels
    under a filter, chunks of at most ``CAP`` pairs. The first read holds
    level 0's counts and the filter's against the rows of the two levels
    below: three launches, one read."""
    api, client = pinned
    assert all(f in stats_mod._METRIC_HELP for f in NEW_FAMILIES)
    t = 40
    table = reference(rides, "g4_by_passengers_year_distance", t)
    # surviving pairs level by level, in the order the chunks take them
    level0 = sorted({g["group"][0]["rowID"] for g in table})
    level1 = sorted({(g["group"][0]["rowID"], g["group"][1]["rowID"]) for g in table})
    masks_launched, counts_launched, chunks = [], 1, 0  # level 0's counts
    lo = 0
    for c0 in _chunks(len(level0)):  # a chunk of passenger rows
        chunks += 1
        masks_launched.append(_pow2(c0))
        counts_launched += 1  # level 1 under this chunk
        mine = [p for p in level1 if p[0] in level0[lo:lo + c0]]
        lo += c0
        at = 0
        for c1 in _chunks(len(mine)):  # a chunk of (passenger, year) pairs
            chunks += 1
            masks_launched.append(_pow2(c1))
            counts_launched += 1  # level 2 under this chunk
            last = [g for g in table
                    if (g["group"][0]["rowID"], g["group"][1]["rowID"]) in mine[at:at + c1]]
            at += c1
            chunks += len(_chunks(len(last)))
    before = {f: _family(client, f) for f in NEW_FAMILIES}
    with GLOBAL_TRACER._lock:
        GLOBAL_TRACER._spans.clear()
    assert ask(api, "g4_by_passengers_year_distance", t) == table
    moved = {f: _family(client, f) - before[f] for f in NEW_FAMILIES}
    assert moved["groupby_queries_total"] == 1
    with client._lock:
        assert client._counters[("groupby_queries_total", (("path", "levels"),))] >= 1
    assert moved["groupby_launches_total"] == 1 + counts_launched + 2 + len(masks_launched)
    assert moved["groupby_level_readbacks_total"] == counts_launched
    assert moved["groupby_mask_bytes_total"] == sum(masks_launched) * PLANE
    assert moved["groupby_chunks_total"] == chunks
    # the spans: each kind under the one executor.GroupBy of the query
    spans = GLOBAL_TRACER.recent(4096)
    (call,) = [s for s in spans if s["name"] == "executor.GroupBy"]
    mine = [s for s in spans if s["name"].startswith("executor.groupby.")]
    assert all(s["parentSpanID"] == call["spanID"] for s in mine)
    by_name = {}
    for s in mine:
        by_name[s["name"]] = by_name.get(s["name"], 0) + 1
    assert by_name == {
        "executor.groupby.filter": 1,
        "executor.groupby.counts": counts_launched + 2,
        "executor.groupby.masks": len(masks_launched),
        "executor.groupby.readback": counts_launched,
    }


def _span_names() -> list[str]:
    return [s["name"] for s in GLOBAL_TRACER.recent(4096)]


def _clear_spans() -> None:
    with GLOBAL_TRACER._lock:
        GLOBAL_TRACER._spans.clear()


def _fused(client: StatsClient) -> float:
    with client._lock:
        return client._counters[("groupby_queries_total", (("path", "fused"),))]


def test_fused_and_aggregate_paths_are_counted(pinned):
    """g1 and g3 count all pairs on the device (one deferred readback, no
    read inside the dispatch; g3 as one chain count, no mask made); so
    does g2: its aggregate opens one ``executor.groupby.sums`` on the
    masks of its one level, and its counts and sums ride the wave's
    readback."""
    api, client = pinned
    api.executor.GROUPBY_MASK_BUDGET = None  # the default: everything fits
    try:
        before = {f: _family(client, f) for f in NEW_FAMILIES}
        fused0 = _fused(client)
        ask(api, "g1_by_cab", 40)
        ask(api, "g3_by_passengers_year", 40)
        assert _fused(client) == fused0 + 2
        moved = {f: _family(client, f) - before[f] for f in NEW_FAMILIES}
        assert moved["groupby_launches_total"] == (1 + 1) + (1 + 1)  # filter, counts | chains
        assert moved["groupby_level_readbacks_total"] == 0
        assert moved["groupby_chain_queries_total"] == 1
        assert moved["groupby_mask_bytes_total"] == 0
        before = {f: _family(client, f) for f in NEW_FAMILIES}
        _clear_spans()
        ask(api, "g2_amount_by_passengers", 40)
        assert _fused(client) == fused0 + 3
        moved = {f: _family(client, f) - before[f] for f in NEW_FAMILIES}
        assert moved["groupby_launches_total"] == 4  # filter, counts, masks [16], sums
        assert moved["groupby_level_readbacks_total"] == 0
        assert moved["groupby_chunk_waits_total"] == 0
        assert moved["groupby_chain_queries_total"] == 0
        assert moved["groupby_mask_bytes_total"] == 16 * PLANE
        names = _span_names()
        assert names.count("executor.groupby.sums") == 1
        assert names.count("executor.groupby.readback") == 0
    finally:
        api.executor.GROUPBY_MASK_BUDGET = pinned_budget()


@pytest.mark.parametrize("template", list(TEMPLATES))
def test_no_level_is_read_back_under_the_default_budget(pinned, rides, template):
    """Every template of the cell takes the deferred walk: no synchronous
    read inside its dispatch, no wait for a chunk, the exact table."""
    api, client = pinned
    api.executor.GROUPBY_MASK_BUDGET = None
    try:
        before = {f: _family(client, f) for f in NEW_FAMILIES}
        fused0 = _fused(client)
        _clear_spans()
        assert ask(api, template, 40) == reference(rides, template, 40)
        moved = {f: _family(client, f) - before[f] for f in NEW_FAMILIES}
        assert _fused(client) == fused0 + 1
        assert moved["groupby_level_readbacks_total"] == 0
        assert moved["groupby_chunk_waits_total"] == 0
        names = _span_names()
        assert "executor.groupby.readback" not in names
        assert "executor.groupby.wait" not in names
    finally:
        api.executor.GROUPBY_MASK_BUDGET = pinned_budget()


def two_chunk_budget() -> int:
    """What the cell's chip gives the fourth query's walk: the filter's
    plane, the 16 padded passenger masks, ONE chunk of 64 (passenger,
    year) masks and the temporaries, 82 planes; its 80 real pairs are
    64 + 16. A walk of two chunks is deferred; without an aggregate it is
    one chain count and holds the filter's plane and the temporaries."""
    return (1 + 16 + 64 + ops.groupby.TEMP_PLANES) * PLANE


def chain_need() -> int:
    return (1 + ops.groupby.TEMP_PLANES) * PLANE


def test_a_g4_in_two_chunks_waits_once_and_reads_nothing_back(pinned, rides):
    """docs/observability.md: the three levels whose (passenger, year)
    pairs would need two chunks of masks are ONE chain count: no mask, no
    wait for a chunk, nothing read back, two planes reserved."""
    api, client = pinned
    api.executor.GROUPBY_MASK_BUDGET = two_chunk_budget()
    try:
        before = {f: _family(client, f) for f in NEW_FAMILIES}
        fused0 = _fused(client)
        mark0 = api.executor.gb_ledger.snapshot()["highWaterBytes"]
        _clear_spans()
        got = ask(api, "g4_by_passengers_year_distance", 40)
        assert got == reference(rides, "g4_by_passengers_year_distance", 40)
        moved = {f: _family(client, f) - before[f] for f in NEW_FAMILIES}
        assert _fused(client) == fused0 + 1
        assert moved["groupby_launches_total"] == 2  # filter, chains [128, 2] x 32
        assert moved["groupby_chain_queries_total"] == 1
        assert moved["groupby_chunk_waits_total"] == 0
        assert moved["groupby_level_readbacks_total"] == 0
        assert moved["groupby_mask_bytes_total"] == 0
        spans = GLOBAL_TRACER.recent(4096)
        (call,) = [s for s in spans if s["name"] == "executor.GroupBy"]
        mine = [s for s in spans if s["name"].startswith("executor.groupby.")]
        assert all(s["parentSpanID"] == call["spanID"] for s in mine)
        assert sorted(s["name"] for s in mine) == [
            "executor.groupby.chains", "executor.groupby.filter"]
        ledger = api.executor.gb_ledger.snapshot()
        assert ledger["heldBytes"] == 0
        assert max(mark0, chain_need()) == ledger["highWaterBytes"]
    finally:
        api.executor.GROUPBY_MASK_BUDGET = pinned_budget()


G3_SUM = (f"GroupBy(Rows(passenger_count), Rows(pickup_year), filter=Row({AMOUNT} > 40), "
          f"aggregate=Sum(field={AMOUNT}))")


def test_a_g3_with_a_sum_in_two_chunks_waits_once(pinned, rides):
    """An aggregate still needs the last level's masks: the (passenger,
    year) pairs in two chunks of the one reservation of 82 planes, one
    wait for the first chunk's sums before the second chunk is made."""
    api, client = pinned
    api.executor.GROUPBY_MASK_BUDGET = two_chunk_budget()
    try:
        before = {f: _family(client, f) for f in NEW_FAMILIES}
        fused0 = _fused(client)
        mark0 = api.executor.gb_ledger.snapshot()["highWaterBytes"]
        _clear_spans()
        got = api.query("taxi", G3_SUM)["results"][0]
        assert got == joint_table(rides, ["passenger_count", "pickup_year"], 40, True)
        moved = {f: _family(client, f) - before[f] for f in NEW_FAMILIES}
        assert _fused(client) == fused0 + 1
        # filter, masks [16], counts [16]x8, masks [64] + sums, masks [16] + sums
        assert moved["groupby_launches_total"] == 7
        assert moved["groupby_chain_queries_total"] == 0
        assert moved["groupby_chunk_waits_total"] == 1
        assert moved["groupby_level_readbacks_total"] == 0
        assert moved["groupby_mask_bytes_total"] == (16 + 64 + 16) * PLANE
        assert sorted(n for n in _span_names() if n.startswith("executor.groupby.")) == sorted(
            ["executor.groupby.filter", "executor.groupby.counts", "executor.groupby.wait"]
            + 3 * ["executor.groupby.masks"] + 2 * ["executor.groupby.sums"])
        ledger = api.executor.gb_ledger.snapshot()
        assert ledger["heldBytes"] == 0
        assert max(mark0, two_chunk_budget()) == ledger["highWaterBytes"]
    finally:
        api.executor.GROUPBY_MASK_BUDGET = pinned_budget()


def test_sixteen_direct_threads_share_the_two_chunk_budget(holder, rides):
    """``batch-mode`` off: every request thread dispatches its own query,
    so sixteen walks reserve and retire each other's reservations at
    once. A g2 takes eighteen planes of the budget and a g1, g3 or g4 two
    (the g3 and g4 as one chain count each, no chunk to wait for): every
    answer exact, the mark inside the budget, nothing held afterwards, no
    thread left waiting."""
    client = StatsClient()
    api = API(holder, stats=client, batch_mode="off",
              router=QueryRouter(mode="device", stats=client))
    api.executor.GROUPBY_MASK_BUDGET = two_chunk_budget()
    want = {(name, t): reference(rides, name, t) for name in TEMPLATES for t in FLOORS}
    wrong: list = []

    def decks(seed: int):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(3):
                for name in rng.permutation(list(TEMPLATES)):
                    t = FLOORS[int(rng.integers(len(FLOORS)))]
                    if ask(api, name, t) != want[(name, t)]:
                        wrong.append((name, t))
        except Exception as e:  # noqa: BLE001 — reported below
            wrong.append(repr(e))

    threads = [threading.Thread(target=decks, args=(300 + k,), daemon=True) for k in range(16)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=300)
    assert not any(th.is_alive() for th in threads), "a thread is still waiting"
    assert not wrong, wrong[:3]
    assert _family(client, "groupby_level_readbacks_total") == 0
    assert _family(client, "groupby_chunk_waits_total") == 0
    assert _family(client, "groupby_chain_queries_total") == 16 * 3 * 2  # a g3 and a g4 a deck
    ledger = api.executor.gb_ledger.snapshot()
    assert 0 < ledger["highWaterBytes"] <= two_chunk_budget()
    assert ledger["heldBytes"] == 0 and ledger["fusedInFlight"] == 0


def test_resources_row_reads_the_ledger(tmp_path):
    """``GET /debug/resources`` row ``groupbyTransient`` on a served node:
    nothing held between queries, the mark a three-level GroupBy left,
    and the budget as its limit once a device query has resolved it."""
    import json
    import urllib.request

    from pilosa_tpu.server.server import Server
    from pilosa_tpu.utils.config import Config

    srv = Server(Config(bind="127.0.0.1:0", data_dir=str(tmp_path / "data"), route_mode="device",
                        anti_entropy_interval=0, diagnostics_interval=0))
    srv.open()
    try:
        srv.wait_mesh(60)

        def call(method, path, body=None):
            req = urllib.request.Request(f"http://127.0.0.1:{srv.port}{path}", data=body, method=method)
            with urllib.request.urlopen(req, timeout=60) as resp:
                return json.loads(resp.read() or b"{}")

        call("POST", "/index/i", b"{}")
        for f in ("a", "b", "c"):
            call("POST", f"/index/i/field/{f}", b"{}")
        call("POST", "/index/i/query", b"".join(
            f"Set({c}, a={c % 3}) Set({c}, b={c % 2}) Set({c}, c={c % 5})".encode() for c in range(40)))
        groups = call("POST", "/index/i/query", b"GroupBy(Rows(a), Rows(b), Rows(c))")["results"][0]
        assert len(groups) == 30
        row = call("GET", "/debug/resources")["subsystems"]["groupbyTransient"]
        assert row["unit"] == "bytes" and row["used"] == 0 and row["fusedInFlight"] == 0
        # one chain count, no mask: the filter's plane and the temporaries of one shard
        assert row["highWaterBytes"] == (1 + ops.groupby.TEMP_PLANES) * WORDS_PER_SHARD * 4
        assert row["limit"] == srv.api.executor._gb_budget() and row["pressure"] == 0.0
    finally:
        srv.close()
