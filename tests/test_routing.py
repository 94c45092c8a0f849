"""Cost-based host/device query routing (ISSUE 2).

Three pillars:
- crossover unit tests: the QueryRouter's cost model driven by a fake
  clock and a pre-filled stats feed — decisions must follow the
  calibrated crossover, and calibration drift must invalidate memos;
- host/device equivalence: every PQL call type executed with
  route-mode host and route-mode device must return identical results
  (the host engine is a second implementation of the same semantics);
- degraded boot: a server whose device probe fails pins the host
  engine and serves at full host speed — no device program compiled,
  every read counted as path=host.
"""

import json

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import FIELD_INT, FIELD_TIME, FieldOptions
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.router import QueryRouter, estimate_words
from pilosa_tpu.pql import parse
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.utils.stats import Ewma, StatsClient

pytestmark = pytest.mark.routing


# ------------------------------------------------------------ cost model
class FakeClock:
    """Scripted perf_counter: each call returns the next value."""

    def __init__(self, values):
        self.values = list(values)

    def __call__(self):
        return self.values.pop(0)


def make_router(**kw):
    kw.setdefault("mode", "auto")
    # deterministic host calibration: the fake clock scripts the three
    # calibration reps at 1 ms each → host_wps = 2*2^18 / 1e-3 words/s
    kw.setdefault("clock", FakeClock([i * 1e-3 for i in range(100)]))
    return QueryRouter(**kw)


def test_ewma_seeds_then_folds():
    e = Ewma(alpha=0.5)
    assert e.value is None
    assert e.update(10.0) == 10.0
    assert e.update(20.0) == 15.0


def test_crossover_small_work_routes_host_large_routes_device():
    r = make_router(
        dispatch_seed_s=1e-3,
        readback_seed_s=2e-3,
        device_wps=1e12,
        host_wps=1e9,
    )
    # crossover ≈ (3 ms overhead) / (1/1e9 - 1/1e12) ≈ 3e6 words
    x = r.crossover_words()
    assert 2.5e6 < x < 3.5e6, x
    assert r.decide(("k1",), 100_000) == "host"
    assert r.decide(("k2",), 50_000_000) == "device"


def test_crossover_override_pins_decision():
    r = make_router(crossover_words=1000.0, host_wps=1e9)
    assert r.decide(("a",), 999) == "host"
    assert r.decide(("b",), 1001) == "device"


def test_forced_modes_ignore_cost():
    host = make_router(mode="host", host_wps=1e9)
    dev = make_router(mode="device", host_wps=1e9)
    assert host.decide(("x",), 10**12) == "host"
    assert dev.decide(("x",), 1) == "device"


def test_observed_readback_moves_the_crossover():
    r = make_router(
        dispatch_seed_s=1e-4,
        readback_seed_s=1e-4,
        device_wps=1e12,
        host_wps=1e9,
        alpha=1.0,  # adopt each observation outright: deterministic
    )
    work = 1_000_000
    assert r.decide(("q",), work) == "device"  # host ~1 ms > device ~0.2 ms
    # a slow readback shows itself: 70 ms waves
    r.observe_readback(0.070)
    assert r.decide(("q",), work) == "host"  # memo invalidated by drift


def test_memo_respects_generation():
    r = make_router(host_wps=1e9, alpha=1.0)
    route = r.decide(("stable",), 1000)
    gen = r._gen
    assert r.decide(("stable",), 1000) == route  # memo hit
    r.observe_readback(1.0)  # massive drift
    assert r._gen > gen
    assert not r._memo  # all memoized decisions dropped


def test_memo_rekeys_on_work_growth():
    """The same plan key with 100x the estimated work must re-evaluate
    even without calibration drift — the work bucket is part of the
    memo identity."""
    r = make_router(
        dispatch_seed_s=1e-3,
        readback_seed_s=2e-3,
        device_wps=1e12,
        host_wps=1e9,
    )
    assert r.decide(("grow",), 100_000) == "host"
    assert r.decide(("grow",), 100_000_000) == "device"


def test_host_observation_refines_throughput():
    r = make_router(host_wps=1e9, alpha=1.0)
    r.observe("host", 10_000_000, 0.001)  # measured 1e10 words/s
    assert r.host_wps.value == pytest.approx(1e10)


def test_refresh_from_stats_feed():
    stats = StatsClient()
    for _ in range(8):
        stats.timing("executor_readback_seconds", 0.065)
    r = make_router(stats=stats, host_wps=1e9, alpha=1.0)
    r.refresh_from_stats()
    # folded the histogram p50 (log-bucketed: within the decade step)
    assert 0.02 < r.readback_s.value < 0.2


def test_snapshot_shape():
    snap = make_router(host_wps=1e9).snapshot()
    for key in (
        "mode",
        "crossoverWords",
        "dispatchSeconds",
        "readbackSeconds",
        "hostWordsPerSecond",
        "decisions",
    ):
        assert key in snap


# ------------------------------------------------------- work estimation
def test_estimate_words_scales_with_shape():
    h = Holder(None)
    idx = h.create_index("est")
    f = idx.create_field("f")
    v = idx.create_field(
        "v", FieldOptions(field_type=FIELD_INT, min=0, max=1000)
    )
    cols = np.arange(100, dtype=np.uint64)
    for r in range(16):
        f.import_bulk(np.full(100, r, dtype=np.uint64), cols)
    v.import_values(cols, np.arange(100, dtype=np.int64))
    unit = WORDS_PER_SHARD
    row = estimate_words(idx, parse("Row(f=1)")[0], 1)
    assert row == unit
    two = estimate_words(idx, parse("Count(Intersect(Row(f=1), Row(f=2)))")[0], 1)
    assert two == 2 * unit
    # BSI condition reads the whole slice block
    cond = estimate_words(idx, parse("Count(Row(v > 3))")[0], 1)
    assert cond > 2 * unit
    # TopN reads every stored row
    topn = estimate_words(idx, parse("TopN(f, n=3)")[0], 1)
    assert topn >= 16 * unit
    # shard count multiplies everything
    assert estimate_words(idx, parse("Row(f=1)")[0], 4) == 4 * unit


def test_1m_column_intersect_count_pins_host():
    """ISSUE 4 satellite: the 1M-column sync PQL path — the
    ``pql_intersect_count_1M_qps`` bench row that regressed to 0.04x in
    BENCH_ALL_r05 by paying a full device dispatch+readback for ~65 µs
    of host work — must be host-routed by the cost model under default
    seeds, and must STAY host-routed as calibration folds in real
    observations."""
    h = Holder(None)
    idx = h.create_index("m")
    f = idx.create_field("f")
    n_shards = -(-1_000_000 // SHARD_WIDTH)  # 1M columns at test width
    for s in range(n_shards):
        cols = np.arange(
            s * SHARD_WIDTH, s * SHARD_WIDTH + 64, dtype=np.uint64
        )
        f.import_bulk(np.ones(64, dtype=np.uint64), cols)
        f.import_bulk(np.full(64, 2, dtype=np.uint64), cols)
        idx.mark_columns_exist(cols)
    e = Executor(h)  # default router: auto mode, config-default seeds
    pql = "Count(Intersect(Row(f=1), Row(f=2)))"
    assert e.route_for("m", pql) == "host"
    # executing feeds host calibration; the decision must not flip
    for _ in range(3):
        e.execute("m", pql)
    assert e.route_for("m", pql) == "host"
    assert e.router.decisions.get("device", 0) == 0


# -------------------------------------------------- host/device parity
@pytest.fixture(scope="module")
def parity_rig():
    rng = np.random.default_rng(3)
    h = Holder(None)
    idx = h.create_index("t")
    f = idx.create_field("f")
    g = idx.create_field("g")
    v = idx.create_field(
        "v", FieldOptions(field_type=FIELD_INT, min=-500, max=500)
    )
    tq = idx.create_field(
        "tq", FieldOptions(field_type=FIELD_TIME, time_quantum="YMD")
    )
    kf = idx.create_field("kf", FieldOptions(keys=True))
    n = 6000
    cols = rng.integers(0, 3 * SHARD_WIDTH, n).astype(np.uint64)
    frows = rng.integers(0, 6, n).astype(np.uint64)
    grows = rng.integers(0, 4, n).astype(np.uint64)
    f.import_bulk(frows, cols)
    g.import_bulk(grows, cols)
    vcols = np.unique(cols)
    v.import_values(vcols, rng.integers(-500, 500, vcols.size).astype(np.int64))
    tq.import_bulk(
        frows[:2000],
        cols[:2000],
        timestamps=[
            __import__("datetime").datetime(2026, 7, 1 + int(i % 20))
            for i in range(2000)
        ],
    )
    for i, key in enumerate(["alpha", "beta"]):
        rid = kf.row_keys.translate_key(key, create=True)
        kf.import_bulk(
            np.full(500, rid, dtype=np.uint64), cols[i * 500 : (i + 1) * 500]
        )
    idx.mark_columns_exist(cols)
    e_host = Executor(h, route_mode="host")
    e_dev = Executor(h, route_mode="device")
    return h, e_host, e_dev, cols, frows


ALL_CALL_QUERIES = [
    "Row(f=2)",
    "Range(f=1)",
    "Count(Union(Row(f=1), Row(f=2), Row(g=3)))",
    "Count(Intersect(Row(f=1), Row(g=2)))",
    "Count(Difference(Row(f=1), Row(g=0), Row(g=1)))",
    "Count(Xor(Row(f=1), Row(g=1)))",
    "Count(Not(Row(f=1)))",
    "Count(All())",
    "Count(Shift(Row(f=1), n=3))",
    "Count(Shift(Row(f=1), n=40))",
    "Count(Row(kf=\"alpha\"))",
    "Count(Union(Row(kf=\"alpha\"), Row(kf=\"beta\")))",
    "Count(Row(tq=1, from='2026-07-02T00:00', to='2026-07-10T00:00'))",
    "Sum(field=v)",
    "Sum(Row(f=1), field=v)",
    "Min(field=v)",
    "Min(Row(g=1), field=v)",
    "Max(field=v)",
    "Max(Row(g=2), field=v)",
    "TopN(f, n=3)",
    "TopN(f)",
    "TopN(f, ids=[0,2,4])",
    "TopN(f, n=2, ids=[0,1,2,3])",
    "TopN(f, n=3, minCount=2)",
    "Count(Row(v > 100))",
    "Count(Row(v >= 100))",
    "Count(Row(v < -100))",
    "Count(Row(v <= -100))",
    "Count(Row(v == 7))",
    "Count(Row(v != 7))",
    "Count(Row(-50 < v < 50))",
    "Count(Row(v != null))",
    "Count(Row(v == null))",
    "GroupBy(Rows(f), Rows(g))",
    "GroupBy(Rows(f), Rows(g), limit=7)",
    "GroupBy(Rows(f), filter=Row(g=1))",
    "GroupBy(Rows(f), aggregate=Sum(field=v))",
    "GroupBy(Rows(f, limit=3), Rows(g, previous=0))",
    "Rows(f)",
    "Rows(f, limit=2)",
    "Options(Count(Row(f=1)), shards=[0,1])",
]


def _norm(r):
    from pilosa_tpu.executor import RowResult

    return r.to_json() if isinstance(r, RowResult) else r


@pytest.mark.parametrize("pql", ALL_CALL_QUERIES)
def test_host_device_equivalence(parity_rig, pql):
    _h, e_host, e_dev, cols, frows = parity_rig
    if "IncludesColumn" not in pql:
        host = [_norm(r) for r in e_host.execute("t", pql)]
        dev = [_norm(r) for r in e_dev.execute("t", pql)]
        assert json.dumps(host, default=str) == json.dumps(dev, default=str), pql


def test_host_device_equivalence_includes_column(parity_rig):
    _h, e_host, e_dev, cols, frows = parity_rig
    for col, row in [(int(cols[0]), int(frows[0])), (int(cols[0]) + 1, 0)]:
        pql = f"IncludesColumn(Row(f={row}), column={col})"
        assert e_host.execute("t", pql) == e_dev.execute("t", pql), pql


def test_host_sees_writes_between_queries(parity_rig):
    """The host stacks must track fragment versions: a Set() between two
    identical queries changes the count on the CACHED host plan."""
    h, e_host, _e_dev, _cols, _frows = parity_rig
    before = e_host.execute("t", "Count(Row(f=5))")[0]
    free_col = 3 * SHARD_WIDTH - 7
    assert e_host.execute("t", f"Set({free_col}, f=5)")[0] is True
    after = e_host.execute("t", f"Count(Row(f=5))")[0]
    assert after == before + 1
    assert e_host.execute("t", f"Clear({free_col}, f=5)")[0] is True
    assert e_host.execute("t", "Count(Row(f=5))")[0] == before


def test_route_counter_and_profile_route(parity_rig):
    h, _e_host, _e_dev, _cols, _frows = parity_rig
    stats = StatsClient()
    e = Executor(h, stats=stats, route_mode="host")
    from pilosa_tpu.utils import tracing

    with tracing.profile_query() as prof:
        e.execute("t", "Count(Row(f=1))")
    assert prof.calls and prof.calls[0]["route"] == "host"
    counters = stats.expvar()["counters"]
    assert counters.get("queries_routed{path=host}") == 1


def test_host_gather_mode_over_budget(parity_rig, monkeypatch):
    """Fields whose host stack exceeds the budget serve in gather mode:
    BSI aggregates/conditions chunk over shards instead of materializing
    the rejected block, and results stay identical to the device path."""
    h, _e_host, e_dev, _cols, _frows = parity_rig
    monkeypatch.setenv("PILOSA_TPU_HOST_STACK_BUDGET", "1")  # reject all
    e_host = Executor(h, route_mode="host")
    for pql in (
        "Sum(field=v)",
        "Min(field=v)",
        "Max(Row(g=2), field=v)",
        "Count(Row(v > 100))",
        "Count(Row(-50 < v < 50))",
        "Count(Row(v != null))",
        "Count(Intersect(Row(f=1), Row(g=2)))",
        "TopN(f, n=3)",
    ):
        host = [_norm(r) for r in e_host.execute("t", pql)]
        dev = [_norm(r) for r in e_dev.execute("t", pql)]
        assert json.dumps(host) == json.dumps(dev), pql
