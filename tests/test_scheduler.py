"""Cross-query wave coalescing (ISSUE 4): the dispatch scheduler that
lets concurrent sync clients share device readback waves.

Pillars:
- batched-vs-solo equivalence over every PQL read call type (the wave
  path must be a pure performance transform);
- error isolation: one failing query in a wave errors alone;
- window-timeout flush driven by a fake clock;
- no-starvation fairness under sustained concurrency with tiny waves;
- single-flight dedup correctness, including stack-token invalidation
  under mutation (a query enqueued after a write never joins a
  pre-write execution);
- host-routed / write bypass, wave observability (stats distribution,
  profile wave section, /debug/vars snapshot), and the multi-query
  /internal RPC's per-entry isolation + trace propagation.
"""

import json
import sys
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import FIELD_INT, FieldOptions
from pilosa_tpu.core.view import IndexStamp
from pilosa_tpu.executor import Executor, RowResult
from pilosa_tpu.executor.scheduler import WaveScheduler, stack_token
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.stats import StatsClient

pytestmark = pytest.mark.batching


def make_rig(route_mode="device", **sched_kw):
    rng = np.random.default_rng(11)
    h = Holder(None)
    idx = h.create_index("b")
    f = idx.create_field("f")
    g = idx.create_field("g")
    v = idx.create_field(
        "v", FieldOptions(field_type=FIELD_INT, min=-200, max=200)
    )
    n = 4000
    cols = rng.integers(0, 2 * SHARD_WIDTH, n).astype(np.uint64)
    f.import_bulk(rng.integers(0, 5, n).astype(np.uint64), cols)
    g.import_bulk(rng.integers(0, 3, n).astype(np.uint64), cols)
    vcols = np.unique(cols)
    v.import_values(vcols, rng.integers(-200, 200, vcols.size).astype(np.int64))
    idx.mark_columns_exist(cols)
    stats = StatsClient()
    e = Executor(h, stats=stats, route_mode=route_mode)
    sched_kw.setdefault("stats", stats)
    sched = WaveScheduler(lambda: e, **sched_kw)
    return h, e, sched, stats


READ_QUERIES = [
    "Row(f=2)",
    "Count(Union(Row(f=1), Row(f=2), Row(g=2)))",
    "Count(Intersect(Row(f=1), Row(g=2)))",
    "Count(Difference(Row(f=1), Row(g=0)))",
    "Count(Xor(Row(f=1), Row(g=1)))",
    "Count(Not(Row(f=1)))",
    "Count(All())",
    "Count(Shift(Row(f=1), n=3))",
    "Sum(field=v)",
    "Sum(Row(f=1), field=v)",
    "Min(field=v)",
    "Max(Row(g=2), field=v)",
    "TopN(f, n=3)",
    "TopN(f, ids=[0,2,4])",
    "Count(Row(v > 50))",
    "Count(Row(-50 < v < 50))",
    "GroupBy(Rows(f), Rows(g))",
    "GroupBy(Rows(f), Rows(g), limit=5)",
    "GroupBy(Rows(f), aggregate=Sum(field=v))",
    "Rows(f)",
    "Options(Count(Row(f=1)), shards=[0,1])",
    "Count(Row(f=1)) Count(Row(g=1)) TopN(f, n=2)",  # multi-call request
]


def _norm(results):
    return json.dumps(
        [r.to_json() if isinstance(r, RowResult) else r for r in results],
        default=str,
    )


@pytest.mark.parametrize("pql", READ_QUERIES)
def test_batched_vs_solo_equivalence(pql):
    _h, e, sched, _stats = make_rig()
    assert _norm(sched.execute("b", pql)) == _norm(e.execute("b", pql)), pql


def test_concurrent_wave_equivalence_mixed_queries():
    """Distinct queries fired concurrently share waves and still each
    return exactly what a solo executor returns."""
    _h, e, sched, stats = make_rig()
    want = {pql: _norm(e.execute("b", pql)) for pql in READ_QUERIES}
    got: dict = {}
    errors: list = []
    barrier = threading.Barrier(len(READ_QUERIES))

    def run(pql):
        barrier.wait()
        try:
            got[pql] = _norm(sched.execute("b", pql))
        except Exception as exc:  # noqa: BLE001 — surfaced in the main thread
            errors.append((pql, exc))

    threads = [
        threading.Thread(target=run, args=(p,), daemon=True)
        for p in READ_QUERIES
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors, errors
    assert got == want
    snap = sched.snapshot()
    # every query accounted for: waved, deduped, or direct (Rows(f) is
    # metadata-only → host-routed → bypasses the window by design)
    assert (
        snap["batchedQueries"] + snap["dedupedQueries"] + snap["directQueries"]
        >= len(READ_QUERIES)
    )
    # some coalescing must have happened across 22 concurrent queries
    assert snap["waves"] < len(READ_QUERIES)
    dist = stats.distribution("queries_per_wave")
    assert dist is not None and dist.count == snap["waves"]


def test_error_isolation_one_bad_query_in_wave():
    _h, _e, sched, _stats = make_rig()
    out = sched.execute_many(
        [
            ("b", "Count(Row(f=1))", None, None),
            ("b", "Count(Row(nope=1))", None, None),  # unknown field
            ("b", "TopN(f, n=2)", None, None),
        ]
    )
    assert isinstance(out[0], list) and isinstance(out[0][0], int)
    assert isinstance(out[1], Exception) and "nope" in str(out[1])
    assert isinstance(out[2], list) and out[2][0]


def test_error_isolation_concurrent_threads():
    _h, e, sched, _stats = make_rig()
    want = _norm(e.execute("b", "Count(Row(f=1))"))
    results: dict = {}
    barrier = threading.Barrier(3)

    def good(k):
        barrier.wait()
        results[k] = _norm(sched.execute("b", "Count(Row(f=1))"))

    def bad():
        barrier.wait()
        try:
            sched.execute("b", "Count(Row(missing=1))")
            results["bad"] = "no error"
        except Exception as exc:  # noqa: BLE001 — the assertion target
            results["bad"] = f"error:{exc}"

    ts = [
        threading.Thread(target=good, args=("g1",), daemon=True),
        threading.Thread(target=good, args=("g2",), daemon=True),
        threading.Thread(target=bad, daemon=True),
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert results["g1"] == want and results["g2"] == want
    assert results["bad"].startswith("error:") and "missing" in results["bad"]


class FakeClock:
    def __init__(self, step=0.001):
        self.now = 0.0
        self.step = step

    def __call__(self):
        self.now += self.step
        return self.now


def test_window_timeout_flush_fake_clock():
    """mode=always holds every wave open for the full window; with a
    fake clock driving the deadline and arrivals never landing, the
    wave must flush with reason=timeout."""
    _h, _e, sched, stats = make_rig(
        mode="always", window_us=5000.0, clock=FakeClock()
    )
    waits: list[float] = []
    sched._wait_arrival = waits.append  # no-op waiter, records timeouts
    res = sched.execute("b", "Count(Row(f=1))")
    assert isinstance(res[0], int)
    assert waits and all(w > 0 for w in waits)
    counters = stats.expvar()["counters"]
    assert counters.get("wave_flush_reason{reason=timeout}") == 1


def test_adaptive_solo_traffic_skips_window():
    """At occupancy ~1 the adaptive window must be zero — the c1 sync
    latency guard: flush reason is solo, and the injected waiter is
    never consulted."""
    _h, _e, sched, stats = make_rig(mode="adaptive")
    waits: list[float] = []
    sched._wait_arrival = waits.append
    for _ in range(3):
        sched.execute("b", "Count(Row(f=1))")
    assert waits == []
    counters = stats.expvar()["counters"]
    assert counters.get("wave_flush_reason{reason=solo}") == 3


def test_no_starvation_tiny_waves():
    """max_queries=2 forces many waves; every query must complete and
    return its own correct result (FIFO drain: nothing starves)."""
    _h, e, sched, _stats = make_rig(max_queries=2)
    queries = [f"Count(Row(f={i % 5}))" for i in range(24)]
    want = [_norm(e.execute("b", q)) for q in queries]
    got: list = [None] * len(queries)
    barrier = threading.Barrier(len(queries))

    def run(i):
        barrier.wait()
        got[i] = _norm(sched.execute("b", queries[i]))

    ts = [
        threading.Thread(target=run, args=(i,), daemon=True)
        for i in range(len(queries))
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert got == want
    assert sched.snapshot()["waves"] >= 2


def test_single_flight_dedup_shares_one_execution():
    _h, e, sched, stats = make_rig()
    calls = []
    orig = e.dispatch
    e.dispatch = lambda *a, **k: calls.append(1) or orig(*a, **k)
    out = sched.execute_many(
        [("b", "TopN(f, n=3)", None, None)] * 4
    )
    assert len(calls) == 1
    assert all(o == out[0] for o in out)
    assert sched.snapshot()["dedupedQueries"] == 3
    counters = stats.expvar()["counters"]
    assert counters.get("queries_deduped") == 3


# Every kind of write the index takes: each lands it and returns how far
# it moved Count(Row(f=1)).  Each bumps its view and, through it, the
# index's stamp BEFORE it returns, which is what the dedup key reads.
FREE_COL = int(2 * SHARD_WIDTH - 1)


def _w_set_bit(h, e):
    h.index("b").field("f").set_bit(1, FREE_COL)
    return 1


def _w_pql_set(h, e):
    e.execute("b", f"Set({FREE_COL}, f=1)")
    return 1


def _w_pql_clear(h, e):
    col = e.execute("b", "Row(f=1)")[0].columns()[0]
    e.execute("b", f"Clear({col}, f=1)")
    return -1


def _w_bulk_import(h, e):
    h.index("b").field("f").import_bulk(
        np.array([1, 1], dtype=np.uint64),
        np.array([FREE_COL, FREE_COL - 1], dtype=np.uint64),
    )
    return 2


def _w_new_fragment(h, e):
    h.index("b").field("f").set_bit(1, 2 * SHARD_WIDTH + 5)
    return 1


def _w_remove_fragment(h, e):
    gone = e.execute("b", "Count(Row(f=1))", shards=[1])[0]
    assert h.index("b").field("f").view("standard").remove_fragment(1)
    return -gone


WRITES = {
    fn.__name__[3:]: fn
    for fn in (_w_set_bit, _w_pql_set, _w_pql_clear, _w_bulk_import,
               _w_new_fragment, _w_remove_fragment)
}


@pytest.mark.parametrize("write", sorted(WRITES))
def test_dedup_stack_token_moves_on_mutation(write):
    h, e, sched, _stats = make_rig()
    idx = h.index("b")
    before = stack_token(idx)
    assert stack_token(idx) == before  # reads leave it alone
    e.execute("b", "Count(Row(f=1))")
    assert stack_token(idx) == before
    WRITES[write](h, e)
    assert stack_token(idx) > before


@pytest.mark.parametrize("write", sorted(WRITES))
def test_dedup_not_joined_across_mutation(write):
    """A query submitted AFTER a write's acknowledgement must not join
    an identical pre-write in-flight execution: the index's stamp in
    the dedup key forces a fresh execution that sees the write,
    whatever kind of write it was."""
    h, e, sched, _stats = make_rig()
    pql = "Count(Row(f=1))"
    base = e.execute("b", pql)[0]
    gate = threading.Event()
    entered = threading.Event()
    calls = []
    orig = e.dispatch

    def blocking_dispatch(*a, **k):
        calls.append(1)
        if len(calls) == 1:
            entered.set()
            assert gate.wait(30)
        return orig(*a, **k)

    res: dict = {}
    e.dispatch = blocking_dispatch
    t1 = threading.Thread(
        target=lambda: res.__setitem__("a", sched.execute("b", pql)[0]),
        daemon=True,
    )
    t1.start()
    assert entered.wait(30)  # prime is mid-dispatch, not sealed
    # land the write; the direct executor below must not be gated
    e.dispatch = orig
    moved = WRITES[write](h, e)
    e.dispatch = blocking_dispatch
    t2 = threading.Thread(
        target=lambda: res.__setitem__("b", sched.execute("b", pql)[0]),
        daemon=True,
    )
    t2.start()
    time.sleep(0.05)  # let t2 enqueue (token differs → no join)
    gate.set()
    t1.join(30)
    t2.join(30)
    assert len(calls) == 2, "post-write query must not share the execution"
    assert moved != 0 and res["b"] == base + moved
    assert res["a"] in (base, base + moved)  # racing write: either order legal


def test_host_routed_and_writes_bypass_waves():
    _h, _e, sched, _stats = make_rig(route_mode="host")
    assert sched.execute("b", "Count(Row(f=1))")[0] >= 0
    snap = sched.snapshot()
    assert snap["waves"] == 0 and snap["directQueries"] == 1
    # writes bypass even on a device-routed executor
    _h2, _e2, sched2, _stats2 = make_rig()
    assert sched2.execute("b", "Set(9, f=1)") == [True]
    assert sched2.snapshot()["waves"] == 0


def test_batch_mode_off_is_direct():
    _h, e, sched, _stats = make_rig(mode="off")
    assert _norm(sched.execute("b", "TopN(f, n=2)")) == _norm(
        e.execute("b", "TopN(f, n=2)")
    )
    snap = sched.snapshot()
    assert snap["waves"] == 0 and snap["directQueries"] == 1


def test_profile_carries_wave_section():
    _h, _e, sched, _stats = make_rig()
    with tracing.profile_query() as prof:
        sched.execute("b", "Count(Row(f=1))")
    j = prof.to_json()
    assert j["wave"]["queries"] == 1
    assert j["wave"]["flushReason"] in ("solo", "drain", "timeout", "full")
    assert any(c["call"] == "_readback" for c in j["calls"])
    assert any(c["call"] == "Count" for c in j["calls"])


def test_dedup_follower_profile_gets_wave_section():
    """A ?profile=true query answered by single-flight dedup still
    documents the shared wave: the follower's own profile carries the
    wave dict + the shared _readback line (docs/observability.md)."""
    _h, e, sched, _stats = make_rig()
    pql = "Count(Row(f=1))"
    gate = threading.Event()
    entered = threading.Event()
    calls = []
    orig = e.dispatch

    def blocking_dispatch(*a, **k):
        calls.append(1)
        if len(calls) == 1:
            entered.set()
            assert gate.wait(30)
        return orig(*a, **k)

    e.dispatch = blocking_dispatch
    profs: dict = {}

    def run(k, release=False):
        with tracing.profile_query() as prof:
            sched.execute("b", pql)
        profs[k] = prof.to_json()

    t1 = threading.Thread(target=run, args=("prime",), daemon=True)
    t1.start()
    assert entered.wait(30)  # prime mid-dispatch → follower will join
    t2 = threading.Thread(target=run, args=("follower",), daemon=True)
    t2.start()
    time.sleep(0.05)
    gate.set()
    t1.join(30)
    t2.join(30)
    assert len(calls) == 1, "identical query must have shared the execution"
    for k in ("prime", "follower"):
        assert profs[k]["wave"]["shared"] >= 2, (k, profs[k])
        assert any(c["call"] == "_readback" for c in profs[k]["calls"]), k


def test_wave_occupancy_feeds_router():
    _h, e, sched, _stats = make_rig()
    out = sched.execute_many([("b", "Count(Row(f=1))", None, None)] * 6)
    assert all(isinstance(o, list) for o in out)
    assert e.router.wave_occupancy.value > 1.0
    assert e.router.snapshot()["waveOccupancy"] > 1.0
    # amortized device overhead: higher occupancy → cheaper device cost
    solo_cost = (
        e.router.dispatch_s.value + e.router.readback_s.value
    ) + 0.0
    assert e.router.device_cost(0) < solo_cost


def test_invalid_batch_mode_rejected():
    with pytest.raises(ValueError):
        WaveScheduler(lambda: None, mode="sometimes")


def test_debug_vars_exposes_query_batching(tmp_path):
    import urllib.request

    from pilosa_tpu.server import Server
    from pilosa_tpu.utils.config import Config
    from tests.test_cluster import free_ports

    port = free_ports(1)[0]
    srv = Server(
        Config(bind=f"127.0.0.1:{port}", data_dir=str(tmp_path / "d"))
    )
    srv.open()
    try:
        srv.wait_mesh(60)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/vars"
        ) as r:
            out = json.loads(r.read())
        assert out["queryBatching"]["mode"] == "adaptive"
        assert "meanQueriesPerWave" in out["queryBatching"]
    finally:
        srv.close()


def test_internal_query_batch_route(tmp_path):
    """The multi-query /internal RPC: per-entry results, per-entry
    error isolation, per-entry trace propagation."""
    from pilosa_tpu.parallel.client import InternalClient, PeerError
    from tests.test_cluster import call, free_ports, make_cluster, shutdown

    servers, ports, _seeds = make_cluster(tmp_path, n=2)
    try:
        call(ports[0], "POST", "/index/qb", {})
        call(ports[0], "POST", "/index/qb/field/f", {})
        cols = list(range(0, 3 * SHARD_WIDTH, 97))
        call(
            ports[0],
            "POST",
            "/index/qb/field/f/import",
            {"rowIDs": [1] * len(cols), "columnIDs": cols},
        )
        client = InternalClient()
        # the batch RPC executes the TARGET node's local shards (same
        # contract as the single /internal/query RPC): expectation comes
        # from that RPC, not the cluster-wide client route
        expect = client.query_node(
            f"http://127.0.0.1:{ports[1]}", "qb", "Count(Row(f=1))", None
        )[0]
        trace_id = "ab" * 16
        outs = client.query_batch_node(
            f"http://127.0.0.1:{ports[1]}",
            [
                {
                    "index": "qb",
                    "query": "Count(Row(f=1))",
                    "shards": None,
                    "traceId": trace_id,
                    "parentSpanId": "cd" * 8,
                },
                {
                    "index": "qb",
                    "query": "Count(Row(ghost=1))",
                    "shards": None,
                    "traceId": None,
                    "parentSpanId": None,
                },
            ],
        )
        assert outs[0][0] == expect
        assert isinstance(outs[1], PeerError) and "ghost" in str(outs[1])
        # the entry's spans joined ITS propagated trace on the peer
        # (scheduler.query when the entry rode a wave, executor.* when
        # the cost router sent it direct/host — either way the trace id
        # from the RPC BODY must parent the remote work)
        spans = call(
            ports[1], "GET", f"/debug/traces?trace_id={trace_id}"
        )["spans"]
        assert spans and all(s["traceID"] == trace_id for s in spans)
        assert any(
            s["name"].startswith(("scheduler.", "executor.")) for s in spans
        )
    finally:
        shutdown(servers)


def test_cluster_concurrent_queries_coalesce_legs(tmp_path):
    """Concurrent client queries against a 2-node cluster stay correct
    with leg coalescing active (the batcher's group-commit path)."""
    from tests.test_cluster import call, free_ports, make_cluster, shutdown

    servers, ports, _seeds = make_cluster(tmp_path, n=2)
    try:
        call(ports[0], "POST", "/index/cc", {})
        call(ports[0], "POST", "/index/cc/field/f", {})
        cols = list(range(0, 6 * SHARD_WIDTH, 61))
        for lo in range(0, len(cols), 4000):
            call(
                ports[0],
                "POST",
                "/index/cc/field/f/import",
                {
                    "rowIDs": [1] * len(cols[lo : lo + 4000]),
                    "columnIDs": cols[lo : lo + 4000],
                },
            )
        expect = call(ports[0], "POST", "/index/cc/query",
                      b"Count(Row(f=1))")["results"][0]
        errors: list = []
        got: list = [None] * 12
        barrier = threading.Barrier(12)

        def run(i):
            barrier.wait()
            try:
                got[i] = call(
                    ports[i % 2], "POST", "/index/cc/query",
                    b"Count(Row(f=1))",
                )["results"][0]
            except Exception as exc:  # noqa: BLE001 — surfaced below
                errors.append(exc)

        ts = [
            threading.Thread(target=run, args=(i,), daemon=True)
            for i in range(12)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not errors, errors
        assert got == [expect] * 12
    finally:
        shutdown(servers)


# ------------------------------------------------- the waiting protocol
# (ISSUE 29) every submitting call sleeps on its own event; _finish
# wakes the call it completed, a releasing leader wakes the queue's
# head, an enqueue wakes at most the leader in its window.  The stub
# stands in for the Executor so the tests count wake-ups and plant
# failures at no JAX cost.
class _StubRouter:
    wave_occupancy = None

    class readback_s:  # noqa: N801 — mirrors the router's EWMA attribute
        value = 0.001

    def observe_wave(self, n):
        pass


class StubExecutor:
    """Exactly what WaveScheduler touches of an Executor.  A query
    answers with its own text; one that names the field ``boom`` fails
    at dispatch.  ``dispatch_s`` sleeps with the interpreter lock
    released, as a device launch does."""

    class _Index:
        stamp = IndexStamp()  # what stack_token reads

    def __init__(self, dispatch_s=0.0002):
        self.holder = self
        self.router = _StubRouter()
        self.dispatch_s = dispatch_s
        self.led_by: list[str] = []  # the thread of every dispatch

    def index(self, name):
        return self._Index

    def _route(self, idx, call, shards):
        return ("device", 1)

    def dispatch(self, index, calls, shards, routes=None):
        self.led_by.append(threading.current_thread().name)
        if self.dispatch_s:
            time.sleep(self.dispatch_s)
        text = [str(c) for c in calls]
        if "boom" in text[0]:
            raise ValueError(f"planted: {text[0]}")
        return text


def stub_rig(**sched_kw):
    e = StubExecutor()
    stats = StatsClient()
    sched = WaveScheduler(lambda: e, stats=stats, **sched_kw)
    return e, sched, stats


def run_threads(n, body, limit_s=60.0):
    """n threads, each with a time limit of its own: a thread still
    alive after it is a lost wake-up."""
    errors: list = []

    def guarded(k):
        try:
            body(k)
        except Exception as exc:  # noqa: BLE001 — surfaced below
            errors.append((k, exc))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # more threads than cores, switching often
    try:
        ts = [
            threading.Thread(target=guarded, args=(k,), daemon=True, name=f"c{k}")
            for k in range(n)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(limit_s)
        hung = [t.name for t in ts if t.is_alive()]
    finally:
        sys.setswitchinterval(old)
    assert not hung, f"calls that never returned: {hung}"
    assert not errors, errors


@pytest.mark.parametrize(
    "threads,per_thread,max_queries", [(16, 40, 64), (8, 60, 3), (32, 15, 8)]
)
def test_wakeup_budget(threads, per_thread, max_queries):
    """Nothing wakes a thread that has nothing to do: no wake-up finds
    neither its call done nor the leadership (``again`` 0), and there
    are at most one a query (``done``) and one a wave (``lead``)."""
    _e, sched, stats = stub_rig(max_queries=max_queries)

    def body(k):
        for j in range(per_thread):
            pql = f"Count(Row(f={k * 1000 + j}))"  # all distinct: no dedup
            assert sched.execute("b", pql) == [pql]

    run_threads(threads, body)
    snap = sched.snapshot()
    wakeups = snap["wakeups"]
    queries = threads * per_thread
    assert snap["batchedQueries"] == queries
    assert wakeups["again"] == 0, wakeups
    assert wakeups["done"] <= queries
    assert wakeups["lead"] <= snap["waves"]
    assert wakeups["done"] + wakeups["lead"] <= queries + snap["waves"]
    # the leader of a wave completes its own query without sleeping
    assert wakeups["done"] >= queries - snap["waves"]
    counters = stats.expvar()["counters"]
    for why, n in wakeups.items():
        assert counters.get(f"scheduler_wakeups_total{{why={why}}}") == n


@pytest.mark.parametrize(
    "case", ["plain", "dedup", "one_query_raises", "wave_raises", "execute_many"]
)
def test_no_lost_wakeup(case):
    """32 threads: every call returns its own answer or raises its own
    error, inside its time limit, whatever fails around it."""
    _e, sched, _stats = stub_rig(
        max_queries=3 if case == "execute_many" else 8
    )
    per_thread = 12
    if case == "wave_raises":
        real = sched._execute_wave
        waves = []

        def every_third(executor, batch, reason):
            waves.append(1)
            if len(waves) % 3 == 0:
                raise RuntimeError("planted wave failure")
            return real(executor, batch, reason)

        sched._execute_wave = every_third
    outcomes = {"ok": 0, "raised": 0}
    tally = threading.Lock()

    def note(got, pql):
        if isinstance(got, Exception):
            assert "planted" in str(got), got
            # a planted query fails alone; a planted wave fails its batch
            assert "boom" in pql or case == "wave_raises", (pql, got)
            key = "raised"
        else:
            assert got == [pql], (got, pql)
            key = "ok"
        with tally:
            outcomes[key] += 1

    def body(k):
        for j in range(per_thread):
            if case == "dedup":
                pqls = [f"Count(Row(f={j % 2}))"]  # 32 threads, 2 texts
            elif case == "one_query_raises" and (k + j) % 5 == 0:
                pqls = [f"Count(Row(boom={k * 100 + j}))"]
            elif case == "execute_many":
                # five items on one thread, over two waves of three,
                # two of them identical (a follower on its own waiter)
                pqls = [f"Count(Row(f={k * 1000 + j * 10 + i % 4}))" for i in range(5)]
                pqls[3] = f"Count(Row(boom={k * 1000 + j}))"
            else:
                pqls = [f"Count(Row(f={k * 100 + j}))"]
            if case == "execute_many":
                out = sched.execute_many([("b", p, None, None) for p in pqls])
            else:
                try:
                    out = [sched.execute("b", pqls[0])]
                except Exception as exc:  # noqa: BLE001 — the call's answer
                    out = [exc]
            assert len(out) == len(pqls)
            for got, pql in zip(out, pqls):
                note(got, pql)

    run_threads(32, body)
    items = 5 if case == "execute_many" else 1
    assert sum(outcomes.values()) == 32 * per_thread * items
    assert outcomes["ok"] > 0
    if case in ("one_query_raises", "wave_raises", "execute_many"):
        assert outcomes["raised"] > 0
    snap = sched.snapshot()
    assert snap["wakeups"]["again"] == 0, snap
    if case == "dedup":
        assert snap["dedupedQueries"] > 0
    # nothing left behind: no queue, no in-flight prime, no leader
    assert not sched._queue and not sched._inflight
    assert not sched._leader_active and sched._heir is None


@pytest.mark.parametrize(
    "max_queries,want",
    [
        (1, ["A", "B", "C", "D"]),  # each heir leads its own wave
        (2, ["A", "B", "B", "D"]),  # B takes C along; D is the next head
        (64, ["A", "B", "B", "B"]),
    ],
)
def test_handoff_goes_to_the_oldest_queued(max_queries, want):
    """A releases with B, C, D queued in that order: the leadership
    goes to B, the head, and a wave's dispatches run on its leader."""
    e, sched, _stats = stub_rig(max_queries=max_queries)
    e.dispatch_s = 0
    gate = threading.Event()
    entered = threading.Event()
    plain = e.dispatch

    def gated(index, calls, shards, routes=None):
        if threading.current_thread().name == "A" and not entered.is_set():
            entered.set()
            assert gate.wait(30)
        return plain(index, calls, shards, routes=routes)

    e.dispatch = gated
    got: dict = {}

    def call(name):
        got[name] = sched.execute("b", f"Count(Row(f={ord(name)}))")

    ts = {n: threading.Thread(target=call, args=(n,), daemon=True, name=n) for n in "ABCD"}
    ts["A"].start()
    assert entered.wait(30)  # A leads, mid-dispatch
    for depth, n in enumerate("BCD", start=1):
        ts[n].start()
        deadline = time.monotonic() + 30
        while len(sched._queue) < depth:  # queued before the next starts
            assert time.monotonic() < deadline
            time.sleep(0.001)
    gate.set()
    for t in ts.values():
        t.join(30)
        assert not t.is_alive()
    assert e.led_by == want
    assert got == {n: [f"Count(Row(f={ord(n)}))"] for n in "ABCD"}
    wakeups = sched.snapshot()["wakeups"]
    # one hand-over a wave after the first; a led query needs no "done"
    waves = len(set(want))
    assert wakeups == {"lead": waves - 1, "done": 4 - waves, "again": 0}


@pytest.mark.parametrize(
    "entry,items", [("execute", 1), ("execute_many", 1), ("execute_many", 3)]
)
def test_solo_client_never_sleeps(entry, items, monkeypatch):
    """One client: its call finds no leader, leads at once and returns,
    with no wait on its event and no wake-up counted (and, one query a
    call, no window: the c1 latency guard)."""
    from pilosa_tpu.executor import scheduler as sched_mod

    class NoSleep(threading.Event):
        def wait(self, timeout=None):
            raise AssertionError("a solo client slept on its event")

    class Waiter(sched_mod._Waiter):
        __slots__ = ()

        def __init__(self):
            super().__init__()
            self.event = NoSleep()

    monkeypatch.setattr(sched_mod, "_Waiter", Waiter)
    _e, sched, _stats = stub_rig()
    windows: list = []
    sched._wait_arrival = windows.append
    for j in range(5):
        pqls = [f"Count(Row(f={j * 10 + i}))" for i in range(3)]
        if entry == "execute":
            assert sched.execute("b", pqls[0]) == [pqls[0]]
        else:
            out = sched.execute_many(
                [("b", p, None, None) for p in pqls[:items]]
            )
            assert out == [[p] for p in pqls[:items]]
    assert items > 1 or windows == []
    snap = sched.snapshot()
    assert snap["wakeups"] == {"done": 0, "lead": 0, "again": 0}
    assert snap["waves"] == 5


# ------------------------------------------- a wave's anatomy (ISSUE 36)
# the leader's settle as a span, the hand-over of the leadership as a
# timer across two threads, and one observation a phase a wave.
def _phase(stats, phase):
    return stats.histogram("scheduler_wave_phase_seconds", {"phase": phase})


def _queued_behind_a_gated_leader(sched, e, names):
    """Thread A leads and stops in its dispatch; the calls of ``names``
    queue behind it in order; then A goes on.  The threads, joined."""
    gate, entered = threading.Event(), threading.Event()
    plain = e.dispatch

    def gated(index, calls, shards, routes=None):
        if threading.current_thread().name == "A" and not entered.is_set():
            entered.set()
            assert gate.wait(30)
        return plain(index, calls, shards, routes=routes)

    e.dispatch = gated
    ts = [
        threading.Thread(
            target=sched.execute, args=("b", f"Count(Row(f={ord(n)}))"),
            daemon=True, name=n,
        )
        for n in "A" + names
    ]
    ts[0].start()
    assert entered.wait(30)
    for depth, t in enumerate(ts[1:], start=1):
        t.start()
        deadline = time.monotonic() + 30
        while len(sched._queue) < depth:
            assert time.monotonic() < deadline
            time.sleep(0.001)
    gate.set()
    for t in ts:
        t.join(30)
        assert not t.is_alive()


@pytest.mark.parametrize("max_queries,handovers", [(1, 3), (2, 2), (64, 1)])
def test_handover_timer_observes_once_a_passed_leadership(max_queries, handovers):
    """A releases with B, C, D queued: every wave after the first began
    with a hand-over to another call, timed from the releasing leader's
    set() to the heir running again, on the scheduler's clock."""
    clock = FakeClock()
    e, sched, stats = stub_rig(max_queries=max_queries, clock=clock)
    e.dispatch_s = 0
    _queued_behind_a_gated_leader(sched, e, "BCD")
    snap = sched.snapshot()
    assert snap["waves"] == handovers + 1
    hist = _phase(stats, "handover")
    count, total = hist.totals()
    assert count == handovers == snap["wakeups"]["lead"]
    # the fake clock steps once a reading: stamp and observation are two
    # readings apart at least
    assert total >= handovers * clock.step


@pytest.mark.parametrize(
    "case", ["solo", "leader_keeps_the_lead"], ids=lambda c: c
)
def test_no_handover_is_observed_without_one(case):
    """A release onto an empty queue, and a leader whose own call heads
    the queue again (an ``execute_many`` larger than a wave), hand
    nothing over."""
    e, sched, stats = stub_rig(max_queries=2, clock=FakeClock())
    if case == "solo":
        for j in range(3):
            sched.execute("b", f"Count(Row(f={j}))")
        waves = 3
    else:
        out = sched.execute_many(
            [("b", f"Count(Row(f={j}))", None, None) for j in range(5)]
        )
        assert out == [[f"Count(Row(f={j}))"] for j in range(5)]
        assert set(e.led_by) == {threading.current_thread().name}
        waves = 3
    assert sched.snapshot()["waves"] == waves
    assert _phase(stats, "handover") is None
    assert stats.histogram("scheduler_wave_seconds").totals()[0] == waves


def test_settle_span_opens_once_a_wave_and_covers_finalize_and_wakeups(monkeypatch):
    from pilosa_tpu.executor import scheduler as sched_mod
    from pilosa_tpu.utils.tracing import GLOBAL_TRACER

    with GLOBAL_TRACER._lock:
        GLOBAL_TRACER._spans.clear()
    e, sched, stats = stub_rig(max_queries=2)
    inside: list = []
    plain_finalize, plain_finish = sched_mod.finalize, sched._finish

    def finalize(raw):
        inside.append(("finalize", GLOBAL_TRACER.current_name()))
        return plain_finalize(raw)

    def finish(item, **kw):
        inside.append(("finish", GLOBAL_TRACER.current_name()))
        return plain_finish(item, **kw)

    monkeypatch.setattr(sched_mod, "finalize", finalize)
    sched._finish = finish
    out = sched.execute_many(
        [("b", f"Count(Row(f={j}))", None, None) for j in range(5)]
    )
    assert out == [[f"Count(Row(f={j}))"] for j in range(5)]
    # five queries in waves of 2, 2 and 1: each finished and completed
    # inside the wave's one settle span
    assert inside.count(("finalize", "scheduler.settle")) == 5
    assert inside.count(("finish", "scheduler.settle")) == 5
    assert len(inside) == 10
    spans = GLOBAL_TRACER.recent(4096)
    waves = [s for s in spans if s["name"] == "scheduler.wave"]
    settles = [s for s in spans if s["name"] == "scheduler.settle"]
    assert len(waves) == len(settles) == 3
    assert [s["tags"]["queries"] for s in settles] == [2, 2, 1]
    # the settle is the wave's child, tagged with its id, and ends inside it
    for wave, settle in zip(waves, settles):
        assert settle["parentSpanID"] == settle["tags"]["wave"] == wave["spanID"]
        assert settle["ts"] >= wave["ts"]
        assert (
            settle["ts"] + settle["durationSeconds"]
            <= wave["ts"] + wave["durationSeconds"] + 1e-6
        )


def test_a_waves_phases_are_observed_once_each_and_fit_inside_it():
    _h, _e, sched, stats = make_rig(mode="always", window_us=200.0)
    for q in ("Count(Row(f=1))", "TopN(f, n=3)", "Sum(field=v)"):
        sched.execute("b", q)
    waves = sched.snapshot()["waves"]
    assert waves == 3
    wave_n, wave_s = stats.histogram("scheduler_wave_seconds").totals()
    assert wave_n == waves
    inside = 0.0
    for phase in ("dispatch", "readback", "settle"):
        n, total = _phase(stats, phase).totals()
        assert n == waves, phase
        inside += total
    # the phases inside the wave are its spans' children: no more than it
    assert 0 < inside <= wave_s
    # the window is held before the wave's span opens; one a wave here
    assert _phase(stats, "window").totals()[0] == waves
    assert _phase(stats, "handover") is None  # one client: nothing passed
    # and a full wave has no window to observe
    _e2, sched2, stats2 = stub_rig(max_queries=1)
    sched2.execute("b", "Count(Row(f=1))")
    assert _phase(stats2, "window") is None
    assert _phase(stats2, "readback") is None  # the stub's results are not pending
    assert _phase(stats2, "dispatch").totals()[0] == 1
