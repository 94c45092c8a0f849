"""Hybrid high-cardinality fields (VERDICT r1 item 5): dense stacks are
budget-capped with an explicit error; Row/Count ride an LRU hot-row slot
stack and TopN streams row chunks — no OOM, exact answers."""

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.compile import StackCache, StackOverBudget
from pilosa_tpu.executor.executor import ExecutionError
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD


@pytest.fixture
def tight_budget(monkeypatch):
    # enough for ~64 resident rows per shard-pair — far below the field
    # sizes used here, so the hot path must engage. This suite pins the
    # LEGACY dense slot path ("slots"); the tiered compressed layer that
    # now serves over-budget fields by default has its own suite
    # (tests/test_residency.py).
    monkeypatch.setattr(
        StackCache, "STACK_BYTES_BUDGET", 64 * 2 * WORDS_PER_SHARD * 4
    )
    monkeypatch.setattr(StackCache, "RESIDENCY_MODE", "slots")


def _high_card_holder(n_rows=100_000, n_shards=2, seed=0):
    rng = np.random.default_rng(seed)
    h = Holder(None)
    idx = h.create_index("hc")
    f = idx.create_field("f")
    # one bit per row (distinct rows), plus a popular band of rows with
    # many columns so TopN has real signal
    rows = np.arange(n_rows, dtype=np.uint64)
    cols = rng.integers(0, n_shards * SHARD_WIDTH, size=n_rows).astype(np.uint64)
    f.import_bulk(rows, cols)
    extra_cols = rng.choice(
        n_shards * SHARD_WIDTH, size=3000, replace=False
    ).astype(np.uint64)
    extra_rows = rng.integers(0, 10, size=3000).astype(np.uint64)
    f.import_bulk(extra_rows, extra_cols)
    idx.mark_columns_exist(cols)
    idx.mark_columns_exist(extra_cols)
    return h, f, rows, cols, extra_rows, extra_cols


def test_over_budget_raises_explicitly(tight_budget):
    h, f, *_ = _high_card_holder(n_rows=5000, n_shards=2)
    e = Executor(h, route_mode="device")
    with pytest.raises(StackOverBudget) as err:
        e.compiler.stacks.matrix(
            h.index("hc"), f, "standard", [0, 1]
        )
    assert "budget" in str(err.value)


def test_row_count_via_hot_path(tight_budget):
    h, f, rows, cols, extra_rows, extra_cols = _high_card_holder(
        n_rows=5000, n_shards=2
    )
    e = Executor(h, route_mode="device")
    stacks = e.compiler.stacks
    # Count on individual high rows — exact, via hot slots
    for rid in (4999, 1234, 7):
        expect = int((rows == rid).sum()) + int((extra_rows == rid).sum())
        got = e.execute("hc", f"Count(Row(f={rid}))")[0]
        assert got == expect, rid
    assert stacks.hot_row_uploads >= 3
    # LRU reuse: repeating a row must not re-upload
    before = stacks.hot_row_uploads
    e.execute("hc", "Count(Row(f=1234))")
    assert stacks.hot_row_uploads == before


def test_hot_rows_track_writes(tight_budget):
    h, f, *_ = _high_card_holder(n_rows=5000, n_shards=2)
    e = Executor(h, route_mode="device")
    base = e.execute("hc", "Count(Row(f=42))")[0]
    assert e.execute("hc", "Set(99, f=42)")[0] in (True, False)
    assert e.execute("hc", "Count(Row(f=42))")[0] >= base
    # composite call across hot rows
    got = e.execute("hc", "Count(Union(Row(f=42), Row(f=43)))")[0]
    fresh = Executor(h, route_mode="device").execute("hc", "Count(Union(Row(f=42), Row(f=43)))")[0]
    assert got == fresh


def test_topn_chunked_exact_100k_rows(tight_budget):
    h, f, rows, cols, extra_rows, extra_cols = _high_card_holder(n_rows=100_000)
    e = Executor(h, route_mode="device")
    res = e.execute("hc", "TopN(f, n=5)")[0]
    counts: dict[int, int] = {}
    for r in np.concatenate([rows, extra_rows]).tolist():
        counts[r] = counts.get(r, 0) + 1
    expect = sorted(counts.items(), key=lambda rc: (-rc[1], rc[0]))[:5]
    assert [(p["id"], p["count"]) for p in res] == expect


def test_union_wider_than_hot_capacity_errors(tight_budget, monkeypatch):
    """A single query needing more resident rows than the hot capacity
    must fail EXPLICITLY (atomic batch), never silently misread an
    evicted slot."""
    monkeypatch.setattr(StackCache, "MAX_DELTA_ROWS", 0)  # isolate hot path
    h, f, *_ = _high_card_holder(n_rows=5000, n_shards=2)
    e = Executor(h, route_mode="device")
    cap = e.compiler.stacks.hot_capacity(2)
    q = "Count(Union(" + ", ".join(f"Row(f={r})" for r in range(cap + 1)) + "))"
    with pytest.raises(ExecutionError) as err:
        e.execute("hc", q)
    assert "budget" in str(err.value)
    # at capacity it works and is exact
    q_ok = "Count(Union(" + ", ".join(f"Row(f={r})" for r in range(20)) + "))"
    got = e.execute("hc", q_ok)[0]
    fresh = Executor(h, route_mode="device").execute("hc", q_ok)[0]
    assert got == fresh


def test_hot_entries_lru_bounded(tight_budget):
    h, f, *_ = _high_card_holder(n_rows=5000, n_shards=2)
    e = Executor(h, route_mode="device")
    stacks = e.compiler.stacks
    # distinct shard subsets create distinct hot entries; the LRU cap
    # bounds them (each entry is budget-sized on a real device)
    for s in range(2):
        e.execute("hc", "Count(Row(f=1))", shards=[s])
    e.execute("hc", "Count(Row(f=1))")
    assert len(stacks._hot) <= stacks.MAX_HOT_ENTRIES


def test_groupby_over_budget_streams_exact(tight_budget):
    """GroupBy on a field whose stack exceeds the device budget must
    stream row chunks (VERDICT r2 item 4) and stay EXACT — same answer a
    budget-free executor gives."""
    h, f, rows, cols, extra_rows, extra_cols = _high_card_holder(
        n_rows=5000, n_shards=2
    )
    e = Executor(h, route_mode="device")
    got = e.execute("hc", "GroupBy(Rows(f))")[0]
    counts: dict[int, int] = {}
    for r in np.concatenate([rows, extra_rows]).tolist():
        counts[r] = counts.get(r, 0) + 1
    assert len(got) == len(counts)
    for entry in got[:50] + got[-50:]:
        rid = entry["group"][0]["rowID"]
        assert entry["count"] == counts[rid], rid
    # output is row-ascending (chunking must not reorder)
    ids = [entry["group"][0]["rowID"] for entry in got]
    assert ids == sorted(ids)
    # limit semantics survive chunking
    limited = e.execute("hc", "GroupBy(Rows(f), limit=7)")[0]
    assert [g["group"][0]["rowID"] for g in limited] == ids[:7]


def test_groupby_over_budget_nested_with_filter(tight_budget):
    """Nested GroupBy where the OUTER level streams (over budget) and the
    inner level is tiny: counts must equal the intersection cardinality."""
    h = Holder(None)
    idx = h.create_index("hc")
    f = idx.create_field("big")
    g = idx.create_field("small")
    n = 3000
    rows = np.arange(n, dtype=np.uint64)
    cols = np.arange(n, dtype=np.uint64) * 3 % np.uint64(2 * SHARD_WIDTH)
    f.import_bulk(rows, cols)
    g.import_bulk((cols % 2).astype(np.uint64), cols)
    idx.mark_columns_exist(cols)
    e = Executor(h, route_mode="device")
    res = e.execute("hc", "GroupBy(Rows(big), Rows(small), limit=40)")[0]
    assert res, "no groups returned"
    for entry in res:
        big_r = entry["group"][0]["rowID"]
        small_r = entry["group"][1]["rowID"]
        expect = int(
            np.count_nonzero((rows == big_r) & (cols % 2 == small_r))
        )
        assert entry["count"] == expect, (big_r, small_r)


def test_stack_budget_resolution(monkeypatch):
    """Budget order: env override → 70% of the device's reported HBM
    limit (2 GiB on the CPU backend, which reports none — per platform
    in tests/test_bringup.py); resolution is cached once per process."""
    from pilosa_tpu.executor import compile as C

    monkeypatch.setattr(C, "_budget_cache", [])
    monkeypatch.setenv("PILOSA_TPU_STACK_BUDGET", "12345")
    assert C._stack_budget() == 12345
    monkeypatch.setattr(C, "_budget_cache", [])
    monkeypatch.delenv("PILOSA_TPU_STACK_BUDGET", raising=False)
    # without env, on the suite's CPU backend
    assert C._stack_budget() == 2 << 30
    # instances see the property; a monkeypatched class int shadows it
    monkeypatch.setattr(C.StackCache, "STACK_BYTES_BUDGET", 777)
    assert C.StackCache().STACK_BYTES_BUDGET == 777


def test_aggregate_budget_evicts_lru_stack(monkeypatch):
    """The budget caps TOTAL resident stack bytes, not just each stack:
    admitting a second near-budget stack must evict the first (LRU)
    instead of holding both on device."""
    from pilosa_tpu.executor import compile as C

    h = Holder(None)
    idx = h.create_index("agg")
    fa = idx.create_field("a")
    fb = idx.create_field("b")
    for f in (fa, fb):
        f.import_bulk(
            np.array([0, 1], dtype=np.uint64), np.array([1, 2], dtype=np.uint64)
        )
    one_stack = 8 * WORDS_PER_SHARD * 4  # [R_pad=8, S=1, W] uint32
    monkeypatch.setattr(C.StackCache, "STACK_BYTES_BUDGET", int(one_stack * 1.5))
    e = Executor(h, route_mode="device")
    stacks = e.compiler.stacks
    stacks.matrix(idx, fa, "standard", [0])
    assert stacks.resident_bytes == one_stack
    stacks.matrix(idx, fb, "standard", [0])  # must evict field a's stack
    assert stacks.resident_bytes == one_stack
    assert len(stacks._cache) == 1
    # field a rebuilds on demand — correctness is unaffected
    assert e.execute("agg", "Count(Row(a=0))", shards=[0])[0] == 1
    assert e.execute("agg", "Count(Row(b=1))", shards=[0])[0] == 1
