"""Batched GroupBy (VERDICT r1 item 6): a whole nesting level evaluates
in O(1) device dispatches, not one per candidate row."""

import numpy as np

import pilosa_tpu.executor.executor as ex_mod
from pilosa_tpu.core import FieldOptions, Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.shardwidth import SHARD_WIDTH


def _setup():
    rng = np.random.default_rng(8)
    h = Holder(None)
    idx = h.create_index("g")
    a = idx.create_field("a")
    b = idx.create_field("b")
    v = idx.create_field("v", FieldOptions(field_type="int", min=-100, max=100))
    n = 4000
    cols = rng.choice(3 * SHARD_WIDTH, size=n, replace=False).astype(np.uint64)
    arows = rng.integers(0, 30, size=n).astype(np.uint64)
    brows = rng.integers(0, 40, size=n).astype(np.uint64)
    vals = rng.integers(-50, 50, size=n)
    a.import_bulk(arows, cols)
    b.import_bulk(brows, cols)
    v.import_values(cols, vals)
    idx.mark_columns_exist(cols)
    return h, cols, arows, brows, vals


def test_groupby_level_dispatch_count(monkeypatch):
    h, cols, arows, brows, vals = _setup()
    e = Executor(h)
    calls = {"counts": 0, "masks": 0, "chains": 0}
    orig_counts, orig_masks, orig_chains = ex_mod._gb_counts, ex_mod._gb_masks, ex_mod._gb_chains
    monkeypatch.setattr(
        ex_mod,
        "_gb_counts",
        lambda *a: (calls.__setitem__("counts", calls["counts"] + 1), orig_counts(*a))[1],
    )
    monkeypatch.setattr(
        ex_mod,
        "_gb_masks",
        lambda *a: (calls.__setitem__("masks", calls["masks"] + 1), orig_masks(*a))[1],
    )
    monkeypatch.setattr(
        ex_mod,
        "_gb_chains",
        lambda *a: (calls.__setitem__("chains", calls["chains"] + 1), orig_chains(*a))[1],
    )
    res = e.execute("g", "GroupBy(Rows(a), Rows(b))")[0]
    # without an aggregate the whole GroupBy is ONE chain count: every
    # (a-row, b-row) pair counted with no mask made, and the readback
    # defers to the execute() wave; 30×40 candidate pairs would have been
    # ≥1200 dispatches on the r1 path, 2 counts + 1 masks + per-level sync
    # readbacks on the r3 level-synchronous path, 1 masks + 1 counts on
    # the all-pairs walk with masks
    assert calls == {"counts": 0, "masks": 0, "chains": 1}
    assert len(res) > 0


def test_groupby_chunked_under_tight_budget(monkeypatch):
    """A tiny mask budget forces chunked depth-first expansion; results
    must stay identical."""
    h, cols, arows, brows, vals = _setup()
    full = Executor(h).execute("g", "GroupBy(Rows(a), Rows(b))")[0]
    monkeypatch.setattr(Executor, "GROUPBY_MASK_BUDGET", 1)  # 1 group/chunk
    chunked = Executor(h).execute("g", "GroupBy(Rows(a), Rows(b))")[0]
    assert chunked == full


def test_groupby_counts_correct():
    h, cols, arows, brows, vals = _setup()
    e = Executor(h)
    res = e.execute("g", "GroupBy(Rows(a), Rows(b))")[0]
    got = {
        (g["group"][0]["rowID"], g["group"][1]["rowID"]): g["count"] for g in res
    }
    expect = {}
    for ar, br in zip(arows.tolist(), brows.tolist()):
        expect[(ar, br)] = expect.get((ar, br), 0) + 1
    assert got == expect
    # lexicographic order like the reference
    keys = [(g["group"][0]["rowID"], g["group"][1]["rowID"]) for g in res]
    assert keys == sorted(keys)


def test_groupby_aggregate_and_limit():
    h, cols, arows, brows, vals = _setup()
    e = Executor(h)
    res = e.execute("g", 'GroupBy(Rows(a), limit=5, aggregate=Sum(field=v))')[0]
    assert len(res) == 5
    by_row = {}
    for ar, val in zip(arows.tolist(), vals.tolist()):
        by_row.setdefault(ar, []).append(val)
    for entry in res:
        rid = entry["group"][0]["rowID"]
        assert entry["count"] == len(by_row[rid])
        assert entry["sum"] == sum(by_row[rid])


def test_groupby_filter():
    h, cols, arows, brows, vals = _setup()
    e = Executor(h)
    res = e.execute("g", "GroupBy(Rows(a), filter=Row(b=3))")[0]
    expect = {}
    for ar, br in zip(arows.tolist(), brows.tolist()):
        if br == 3:
            expect[ar] = expect.get(ar, 0) + 1
    got = {g["group"][0]["rowID"]: g["count"] for g in res}
    assert got == expect


def test_groupby_fused_matches_level_synchronous():
    """The fused all-pairs path (one deferred readback) and the
    level-synchronous fallback must produce byte-identical results,
    including nested order and limit semantics."""
    h, cols, arows, brows, vals = _setup()
    e = Executor(h)
    fused = e.execute("g", "GroupBy(Rows(a), Rows(b), limit=7)")[0]
    e2 = Executor(h)
    e2.GROUPBY_MASK_BUDGET = 0  # any fold exceeds -> level-synchronous
    sync = e2.execute("g", "GroupBy(Rows(a), Rows(b), limit=7)")[0]
    assert fused == sync and len(fused) == 7


def test_mixed_aggregate_wave_single_transfer(monkeypatch):
    """A request mixing Count/TopN/Sum/Min/Max/GroupBy resolves every
    deferred aggregate in ONE readback wave (the _Pending wave): all
    its arrays cross in one ``jax.device_get`` inside fetch_wave, whose
    copies are in flight together, and no array crosses anywhere else
    (a stray np.asarray on a device array is a round trip of its own),
    so the wave count IS the latency model."""
    import jax

    import pilosa_tpu.executor.executor as ex_mod

    h, cols, arows, brows, vals = _setup()
    e = Executor(h)
    q = ("Count(Row(a=1)) TopN(a, n=3) Sum(field=v) Min(field=v) "
         "Max(field=v) GroupBy(Rows(a), Rows(b))")
    expected = e.execute("g", q)

    stray, waves = {"n": 0}, []
    orig, orig_get = ex_mod.np.asarray, jax.device_get

    def counting(x, *a, **k):
        if hasattr(x, "devices"):  # jax array -> host transfer
            stray["n"] += 1
        return orig(x, *a, **k)

    def counting_get(x):
        waves.append(len(x))
        return orig_get(x)

    monkeypatch.setattr(ex_mod.np, "asarray", counting)
    monkeypatch.setattr(jax, "device_get", counting_get)
    got = e.execute("g", q)
    monkeypatch.undo()
    assert got == expected
    assert len(waves) == 1, f"expected 1 readback wave, saw {len(waves)}"
    assert waves[0] >= 1 and stray["n"] == 0, (waves, stray)
