"""Device-side delta ingest: interleaved writes and queries must upload
O(dirty rows), not O(S·R·W) (VERDICT r1 item 4).

The StackCache exposes restack/delta counters; these tests pin the write
path to the incremental scatter and verify correctness against fresh
recomputation.
"""

import numpy as np

from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.shardwidth import SHARD_WIDTH


def _setup(n_shards=4, rows=6, seed=0):
    rng = np.random.default_rng(seed)
    h = Holder(None)
    idx = h.create_index("d")
    f = idx.create_field("f")
    n_bits = 2000
    cols = rng.choice(n_shards * SHARD_WIDTH, size=n_bits, replace=False).astype(
        np.uint64
    )
    rids = rng.integers(0, rows, size=n_bits).astype(np.uint64)
    f.import_bulk(rids, cols)
    idx.mark_columns_exist(cols)
    return h, idx, f, rids, cols


def test_interleaved_set_query_uses_delta_path():
    h, idx, f, rids, cols = _setup()
    e = Executor(h, route_mode="device")
    stacks = e.compiler.stacks

    base = e.execute("d", "Count(Row(f=1))")[0]
    restacks_after_first = stacks.full_restacks
    assert restacks_after_first >= 1

    # ten write→query cycles: every one must ride the delta path
    free = sorted(set(range(3 * SHARD_WIDTH)) - set(cols.tolist()))
    for i in range(10):
        col = free[i]
        assert e.execute("d", f"Set({col}, f=1)")[0] is True
        got = e.execute("d", "Count(Row(f=1))")[0]
        base += 1
        assert got == base
    assert stacks.full_restacks == restacks_after_first, (
        "point writes forced full restacks"
    )
    assert stacks.delta_updates >= 10
    # each cycle dirtied one row (plus the existence row's stack is
    # separate); uploads stay tiny
    assert stacks.delta_rows_uploaded <= 2 * 10


def test_delta_path_matches_fresh_executor():
    h, idx, f, rids, cols = _setup(seed=3)
    e = Executor(h, route_mode="device")
    e.execute("d", "Count(Row(f=0))")
    rng = np.random.default_rng(7)
    for _ in range(25):
        col = int(rng.integers(0, 4 * SHARD_WIDTH))
        row = int(rng.integers(0, 6))
        if rng.random() < 0.5:
            e.execute("d", f"Set({col}, f={row})")
        else:
            e.execute("d", f"Clear({col}, f={row})")
    # incremental state must equal a from-scratch evaluation
    fresh = Executor(h, route_mode="device")
    for row in range(6):
        q = f"Count(Row(f={row}))"
        assert e.execute("d", q) == fresh.execute("d", q)
    q = "Count(Union(Row(f=0), Row(f=1), Row(f=2)))"
    assert e.execute("d", q) == fresh.execute("d", q)


def test_bulk_import_falls_back_to_restack():
    h, idx, f, rids, cols = _setup(seed=5)
    e = Executor(h, route_mode="device")
    e.execute("d", "Count(Row(f=1))")
    before = e.compiler.stacks.full_restacks
    # dirty MORE distinct rows than the delta budget allows — the cache
    # must take the restack fallback, not a 1500-row scatter
    assert e.compiler.stacks.MAX_DELTA_ROWS < 1500
    rng = np.random.default_rng(11)
    new_cols = rng.choice(4 * SHARD_WIDTH, size=1500, replace=False).astype(np.uint64)
    new_rows = np.arange(1500, dtype=np.uint64) + 10
    f.import_bulk(new_rows, new_cols)
    got = e.execute("d", "Count(Row(f=1))")[0]
    expect = Executor(h, route_mode="device").execute("d", "Count(Row(f=1))")[0]
    assert got == expect
    assert e.compiler.stacks.full_restacks > before


def test_delta_keeps_namedsharding_on_mesh():
    """Point writes on a multi-device server must not demote the stack's
    SPMD layout (code-review r2 finding)."""
    from jax.sharding import NamedSharding

    from pilosa_tpu.parallel.mesh import MeshContext

    h, idx, f, rids, cols = _setup(n_shards=8, seed=13)
    ctx = MeshContext.auto()
    assert ctx is not None  # conftest gives 8 virtual devices
    # pinned like its neighbours: under "auto" the router serves these
    # tiny queries from the host engine and no device stack is touched
    e = Executor(h, mesh_ctx=ctx, route_mode="device")
    stacks = e.compiler.stacks
    base = e.execute("d", "Count(Row(f=1))")[0]
    restacks = stacks.full_restacks
    free = sorted(set(range(8 * SHARD_WIDTH)) - set(cols.tolist()))
    for i in range(5):
        e.execute("d", f"Set({free[i]}, f=1)")
        assert e.execute("d", "Count(Row(f=1))")[0] == base + i + 1
    assert stacks.full_restacks == restacks
    assert stacks.delta_updates >= 5
    for entry in stacks._cache.values():
        arr = entry[1]
        assert isinstance(arr.sharding, NamedSharding)
        assert not arr.sharding.is_fully_replicated


def test_row_growth_forces_restack_and_stays_correct():
    h, idx, f, rids, cols = _setup(rows=8, seed=9)
    e = Executor(h, route_mode="device")
    e.execute("d", "Count(Row(f=1))")
    # write to a row far beyond the padded height
    e.execute("d", f"Set(5, f=100)")
    assert e.execute("d", "Count(Row(f=100))")[0] == 1
    assert e.execute("d", "Count(Row(f=1))") == Executor(h, route_mode="device").execute(
        "d", "Count(Row(f=1))"
    )
