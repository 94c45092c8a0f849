"""Wire-speed bulk-ingest suite (docs/ingest.md) — the vectorized
container builders, the roaring WAL-adopt lane, batched key translation,
the loader's backoff protocol, and the bulk lane's crash recovery.

The acceptance core is bit-equivalence: the vectorized bulk lane must
produce EXACTLY the bits the per-bit ``Set()`` path produces, over every
container class (dense / sparse / run, plus BSI via import-value),
asserted by fragment checksum after compaction settles.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import loader, roaring
from pilosa_tpu.core import Holder
from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.core.translate import TranslateStore
from pilosa_tpu.parallel.faultinject import FSFaultInjector
from pilosa_tpu.roaring import build as rb
from pilosa_tpu.server.api import API
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import durable
from pilosa_tpu.utils.durable import SimulatedCrash

pytestmark = pytest.mark.ingest


@pytest.fixture
def fs_hook():
    """Install a seeded FS fault injector; ALWAYS uninstalled after the
    test — the hook is process-global."""
    def install(rules, seed=7):
        inj = FSFaultInjector(rules, seed=seed)
        durable.install_fs_hook(inj)
        return inj

    yield install
    durable.install_fs_hook(None)


def container_class_bits(rng):
    """(rows, cols) covering every container class in one batch:
    row 0 = RUN (contiguous spans), row 1 = SPARSE arrays (scattered),
    row 2 = DENSE bitmaps (random past the 4096 array cap)."""
    runs = np.arange(0, 20_000, dtype=np.uint64)  # contiguous → run
    sparse = rng.choice(SHARD_WIDTH, size=min(900, SHARD_WIDTH // 8),
                        replace=False).astype(np.uint64)
    # >4096 distinct per 2^16 container span → bitmap class
    dense_span = min(SHARD_WIDTH, 1 << 16)
    dense = rng.choice(dense_span, size=min(9000, dense_span * 3 // 4),
                       replace=False).astype(np.uint64)
    rows = np.concatenate([
        np.zeros(runs.size, np.uint64),
        np.ones(sparse.size, np.uint64),
        np.full(dense.size, 2, np.uint64),
    ])
    cols = np.concatenate([runs, sparse, dense])
    # spill a slice into shard 1 so the shard split is exercised too
    cols = np.concatenate([cols, cols[: cols.size // 3] + SHARD_WIDTH])
    rows = np.concatenate([rows, rows[: rows.size // 3]])
    return rows, cols


def frag_checksum(frag):
    return sorted((b, c.hex()) for b, c in frag.block_checksums())


def settle(holder):
    assert holder.compactor.wait_idle(10)


# ------------------------------------------------- builders / format
def test_shard_payloads_matches_brute_force(rng):
    rows = rng.integers(0, 40, 30_000).astype(np.uint64)
    cols = rng.integers(0, 3 * SHARD_WIDTH, 30_000).astype(np.uint64)
    want: dict[int, set] = {}
    for r, c in zip(rows.tolist(), cols.tolist()):
        want.setdefault(c // SHARD_WIDTH, set()).add(
            r * SHARD_WIDTH + c % SHARD_WIDTH
        )
    got = rb.shard_payloads(rows, cols)
    assert [s for s, _, _ in got] == sorted(want)
    for s, frame, n_bits in got:
        bm, _ = roaring.deserialize(frame)
        assert n_bits == len(want[s]) == bm.count()
        assert np.array_equal(
            bm.values(), np.array(sorted(want[s]), dtype=np.uint64)
        )


def test_shard_payloads_fallback_huge_row_ids():
    # row ids large enough that the combined (shard, position) key
    # would overflow 64 bits → sorted-split fallback (the positions
    # themselves still fit: row * SHARD_WIDTH stays under 2^63)
    big = (1 << 62) // SHARD_WIDTH
    rows = np.array([big, 1, big], dtype=np.uint64)
    cols = np.array([3, 3, 15 * SHARD_WIDTH + 4], dtype=np.uint64)
    got = rb.shard_payloads(rows, cols)
    assert [s for s, _, _ in got] == [0, 15]
    bm, _ = roaring.deserialize(got[0][1])
    assert bm.count() == 2 and bm.contains(big * SHARD_WIDTH + 3)


def test_split_by_shard_highest_shard_at_64bit_key_edge():
    """Regression: the dense-path boundary sentinel (max_shard+1) <<
    pos_bits wraps to 0 in uint64 when the combined key uses all 64
    bits — the highest shard's slice silently vanished."""
    sw = SHARD_WIDTH
    # rows sized so pos_bits + bit_length(max_shard) == 64 exactly
    max_shard = (1 << 16) - 1
    pos_bits = 64 - 16
    big_row = ((1 << pos_bits) - 1) // sw - 1
    rows = np.array([big_row, big_row], dtype=np.uint64)
    cols = np.array([5, max_shard * sw + 7], dtype=np.uint64)
    got = rb.split_by_shard(rows, cols, sw)
    assert [s for s, _ in got] == [0, max_shard]
    assert got[1][1].tolist() == [big_row * sw + 7]
    frames = rb.shard_payloads(rows, cols, sw)
    assert [s for s, _, _ in frames] == [0, max_shard]
    assert sum(b for _, _, b in frames) == 2


def test_union_op_roundtrip_and_torn_tail():
    bm = roaring.Bitmap()
    bm.add_many(np.arange(0, 70_000, 3, dtype=np.uint64))
    rec = roaring.append_union_op(roaring.serialize(bm))
    out = roaring.Bitmap()
    out.add_many(np.array([1, 5], dtype=np.uint64))
    res = roaring.replay_ops_checked(out, rec)
    assert res.n_ops == 1 and not res.corrupt
    assert out.count() == bm.count() + 2 - int(bm.contains(1))
    # torn anywhere inside the record: clean truncation, nothing applied
    for cut in (1, 10, len(rec) // 2, len(rec) - 1):
        fresh = roaring.Bitmap()
        r = roaring.replay_ops_checked(fresh, rec[:cut])
        assert r.n_ops == 0 and r.good_bytes == 0 and not r.corrupt
    # in-place corruption: loud, conservative truncation
    bad = bytearray(rec)
    bad[len(rec) // 2] ^= 0xFF
    r = roaring.replay_ops_checked(roaring.Bitmap(), bytes(bad))
    assert r.corrupt and r.corrupt_offset == 0


# ---------------------------------------------- bit-equivalence core
def test_bulk_lane_bit_equivalent_to_set_path(tmp_path, rng):
    """THE satellite acceptance: vectorized bulk lane vs per-bit Set()
    over run/sparse/dense container classes, fragment checksums compared
    after compaction settles."""
    rows, cols = container_class_bits(rng)

    bulk_holder = Holder(str(tmp_path / "bulk"), compaction_workers=1)
    bulk_holder.open()
    bulk_api = API(bulk_holder, max_writes=0)
    bulk_api.create_index("i", {})
    bulk_api.create_field("i", "f", {})
    for shard, frame, _bits in rb.shard_payloads(rows, cols):
        bulk_api.import_roaring("i", "f", shard, frame)

    bit_holder = Holder(str(tmp_path / "bits"), compaction_workers=1)
    bit_holder.open()
    bit_api = API(bit_holder, max_writes=0)
    bit_api.create_index("i", {})
    bit_api.create_field("i", "f", {})
    field = bit_holder.index("i").field("f")
    view = field.create_view_if_not_exists("standard")
    for r, c in zip(rows.tolist(), cols.tolist()):
        frag = view.create_fragment_if_not_exists(int(c // SHARD_WIDTH))
        frag.set_bit(int(r), int(c))  # the per-bit reference path
    bit_holder.index("i").mark_columns_exist(cols)

    # fold the union frames / op logs before comparing
    for holder in (bulk_holder, bit_holder):
        for idx in holder.indexes.values():
            for f in idx.fields.values():
                for v in f.views.values():
                    for frag in v.fragments.values():
                        frag.compact()
    settle(bulk_holder)
    settle(bit_holder)

    for fname in ("f", "_exists"):
        bulk_view = bulk_holder.index("i").field(fname).view("standard")
        bit_view = bit_holder.index("i").field(fname).view("standard")
        assert set(bulk_view.fragments) == set(bit_view.fragments), fname
        for shard in bulk_view.fragments:
            fa, fb = bulk_view.fragment(shard), bit_view.fragment(shard)
            assert frag_checksum(fa) == frag_checksum(fb), (fname, shard)
            assert np.array_equal(fa.bitmap.values(), fb.bitmap.values())
    # the run/sparse/dense classes were actually present in the frames
    frag0 = bulk_holder.index("i").field("f").view("standard").fragment(0)
    kinds = {c.type for c in frag0.bitmap._containers.values()}
    assert len(kinds) >= 2  # storage form post-compaction (runs appear
    # at serialize time; reopened snapshots materialize them)
    bulk_holder.close()
    bit_holder.close()


def test_bulk_lane_survives_reopen_equivalent(tmp_path, rng):
    """Adopted frames land durably: a reopen from disk (snapshot +
    union-op replay, NO compaction) equals the in-memory state."""
    rows, cols = container_class_bits(rng)
    h = Holder(str(tmp_path / "h"), compaction_workers=1)
    h.open()
    api = API(h, max_writes=0)
    api.create_index("i", {})
    api.create_field("i", "f", {})
    for shard, frame, _bits in rb.shard_payloads(rows, cols):
        api.import_roaring("i", "f", shard, frame)
    durable.ack_barrier()
    want = {
        shard: frag.bitmap.values()
        for shard, frag in h.index("i").field("f").view("standard").fragments.items()
    }
    h.close()
    h2 = Holder(str(tmp_path / "h"))
    h2.open()
    for shard, vals in want.items():
        frag = h2.index("i").field("f").view("standard").fragment(shard)
        assert np.array_equal(frag.bitmap.values(), vals)
    h2.close()


def test_bsi_import_value_bit_equivalent(tmp_path, rng):
    """BSI lane: one vectorized import_values batch vs per-value
    singles — identical BSI fragments after compaction settles."""
    n = 400
    cols = rng.choice(SHARD_WIDTH, size=n, replace=False).astype(np.uint64)
    values = rng.integers(-500, 500, n)

    ha = Holder(str(tmp_path / "a"), compaction_workers=1)
    ha.open()
    api_a = API(ha, max_writes=0)
    api_a.create_index("i", {})
    api_a.create_field("i", "v", {"type": "int"})
    api_a.import_values("i", "v", {"columnIDs": cols.tolist(),
                                   "values": values.tolist()})

    hb = Holder(str(tmp_path / "b"), compaction_workers=1)
    hb.open()
    api_b = API(hb, max_writes=0)
    api_b.create_index("i", {})
    api_b.create_field("i", "v", {"type": "int"})
    for c, v in zip(cols.tolist(), values.tolist()):
        api_b.import_values("i", "v", {"columnIDs": [c], "values": [v]})

    for h in (ha, hb):
        for idx in h.indexes.values():
            for f in idx.fields.values():
                for vw in f.views.values():
                    for frag in vw.fragments.values():
                        frag.compact()
        settle(h)
    va = ha.index("i").field("v").view("bsi")
    vb = hb.index("i").field("v").view("bsi")
    assert set(va.fragments) == set(vb.fragments)
    for shard in va.fragments:
        assert frag_checksum(va.fragment(shard)) == frag_checksum(
            vb.fragment(shard)
        )
    # and the values read back
    for c, v in zip(cols.tolist()[:20], values.tolist()[:20]):
        assert ha.index("i").field("v").value(c) == (v, True)
    ha.close()
    hb.close()


# -------------------------------------------------- batched translate
def test_translate_keys_one_wal_append_per_batch(tmp_path, monkeypatch):
    store = TranslateStore(str(tmp_path / "k.jsonl"))
    store.open()
    calls = []
    real = durable.wal_write
    monkeypatch.setattr(
        durable, "wal_write", lambda f, d, p: (calls.append(p), real(f, d, p))
    )
    keys = [f"k{i}" for i in range(500)] + ["k7", "k8"]  # dups are hits
    ids = store.translate_keys(keys)
    assert len(calls) == 1, "a batch must pay exactly ONE WAL append"
    assert ids[7] == ids[500] and len({i for i in ids[:500]}) == 500
    # hit-only batch: no append at all
    calls.clear()
    store.translate_keys(["k1", "k2"])
    assert calls == []
    store.close()
    s2 = TranslateStore(str(tmp_path / "k.jsonl"))
    s2.open()
    assert s2.translate_key("k499", create=False) == ids[499]
    s2.close()


def test_translate_batch_torn_tail_recovery(tmp_path, fs_hook):
    """In-process bulk-lane crash point 2: death mid batched-translate
    append. Acked batches survive; the torn batch's tail is truncated
    and the store reopens consistent."""
    path = str(tmp_path / "k.jsonl")
    store = TranslateStore(path)
    store.open()
    acked = []
    for b in range(5):
        keys = [f"b{b}_{i}" for i in range(50)]
        ids = store.translate_keys(keys)
        durable.ack_barrier()
        acked.append((keys, ids))
    fs_hook([{"op": "wal-append", "action": "torn", "cap_bytes": 13,
              "then": "crash", "path": "k.jsonl"}])
    with pytest.raises(SimulatedCrash):
        store.translate_keys([f"torn_{i}" for i in range(50)])
    durable.install_fs_hook(None)
    s2 = TranslateStore(path)
    s2.open()
    for keys, ids in acked:
        assert s2.translate_keys(keys, create=False) == ids
    # bindings from the torn (never-acked) batch may be partially
    # truncated, but the maps must be internally consistent
    for k, i in s2._by_key.items():
        assert s2._by_id[i] == k
    s2.close()


# ------------------------------------------------ roaring-adopt crash
def test_roaring_adopt_torn_append_recovery(tmp_path, fs_hook):
    """In-process bulk-lane crash point 1: death mid roaring-adopt WAL
    append. Every acked frame survives the reopen; the torn frame
    vanishes cleanly."""
    frag = Fragment(str(tmp_path / "frag0"), "i", "f", "standard", 0)
    frag.open()
    acked_frames = []
    rng = np.random.default_rng(5)
    for b in range(6):
        positions = rng.choice(
            min(SHARD_WIDTH * 4, 1 << 18), size=3000, replace=False
        ).astype(np.uint64)
        frame = rb.payload_from_positions(positions)
        frag.import_roaring(frame)
        durable.ack_barrier()
        acked_frames.append(positions)
    fs_hook([{"op": "wal-append", "action": "torn", "cap_bytes": 33,
              "then": "crash", "path": "frag0"}])
    torn = np.arange(900_000, 901_000, dtype=np.uint64)
    with pytest.raises(SimulatedCrash):
        frag.import_roaring(rb.payload_from_positions(torn))
    durable.install_fs_hook(None)
    f2 = Fragment(frag.path, "i", "f", "standard", 0)
    f2.open()
    assert not (f2.last_recovery or {}).get("corrupt")
    want = np.unique(np.concatenate(acked_frames))
    assert np.array_equal(f2.bitmap.values(), want)
    assert not f2.bitmap.contains(900_000)
    # the repaired log accepts new frames and survives another reopen
    f2.import_roaring(rb.payload_from_positions(torn))
    f3 = Fragment(frag.path, "i", "f", "standard", 0)
    f3.open()
    assert f3.bitmap.contains(900_000)


def test_adopt_fold_triggers_and_preserves_bits(tmp_path):
    """Union frames fold via the normal compaction path: after the
    byte-debt trigger fires, the snapshot holds everything and op debt
    resets — with identical bits."""
    frag = Fragment(str(tmp_path / "frag0"), "i", "f", "standard", 0)
    frag.open()
    frag.max_op_bytes = 1  # every append over-triggers
    frag.FOLD_BYTES_FACTOR = 0
    for i in range(4):
        frag.import_roaring(
            rb.payload_from_positions(
                np.arange(i * 1000, i * 1000 + 800, dtype=np.uint64)
            )
        )
        # no compactor attached → inline snapshot on threshold
        assert frag.op_n == 0 and frag.ops_bytes == 0
    f2 = Fragment(frag.path, "i", "f", "standard", 0)
    f2.open()
    assert f2.bitmap.count() == 4 * 800 and f2.op_n == 0


# --------------------------------------------------- holder threshold
def test_holder_parallel_load_threshold(tmp_path, monkeypatch):
    """Satellite: the holder-load-workers pool spins up only past the
    fragment-count threshold — serial dispatch below it (the r08
    regression: pool spin-up cost > overlap at 12 fragments)."""
    import pilosa_tpu.core.holder as holder_mod

    path = str(tmp_path / "h")
    h = Holder(path)
    h.open()
    api = API(h, max_writes=0)
    api.create_index("i", {})
    api.create_field("i", "f", {})
    field = h.index("i").field("f")
    view = field.create_view_if_not_exists("standard")
    for shard in range(6):
        view.create_fragment_if_not_exists(shard).set_bit(0, 1)
    durable.ack_barrier()
    h.close()

    pools = []
    real_pool = holder_mod._LoadPool

    class SpyPool(real_pool):
        def __init__(self, workers):
            pools.append(workers)
            super().__init__(workers)

    monkeypatch.setattr(holder_mod, "_LoadPool", SpyPool)
    # 6 fragments < threshold 32 → serial dispatch, no pool
    h2 = Holder(path, load_workers=8)
    h2.open()
    assert pools == [], "below the threshold the pool must not spin up"
    assert h2.index("i").field("f").view("standard").fragment(3) is not None
    h2.close()
    # explicit low threshold → pool used
    h3 = Holder(path, load_workers=8, load_min_fragments=4)
    h3.open()
    assert pools == [8]
    h3.close()
    # threshold 0 = always parallel
    h4 = Holder(path, load_workers=8, load_min_fragments=0)
    h4.open()
    assert pools == [8, 8]
    h4.close()


# -------------------------------------------------------- loader unit
def test_loader_parse_formats(tmp_path):
    rows, cols = loader.parse_records(["1,10", "2,20", "", "3,30,ts"], "csv")
    assert rows.tolist() == [1, 2, 3] and cols.tolist() == [10, 20, 30]
    rows, cols = loader.parse_records(
        ['{"rowID": 1, "columnID": 5}', '{"row": 2, "col": 6}'], "jsonl"
    )
    assert rows.tolist() == [1, 2] and cols.tolist() == [5, 6]
    with pytest.raises(loader.LoaderError):
        loader.parse_records(['{"rowID": 1}'], "jsonl")
    with pytest.raises(loader.LoaderError):
        loader.parse_records(["1"], "csv")
    with pytest.raises(loader.LoaderError):
        loader.parse_records([], "parquet")
    assert loader.detect_format("x.ndjson") == "jsonl"
    assert loader.detect_format("x.csv") == "csv"
    assert loader.detect_format("x.dat") == "csv"


def test_loader_build_frames_chunking(rng):
    rows = np.zeros(10_000, dtype=np.uint64)
    cols = rng.choice(SHARD_WIDTH, size=10_000, replace=False).astype(np.uint64)
    frames = loader.build_frames(rows, cols, batch_bits=3000)
    assert len(frames) == 4  # ceil(10000/3000) record slices, one shard
    total = 0
    merged = roaring.Bitmap()
    for shard, frame, n_bits in frames:
        assert shard == 0 and n_bits <= 3000
        bm, _ = roaring.deserialize(frame)
        merged.union_in_place(bm)
        total += n_bits
    assert total == 10_000 and merged.count() == 10_000


def test_payload_from_rows_matches_positions_path(rng):
    """Dense packed rows → the same frame content the position-vector
    builder gives, with sparse spans demoted out of bitmap containers."""
    dense = rng.integers(0, 2, SHARD_WIDTH).astype(bool)
    sparse = np.zeros(SHARD_WIDTH, dtype=bool)
    sparse[rng.choice(SHARD_WIDTH, 40, replace=False)] = True
    rows = {3: dense, 9: sparse, 11: np.zeros(SHARD_WIDTH, dtype=bool)}
    frame, n_bits = roaring.payload_from_rows(
        (r, np.packbits(m, bitorder="little").view(np.uint32))
        for r, m in rows.items()
    )
    want = np.concatenate(
        [np.flatnonzero(m).astype(np.uint64) + np.uint64(r * SHARD_WIDTH)
         for r, m in rows.items()]
    )
    got, _ = roaring.deserialize(frame)
    assert n_bits == want.size == got.count()
    assert np.array_equal(got.values(), np.sort(want))
    assert frame == roaring.payload_from_positions(want)
    with pytest.raises(ValueError):
        roaring.payload_from_rows([], shard_width=1 << 12)


def test_stream_frames_addresses_field_and_view(monkeypatch):
    """Pre-built frames carry their own field and view: one pipeline
    loads a whole schema (chip_smoke.py's three fields per shard)."""
    posts = []

    class OkConn:
        def __init__(self, *a, **k):
            pass

        def post(self, path, body):
            posts.append((path, body))
            return 200, b"{}", None

        def close(self):
            pass

    monkeypatch.setattr(loader, "_Conn", OkConn)
    st = loader.stream_frames(
        "http://x", "i",
        [("f", "standard", 0, b"aa", 2), ("v", "bsi", 5, b"bbb", 7)],
        pipeline=1,
    )
    assert st["posts"] == 2 and st["bits"] == 9 and st["bytes"] == 5
    assert posts == [
        ("/index/i/field/f/import-roaring/0?view=standard", b"aa"),
        ("/index/i/field/v/import-roaring/5?view=bsi", b"bbb"),
    ]


def test_loader_429_backoff_then_success(monkeypatch):
    """The loader honors Retry-After and retries the SAME frame; a
    persistent non-429 error raises."""
    posts = []

    class FakeConn:
        def __init__(self, *a, **k):
            pass

        def post(self, path, body):
            posts.append(path)
            if len(posts) == 1:
                return 429, b"busy", "0.01"
            return 200, b"{}", None

        def close(self):
            pass

    monkeypatch.setattr(loader, "_Conn", FakeConn)
    rows = np.zeros(10, dtype=np.uint64)
    cols = np.arange(10, dtype=np.uint64)
    st = loader.bulk_load("http://x", "i", "f", rows, cols, pipeline=1)
    assert st["backoffs429"] == 1 and st["posts"] == 1 and st["bits"] == 10
    assert posts[0] == posts[1]  # identical frame retried

    class FailConn(FakeConn):
        def post(self, path, body):
            return 500, b"boom", None

    monkeypatch.setattr(loader, "_Conn", FailConn)
    with pytest.raises(loader.LoaderError):
        loader.bulk_load("http://x", "i", "f", rows, cols, pipeline=1)


def test_stream_load_stop_event(monkeypatch):
    class OkConn:
        def __init__(self, *a, **k):
            pass

        def post(self, path, body):
            return 200, b"{}", None

        def close(self):
            pass

    monkeypatch.setattr(loader, "_Conn", OkConn)
    stop = threading.Event()

    def batches():
        yield np.zeros(5, np.uint64), np.arange(5, dtype=np.uint64)
        stop.set()
        yield np.zeros(5, np.uint64), np.arange(5, dtype=np.uint64)

    st = loader.stream_load("http://x", "i", "f", batches(), stop=stop)
    assert st["posts"] == 1  # second batch cut off cleanly


# -------------------------------------------- end-to-end over HTTP
@pytest.fixture
def srv(tmp_path):
    from pilosa_tpu.server import Server
    from pilosa_tpu.utils.config import Config

    s = Server(Config(bind="127.0.0.1:0", data_dir=str(tmp_path / "d"),
                      anti_entropy_interval=0, max_writes_per_request=0))
    s.open()
    yield s
    s.close()


def test_loader_end_to_end_and_ingest_observability(srv, rng):
    uri = f"http://127.0.0.1:{srv.port}"
    for p, b in (("/index/ing", b"{}"), ("/index/ing/field/f", b"{}")):
        urllib.request.urlopen(
            urllib.request.Request(uri + p, data=b, method="POST")
        ).read()
    n = 5000
    rows = rng.integers(0, 7, n).astype(np.uint64)
    cols = rng.integers(0, 2 * SHARD_WIDTH, n).astype(np.uint64)
    st = loader.bulk_load(uri, "ing", "f", rows, cols, pipeline=2)
    truth = len(set(zip(rows.tolist(), cols.tolist())))
    assert st["bits"] == truth
    # bit-exact through the public query surface
    body = b"Count(Union(" + b",".join(
        b"Row(f=%d)" % r for r in range(7)
    ) + b"))"
    out = json.loads(urllib.request.urlopen(urllib.request.Request(
        f"{uri}/index/ing/query", data=body, method="POST")).read())
    assert out["results"][0] == len(set(cols.tolist()))
    # ingest metrics + resources row (satellite: observability)
    mets = urllib.request.urlopen(f"{uri}/metrics").read().decode()
    assert 'pilosa_tpu_import_bytes_total{route="import-roaring"}' in mets
    assert "pilosa_tpu_import_bits_total" in mets
    assert "pilosa_tpu_import_batch_seconds_count" in mets
    res = json.loads(
        urllib.request.urlopen(f"{uri}/debug/resources").read()
    )
    ing = res["subsystems"]["ingest"]
    assert ing["bitsTotal"] == truth and ing["postsTotal"] >= st["posts"]
    assert ing["used"] == st["bytes"]


def test_cli_roaring_import(srv, tmp_path, capsys):
    from pilosa_tpu import cli

    csv = tmp_path / "data.csv"
    csv.write_text("1,10\n1,20\n2,10\n2,%d\n" % (SHARD_WIDTH + 7))
    host = f"127.0.0.1:{srv.port}"
    assert cli.main([
        "import", str(csv), "--host", host, "-i", "ri", "-f", "f",
        "--create", "--roaring", "--pipeline", "2",
    ]) == 0
    out = capsys.readouterr().out
    assert "4 bits" in out and "roaring" in out
    frag = srv.holder.index("ri").field("f").view("standard").fragment(0)
    assert frag.contains(1, 10) and frag.contains(2, 10)
    frag1 = srv.holder.index("ri").field("f").view("standard").fragment(1)
    assert frag1.contains(2, SHARD_WIDTH + 7)


def test_existence_saturated_shard_skips_mark(tmp_path):
    """Sustained re-ingest into a fully-marked shard must not pay the
    existence union per post (the O(1) early-out)."""
    h = Holder(str(tmp_path / "h"))
    h.open()
    api = API(h, max_writes=0)
    api.create_index("i", {})
    api.create_field("i", "f", {})
    # mark every column of shard 0
    full = np.arange(SHARD_WIDTH, dtype=np.uint64)
    api.import_roaring(
        "i", "f", 0, rb.payload_from_positions(full)
    )
    ef = h.index("i").field("_exists").view("standard").fragment(0)
    assert ef.row_count(0) == SHARD_WIDTH
    v0 = ef.version
    api.import_roaring(
        "i", "f", 0,
        rb.payload_from_positions(
            np.uint64(SHARD_WIDTH) + np.arange(100, dtype=np.uint64)
        ),
    )
    assert ef.version == v0, "saturated existence row must not be touched"
    h.close()
