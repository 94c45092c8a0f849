"""L0 roaring codec tests — property-tested against Python sets.

Mirrors the reference's test strategy for roaring/ (roaring_internal_test.go:
randomized container-op tests across all type pairs + serialization
round-trips)."""

import numpy as np
import pytest

from pilosa_tpu import roaring
from pilosa_tpu.roaring import containers as ct


def random_values(rng, n, span):
    return np.unique(rng.integers(0, span, size=n, dtype=np.uint64))


# ---------------------------------------------------------------- containers
@pytest.mark.parametrize("na,nb", [(10, 10), (10, 5000), (5000, 5000), (0, 100)])
def test_container_ops_match_sets(rng, na, nb):
    a = np.unique(rng.integers(0, 1 << 16, size=na, dtype=np.uint16)) if na else np.empty(0, np.uint16)
    b = np.unique(rng.integers(0, 1 << 16, size=nb, dtype=np.uint16))
    ca, cb = ct.from_values(a), ct.from_values(b)
    sa, sb = set(a.tolist()), set(b.tolist())
    assert set(ct.as_values(ct.container_and(ca, cb)).tolist()) == sa & sb
    assert set(ct.as_values(ct.container_or(ca, cb)).tolist()) == sa | sb
    assert set(ct.as_values(ct.container_xor(ca, cb)).tolist()) == sa ^ sb
    assert set(ct.as_values(ct.container_andnot(ca, cb)).tolist()) == sa - sb


def test_container_run_optimization():
    # write path picks array/bitmap only; explicit optimize (the
    # snapshot-time pass) compacts a dense consecutive range to a run
    c = ct.from_values(np.arange(10000, dtype=np.uint16))
    assert c.type == ct.TYPE_BITMAP
    c = ct.optimize(c, runs=True)
    assert c.type == ct.TYPE_RUN
    assert ct.container_count(c) == 10000
    assert ct.container_contains(c, 9999)
    assert not ct.container_contains(c, 10000)


def test_container_type_transitions():
    c = ct.from_values(np.empty(0, np.uint16))
    for v in range(0, 9000, 2):  # stride-2 defeats run encoding
        c, changed = ct.container_add(c, v)
        assert changed
    assert c.type == ct.TYPE_BITMAP
    assert ct.container_count(c) == 4500
    c2, changed = ct.container_add(c, 0)
    assert not changed and c2 is c


# -------------------------------------------------------------------- bitmap
def test_bitmap_add_remove_contains(rng):
    b = roaring.Bitmap()
    vals = random_values(rng, 500, 1 << 40)
    for v in vals.tolist():
        assert b.add(v)
        assert not b.add(v)
    assert b.count() == vals.size
    assert np.array_equal(b.values(), vals)
    for v in vals[:50].tolist():
        assert b.contains(v)
        assert b.remove(v)
        assert not b.contains(v)
        assert not b.remove(v)
    assert b.count() == vals.size - 50


def test_bitmap_add_many_matches_loop(rng):
    vals = random_values(rng, 20000, 1 << 32)
    b1 = roaring.Bitmap.from_values(vals)
    b2 = roaring.Bitmap()
    for v in vals[:1000].tolist():
        b2.add(v)
    assert b1.range_count(0, 1 << 33) == vals.size
    assert set(b2.values().tolist()) <= set(b1.values().tolist())


def test_bitmap_setops_match_sets(rng):
    va = random_values(rng, 3000, 1 << 24)
    vb = random_values(rng, 3000, 1 << 24)
    a, b = roaring.Bitmap.from_values(va), roaring.Bitmap.from_values(vb)
    sa, sb = set(va.tolist()), set(vb.tolist())
    assert set((a & b).values().tolist()) == sa & sb
    assert set((a | b).values().tolist()) == sa | sb
    assert set((a - b).values().tolist()) == sa - sb
    assert set((a ^ b).values().tolist()) == sa ^ sb


def test_bitmap_range(rng):
    vals = random_values(rng, 5000, 1 << 20)
    b = roaring.Bitmap.from_values(vals)
    lo, hi = 1 << 10, 1 << 18
    expect = vals[(vals >= lo) & (vals < hi)]
    assert b.range_count(lo, hi) == expect.size
    assert np.array_equal(b.range_values(lo, hi), expect)
    assert b.min() == int(vals.min())
    assert b.max() == int(vals.max())


# ------------------------------------------------------------- serialization
def test_serialize_roundtrip(rng):
    vals = np.concatenate(
        [
            random_values(rng, 2000, 1 << 16),  # array/bitmap containers
            np.arange(1 << 20, (1 << 20) + 30000, dtype=np.uint64),  # run
            random_values(rng, 100, 1 << 48),  # sparse high keys
        ]
    )
    b = roaring.Bitmap.from_values(vals)
    data = roaring.serialize(b)
    b2, consumed = roaring.deserialize(data)
    assert consumed == len(data)
    assert b2 == b


def test_ops_log_replay(rng):
    b = roaring.Bitmap.from_values(random_values(rng, 1000, 1 << 20))
    snapshot = roaring.serialize(b)
    adds = random_values(rng, 200, 1 << 20)
    removes = b.values()[:100]
    log = roaring.append_op(roaring.OP_ADD, adds) + roaring.append_op(
        roaring.OP_REMOVE, removes
    )
    expect = b.copy()
    expect.add_many(adds)
    expect.remove_many(removes)

    loaded, consumed = roaring.deserialize(snapshot + log)
    n = roaring.replay_ops(loaded, (snapshot + log)[consumed:])
    assert n == 2
    assert loaded == expect

    # torn write at the tail is ignored
    torn = snapshot + log + roaring.append_op(roaring.OP_ADD, adds)[:-3]
    loaded2, consumed2 = roaring.deserialize(torn)
    assert roaring.replay_ops(loaded2, torn[consumed2:]) == 2
    assert loaded2 == expect


# -------------------------------------------------------------------- packing
def test_pack_unpack_roundtrip(rng):
    vals = random_values(rng, 4000, 1 << 16)
    b = roaring.Bitmap.from_values(vals)
    words = roaring.pack_range(b, 0, 1 << 16)
    assert words.dtype == np.uint32 and words.size == (1 << 16) // 32
    assert roaring.words_count(words) == vals.size
    assert np.array_equal(roaring.unpack_words(words), vals.astype(np.int64))


def test_pack_range_offset(rng):
    base = 3 * (1 << 16)
    vals = random_values(rng, 1000, 1 << 16) + np.uint64(base)
    b = roaring.Bitmap.from_values(vals)
    words = roaring.pack_range(b, base, base + (1 << 16))
    assert np.array_equal(
        roaring.unpack_words(words) + base, vals.astype(np.int64)
    )
    # adjacent empty range packs to zeros
    assert roaring.words_count(roaring.pack_range(b, 0, 1 << 16)) == 0


def _kinds_bitmap(rng, kinds, width):
    """A bitmap of four rows of ``width`` columns whose containers are of
    the ``kinds`` named: sparse arrays, dense bitmaps, runs, or all."""
    parts = []
    for r in range(4):
        if "array" in kinds:
            parts.append(rng.integers(0, width, 40 * (r + 1)))
        if "bitmap" in kinds:
            parts.append(rng.integers(0, width, width // 8))
        if "run" in kinds:
            s = int(rng.integers(0, width - 70_000))
            parts.append(np.arange(s, s + 70_000))
        parts[-1] = parts[-1].astype(np.uint64) + np.uint64(r * width)
    b = roaring.Bitmap.from_values(np.unique(np.concatenate(parts)))
    if "run" in kinds:
        b._containers = {k: ct.optimize(c, runs=True) for k, c in b._containers.items()}
    return b


KINDS = [("array",), ("bitmap",), ("run",), ("array", "bitmap", "run")]


@pytest.mark.parametrize("kinds", KINDS, ids=["+".join(k) for k in KINDS])
def test_pack_range_of_aligned_rows_equals_the_value_path(rng, kinds):
    """A container-aligned row packs container by container (a bitmap or
    run container's words copied, the arrays' values scattered in one
    pass) to exactly the words its values pack to."""
    width = 1 << 20
    b = _kinds_bitmap(rng, kinds, width)
    for r in range(5):  # row 4 is empty
        lo = r * width
        want = roaring.pack_positions((b.range_values(lo, lo + width) - np.uint64(lo)).astype(np.int64), width)
        got = roaring.pack_range(b, lo, lo + width)
        assert got.dtype == np.uint32 and np.array_equal(got, want)


@pytest.mark.parametrize("kinds", KINDS, ids=["+".join(k) for k in KINDS])
def test_fold_to_columns_is_the_union_of_the_rows(rng, kinds):
    """Every row's containers folded onto the column space, one union a
    column key: the columns of any row, each container an array at
    ARRAY_MAX values or under and a bitmap above."""
    from pilosa_tpu.roaring.build import fold_to_columns

    width = 1 << 20
    b = _kinds_bitmap(rng, kinds, width)
    folded = fold_to_columns(b, width)
    assert np.array_equal(folded.values(), np.unique(b.values() % np.uint64(width)))
    for c in folded._containers.values():
        n = ct.container_count(c)
        assert c.type == ct.TYPE_RUN or (c.type == ct.TYPE_ARRAY) == (n <= ct.ARRAY_MAX)
    assert ct.as_values(ct.container_union([ct.from_values(np.arange(3, dtype=np.uint16))] * 2)).tolist() == [0, 1, 2]


# ------------------------------------------------------- regression findings
def test_high_key_range_ops_no_overflow():
    # values >= 2^63 must work through range_count/range_values/pack_range
    b = roaring.Bitmap()
    v = (1 << 63) + 5
    b.add(v)
    assert b.range_count(1 << 63, (1 << 63) + 10) == 1
    assert b.range_values(1 << 63, (1 << 63) + 10).tolist() == [v]
    words = roaring.pack_range(b, 1 << 63, (1 << 63) + (1 << 16))
    assert roaring.unpack_words(words).tolist() == [5]


def test_container_add_keeps_run_compact():
    c = ct.optimize(ct.from_values(np.arange(100, dtype=np.uint16)), runs=True)
    assert c.type == ct.TYPE_RUN
    c2, changed = ct.container_add(c, 200)
    assert changed and c2.type != ct.TYPE_BITMAP
    assert ct.container_count(c2) == 101


def test_deserialize_truncated_raises_valueerror(rng):
    data = roaring.serialize(roaring.Bitmap.from_values(random_values(rng, 100, 1 << 20)))
    for cut in (1, 6, 10, len(data) - 3):
        with pytest.raises(ValueError):
            roaring.deserialize(data[:cut])


def test_pilosa_cookie_format_roundtrip():
    """Snapshots are written in the upstream-pilosa layout (cookie 12348 |
    storageVersion 0) and round-trip all three container types
    (reference: roaring.go WriteTo/UnmarshalBinary)."""
    import struct

    rng = np.random.default_rng(12)
    b = roaring.Bitmap()
    b.add_many(rng.choice(1 << 16, size=500, replace=False).astype(np.uint64))  # array
    b.add_many((np.uint64(1 << 16) + rng.choice(1 << 16, size=30_000, replace=False).astype(np.uint64)))  # bitmap
    b.add_many(np.arange(3 << 16, (3 << 16) + 9000, dtype=np.uint64))  # run
    data = roaring.serialize(b)
    cookie, n = struct.unpack_from("<II", data, 0)
    assert cookie & 0xFFFF == 12348
    assert cookie >> 16 == 0  # upstream storageVersion
    assert n == len(b._containers)
    got, consumed = roaring.deserialize(data)
    assert consumed == len(data)
    assert got == b
    # serialize run-compacts: the arange block comes back as a run
    types = sorted(c.type for c in got._containers.values())
    assert types == [ct.TYPE_ARRAY, ct.TYPE_BITMAP, ct.TYPE_RUN]


def test_legacy_snapshot_still_loads():
    """Round-1 snapshots (version word 1) remain readable."""
    from pilosa_tpu.roaring import serialize as ser_mod

    b = roaring.Bitmap.from_values(np.array([1, 70000, 1 << 20], dtype=np.uint64))
    # re-create the legacy writer inline: header v1 + meta + u64 offsets
    import io, struct

    keys = sorted(b._containers)
    buf = io.BytesIO()
    buf.write(struct.pack("<HHI", 12348, 1, len(keys)))
    payloads = []
    for key in keys:
        c = b._containers[key]
        payloads.append(c.data.tobytes())
        buf.write(struct.pack("<QHHI", key, c.type, 0, len(c.data)))
    offset = 8 + len(keys) * (16 + 8)
    for p in payloads:
        buf.write(struct.pack("<Q", offset))
        offset += len(p)
    for p in payloads:
        buf.write(p)
    got, consumed = roaring.deserialize(buf.getvalue())
    assert got == b and consumed == len(buf.getvalue())


def test_pilosa_format_through_import_roaring():
    """A pilosa-layout payload unions straight into a fragment
    (reference: fragment.importRoaring fast path)."""
    from pilosa_tpu.core import Holder

    h = Holder(None)
    idx = h.create_index("ir")
    f = idx.create_field("f")
    vals = np.array([5, 9, (1 << 16) + 3], dtype=np.uint64)  # row 0 + row 1
    payload = roaring.serialize(roaring.Bitmap.from_values(vals))
    frag = f.create_view_if_not_exists("standard").create_fragment_if_not_exists(0)
    frag.import_roaring(payload)
    assert frag.contains(0, 5) and frag.contains(0, 9) and frag.contains(1, 3)


def test_bulk_mutation_fuzz_against_set_oracle():
    """add_many/remove_many batch paths vs a python-set oracle across
    mixed container types (serialize round trips force array/bitmap/run
    transitions mid-sequence)."""
    rng = np.random.default_rng(99)
    for trial in range(15):
        b = roaring.Bitmap()
        oracle: set[int] = set()
        for _ in range(6):
            n = int(rng.integers(1, 30000))
            span = int(rng.choice([1 << 16, 1 << 20, 1 << 24]))
            vals = rng.integers(0, span, n).astype(np.uint64)
            if rng.random() < 0.25:  # dense run-like block
                start = int(rng.integers(0, span))
                vals = np.arange(start, start + n, dtype=np.uint64)
            if rng.random() < 0.5:
                b.add_many(vals)
                oracle |= set(vals.tolist())
            else:
                b.remove_many(vals)
                oracle -= set(vals.tolist())
            if rng.random() < 0.2:
                b, _ = roaring.deserialize(roaring.serialize(b))
        assert set(b.values().tolist()) == oracle, trial
        assert b.count() == len(oracle)


def test_official_roaring_format_reads():
    """Payloads in the OFFICIAL 32-bit roaring interchange layout
    (RoaringFormatSpec cookies 12346/12347) parse — stock CRoaring /
    RoaringBitmap clients' import-roaring bodies work, as upstream
    pilosa's UnmarshalBinary allows."""
    import struct

    # cookie 12347 (SERIAL_COOKIE: runs present, count-1 packed in the
    # high half), 3 containers (array, run, bitmap), n<4 ⇒ no offsets
    n = 3
    buf = struct.pack("<I", 12347 | (n - 1) << 16)
    buf += bytes([0b010])  # container 1 is a run
    arr_vals = np.array([1, 5, 9, 100], dtype=np.uint16)
    run_start, run_len = 100, 50  # values 100..149
    bm_vals = np.arange(0, 65536, 13, dtype=np.uint16)  # card 5042 > 4096
    buf += struct.pack("<HH", 0, arr_vals.size - 1)
    buf += struct.pack("<HH", 1, run_len - 1)
    buf += struct.pack("<HH", 2, bm_vals.size - 1)
    buf += arr_vals.tobytes()
    buf += struct.pack("<HHH", 1, run_start, run_len - 1)  # n_runs, start, len-1
    words = np.zeros(1024, dtype=np.uint64)
    np.bitwise_or.at(
        words,
        bm_vals.astype(np.uint64) >> np.uint64(6),
        np.uint64(1) << (bm_vals.astype(np.uint64) & np.uint64(63)),
    )
    buf += words.tobytes()

    got, consumed = roaring.deserialize(buf)
    assert consumed == len(buf)
    expect = set(arr_vals.tolist())
    expect |= {(1 << 16) + v for v in range(run_start, run_start + run_len)}
    expect |= {(2 << 16) + int(v) for v in bm_vals.tolist()}
    assert set(got.values().tolist()) == expect

    # cookie 12346 (SERIAL_COOKIE_NO_RUNCONTAINER): separate uint32
    # count, offsets always present
    vals = np.array([7, 8, 9], dtype=np.uint16)
    buf2 = struct.pack("<II", 12346, 1)
    buf2 += struct.pack("<HH", 4, vals.size - 1)
    buf2 += struct.pack("<I", 8 + 4 + 4)  # offset of data from start
    buf2 += vals.tobytes()
    got2, consumed2 = roaring.deserialize(buf2)
    assert consumed2 == len(buf2)
    assert set(got2.values().tolist()) == {(4 << 16) + v for v in (7, 8, 9)}


def test_official_format_through_import_roaring():
    """An official-format payload unions into a fragment via the same
    import-roaring path as pilosa-layout payloads."""
    import struct

    from pilosa_tpu.core import Holder

    h = Holder(None)
    f = h.create_index("of").create_field("f")
    vals = np.array([3, 4, 50], dtype=np.uint16)
    payload = struct.pack("<II", 12346, 1)
    payload += struct.pack("<HH", 0, vals.size - 1)
    payload += struct.pack("<I", 16)
    payload += vals.tobytes()
    frag = f.create_view_if_not_exists("standard").create_fragment_if_not_exists(0)
    frag.import_roaring(payload)
    assert frag.contains(0, 3) and frag.contains(0, 50) and not frag.contains(0, 5)


def test_serialize_official_roundtrip(rng):
    """serialize_official emits spec-conformant 12346/12347 payloads that
    our official reader (and therefore stock clients) round-trip, across
    array/bitmap/run container mixes and the n<4 no-offsets branch."""
    cases = [
        np.array([7], dtype=np.uint64),  # single array container, no runs
        random_values(rng, 200, 1 << 20),  # multiple array containers
        np.arange(100_000, 160_000, dtype=np.uint64),  # run container
        np.concatenate(  # mixed: array + dense bitmap + run, ≥4 containers
            [
                random_values(rng, 100, 1 << 16),
                (1 << 16) + random_values(rng, 9000, 1 << 16),
                np.arange(1 << 17, (1 << 17) + 30_000, dtype=np.uint64),
                np.array([(1 << 18) + 5], dtype=np.uint64),
                np.array([(1 << 19) + 1, (1 << 19) + 2], dtype=np.uint64),
            ]
        ),
    ]
    for vals in cases:
        b = roaring.Bitmap.from_values(vals)
        data = roaring.serialize_official(b)
        got, consumed = roaring.deserialize(data)
        assert got == b, f"mismatch for {len(vals)} values"
        assert consumed == len(data)


def test_serialize_official_rejects_64bit_keys():
    b = roaring.Bitmap.from_values(np.array([1 << 33], dtype=np.uint64))
    with pytest.raises(ValueError, match="32-bit"):
        roaring.serialize_official(b)


def test_serialize_official_through_import_roaring():
    """An official-format payload we produce imports into a fragment the
    same way a stock client's would."""
    from pilosa_tpu.core import Holder

    h = Holder(None)
    idx = h.create_index("iro")
    f = idx.create_field("f")
    vals = np.array([5, 9, (1 << 16) + 3], dtype=np.uint64)
    payload = roaring.serialize_official(roaring.Bitmap.from_values(vals))
    frag = f.create_view_if_not_exists("standard").create_fragment_if_not_exists(0)
    frag.import_roaring(payload)
    assert frag.contains(0, 5) and frag.contains(0, 9) and frag.contains(1, 3)


def test_serialize_official_fuzz_roundtrip(rng):
    """Randomized container mixes through the official writer/reader:
    densities crossing the array/bitmap threshold, runs, container-count
    edges around the no-offsets branch (n < 4), single containers."""
    for trial in range(30):
        parts = []
        n_containers = int(rng.integers(1, 8))
        for c in range(n_containers):
            base = c << 16
            kind = int(rng.integers(0, 3))
            if kind == 0:  # sparse array
                parts.append(base + rng.choice(1 << 16, int(rng.integers(1, 200)), replace=False))
            elif kind == 1:  # dense bitmap
                parts.append(base + rng.choice(1 << 16, int(rng.integers(5000, 9000)), replace=False))
            else:  # run
                start = int(rng.integers(0, 30000))
                parts.append(base + np.arange(start, start + int(rng.integers(4200, 20000))))
        vals = np.unique(np.concatenate(parts).astype(np.uint64))
        b = roaring.Bitmap.from_values(vals)
        data = roaring.serialize_official(b)
        got, consumed = roaring.deserialize(data)
        assert consumed == len(data), f"trial {trial}: trailing bytes"
        assert got == b, f"trial {trial}: contents diverged"


def test_batch_optimize_matches_per_container_oracle(rng):
    """batch_optimize (the vectorized snapshot-serialize pass) must make
    the EXACT decision optimize(c, runs=True) makes for every container
    type and density, including the degenerate shapes."""
    from pilosa_tpu.roaring import containers as ct

    conts = [
        ct.array_container(np.empty(0, np.uint16)),
        ct.array_container(np.array([5], np.uint16)),
        ct.array_container(np.arange(1000, 3000, dtype=np.uint16)),  # run wins
        ct.run_container(np.array([[0, 10], [20, 30]], np.uint16)),  # untouched
    ]
    w_full = np.full(1024, ~np.uint64(0))
    conts.append(ct.bitmap_container(w_full))  # one 65536-bit run
    for _ in range(150):
        kind = int(rng.integers(0, 3))
        if kind == 0:
            n = int(rng.integers(0, 4097))
            conts.append(ct.array_container(
                np.sort(rng.choice(1 << 16, n, replace=False)).astype(np.uint16)))
        elif kind == 1:
            n = int(rng.integers(1, 60000))
            vv = np.sort(rng.choice(1 << 16, n, replace=False)).astype(np.uint64)
            ww = np.zeros(1024, np.uint64)
            ww[vv >> np.uint64(6)] |= np.uint64(1) << (vv & np.uint64(63))
            conts.append(ct.bitmap_container(ww))
        else:
            lo = np.sort(rng.choice(60000, 10, replace=False))
            conts.append(ct.run_container(np.stack(
                [lo, lo + rng.integers(0, 100, 10)], axis=1).astype(np.uint16)))
    batch = ct.batch_optimize(conts)
    for i, c in enumerate(conts):
        want = c if c.type == ct.TYPE_RUN else ct.optimize(c, runs=True)
        assert batch[i].type == want.type, i
        assert np.array_equal(batch[i].data, want.data), i


def test_values_all_array_fast_path_matches_mixed(rng):
    """Bitmap.values() takes a batched path when every container is an
    array; it must agree with the generic per-container path."""
    vals = np.unique(rng.choice(1 << 22, 5000, replace=False).astype(np.uint64))
    b = roaring.Bitmap.from_values(vals)
    assert np.array_equal(b.values(), vals)
    # force a bitmap container into the mix → generic path
    dense = (np.uint64(7) << np.uint64(16)) + np.arange(6000, dtype=np.uint64)
    b2 = roaring.Bitmap.from_values(np.unique(np.concatenate([vals, dense])))
    assert np.array_equal(
        b2.values(), np.unique(np.concatenate([vals, dense]))
    )
