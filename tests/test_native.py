"""Native C++ kernel tests — parity with the numpy fallbacks."""

import os

import numpy as np
import pytest

from pilosa_tpu import native


@pytest.fixture(scope="module", autouse=True)
def require_native():
    if not native.available():
        pytest.skip("native toolchain unavailable; numpy fallback covered elsewhere")


def test_freshness_is_decided_by_source_hash(tmp_path, monkeypatch):
    """A copied tree keeps no mtimes: the library is rebuilt when the
    source's content differs from the hash stored beside it, and only
    then."""
    import shutil
    import subprocess

    src = tmp_path / "bitmap_kernels.cpp"
    shutil.copy(native._SRC, src)
    lib = str(tmp_path / "libbitmap_kernels.so")
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_LIB", lib)
    monkeypatch.setattr(native, "_LIB_HASH", lib + ".sha256")
    builds = []
    real_run = subprocess.run

    def counting_run(*a, **k):
        builds.append(a)
        return real_run(*a, **k)

    monkeypatch.setattr(subprocess, "run", counting_run)
    assert native._build() and len(builds) == 1
    os.utime(src, (1, 1))  # older than the library: mtimes say nothing
    os.utime(lib, (2, 2))
    assert native._build() and len(builds) == 1
    src.write_text(src.read_text() + "\n// edited\n")
    os.utime(src, (1, 1))  # still "older" than the library
    assert native._build() and len(builds) == 2


def test_popcounts(rng):
    a = rng.integers(0, 2**32, 10001, dtype=np.uint32)
    b = rng.integers(0, 2**32, 10001, dtype=np.uint32)
    assert native.words_count(a) == int(np.bitwise_count(a).sum())
    assert native.and_count(a, b) == int(np.bitwise_count(a & b).sum())


def test_matrix_filter_counts(rng):
    m = rng.integers(0, 2**32, (13, 257), dtype=np.uint32)
    f = rng.integers(0, 2**32, 257, dtype=np.uint32)
    got = native.matrix_filter_counts(m, f)
    expect = np.bitwise_count(m & f[None, :]).sum(axis=1)
    assert np.array_equal(got, expect)


def test_pack_unpack_roundtrip(rng):
    width = 1 << 16
    positions = np.unique(rng.integers(0, width, 5000, dtype=np.int64))
    words = native.pack_positions(positions, width)
    assert native.words_count(words) == positions.size
    assert np.array_equal(native.unpack_words(words), positions)
    # empty
    empty = native.pack_positions(np.empty(0, dtype=np.int64), width)
    assert native.words_count(empty) == 0
    assert native.unpack_words(empty).size == 0


def test_u64_merges(rng):
    a = np.unique(rng.integers(0, 1 << 40, 3000, dtype=np.uint64))
    b = np.unique(rng.integers(0, 1 << 40, 3000, dtype=np.uint64))
    assert np.array_equal(native.u64_merge("union", a, b), np.union1d(a, b))
    assert np.array_equal(
        native.u64_merge("intersect", a, b), np.intersect1d(a, b)
    )
    assert np.array_equal(
        native.u64_merge("difference", a, b), np.setdiff1d(a, b)
    )


def test_native_backs_roaring_pack(rng):
    from pilosa_tpu import roaring

    vals = np.unique(rng.integers(0, 1 << 16, 2000, dtype=np.uint64))
    bm = roaring.Bitmap.from_values(vals)
    words = roaring.pack_range(bm, 0, 1 << 16)
    assert roaring.words_count(words) == vals.size
    assert np.array_equal(roaring.unpack_words(words), vals.astype(np.int64))


def test_pack_positions_bounds_checked():
    with pytest.raises(IndexError):
        native.pack_positions(np.array([70000], dtype=np.int64), 1 << 16)
    with pytest.raises(IndexError):
        native.pack_positions(np.array([-1], dtype=np.int64), 1 << 16)


def test_sort_unique_u64_matches_numpy(rng):
    for n in (0, 1, 100, 5000, 200_000):
        vals = rng.integers(0, 1 << 63, n, dtype=np.uint64)
        vals = np.concatenate([vals, vals[: n // 2]])  # force duplicates
        got = native.sort_unique_u64(vals)
        want = np.unique(vals)
        assert np.array_equal(got, want), n
    # clustered values exercise the skip-constant-byte passes
    vals = (np.uint64(7) << np.uint64(20)) + rng.integers(
        0, 1 << 20, 100_000, dtype=np.uint64
    )
    assert np.array_equal(native.sort_unique_u64(vals), np.unique(vals))


def test_counting_argsort_matches_numpy(rng):
    for n in (0, 1, 5000, 100_000):
        keys = rng.integers(0, 37, n, dtype=np.uint64)
        got = native.counting_argsort(keys)
        want = np.argsort(keys, kind="stable")
        assert np.array_equal(got, want), n
