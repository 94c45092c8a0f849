"""Device kernel tests — validated against the host roaring oracle / numpy.

Mirrors the reference's strategy of randomized cross-checks between the
fast path and a trivial implementation (roaring_internal_test.go)."""

import numpy as np
import pytest

from pilosa_tpu import ops
from pilosa_tpu.roaring import pack_positions, unpack_words

W = 256  # words per test vector (8192 bits)
BITS = W * 32


def random_words(rng, density=0.3):
    positions = np.flatnonzero(rng.random(BITS) < density).astype(np.int64)
    return pack_positions(positions, BITS), set(positions.tolist())


def test_bitwise_ops_match_sets(rng):
    a, sa = random_words(rng)
    b, sb = random_words(rng)
    assert set(unpack_words(np.asarray(ops.w_and(a, b)))) == sa & sb
    assert set(unpack_words(np.asarray(ops.w_or(a, b)))) == sa | sb
    assert set(unpack_words(np.asarray(ops.w_xor(a, b)))) == sa ^ sb
    assert set(unpack_words(np.asarray(ops.w_andnot(a, b)))) == sa - sb
    assert int(ops.count_and(a, b)) == len(sa & sb)
    assert int(ops.count_or(a, b)) == len(sa | sb)
    assert int(ops.count_xor(a, b)) == len(sa ^ sb)
    assert int(ops.count_andnot(a, b)) == len(sa - sb)
    assert int(ops.popcount(a)) == len(sa)


def test_not_with_column_mask(rng):
    a, sa = random_words(rng)
    width = BITS - 100  # partial final word
    mask = np.asarray(ops.column_mask(width, W))
    complement = np.asarray(ops.w_and(ops.w_not(a), mask))
    expect = set(range(width)) - sa
    assert set(unpack_words(complement)) == expect


@pytest.mark.parametrize("n", [0, 1, 31, 32, 33, 100, 8191])
def test_shift_words(rng, n):
    a, sa = random_words(rng, density=0.1)
    shifted = np.asarray(ops.shift_words(a, n))
    expect = {p + n for p in sa if p + n < BITS}
    assert set(unpack_words(shifted)) == expect


def test_matrix_filter_counts(rng):
    rows = 37
    mats, sets_ = zip(*(random_words(rng, 0.2) for _ in range(rows)))
    matrix = np.stack(mats)
    filt, sf = random_words(rng, 0.5)
    counts = np.asarray(ops.matrix_filter_counts(matrix, filt))
    for i in range(rows):
        assert counts[i] == len(sets_[i] & sf)


# ------------------------------------------------------------------------ BSI
def make_bsi(rng, n_cols=4000, lo=-1000, hi=1000):
    """Random BSI block + dict oracle."""
    cols = np.sort(rng.choice(BITS, size=n_cols, replace=False)).astype(np.int64)
    vals = rng.integers(lo, hi + 1, size=n_cols)
    oracle = dict(zip(cols.tolist(), vals.tolist()))
    depth = max(int(abs(int(v)).bit_length()) for v in vals) or 1
    slices = np.zeros((2 + depth, W), dtype=np.uint32)
    slices[ops.bsi.EXISTS_ROW] = pack_positions(cols, BITS)
    slices[ops.bsi.SIGN_ROW] = pack_positions(cols[vals < 0], BITS)
    mags = np.abs(vals)
    for k in range(depth):
        slices[ops.bsi.OFFSET_ROW + k] = pack_positions(
            cols[(mags >> k) & 1 == 1], BITS
        )
    return slices, oracle


OPS = {
    "==": lambda v, c: v == c,
    "!=": lambda v, c: v != c,
    "<": lambda v, c: v < c,
    "<=": lambda v, c: v <= c,
    ">": lambda v, c: v > c,
    ">=": lambda v, c: v >= c,
}


@pytest.mark.parametrize("c", [-1001, -500, -1, 0, 1, 123, 999, 1001])
def test_bsi_compare(rng, c):
    slices, oracle = make_bsi(rng)
    for op, pyop in OPS.items():
        got = set(unpack_words(np.asarray(ops.bsi.compare(slices, op, c))))
        expect = {col for col, v in oracle.items() if pyop(v, c)}
        assert got == expect, f"op {op} c={c}"


def test_bsi_between(rng):
    slices, oracle = make_bsi(rng)
    got = set(unpack_words(np.asarray(ops.bsi.between(slices, -250, 250))))
    assert got == {c for c, v in oracle.items() if -250 <= v <= 250}


def test_bsi_sum(rng):
    slices, oracle = make_bsi(rng)
    filt, sf = random_words(rng, 0.5)
    pos, neg, n = ops.bsi.sum_counts(slices, filt)
    selected = {c: v for c, v in oracle.items() if c in sf}
    assert int(n) == len(selected)
    assert ops.bsi.weigh_sum(np.asarray(pos), np.asarray(neg)) == sum(
        selected.values()
    )
    s_dev, n_dev = ops.bsi.sum_device(slices, filt)
    assert int(s_dev) == sum(selected.values()) and int(n_dev) == len(selected)


@pytest.mark.parametrize("depth,top", [(24, 1 << 25), (40, 1 << 25), (3, 5), (0, 9)])
def test_bsi_weigh_sums_equals_weigh_sum_a_row(rng, depth, top):
    """The grouped sums' weighing: every row as ``weigh_sum`` weighs it,
    in int64 where no sum can overflow (24 planes of 2**25 counts) and
    in Python ints where one could (40 planes)."""
    pos = rng.integers(0, top, (7, depth))
    neg = rng.integers(0, top, (7, depth))
    assert ops.bsi.weigh_sums(pos, neg) == [ops.bsi.weigh_sum(p, q) for p, q in zip(pos, neg)]


@pytest.mark.parametrize("lo,hi", [(-1000, 1000), (5, 900), (-900, -5), (7, 7)])
def test_bsi_min_max(rng, lo, hi):
    slices, oracle = make_bsi(rng, lo=lo, hi=hi)
    filt, sf = random_words(rng, 0.6)
    selected = {c: v for c, v in oracle.items() if c in sf}
    if not selected:
        pytest.skip("empty selection")
    vmax, cmax = ops.bsi.min_max(slices, filt, want_max=True)
    vmin, cmin = ops.bsi.min_max(slices, filt, want_max=False)
    assert int(vmax) == max(selected.values())
    assert int(cmax) == sum(1 for v in selected.values() if v == max(selected.values()))
    assert int(vmin) == min(selected.values())
    assert int(cmin) == sum(1 for v in selected.values() if v == min(selected.values()))


# ----------------------------------------------------------------------- TopN
def test_top_rows_and_candidates(rng):
    rows = 50
    mats, sets_ = zip(*(random_words(rng, rng.uniform(0.01, 0.5)) for _ in range(rows)))
    matrix = np.stack(mats)
    filt, sf = random_words(rng, 0.7)
    true_counts = np.array([len(s & sf) for s in sets_])

    vals, ids = ops.topn.top_rows(matrix, filt, 10)
    vals, ids = np.asarray(vals), np.asarray(ids)
    order = np.sort(true_counts)[::-1]
    assert np.array_equal(vals, order[:10])
    for v, i in zip(vals, ids):
        assert true_counts[i] == v

    cand = np.array([3, 7, 49, 60, -1], dtype=np.int32)  # 60, -1 out of range
    counts = np.asarray(ops.topn.candidate_counts(matrix, cand, filt))
    assert counts[0] == true_counts[3]
    assert counts[1] == true_counts[7]
    assert counts[2] == true_counts[49]
    assert counts[3] == 0 and counts[4] == 0
