"""Bit-Sliced Index (BSI) kernels — integer fields on packed words.

Reference: field.go (bsiGroup, constants bsiExistsBit=0, bsiSignBit=1,
bsiOffsetBit=2) and the executor's Sum/Min/Max/Range paths. Layout is kept
semantically identical to the reference: an int field's fragment rows are

    row 0            — existence bit (column has a value)
    row 1            — sign bit (value is negative)
    rows 2..2+depth  — magnitude bits, LSB first

so a device BSI block is ``uint32[2 + depth, W]``. Values are
sign-magnitude. All comparisons/aggregations below are O(depth) chains of
elementwise bitwise ops + popcounts — each compiles to one fused XLA kernel
(the reference walks the same slices with per-container Go loops).

``depth`` is static at trace time (fields carry a fixed bit depth), so the
Python loops below unroll into straight-line XLA ops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu.ops.bitwise import matrix_filter_counts, popcount

EXISTS_ROW = 0
SIGN_ROW = 1
OFFSET_ROW = 2

# numpy, not jnp: a module-level jnp scalar would initialize the XLA
# backend at import, which forbids a later jax.distributed.initialize
# (multi-host servers import this module long before joining the group)
_ONES = np.uint32(0xFFFFFFFF)


def _magnitude_cmp(mag: jax.Array, c_abs: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-column compare of magnitude slices vs constant |c|.

    ``mag``: uint32[depth, W], LSB-first. Returns (eq, lt, gt) word masks.
    Classic MSB→LSB bit-sliced comparison (O'Neil/Quass); the loop unrolls
    at trace time.
    """
    depth, w = mag.shape
    eq = jnp.full((w,), _ONES)
    lt = jnp.zeros((w,), jnp.uint32)
    gt = jnp.zeros((w,), jnp.uint32)
    for k in range(depth - 1, -1, -1):
        bit = mag[k]
        if (c_abs >> k) & 1:
            lt = lt | (eq & ~bit)
            eq = eq & bit
        else:
            gt = gt | (eq & bit)
            eq = eq & ~bit
    return eq, lt, gt


@jax.named_scope("pilosa.bsi_compare")
def compare(slices: jax.Array, op: str, value: int) -> jax.Array:
    """Columns whose stored value ⟨op⟩ ``value`` → uint32[W] mask.

    ``op`` ∈ {"==", "!=", "<", "<=", ">", ">="}. The caller intersects the
    result with its row filter; existence is applied here.
    """
    exists = slices[EXISTS_ROW]
    sign = slices[SIGN_ROW]
    mag = slices[OFFSET_ROW:]
    pos = exists & ~sign
    neg = exists & sign
    c_abs = abs(value)
    if c_abs >= 1 << mag.shape[0]:
        # |c| exceeds every representable magnitude: nothing equal/greater,
        # every stored magnitude is smaller
        w = mag.shape[1]
        eq_m = jnp.zeros((w,), jnp.uint32)
        gt_m = jnp.zeros((w,), jnp.uint32)
        lt_m = jnp.full((w,), _ONES)
    else:
        eq_m, lt_m, gt_m = _magnitude_cmp(mag, c_abs)

    if value >= 0:
        eq = pos & eq_m
        # v < c: every negative, plus positives with smaller magnitude
        lt = neg | (pos & lt_m)
        gt = pos & gt_m
    else:
        eq = neg & eq_m
        # v < c (c negative): negatives with larger magnitude
        lt = neg & gt_m
        gt = pos | (neg & lt_m)

    if op == "==":
        return eq
    if op == "!=":
        return exists & ~eq
    if op == "<":
        return lt
    if op == "<=":
        return lt | eq
    if op == ">":
        return gt
    if op == ">=":
        return gt | eq
    raise ValueError(f"bad BSI comparison op {op!r}")


def between(slices: jax.Array, lo: int, hi: int) -> jax.Array:
    """Columns with lo <= value <= hi (PQL Range/between) → uint32[W]."""
    return compare(slices, ">=", lo) & compare(slices, "<=", hi)


@jax.named_scope("pilosa.bsi_sum")
def sum_counts(slices: jax.Array, filt: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-magnitude-bit signed counts for Sum.

    Returns (pos_counts int32[depth], neg_counts int32[depth], n int64):
    the exact sum is Σ_k 2^k (pos[k] - neg[k]), accumulated by the caller
    in arbitrary precision (host Python ints, or an int64 dot on device —
    see ``sum_device``). Two-phase split keeps device counts in int32
    (≤ 2^20 per shard) regardless of bit depth.
    """
    exists = slices[EXISTS_ROW]
    sign = slices[SIGN_ROW]
    mag = slices[OFFSET_ROW:]
    pos = exists & ~sign & filt
    neg = exists & sign & filt
    pos_counts = matrix_filter_counts(mag, pos)
    neg_counts = matrix_filter_counts(mag, neg)
    n = popcount(exists & filt)
    return pos_counts, neg_counts, n


def weigh_sum(pos_counts, neg_counts) -> int:
    """Host-side exact weighted sum of per-bit counts (Python ints)."""
    total = 0
    for k, (p, q) in enumerate(zip(pos_counts.tolist(), neg_counts.tolist())):
        total += (int(p) - int(q)) << k
    return total


def sum_device(slices: jax.Array, filt: jax.Array) -> tuple[jax.Array, jax.Array]:
    """All-device Sum → (sum int64, count int64). Used inside sharded
    programs where the result participates in a psum; needs x64 enabled
    (pilosa_tpu.ops turns it on at import)."""
    pos_counts, neg_counts, n = sum_counts(slices, filt)
    depth = pos_counts.shape[0]
    weights = jnp.asarray([1 << k for k in range(depth)], dtype=jnp.int64)
    diff = pos_counts.astype(jnp.int64) - neg_counts.astype(jnp.int64)
    return jnp.sum(diff * weights), n


@jax.named_scope("pilosa.bsi_minmax")
def min_max(slices: jax.Array, filt: jax.Array, want_max: bool) -> tuple[jax.Array, jax.Array]:
    """(value int64, count int64) of the min/max stored value among
    filtered, existing columns. count==0 ⇒ no value (result undefined).

    Branch-free: computes both the positive-candidate walk and the
    negative-candidate walk, then selects — keeps everything inside one
    jitted program (no data-dependent Python control flow).
    """
    exists = slices[EXISTS_ROW]
    sign = slices[SIGN_ROW]
    mag = slices[OFFSET_ROW:]
    depth = mag.shape[0]

    base = exists & filt
    pos_cand = base & ~sign
    neg_cand = base & sign
    has_pos = popcount(pos_cand) > 0
    has_neg = popcount(neg_cand) > 0

    def walk(cand, prefer_set: bool):
        """MSB→LSB: narrow candidates toward extreme magnitude."""
        val = jnp.int64(0)
        for k in range(depth - 1, -1, -1):
            t = (cand & mag[k]) if prefer_set else (cand & ~mag[k])
            nonempty = popcount(t) > 0
            cand = jnp.where(nonempty, t, cand)
            bit_is_one = nonempty if prefer_set else ~nonempty
            val = val + (bit_is_one.astype(jnp.int64) << k)
        return val, cand

    if want_max:
        # max = largest positive if any, else negative with smallest magnitude
        pv, pc = walk(pos_cand, prefer_set=True)
        nv, nc = walk(neg_cand, prefer_set=False)
        value = jnp.where(has_pos, pv, -nv)
        cand = jnp.where(has_pos, pc, nc)
    else:
        # min = most-negative if any, else positive with smallest magnitude
        nv, nc = walk(neg_cand, prefer_set=True)
        pv, pc = walk(pos_cand, prefer_set=False)
        value = jnp.where(has_neg, -nv, pv)
        cand = jnp.where(has_neg, nc, pc)
    return value, popcount(cand)
