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


# A comparison constant travels to the program as DATA, like a row id
# (executor/compile.py ``_add_scalar``): ``CONSTANT_WORDS`` int32 words,
# the same layout whatever the field's depth, so one compiled program
# serves every threshold. Word 0 holds the flags, words 1..3 hold |c| in
# 21-bit chunks, LSB first: 63 magnitude bits, every word a non-negative
# int32 (nothing to reinterpret, exact at every depth an int64 field can
# have).
CONSTANT_WORDS = 4
_CHUNK_BITS = 21
_CHUNK_MASK = (1 << _CHUNK_BITS) - 1
_CHUNKS = CONSTANT_WORDS - 1
_MAG_BITS = _CHUNKS * _CHUNK_BITS
_FLAG_NEGATIVE = 1
_FLAG_HUGE = 2  # |c| >= 2**63: beyond every depth


def constant_words(value: int) -> np.ndarray:
    """Host encoding of a comparison constant → int32[CONSTANT_WORDS]."""
    value = int(value)
    mag = abs(value)
    flags = _FLAG_NEGATIVE if value < 0 else 0
    if mag >> _MAG_BITS:
        flags |= _FLAG_HUGE
        mag = 0
    return np.array(
        [flags]
        + [(mag >> (j * _CHUNK_BITS)) & _CHUNK_MASK for j in range(_CHUNKS)],
        dtype=np.int32,
    )


def _as_words(value) -> jax.Array:
    """A Python int is encoded here (a constant of the program that
    traces it); anything else is ``constant_words``' vector already."""
    if isinstance(value, (int, np.integer)):
        return jnp.asarray(constant_words(value))
    return value


def _mask(flag) -> jax.Array:
    """0/1 scalar → all-zeros / all-ones uint32 scalar."""
    return jnp.uint32(0) - flag.astype(jnp.uint32)


def _beyond(words: jax.Array, depth: int) -> jax.Array:
    """bool scalar: |c| >= 2**depth, no stored magnitude reaches it."""
    out = (words[0] & _FLAG_HUGE) != 0
    for j in range(_CHUNKS):
        lo = j * _CHUNK_BITS
        if depth <= lo:
            out = out | (words[1 + j] != 0)
        elif depth < lo + _CHUNK_BITS:
            out = out | ((words[1 + j] >> (depth - lo)) != 0)
    return out


def _magnitude_cmp(mag: jax.Array, words: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-column compare of magnitude slices vs constant |c|.

    ``mag``: uint32[depth, ...], LSB-first; ``words``: the encoded
    constant. Returns (eq, lt, gt) word masks. Classic MSB→LSB bit-sliced
    comparison (O'Neil/Quass); the loop unrolls at trace time, and the
    constant's bit is a SELECT (a broadcast scalar mask), not a branch:
    where stored bit and constant bit differ among the columns still
    equal, the constant's bit says which side they fall on.
    """
    depth, shape = mag.shape[0], mag.shape[1:]
    eq = jnp.full(shape, _ONES)
    lt = jnp.zeros(shape, jnp.uint32)
    for k in range(depth - 1, -1, -1):
        if k < _MAG_BITS:
            c = _mask((words[1 + k // _CHUNK_BITS] >> (k % _CHUNK_BITS)) & 1)
        else:
            c = jnp.uint32(0)
        differ = eq & (mag[k] ^ c)
        lt = lt | (differ & c)
        eq = eq & ~differ
    # |c| beyond the depth: nothing equal or greater, every stored
    # magnitude is smaller
    beyond = _mask(_beyond(words, depth))
    eq = eq & ~beyond
    lt = lt | beyond
    return eq, lt, ~(eq | lt)


@jax.named_scope("pilosa.bsi_compare")
def compare(slices: jax.Array, op: str, value) -> jax.Array:
    """Columns whose stored value ⟨op⟩ ``value`` → uint32 mask, shaped
    as one slice (``[W]`` for a ``[2 + depth, W]`` block, ``[S, W]`` for
    a stacked one: every step is elementwise).

    ``op`` ∈ {"==", "!=", "<", "<=", ">", ">="} is static. ``value`` is
    ``constant_words(c)``, traced: the constant, its sign and "beyond
    the field's depth" are operands of the program, not part of it (a
    Python int is accepted and encoded in place). The caller intersects
    the result with its row filter; existence is applied here.
    """
    words = _as_words(value)
    exists = slices[EXISTS_ROW]
    sign = slices[SIGN_ROW]
    mag = slices[OFFSET_ROW:]
    pos = exists & ~sign
    neg = exists & sign
    eq_m, lt_m, gt_m = _magnitude_cmp(mag, words)
    negative = (words[0] & _FLAG_NEGATIVE) != 0

    # unused masks are dead code to XLA: each operator pays for its own
    eq = jnp.where(negative, neg, pos) & eq_m
    # v < c, c >= 0: every negative, plus positives with smaller magnitude;
    # c < 0: negatives with larger magnitude
    lt = jnp.where(negative, neg & gt_m, neg | (pos & lt_m))
    gt = jnp.where(negative, pos | (neg & lt_m), pos & gt_m)

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


def between(slices: jax.Array, lo, hi) -> jax.Array:
    """Columns with lo <= value <= hi (PQL Range/between) → uint32 mask.
    Both bounds as ``compare``'s ``value``; traced together they are one
    fused pass over the stack."""
    return compare(slices, ">=", lo) & compare(slices, "<=", hi)


def block(m: jax.Array, need: int) -> jax.Array:
    """THE depth rule of an int field's stack ``uint32[R, ...]`` against
    the ``need = OFFSET_ROW + bit_depth`` planes its field declares.
    Called at trace time INSIDE the consuming program, on the stack as
    it lies in memory (stack heights pad to a power of two, so R is
    seldom ``need``); never on a concrete array, where it would be a
    device program of its own a query.

    Deeper than declared: a static slice, which XLA fuses into the reads
    (the planes left out are never fetched). Shallower: taken whole,
    nothing padded. The planes it lacks hold no bit by construction (a
    value that needs plane k makes the stack at least k + 1 deep), and
    every kernel here takes its depth from the array: zeros add nothing
    to ``sum_counts``, decide nothing in a ``min_max`` walk, and a
    constant past the stack's depth is ``_beyond`` to ``compare``."""
    return m[:need] if m.shape[0] > need else m


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


def weigh_sums(pos_counts, neg_counts) -> list[int]:
    """``weigh_sum`` of every row of ``[G, D]`` counts, exact: one int64
    product where no sum can pass 2**63 (every count under ``2**b`` and
    ``b + D`` under 63 bits), Python ints a row otherwise."""
    pos = np.asarray(pos_counts, dtype=np.int64)
    neg = np.asarray(neg_counts, dtype=np.int64)
    depth = pos.shape[-1]
    top = int(max(pos.max(initial=0), neg.max(initial=0)))
    if top.bit_length() + depth >= 63:
        return [weigh_sum(p, q) for p, q in zip(pos, neg)]
    return ((pos - neg) @ (np.int64(1) << np.arange(depth, dtype=np.int64))).tolist()


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
