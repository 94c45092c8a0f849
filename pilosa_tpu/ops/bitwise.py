"""Packed-word bitwise kernels (the device hot loops).

Reference: roaring/roaring.go — intersectArrayArray/ArrayBitmap/BitmapBitmap,
unionRunRun, differenceBitmapRun, popcount helpers. The reference hand-writes
nine pairwise-typed CPU loops; on TPU every fragment row is a dense packed
``uint32[W]`` vector, so all set ops collapse to elementwise VPU bitwise ops
and counts to ``lax.population_count`` + reductions — XLA fuses the
op+popcount+sum chains into single kernels, which replaces the reference's
fused count loops (e.g. intersectionCount*).

All functions are jit-compatible and shape-polymorphic over leading batch
dims; ``W`` (words per shard) is the trailing axis.

Hand-scheduled Pallas versions of count_and / matrix_filter_counts were
measured against these on the real TPU (2026-07-29) and LOST at every
operand size — 0.51 vs 0.02 ms at 8 MB, 9.5 vs 4.0 ms at 128 MB, 20.1 vs
9.0 ms at 2 GB per operand — XLA's fusion pipelines the HBM stream better
at both ends of the range, so the kernels were deleted (round-2 review
item: no unreachable kernel path in the tree). Reintroduce Pallas only
for fusions XLA cannot express, with a measurement.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BITS_PER_WORD = 32


def w_and(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.bitwise_and(a, b)


def w_or(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.bitwise_or(a, b)


def w_xor(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.bitwise_xor(a, b)


def w_andnot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.bitwise_and(a, jnp.bitwise_not(b))


def w_not(a: jax.Array) -> jax.Array:
    """Complement. Caller must mask to the valid column range afterwards
    (Not() in PQL is bounded by the index's existence row)."""
    return jnp.bitwise_not(a)


def popcount_words(words) -> jax.Array:
    """Per-word popcount, same shape as input, int32."""
    return jax.lax.population_count(words).astype(jnp.int32)


def popcount(words) -> jax.Array:
    """Total set bits over all axes → int64 scalar.

    Two-stage accumulation: the trailing word axis reduces in int32 (one
    shard row holds ≤ 2^20 bits, so int32 cannot overflow), and only the
    tiny per-row vector widens to int64 for the cross-row total. The
    dtype staging matters for memory, not just overflow: with x64 on,
    a bare ``jnp.sum`` promotes the FULL ``[..., W]`` popcount tensor to
    int64 before reducing, and on TPU that int64 intermediate makes XLA
    relayout-copy the whole packed operand — at 10B columns that is a
    10 GiB HLO temp that OOMs HBM (measured 2026-07-30: the staged form
    compiles with 0 B temp, the promoted form exceeds HBM by 4.25 G).
    """
    return jnp.sum(popcount_rows(words).astype(jnp.int64))


# Kernels carry a ``pilosa.<kernel>`` named scope: inside a program that
# holds more than one (a filter's mask, then the popcount under it; the
# BSI planes' sum) the device trace's op names say which is which.
@jax.named_scope("pilosa.popcount_rows")
def popcount_rows(matrix) -> jax.Array:
    """Reduce the trailing word axis: ``uint32[..., W] → int32[...]``.

    int32 accumulation is forced (not promoted to int64 under x64) — safe
    per row (≤ 2^20 bits) and required so the packed operand keeps its
    stored layout; see popcount() for the relayout-OOM rationale.
    """
    return jnp.sum(popcount_words(matrix), axis=-1, dtype=jnp.int32)


# Fused op+count — these compile to a single XLA fusion (no materialized
# intermediate), the analogue of the reference's intersectionCount fast path.
def count_and(a, b) -> jax.Array:
    return popcount(jnp.bitwise_and(a, b))


def count_or(a, b) -> jax.Array:
    return popcount(jnp.bitwise_or(a, b))


def count_xor(a, b) -> jax.Array:
    return popcount(jnp.bitwise_xor(a, b))


def count_andnot(a, b) -> jax.Array:
    return popcount(jnp.bitwise_and(a, jnp.bitwise_not(b)))


@jax.named_scope("pilosa.filter_counts")
def matrix_filter_counts(matrix, filt) -> jax.Array:
    """Per-row filtered counts: ``uint32[R, W] & uint32[W] → int32[R]``.

    The workhorse of TopN phase 2 (exact candidate recount), Rows(), and
    GroupBy: one fused kernel over the whole row matrix instead of the
    reference's per-row fragment.top loops.
    """
    return popcount_rows(jnp.bitwise_and(matrix, filt[..., None, :]))


def shift_words(words: jax.Array, n: int) -> jax.Array:
    """Shift set-bit positions up by static ``n`` (PQL Shift): bit p → p+n,
    bits shifted past the end of the word vector fall off.

    Implemented as a word roll + cross-word carry. ``n`` is static so XLA
    sees fixed shift amounts.
    """
    if n < 0:
        raise ValueError(f"shift amount must be non-negative, got {n}")
    if n == 0:
        return words
    q, r = n // BITS_PER_WORD, n % BITS_PER_WORD
    w = words
    if q:
        w = jnp.roll(w, q, axis=-1)
        idx = jnp.arange(w.shape[-1])
        w = jnp.where(idx < q, jnp.uint32(0), w)
    if r:
        up = w << jnp.uint32(r)
        carry = jnp.roll(w, 1, axis=-1) >> jnp.uint32(BITS_PER_WORD - r)
        idx = jnp.arange(w.shape[-1])
        carry = jnp.where(idx == 0, jnp.uint32(0), carry)
        w = up | carry
    return w


def column_mask(width: int, n_words: int) -> jax.Array:
    """uint32[n_words] with the low ``width`` bits set — masks a shard's
    valid column range (the last shard of an index may be partial)."""
    idx = jnp.arange(n_words, dtype=jnp.int32)
    full = width // BITS_PER_WORD
    rem = width % BITS_PER_WORD
    w = jnp.where(idx < full, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    if rem:
        w = jnp.where(idx == full, jnp.uint32((1 << rem) - 1), w)
    return w
