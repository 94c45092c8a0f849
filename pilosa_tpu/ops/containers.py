"""Device kernels over COMPRESSED row containers.

The tiered residency layer (executor/residency.py, docs/device-residency.md)
keeps hot rows of over-budget fields on device in layout-adaptive
containers — dense packed words, sorted sparse column ids, or run
intervals — following the Roaring container taxonomy (arXiv 1402.6407 /
1603.06549) applied to device HBM instead of host RAM.

These kernels evaluate queries DIRECTLY over the compressed payloads:
the [S, W] word plane a query consumes is reconstructed *inside* the
consuming XLA program (scatter-to-mask for sparse ids, interval
arithmetic for runs), so the compressed form is what lives in HBM and
what crosses the memory bus between queries — decompression is a fused,
transient step of the query program, never a resident copy.  Counts
over sparse/run rows skip the plane entirely (``sparse_count`` /
``run_count`` read O(payload) values).

Position encoding: a payload id is a GLOBAL bit position in the stacked
plane's flattened [S * W * 32) bit space (shard-major, bit-minor — the
same order ``np.unpackbits(..., bitorder="little")`` yields on the
packed uint32 words).  int32 ids bound the plane at 2^31 bits; the
chooser (executor/residency.py) refuses sparse/run containers past
that, falling back to dense.

All functions are jit/shard_map compatible and pure.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# numpy, not jnp: a module-level jnp scalar is a device array, and making
# one initializes the backend at import
_FULL_WORD = np.uint32(0xFFFFFFFF)


def sparse_plane(ids, n_shards: int, n_words: int):
    """Sorted sparse ids ``int32[K]`` (−1 padding) → ``uint32[S, W]``.

    Scatter-to-mask: each id contributes its bit ``1 << (id & 31)`` to
    word ``id >> 5``.  Distinct ids target distinct (word, bit) pairs,
    so a scatter-ADD equals the scatter-OR XLA has no primitive for.
    Padding ids scatter out of bounds and drop.
    """
    total = n_shards * n_words
    valid = ids >= 0
    word = jnp.where(valid, ids >> 5, total)  # OOB ⇒ mode="drop" skips
    mask = jnp.where(
        valid, jnp.uint32(1) << (ids & 31).astype(jnp.uint32), jnp.uint32(0)
    )
    flat = jnp.zeros(total, jnp.uint32).at[word].add(mask, mode="drop")
    return flat.reshape(n_shards, n_words)


def run_plane(runs, n_shards: int, n_words: int):
    """Run intervals ``int32[K, 2]`` of [start, end) bit positions
    (0,0 padding) → ``uint32[S, W]`` by interval arithmetic, O(K + S·W):

    - FULL words inside a run accumulate through a coverage scatter
      (+1 at the first full word, −1 past the last) and a cumulative
      sum — coverage > 0 ⇒ all-ones word;
    - the ≤2 PARTIAL boundary words per run scatter their bit groups
      directly (maximal runs are disjoint, so scatter-ADD equals the
      scatter-OR XLA lacks).

    The naive [K, S·W] per-(run, word) overlap product was measured
    ~60 ms per 8-row union on the CPU backend; this form is the same
    arithmetic with the K×W product replaced by one prefix sum.
    """
    total = n_shards * n_words
    lo, hi = runs[:, 0], runs[:, 1]
    empty = hi <= lo
    w_lo, b_lo = lo >> 5, (lo & 31).astype(jnp.uint32)
    w_hi, b_hi = hi >> 5, (hi & 31).astype(jnp.uint32)
    same = w_lo == w_hi
    # full-word coverage: [w_lo + (b_lo != 0), w_hi) — dropped when the
    # run lives in one word or is padding
    start_full = w_lo + (b_lo != 0)
    has_full = (~empty) & (start_full < w_hi)
    oob = jnp.int32(total + 1)
    delta = jnp.zeros(total + 2, jnp.int32)
    delta = delta.at[jnp.where(has_full, start_full, oob)].add(1, mode="drop")
    delta = delta.at[jnp.where(has_full, w_hi, oob)].add(-1, mode="drop")
    full = jnp.cumsum(delta)[:total] > 0
    # partial boundary words (disjoint bit groups ⇒ add == or)
    ones = _FULL_WORD
    head_mask = jnp.where(
        (~empty) & (~same) & (b_lo > 0), ones << b_lo, jnp.uint32(0)
    )
    tail_mask = jnp.where(
        (~empty) & (~same) & (b_hi > 0),
        (jnp.uint32(1) << b_hi) - jnp.uint32(1),
        jnp.uint32(0),
    )
    span = jnp.minimum(b_hi - b_lo, jnp.uint32(31))
    same_mask = jnp.where(
        (~empty) & same,
        ((jnp.uint32(1) << span) - jnp.uint32(1)) << b_lo,
        jnp.uint32(0),
    )
    partial = jnp.zeros(total, jnp.uint32)
    partial = partial.at[jnp.where(head_mask > 0, w_lo, oob)].add(
        head_mask, mode="drop"
    )
    partial = partial.at[jnp.where(tail_mask > 0, w_hi, oob)].add(
        tail_mask, mode="drop"
    )
    partial = partial.at[jnp.where(same_mask > 0, w_lo, oob)].add(
        same_mask, mode="drop"
    )
    flat = jnp.where(full, ones, jnp.uint32(0)) | partial
    return flat.reshape(n_shards, n_words)


def sparse_count(ids) -> jnp.ndarray:
    """Set-bit count of a sparse container WITHOUT building the plane —
    every valid id is one bit. int64 scalar (matches count_async)."""
    return jnp.sum((ids >= 0).astype(jnp.int64))


def run_count(runs) -> jnp.ndarray:
    """Set-bit count of a run container — Σ (end − start); padding
    intervals are empty. int64 scalar (matches count_async)."""
    return jnp.sum((runs[:, 1] - runs[:, 0]).astype(jnp.int64))
