"""Top-k over row counts (TopN phases) on device.

Reference: executor.go (executeTopN two-phase) + fragment.go (top) +
cache.go (rankCache). Phase 1 in the reference reads a per-fragment rank
cache and scans candidate rows per shard; on TPU the whole row matrix is
resident, so phase 1 is one fused masked-popcount over every row followed
by ``lax.top_k`` — and phase 2 (exact recount of the merged candidate set)
is a batched gather + masked popcount.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pilosa_tpu.ops.bitwise import matrix_filter_counts


@jax.named_scope("pilosa.topn_rows")
def top_rows(matrix: jax.Array, filt: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """(counts int32[k], row_ids int32[k]) of the k largest filtered row
    counts in one fragment. Rows with zero count still appear if k exceeds
    the number of nonzero rows; callers drop zeros."""
    counts = matrix_filter_counts(matrix, filt)
    k = min(k, counts.shape[0])
    vals, idx = jax.lax.top_k(counts, k)
    return vals, idx.astype(jnp.int32)


@jax.named_scope("pilosa.topn_candidates")
def candidate_counts(
    matrix: jax.Array, row_ids: jax.Array, filt: jax.Array
) -> jax.Array:
    """Phase-2 exact recount: gather candidate rows and popcount under the
    filter. ``row_ids`` int32[C] may contain out-of-range ids (rows another
    shard has but this one doesn't); they gather a zero row.

    Returns int32[C].
    """
    n_rows = matrix.shape[0]
    in_range = (row_ids >= 0) & (row_ids < n_rows)
    safe_ids = jnp.where(in_range, row_ids, 0)
    gathered = matrix[safe_ids]
    counts = matrix_filter_counts(gathered, filt)
    return jnp.where(in_range, counts, 0)
