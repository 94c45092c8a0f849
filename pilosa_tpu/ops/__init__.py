"""TPU kernel library: the framework's native hot loops.

Replaces the reference's Go roaring CPU loops (roaring/roaring.go) and
executor aggregation loops (executor.go) with fused XLA programs over dense
packed words. Everything here is pure-functional and jit/shard_map
compatible; the executor composes these into per-query programs.

x64 is enabled process-wide: cross-shard Sum/Count reductions carry int64
on device (TPU emulates 64-bit integer ops; these are tiny scalar/[depth]
tensors, so the cost is noise next to the popcount scans).
"""

import os as _os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: query programs at pod scale take
# minutes to compile (the gather program at 10k shards); caching them on
# disk makes server restarts skip every compile. Where
# JAX_COMPILATION_CACHE_DIR is set (or a caller already configured a
# directory) nothing is set here. Otherwise the cache is ONE fixed
# directory inside the checkout: the path is part of the cache key, so
# it must never move, and only a directory under the checkout survives
# a sealed machine whose home directory is thrown away.
COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))),
    ".jax_cache",
)
if (
    not _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    and jax.config.jax_compilation_cache_dir is None
):
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
# wherever the cache lives: low enough that every query program of a cold
# boot is stored (small ones compile in well under a second on the chip),
# so a restart compiles nothing
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

from pilosa_tpu.ops import bsi, containers, groupby, similarity, topn
from pilosa_tpu.ops.bitwise import (
    column_mask,
    count_and,
    count_andnot,
    count_or,
    count_xor,
    matrix_filter_counts,
    popcount,
    popcount_rows,
    popcount_words,
    shift_words,
    w_and,
    w_andnot,
    w_not,
    w_or,
    w_xor,
)

__all__ = [
    "bsi",
    "containers",
    "groupby",
    "similarity",
    "topn",
    "column_mask",
    "count_and",
    "count_andnot",
    "count_or",
    "count_xor",
    "matrix_filter_counts",
    "popcount",
    "popcount_rows",
    "popcount_words",
    "shift_words",
    "w_and",
    "w_andnot",
    "w_not",
    "w_or",
    "w_xor",
]
