"""GroupBy's device bodies, shared by the single-program engine
(executor/executor.py jits them) and the mesh engine (parallel/mesh.py
runs them inside ``shard_map`` under a psum tree).

None gathers a ``[K, S, W]`` copy of the candidate rows before it
starts: they read a row of a stack where it lies (``plane``: a dynamic
slice that fuses into its consumer), or gather the rows of one block of
shards at a time. Compiled for a v5e at the cell's shapes (64 group
masks by 32 rows of 128 shards) the whole-stack gathers were 70 planes
of temporaries beside the count pass and 76 beside the mask pass,
1.1-1.2 GiB that no budget knew of (tests/test_tpu_compile.py holds
what is left to ``TEMP_PLANES``).

The count pass of ONE mask against a level taller than a tile (the level
walk's first read: the filter against the 1,024 brands or the 256 cities
of ssb-24) is one pass over the whole stack instead (``whole_stack``):
each row's block of shards taken apart as a dynamic slice along the shard
axis loads at about 270 GB/s on a v5e, the whole stack as one reduction
at about 735.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu.ops.bitwise import popcount_rows
from pilosa_tpu.ops.bsi import EXISTS_ROW, OFFSET_ROW, SIGN_ROW

# Upper bound, in [S, W] planes, of the temporaries XLA allocates beside
# the arguments and outputs of ANY program of a GroupBy (the four below),
# whatever the number of groups: what the executor's transient ledger adds
# to the masks a GroupBy holds. tests/test_tpu_compile.py compiles them for
# a described v5e at taxi-128g's and ssb-24's shapes and holds
# memory_analysis() to it.
TEMP_PLANES = 1


def plane(m: jax.Array, r) -> jax.Array:
    """Row ``r`` of the row-major stack ``m [R, S, W]``; zeros where ``r``
    is outside it (the -1 of a padded row list, a row past the stack)."""
    rr = jnp.clip(r, 0, m.shape[0] - 1)
    p = jax.lax.dynamic_index_in_dim(m, rr, axis=0, keepdims=False)
    return jnp.where((r >= 0) & (r < m.shape[0]), p, jnp.uint32(0))


def _groups(masks: jax.Array) -> jax.Array:
    """A level's parent masks as ``[G, S, W]``; the root of a GroupBy is
    its filter's one ``[S, W]`` plane, taken as it is (an eager ``[None]``
    outside would be a device program of its own a query)."""
    return masks[None] if masks.ndim == 2 else masks


# Shards a block of the count pass, where the shard count divides by it
# (8 is the sublane count of a TPU tile: blocks of 2 or 4 shards made XLA
# copy the whole operands, 96 planes of temporaries). Inside a block the
# candidate rows stay put while the group masks go by, so the compiler can
# keep them in fast memory. One launch of 64 masks x 32 rows at 128 shards
# (my chip runs, PR 34): 47 ms row by row over whole planes (742 GB/s:
# every row re-reads every mask from HBM), 21 ms in blocks of 8 with the
# masks kept and the rows going by, 13.7 ms this way; blocks of 16: 18 ms.
SHARD_BLOCK = 8
# The most masks and candidate rows of one tile of the count pass (a block
# of shards of each): 64 x 32 is taxi-128g's largest level, whose rows stay
# in fast memory; a larger level goes tile by tile (ssb-24's 1,024 brands)
MASK_BLOCK, ROW_BLOCK = 64, 32


def mesh_tile_bytes(shards: int, words: int) -> int:
    """What a mesh of several chips adds to ``TEMP_PLANES`` on one device
    whose block of a plane is ``shards`` x ``words``. Compiled for a 2x2
    v5e, the count pass of more masks than a tile holds (the grouped sums
    of 128 or 256 groups) keeps the rows of a tile's block of shards
    (``_gathered_block``, hoisted out of the loop over chunks of masks) in
    HBM where one chip's program keeps them in fast memory: 8.7-9.1 planes
    at ssb-96x4's 24 shards a chip against one chip's 0.4-0.6
    (tests/test_tpu_compile.py). At most ROW_BLOCK rows of SHARD_BLOCK
    shards; 0 where the shards do not divide into blocks and the pass goes
    by whole planes."""
    return 0 if shards % SHARD_BLOCK else ROW_BLOCK * SHARD_BLOCK * words * 4


def _rows_block(matrix: jax.Array, rows: jax.Array, start) -> jax.Array:
    """The ``rows`` of the stack ``matrix [R, S, W]`` in the block of
    shards from ``start`` -> ``[K, block, W]``; zeros for a -1 (``take``
    would wrap it to the last row) or a row past the stack."""
    x = jax.lax.dynamic_slice_in_dim(matrix, start, SHARD_BLOCK, axis=1)
    rows = jnp.where(rows < 0, matrix.shape[0], rows)
    return jnp.take(x, rows, axis=0, mode="fill", fill_value=0)


def _gathered_block(matrix: jax.Array, rows: jax.Array, start) -> jax.Array:
    """``_rows_block`` as one gather of ``[K, block, W]``: for a stack too
    tall for its whole block of shards to be sliced out first (XLA lifts
    that slice out of the tiles' loop and keeps it)."""
    rr = jnp.clip(rows, 0, matrix.shape[0] - 1).astype(jnp.int32)
    x = jax.vmap(
        lambda r: jax.lax.dynamic_slice(
            matrix, (r, start, jnp.int32(0)), (1, SHARD_BLOCK, matrix.shape[2])
        )[0]
    )(rr)
    return jnp.where(((rows >= 0) & (rows < matrix.shape[0]))[:, None, None], x, jnp.uint32(0))


def _tile(masks: jax.Array, matrix: jax.Array, rows: jax.Array, start, gather=_rows_block,
          within=None) -> jax.Array:
    """The ``[G, block, W]`` masks of the block of shards from ``start`` x
    the ``rows`` of the stack -> int32 ``[G, K]``: the rows stay put while
    the masks go by. ``within(start, size)``, where given, is a
    ``[size, W]`` plane ANDed into every mask."""
    m = jax.lax.dynamic_slice_in_dim(masks, start, SHARD_BLOCK, axis=1)
    x = gather(matrix, rows, start)
    f = None if within is None else within(start, SHARD_BLOCK)
    return jax.lax.map(
        lambda mg: jnp.sum(
            popcount_rows(x & (mg if f is None else mg & f)[None]), axis=1, dtype=jnp.int32
        ),
        m,
    )


def whole_stack(masks, matrix, rows) -> bool:
    """Whether ``level_counts(masks, matrix, rows)`` (no ``within``) is one
    pass over the whole stack: one mask, more candidate rows than a tile
    holds, and a stack at most twice as tall as the rows (the pass reads
    every row, at under three times the rate of the tiles that read only
    the candidates). None of these shapes is sharded, so the executor
    counts with this, from the launch's arguments, what every device's
    program does."""
    n_groups = 1 if masks.ndim == 2 else masks.shape[0]
    k = rows.shape[0]
    return n_groups == 1 and ROW_BLOCK < k and matrix.shape[0] <= 2 * k


def _whole_stack(mask: jax.Array, matrix: jax.Array, rows: jax.Array) -> jax.Array:
    """The ``[S, W]`` mask x every row of ``matrix [R, S, W]`` in one
    reduction (int32 along the words, int64 over the shards), then the K
    candidate ``rows`` picked -> int64 ``[1, K]``; 0 for a -1 or a row past
    the stack. One launch against 1,024 rows of 24 shards on a v5e: 4.38
    ms, against 12.46 in gathered tiles; summed in int32 over the shards
    too, XLA compiles a reduction that takes 11.9."""
    n_rows = matrix.shape[0]
    counts = jnp.sum(popcount_rows(matrix & mask[None]).astype(jnp.int64), axis=1)
    ok = (rows >= 0) & (rows < n_rows)
    return jnp.where(ok, counts[jnp.clip(rows, 0, n_rows - 1)], 0)[None]


def level_counts(
    masks: jax.Array, matrix: jax.Array, rows: jax.Array, within=None
) -> jax.Array:
    """``[G, S, W]`` group masks x the K candidate ``rows`` (ids into the
    ``[R, S, W]`` stack, -1 padding) -> int64 ``[G, K]`` columns in each
    (group, row) pair. Popcounts accumulate in int32 along the word axis
    and inside a block of shards (at most 2**23 bits); only the small
    partials widen. ``within(start, size)``, where given, is the
    ``[size, W]`` plane of the shards from ``start`` that every mask is
    ANDed with first (``grouped_sums``' sign planes).

    One mask against a level taller than a tile is one pass over the
    whole stack (``whole_stack``). Otherwise, over blocks of shards, one
    tile of at most ``MASK_BLOCK`` masks by ``ROW_BLOCK`` rows holds its
    rows while the masks go by, and a larger pass goes tile by tile."""
    if within is None and whole_stack(masks, matrix, rows):
        return _whole_stack(_groups(masks)[0], matrix, rows)
    masks = _groups(masks)
    n_groups, n_shards = masks.shape[:2]
    if n_shards % SHARD_BLOCK:
        # whole planes, one row at a time: the transient is one plane
        f = None if within is None else within(0, n_shards)

        def per_row(r):
            p = plane(matrix, r) if f is None else plane(matrix, r) & f
            return jnp.sum(popcount_rows(masks & p[None]).astype(jnp.int64), axis=1)

        return jax.lax.map(per_row, rows).T

    k = rows.shape[0]
    gc, kc = min(n_groups, MASK_BLOCK), min(k, ROW_BLOCK)

    def block(start):
        if (gc, kc) == (n_groups, k):
            return _tile(masks, matrix, rows, start, within=within)

        # tiles of at most MASK_BLOCK masks by ROW_BLOCK rows (the walks pad
        # both to powers of two). Whole, the block of a 1,024-row level is
        # 1 GiB, which no fast memory holds: compiled for a v5e at 24
        # shards XLA copied it out twice, 683 planes of temporaries that
        # the transient ledger did not count, and 128 masks' block once
        def row_chunk(rc):
            def mask_chunk(g0):
                return _tile(
                    jax.lax.dynamic_slice_in_dim(masks, g0, gc, axis=0), matrix, rc, start,
                    _gathered_block, within,
                )

            return jax.lax.map(mask_chunk, jnp.arange(n_groups // gc, dtype=jnp.int32) * gc)

        tiles = jax.lax.map(row_chunk, rows.reshape(k // kc, kc))  # [K/kc, G/gc, gc, kc]
        return jnp.transpose(tiles, (1, 2, 0, 3)).reshape(n_groups, k)

    starts = jnp.arange(n_shards // SHARD_BLOCK, dtype=jnp.int32) * SHARD_BLOCK
    return jnp.sum(jax.lax.map(block, starts).astype(jnp.int64), axis=0)


def grouped_sums(stack: jax.Array, masks: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """GroupBy's ``aggregate=Sum``: the BSI ``stack [R, S, W]`` (existence,
    sign and magnitude planes as ``ops.bsi`` lays them, cut to the field's
    depth) under ``[G, S, W]`` group masks -> (pos int64 ``[G, D]``, neg
    int64 ``[G, D]``, n int64 ``[G]``): ``ops.bsi.sum_counts`` of every
    group, D the magnitude planes the stack holds.

    The count pass with the magnitude planes and the existence plane for
    rows and the sign ANDed into every mask: inside a block of shards the
    planes stay put while the masks go by, where a group at a time
    re-read the whole block from HBM. The negative counts are a second
    such pass, run only where the stack holds a negative value at all;
    otherwise they are zeros, as counted."""
    depth = stack.shape[0] - OFFSET_ROW
    k = depth + 1  # the magnitudes, then the existence plane
    k_pad = k if k <= ROW_BLOCK else -(-k // ROW_BLOCK) * ROW_BLOCK
    rows = np.full(k_pad, -1, dtype=np.int32)
    rows[:depth] = np.arange(OFFSET_ROW, OFFSET_ROW + depth)
    rows[depth] = EXISTS_ROW

    def signed(neg: bool):
        def within(start, size):
            exists, sign = (
                jax.lax.dynamic_slice_in_dim(stack[r], start, size, axis=0)
                for r in (EXISTS_ROW, SIGN_ROW)
            )
            return exists & (sign if neg else ~sign)

        return within

    pos = level_counts(masks, stack, rows, signed(False))
    has_neg = jnp.any((stack[EXISTS_ROW] & stack[SIGN_ROW]) != 0)
    neg = jax.lax.cond(
        has_neg,
        lambda: level_counts(masks, stack, rows, signed(True)),
        lambda: jnp.zeros_like(pos),
    )
    return pos[:, :depth], neg[:, :depth], pos[:, depth] + neg[:, depth]


def chain_counts(
    filt: jax.Array,
    uppers: tuple,
    upper_rows: tuple,
    chains: jax.Array,
    n_chains: jax.Array,
    matrix: jax.Array,
    rows: jax.Array,
) -> jax.Array:
    """The counts of a whole ``GroupBy`` without an aggregate, with no
    group mask written to memory. ``filt`` is the filter's ``[S, W]``
    plane (``[1, S, W]`` on the mesh route), ``uppers`` the stacks of
    every level above the last and ``upper_rows`` each one's candidate
    row ids (-1 padding), ``chains [P, L-1]`` the parent chains as
    places in those lists, the ``n_chains`` real ones first, ``matrix``
    the last level's stack and ``rows`` its K candidate row ids (-1
    padding). -> int64 ``[P, K]``: entry ``[c, k]`` counts the columns of
    ``filt & uppers[0][upper_rows[0][chains[c, 0]]] & ... &
    matrix[rows[k]]``; the rows from ``n_chains`` on are 0 and cost
    nothing.

    The blocked form of ``level_counts``: inside a block of shards every
    level's candidate rows stay put (the only rows gathered, so the
    transient follows the query and not the stacks) while the chains go
    by, and a chain's mask is ANDed once a block from them."""
    filt = filt if filt.ndim == 2 else filt[0]
    chains = jnp.asarray(chains)  # indexed by the loop's traced counter
    n_shards = filt.shape[0]

    def zeros(dtype):
        # like the filter: inside shard_map the loop's carry then varies
        # over the mesh axes as its body's output does
        return jnp.zeros_like(filt, shape=(chains.shape[0], rows.shape[0]), dtype=dtype)

    if n_shards % SHARD_BLOCK:
        # whole planes, a chain's mask at a time: the transient is one plane
        def per_chain(c, acc):
            m = filt
            for level, (stack, ids) in enumerate(zip(uppers, upper_rows)):
                m = m & plane(stack, jnp.asarray(ids)[chains[c, level]])
            counts = jax.lax.map(
                lambda r: jnp.sum(popcount_rows(m & plane(matrix, r)).astype(jnp.int64)),
                rows,
            )
            return jax.lax.dynamic_update_index_in_dim(acc, counts, c, axis=0)

        return jax.lax.fori_loop(0, n_chains, per_chain, zeros(jnp.int64))

    def block(start):
        x = _rows_block(matrix, rows, start)
        f = jax.lax.dynamic_slice_in_dim(filt, start, SHARD_BLOCK, axis=0)
        ups = [_rows_block(u, ids, start) for u, ids in zip(uppers, upper_rows)]

        def per_chain(c, acc):
            m = f
            for level, u in enumerate(ups):
                m = m & jax.lax.dynamic_index_in_dim(u, chains[c, level], keepdims=False)
            counts = jnp.sum(popcount_rows(x & m[None]), axis=1, dtype=jnp.int32)
            return jax.lax.dynamic_update_index_in_dim(acc, counts, c, axis=0)

        return jax.lax.fori_loop(0, n_chains, per_chain, zeros(jnp.int32))

    starts = jnp.arange(n_shards // SHARD_BLOCK, dtype=jnp.int32) * SHARD_BLOCK
    return jnp.sum(jax.lax.map(block, starts).astype(jnp.int64), axis=0)


def pair_masks(
    masks: jax.Array, matrix: jax.Array, g_idx: jax.Array, row_sel: jax.Array
) -> jax.Array:
    """The masks of P (parent group, row) pairs -> ``[P, S, W]``: parent
    ``g_idx[p]`` AND row ``row_sel[p]`` (-1: an all-zero padding mask)."""
    masks = _groups(masks)
    return jax.lax.map(
        lambda gr: plane(masks, gr[0]) & plane(matrix, gr[1]), (g_idx, row_sel)
    )
