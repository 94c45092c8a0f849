"""Roaring ↔ dense packed-word conversion (the TPU interchange boundary).

The device-side representation of a fragment is a dense packed bit matrix
``uint32[rows, WORDS_PER_SHARD]`` (see SURVEY.md §7): XLA wants static
shapes and vectorised bitwise ops, so roaring is only the at-rest / import
format and everything hot runs on packed words. These helpers convert a
host Bitmap range to/from packed uint32 words.
"""

from __future__ import annotations

import numpy as np

from pilosa_tpu.roaring import containers as ct
from pilosa_tpu.roaring.bitmap import Bitmap
from pilosa_tpu.shardwidth import BITS_PER_WORD


def pack_range(bitmap: Bitmap, start: int, stop: int) -> np.ndarray:
    """Pack bits for positions [start, stop) into uint32 words.

    ``stop - start`` must be a multiple of 32. Bit ``p`` (absolute) maps to
    word ``(p - start) // 32``, bit ``(p - start) % 32`` (little-endian bit
    order within a word).
    """
    width = stop - start
    if width % BITS_PER_WORD:
        raise ValueError("range width must be a multiple of 32")
    if start % ct.CONTAINER_BITS or width % ct.CONTAINER_BITS:
        positions = (bitmap.range_values(start, stop) - np.uint64(start)).astype(np.int64)
        return pack_positions(positions, width)
    # container-aligned (every row of a fragment at the production
    # widths): a bitmap or run container is its own 2,048 words, copied
    # in place, with no unpacking to positions and packing again; only
    # the array containers' values are scattered, in one pass
    words = np.zeros(width // BITS_PER_WORD, dtype=np.uint32)
    span = ct.CONTAINER_BITS // BITS_PER_WORD
    arrays = []
    for key in bitmap._range_keys(start, stop):
        c = bitmap._containers[key]
        rel = (key << 16) - start
        if c.type == ct.TYPE_ARRAY:
            arrays.append(c.data.astype(np.int64) + rel)
        else:
            at = rel // BITS_PER_WORD
            words[at : at + span] = ct.as_words(c).view(np.uint32)
    if arrays:
        words |= pack_positions(np.concatenate(arrays), width)
    return words


def pack_positions(positions: np.ndarray, width: int) -> np.ndarray:
    """Pack sorted in-range bit positions into uint32[width // 32]."""
    from pilosa_tpu import native

    return native.pack_positions(np.asarray(positions, dtype=np.int64), width)


def unpack_words(words: np.ndarray) -> np.ndarray:
    """Set-bit positions (int64, ascending) of packed uint32 words."""
    from pilosa_tpu import native

    return native.unpack_words(words)


def words_count(words: np.ndarray) -> int:
    from pilosa_tpu import native

    return native.words_count(words)
