"""Chemical-similarity demo: Tanimoto search over molecule fingerprints.

Parity target: the reference's chemical-similarity usecase (reference:
docs/ examples — molecule fingerprints stored one-per-row, searched by
Tanimoto coefficient). TPU-native twist: the one-vs-all search is a fused
AND+popcount scan on the VPU, and the all-pairs variant becomes a single
bf16 matmul on the MXU (pilosa_tpu/ops/similarity.py) — an op shape the
reference's per-pair Go loops cannot express.

Run:

    python examples/chemical_similarity.py --molecules 8192

Fingerprints are synthetic 2048-bit Morgan-style vectors; structural
families share a base pattern so the search has real signal.
"""

from __future__ import annotations

import os as _os
import sys as _sys

# runnable from anywhere: put the repo root on sys.path
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import argparse
import os
import time

os.environ.setdefault("PILOSA_TPU_SHARD_WIDTH_EXP", "16")

import numpy as np

FP_BITS = 2048
FP_WORDS = FP_BITS // 32


def make_fingerprints(n: int, n_families: int = 64, seed: int = 3):
    """uint32[n, FP_WORDS]: family base pattern + per-molecule noise."""
    rng = np.random.default_rng(seed)
    fams = rng.integers(0, 2**32, (n_families, FP_WORDS), dtype=np.uint32)
    fams &= rng.integers(0, 2**32, (n_families, FP_WORDS), dtype=np.uint32)
    family = rng.integers(0, n_families, n)
    noise = rng.integers(0, 2**32, (n, FP_WORDS), dtype=np.uint32)
    noise &= rng.integers(0, 2**32, (n, FP_WORDS), dtype=np.uint32)
    noise &= rng.integers(0, 2**32, (n, FP_WORDS), dtype=np.uint32)
    return fams[family] | noise, family


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--molecules", type=int, default=8192)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--threshold", type=float, default=0.3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from pilosa_tpu.ops import similarity

    fps, family = make_fingerprints(args.molecules)
    print(f"{args.molecules:,} molecules × {FP_BITS}-bit fingerprints "
          f"({fps.nbytes / 1e6:.1f} MB packed)")

    matrix = jnp.asarray(fps)
    query = matrix[17]  # pick a molecule; its family-mates should surface

    # ---- one-vs-all Tanimoto top-k (fused AND+popcount scan)
    search = jax.jit(similarity.tanimoto_search, static_argnames=("k",))
    scores, ids = search(matrix, query, k=args.k)  # compile + warm
    jax.block_until_ready((scores, ids))
    t0 = time.perf_counter()
    scores, ids = search(matrix, query, k=args.k)
    jax.block_until_ready((scores, ids))
    dt = (time.perf_counter() - t0) * 1e3
    print(f"\ntop-{args.k} Tanimoto neighbours of molecule 17 "
          f"(family {family[17]})  [{dt:.2f} ms]:")
    for s, i in zip(np.asarray(scores), np.asarray(ids)):
        print(f"    molecule {i:6d}  family {family[i]:3d}  tanimoto={s:.3f}")

    # ---- all-pairs block: one MXU matmul
    n_block = min(args.molecules, 2048)
    block = matrix[:n_block]
    pair = jax.jit(similarity.tanimoto_matrix)
    sims = pair(block, block)  # compile + warm
    sims.block_until_ready()
    t0 = time.perf_counter()
    sims = pair(block, block)
    sims.block_until_ready()
    dt = (time.perf_counter() - t0) * 1e3
    n_pairs = n_block * n_block
    above = int((np.asarray(sims) >= args.threshold).sum()) - n_block
    print(f"\nall-pairs {n_block}×{n_block} Tanimoto matrix in {dt:.1f} ms "
          f"({n_pairs / (dt / 1e3) / 1e6:,.0f}M pairs/s)")
    print(f"pairs ≥ {args.threshold}: {above // 2:,} (excluding self-pairs)")


if __name__ == "__main__":
    main()
