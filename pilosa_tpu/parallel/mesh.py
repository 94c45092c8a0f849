"""Device-mesh query execution: whole-index programs under one pjit.

Reference mapping (SURVEY.md §3 parallelism inventory): the reference's
only parallelism is shard scatter-gather over HTTP (executor.go mapReduce →
mapperLocal goroutines / mapperRemote HTTP). On a TPU pod the same shards
live as one stacked dense array across a ``jax.sharding.Mesh`` and the
reduce is an XLA collective over ICI, not an HTTP merge:

- mesh axis ``"shards"``  — data parallelism over the column space
  (shard s ↔ column range [s·SHARD_WIDTH, (s+1)·SHARD_WIDTH));
- mesh axis ``"words"``   — intra-shard parallelism over the packed word
  dimension: one logical row is a distributed bit-vector, the long-context
  / sequence-parallel analogue (a 10B-column row never materializes on one
  chip); cross-device ops on it are elementwise, only aggregations
  communicate (psum tree over ICI).

Arrays (row-major: rows lead so a row gather reads a contiguous [S, W]
plane — see executor.compile.stack_view_matrices for the measured why):
    row matrix   uint32[R, S, W]  sharded P(None, "shards", "words")
    row/filter   uint32[S, W]     sharded P("shards", "words")
    BSI slices   uint32[D, S, W]  sharded P(None, "shards", "words")

All counts psum over both axes; TopN does a words-then-shards psum of the
per-row count vector, then a replicated top_k (the reference's two-phase
merge collapses into one collective).
"""

from __future__ import annotations

import functools
import math
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pilosa_tpu import ops
from pilosa_tpu.ops import bsi as bsi_ops
from pilosa_tpu.parallel import shard_map

AXIS_SHARDS = "shards"
AXIS_WORDS = "words"
_BOTH = (AXIS_SHARDS, AXIS_WORDS)

# ----------------------------------------------------- mesh read coverage
# The serving-path SPMD surface (docs/spmd.md). The analyzer's parity
# rule diffs these literals against the executor's BITMAP_CALLS: every
# bitmap call type must either have a MeshQueryEngine program (its
# planner closure runs inside shard_map) or carry an explicit fallback
# annotation here — a silent gap would 500 (or worse, mis-reduce) the
# day the router sends that call type down the mesh path.
MESH_PROGRAMS = {
    "Row",
    "Range",
    "Union",
    "Intersect",
    "Difference",
    "Xor",
    "Not",
    "All",
}
# Aggregates served as mesh programs (psum/all_gather reduction trees —
# the multi-node merge transforms, intra-mesh and on-device).
MESH_AGGREGATES = {"Count", "Sum", "Min", "Max", "TopN", "GroupBy"}
# Host-fallback annotations: call types the mesh route hands back to the
# single-program device path (which still executes SPMD via the stacks'
# NamedSharding — GSPMD inserts the cross-device carries shard_map makes
# explicit).
#   Shift — the cross-word bit carry (ops.bitwise.shift_words rolls the
#   packed word axis) crosses device boundaries whenever the words axis
#   is split; expressing it under shard_map needs a words-axis
#   collective-permute chain that buys nothing for a metadata-rare call.
MESH_FALLBACK_CALLS = {"Shift"}


def mesh_supported(call) -> bool:
    """Can this call tree execute as explicit mesh (shard_map) programs?

    Walks the whole tree — a fallback-annotated call anywhere (e.g. a
    Shift inside an Intersect) sends the full query down the device
    path, since a mesh program cannot splice a non-SPMD subexpression.
    GroupBy's Rows() children and its aggregate=Sum() argument are row
    universes / aggregate specs, not bitmap subtrees — only their own
    filter children matter."""
    name = call.name
    if name == "Options":
        return all(mesh_supported(ch) for ch in call.children)
    if name in MESH_FALLBACK_CALLS:
        return False
    if name == "GroupBy":
        filt = call.arg("filter")
        if filt is not None and hasattr(filt, "name") and not mesh_supported(filt):
            return False
        return all(
            ch.name == "Rows" or mesh_supported(ch) for ch in call.children
        )
    if name in MESH_PROGRAMS or name in MESH_AGGREGATES:
        return all(mesh_supported(ch) for ch in call.children)
    return False


def make_mesh(devices=None, words_axis: int = 1) -> Mesh:
    """2-D device mesh (shards × words). ``words_axis`` > 1 splits the
    packed word dimension across devices (for giant rows); defaults to 1
    so every device owns whole shards."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n % words_axis:
        raise ValueError(f"{n} devices not divisible by words_axis={words_axis}")
    grid = np.array(devices).reshape(n // words_axis, words_axis)
    return Mesh(grid, (AXIS_SHARDS, AXIS_WORDS))


class MeshContext:
    """Serving-path device placement over a (shards × words) mesh.

    The executor's stacked field matrices are placed with a
    ``NamedSharding`` so every compiled query program runs SPMD across
    the mesh: elementwise bitwise ops stay local to each device's shard
    slice, and the Count/TopN/Sum reductions become XLA all-reduces over
    ICI (the reference's executor.go mapReduce HTTP merge, collapsed
    into collectives). Single-device processes use no context (None) and
    keep plain device arrays.
    """

    def __init__(self, mesh: Mesh, multihost: bool = False):
        self.mesh = mesh
        # multihost: the mesh spans >1 process. Host arrays are then
        # placed with jax.make_array_from_process_local_data — each
        # process contributes ITS addressable slice of the global array
        # (its owned shards), so a psum over the mesh is a GLOBAL
        # reduction with no HTTP merge. Requires every process to run the
        # same program in lockstep (jax.distributed SPMD contract).
        self.multihost = multihost

    @classmethod
    def auto(cls, words_axis: int = 1, devices=None) -> "MeshContext | None":
        """A context over all LOCAL devices, or None when only one device
        is visible (the sharded and unsharded programs are identical
        there — skip the placement overhead). Local, not global: the
        serving stack places host numpy arrays with jax.device_put, which
        requires every mesh device to be addressable by this process; the
        cross-host data plane goes through parallel.cluster scatter-gather
        (and multihost.make_multihost_mesh for explicit pod meshes)."""
        devices = list(devices if devices is not None else jax.local_devices())
        if len(devices) <= 1:
            return None
        return cls(make_mesh(devices, words_axis=words_axis))

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def _spec(self, n_shards: int, n_words: int, lead_dims: int) -> P:
        """Placement rule: shard the S axis over the mesh when it divides
        evenly (the data-parallel layout — whole shards per device);
        otherwise shard the packed word axis over ALL devices (always a
        power of two, so any shard count — even S=1 — still uses the full
        mesh); tiny odd shapes replicate. ``jax.device_put`` requires
        exact divisibility, hence the explicit rule instead of padding.
        ``lead_dims`` is the number of leading (row) dims BEFORE the shard
        axis — row-major stacks are [R, S, W], so the shards axis sits at
        position ``lead_dims``."""
        shard_rows = self.mesh.shape[AXIS_SHARDS]
        lead = (None,) * lead_dims
        if n_shards % shard_rows == 0 and n_words % self.mesh.shape[AXIS_WORDS] == 0:
            return P(*lead, AXIS_SHARDS, AXIS_WORDS)
        if n_words % self.n_devices == 0:
            return P(*lead, None, (AXIS_SHARDS, AXIS_WORDS))
        return P()

    def _check_uniform_s(self, s: int) -> None:
        """Global shape is ``s × process_count``, which is only coherent
        when every process contributes the SAME shard count — topology
        does not guarantee that (5 shards over 2 hosts), and a mismatch
        would hang the next collective with no diagnostic. Unconditional
        (never cached): _place is itself collective under the lockstep
        contract, and a per-value cache would desynchronize the group the
        first time one process's S diverges (the cached side would skip
        the allgather the other side enters)."""
        from jax.experimental import multihost_utils

        counts = np.asarray(multihost_utils.process_allgather(np.int64(s)))
        if not (counts == s).all():
            raise ValueError(
                f"multi-host placement needs a uniform per-process shard "
                f"count; got {counts.tolist()} — pad every process to the "
                "same S (empty shards are all-zero rows)"
            )

    def _place(self, arr, lead_dims: int):
        s = arr.shape[lead_dims]
        w = arr.shape[-1]
        if self.multihost:
            n_proc = jax.process_count()
            self._check_uniform_s(s)
            s_global = s * n_proc
            spec = self._spec(s_global, w, lead_dims)
            if len(spec) <= lead_dims or spec[lead_dims] != AXIS_SHARDS:
                raise ValueError(
                    f"multi-host placement needs the shards axis sharded: "
                    f"global S={s_global} not divisible by mesh "
                    f"{self.mesh.shape[AXIS_SHARDS]} shard rows"
                )
            global_shape = (
                arr.shape[:lead_dims] + (s_global,) + arr.shape[lead_dims + 1 :]
            )
            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, spec), arr, global_shape
            )
        return jax.device_put(arr, NamedSharding(self.mesh, self._spec(s, w, lead_dims)))

    def block_bytes(self, shape: tuple) -> int:
        """Bytes of one device's block of a uint32 stack of ``shape``
        [R, S, W] placed as ``place_stack`` places it (multi-host: S is
        this process's shards): what the fullest device holds of it. No
        device is touched — the stack ledger's projection before a build."""
        r, s, w = shape
        s *= jax.process_count() if self.multihost else 1
        spec = self._spec(s, w, 1)
        return math.prod(NamedSharding(self.mesh, spec).shard_shape((r, s, w))) * 4

    def place_stack(self, stacked):
        """uint32[R, S, W] (or [D, S, W] BSI block) → sharded device array.
        Multi-host: S is this process's shard count; the global array
        concatenates every process's slice along S."""
        return self._place(stacked, 1)

    def place_rows(self, arr):
        """uint32[S, W] → sharded device array."""
        return self._place(arr, 0)

    def place_block(self, arr):
        """Compressed container payload stores (tiered residency:
        sparse [H, K] id lists, run [H, K, 2] interval lists) → mesh-
        placed REPLICATED arrays.  Payload ids live in the stacked
        plane's global position space, so there is no [S, W] plane axis
        to shard; replication keeps the single-program SPMD path working
        — the decoded planes the query programs build from these blocks
        merge with sharded dense stacks under GSPMD as usual."""
        if self.multihost:
            # replication requires identical data on every process, but
            # container payloads are packed from process-local fragments
            # — the tiered layer disables itself on multi-host meshes
            # (StackCache.residency_mode), so reaching here is a bug
            raise ValueError(
                "compressed container stores cannot be placed on a "
                "multi-host mesh (process-local payloads are not "
                "replicable); over-budget fields use the slot path there"
            )
        return jax.device_put(arr, NamedSharding(self.mesh, P()))


class MeshQueryEngine:
    """Compiles and caches sharded query programs over a fixed mesh.

    Two program families live here:

    - the concrete demo/bench programs (count_and, topn, bsi_sum,
      tanimoto/cosine, ingest_and_aggregate) — fixed signatures, used by
      dryrun_multichip, the examples and the multichip bench;
    - the serving-path program BUILDERS (bitmap_tree, count_tree,
      topn_tree, sum_tree, minmax_tree, groupby_*_tree, …): each takes a
      query-compiler planner closure and wraps it in ``shard_map`` over
      this mesh, turning the whole PQL read call into one SPMD program
      whose reduction is a psum tree over ICI (words — the minor/fast
      axis — first, then shards). The executor caches the built
      programs per structural key like every other program.
    """

    def __init__(self, mesh: Mesh, stats=None):
        self.mesh = mesh
        # observability (/debug/vars meshExecution): program builds and
        # per-program-family call counts; a plain dict under a lock —
        # executor threads increment concurrently
        self._stats_lock = threading.Lock()
        self.programs_built = 0
        self.calls: dict[str, int] = {}
        self.fallbacks = 0
        # the same counts on /metrics (docs/spmd.md): the fallback
        # family starts at 0 so a scrape shows it before the first one
        self.stats = stats
        if stats is not None:
            stats.gauge("mesh_devices", self.n_devices)
            stats.count("mesh_fallbacks_total", 0)

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    # ------------------------------------------------- placement algebra
    def spec_mode(self, n_shards: int, n_words: int) -> str | None:
        """How a [.., S, W] stack maps onto this mesh — the SAME rule as
        MeshContext._spec, so the specs a program compiles against match
        the placement the stack cache already gave its arrays:

        - "grid":  S divides the shards axis and W the words axis —
          whole shard slices per device row (data parallel);
        - "words": W divides the full device count — the packed word
          axis spans every device (a 1-shard query still uses the whole
          mesh);
        - None:    tiny odd shapes replicate; no mesh program (the
          device path serves them — psum over replicated data would
          multiply by the axis size).
        """
        if (
            n_shards % self.mesh.shape[AXIS_SHARDS] == 0
            and n_words % self.mesh.shape[AXIS_WORDS] == 0
        ):
            return "grid"
        if n_words % self.n_devices == 0:
            return "words"
        return None

    def block_shape(self, n_shards: int, n_words: int, mode: str) -> tuple[int, int]:
        """Per-device (S_local, W_local) block of an [S, W] plane — what
        planner closures see inside shard_map (zero leaves must be
        block-shaped, not global)."""
        if mode == "grid":
            return (
                n_shards // self.mesh.shape[AXIS_SHARDS],
                n_words // self.mesh.shape[AXIS_WORDS],
            )
        return (n_shards, n_words // self.n_devices)

    def _arr_spec(self, lead: int, mode: str) -> P:
        """Spec for an array with ``lead`` unsharded leading dims before
        its [S, W] plane (stacks are [R, S, W] ⇒ lead=1)."""
        lead_none = (None,) * lead
        if mode == "grid":
            return P(*lead_none, AXIS_SHARDS, AXIS_WORDS)
        return P(*lead_none, None, _BOTH)

    def row_spec(self, mode: str) -> P:
        return self._arr_spec(0, mode)

    @staticmethod
    def _psum_both(v):
        """The cross-chip reduction tree: words (minor/ICI) hop first,
        then shards — the multi-node merge transforms' order, intra-mesh.
        Scoped ``pilosa.mesh_psum`` so the collectives carry a name in
        the compiled program's op metadata and on the device trace."""
        with jax.named_scope("pilosa.mesh_psum"):
            return jax.lax.psum(jax.lax.psum(v, AXIS_WORDS), AXIS_SHARDS)

    def _spmd(
        self, kind: str, local, in_specs, out_specs, check_rep: bool = True
    ):
        """One serving-path shard_map program, named ``pilosa_mesh_<kind>``
        on the device trace and in the compile counter (named_jit)."""
        from pilosa_tpu.executor.compile import named_jit

        prog = named_jit(
            f"pilosa_mesh_{kind}",
            shard_map(
                local,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_rep=check_rep,
            ),
        )
        with self._stats_lock:
            self.programs_built += 1
        return prog

    def note_call(self, name: str) -> None:
        with self._stats_lock:
            self.calls[name] = self.calls.get(name, 0) + 1
        if self.stats is not None:
            self.stats.count("mesh_program_calls_total", tags={"program": name})

    def note_fallback(self) -> None:
        """One read that a mesh route handed to the device path."""
        with self._stats_lock:
            self.fallbacks += 1
        if self.stats is not None:
            self.stats.count("mesh_fallbacks_total")

    def snapshot(self) -> dict:
        """Live view for /debug/vars (meshExecution)."""
        with self._stats_lock:
            calls = dict(self.calls)
            built, fallbacks = self.programs_built, self.fallbacks
        return {
            "devices": self.n_devices,
            "meshShape": {
                AXIS_SHARDS: int(self.mesh.shape[AXIS_SHARDS]),
                AXIS_WORDS: int(self.mesh.shape[AXIS_WORDS]),
            },
            "programsBuilt": built,
            "calls": calls,
            "fallbacks": fallbacks,
        }

    # --------------------------------------- serving-path program builders
    # Each builder closes over a planner closure ``run(arrays, scalars) →
    # uint32[S_local, W_local]`` (executor/compile.py plans it with this
    # mesh's block shape) and returns a jitted shard_map program. The
    # executor caches them per structural key; shapes retrace via jit.

    def bitmap_tree(self, run, mode: str):
        """(arrays [*,S,W]×N, scalars) → sharded uint32[S, W] — the whole
        bitmap call tree, elementwise per device block (no collectives)."""

        def local(arrays, scalars):
            return run(arrays, scalars)

        return self._spmd(
            "bitmap",
            local,
            (self._arr_spec(1, mode), P()),
            self.row_spec(mode),
        )

    def count_tree(self, run, mode: str):
        """(arrays, scalars) → replicated int64 count (psum tree)."""

        def local(arrays, scalars):
            words = run(arrays, scalars)
            return self._psum_both(
                jnp.sum(ops.popcount_rows(words).astype(jnp.int64))
            )

        return self._spmd("count", local, (self._arr_spec(1, mode), P()), P())

    def topn_tree(self, mode: str, filtered: bool, ids: bool, frun=None):
        """Per-row global counts int64[R] (or [K] for ids=), replicated:
        local masked popcounts, psum over words-then-shards. The filter
        expression (when present) computes INSIDE the program from its
        own planner closure — never materialized between dispatches."""

        def row_counts(matrix, filt):
            m = matrix & filt[None] if filt is not None else matrix
            return jnp.sum(ops.popcount_rows(m).astype(jnp.int64), axis=1)

        spec3 = self._arr_spec(1, mode)
        if ids and filtered:

            def local(matrix, row_ids, farrays, fscalars):
                g = jnp.take(matrix, row_ids, axis=0, mode="fill", fill_value=0)
                return self._psum_both(row_counts(g, frun(farrays, fscalars)))

            return self._spmd(
                "topn", local, (spec3, P(), spec3, P()), P()
            )
        if ids:

            def local(matrix, row_ids):
                g = jnp.take(matrix, row_ids, axis=0, mode="fill", fill_value=0)
                return self._psum_both(row_counts(g, None))

            return self._spmd("topn", local, (spec3, P()), P())
        if filtered:

            def local(matrix, farrays, fscalars):
                return self._psum_both(
                    row_counts(matrix, frun(farrays, fscalars))
                )

            return self._spmd("topn", local, (spec3, spec3, P()), P())

        def local(matrix):
            return self._psum_both(row_counts(matrix, None))

        return self._spmd("topn", local, (spec3,), P())

    def sum_tree(self, sum_fn, mode: str, frun=None):
        """BSI Sum: (stack [R,S,W], filter) → (pos[D], neg[D], n),
        replicated — ``sum_fn`` is Executor._sum_fn's, THE one reduction
        body (device and mesh stay in sync by construction). It takes
        the resident stack as placed (the plane axis is not sharded) and
        applies the field's depth rule inside this shard_map body."""
        spec3 = self._arr_spec(1, mode)
        if frun is not None:

            def local(slices, farrays, fscalars):
                pos, neg, n = sum_fn(slices, frun(farrays, fscalars))
                return (
                    self._psum_both(pos),
                    self._psum_both(neg),
                    self._psum_both(n),
                )

            return self._spmd(
                "sum", local, (spec3, spec3, P()), (P(), P(), P())
            )

        def local(slices, filt):
            pos, neg, n = sum_fn(slices, filt)
            return (
                self._psum_both(pos),
                self._psum_both(neg),
                self._psum_both(n),
            )

        return self._spmd(
            "sum", local, (spec3, self.row_spec(mode)), (P(), P(), P())
        )

    def grouped_sum_tree(self, grouped_sum_fn, mode: str):
        """(stack [R,S,W], masks [G,S,W]) → (pos[G,D], neg[G,D], n[G])
        replicated — GroupBy's aggregate=Sum under the same psum tree
        (``grouped_sum_fn`` is Executor._grouped_sum_fn's)."""
        spec3 = self._arr_spec(1, mode)

        def local(slices, masks):
            pos, neg, n = grouped_sum_fn(slices, masks)
            return (
                self._psum_both(pos),
                self._psum_both(neg),
                self._psum_both(n),
            )

        return self._spmd(
            "sum_groups", local, (spec3, spec3), (P(), P(), P())
        )

    def minmax_tree(self, minmax_fn, mode: str, frun=None):
        """BSI Min/Max: per-device per-shard extremes (``minmax_fn`` is
        Executor._minmax_fn's, the device route's body, on the resident
        stack), all-gathered to a replicated partial list the executor's
        finish() merges exactly like per-shard device partials
        (min/max-with-count merges associatively over disjoint column
        blocks).

        check_rep=False: all_gather's replication isn't statically
        inferred on the pinned jax — the gather of every block IS full
        replication, the checker just can't prove it."""
        spec3 = self._arr_spec(1, mode)

        def gather_all(v):
            v = jax.lax.all_gather(v, AXIS_WORDS).reshape(-1)
            return jax.lax.all_gather(v, AXIS_SHARDS).reshape(-1)

        def body(slices, filt):
            vals, counts = minmax_fn(slices, filt)
            return gather_all(vals), gather_all(counts)

        if frun is not None:

            def local(slices, farrays, fscalars):
                return body(slices, frun(farrays, fscalars))

            return self._spmd(
                "minmax",
                local,
                (spec3, spec3, P()),
                (P(), P()),
                check_rep=False,
            )

        def local(slices, filt):
            return body(slices, filt)

        return self._spmd(
            "minmax",
            local,
            (spec3, self.row_spec(mode)),
            (P(), P()),
            check_rep=False,
        )

    def groupby_counts_tree(self, mode: str):
        """(masks [G,S,W], matrix [R,S,W], rows [K]) → int64[G,K]
        replicated — the level-synchronous GroupBy count pass with the
        per-level merge as one psum tree (executor._gb_counts, intra-mesh)."""
        spec3 = self._arr_spec(1, mode)

        def local(masks, matrix, rows):
            return self._psum_both(ops.groupby.level_counts(masks, matrix, rows))

        return self._spmd("groupby_counts", local, (spec3, spec3, P()), P())

    def groupby_masks_tree(self, mode: str):
        """(masks, matrix, g_idx, row_sel) → sharded [P,S,W] surviving
        group masks — pure elementwise select+AND, no collectives."""
        spec3 = self._arr_spec(1, mode)

        return self._spmd(
            "groupby_masks", ops.groupby.pair_masks, (spec3, spec3, P(), P()), spec3
        )

    def groupby_chains_tree(self, mode: str):
        """(filter [1,S,W], upper stacks, their rows, chains [P, L-1],
        real chains, matrix [R,S,W], rows [K]) → int64[P,K] replicated: a
        whole GroupBy without an aggregate, no mask made, one psum tree
        (executor._gb_chains, intra-mesh)."""
        spec3 = self._arr_spec(1, mode)

        def local(filt, uppers, upper_rows, chains, n_chains, matrix, rows):
            return self._psum_both(ops.groupby.chain_counts(
                filt, uppers, upper_rows, chains, n_chains, matrix, rows
            ))

        return self._spmd(
            "groupby_chains", local, (spec3, spec3, P(), P(), P(), spec3, P()), P()
        )

    # ------------------------------------------------------------ placement
    def spec_matrix(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(None, AXIS_SHARDS, AXIS_WORDS))

    def spec_row(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(AXIS_SHARDS, AXIS_WORDS))

    def place_matrix(self, stacked: np.ndarray):
        """uint32[R, S, W] (row-major) → device, sharded (shards, words)."""
        return jax.device_put(stacked, self.spec_matrix())

    def place_row(self, stacked: np.ndarray):
        """uint32[S, W] → device."""
        return jax.device_put(stacked, self.spec_row())

    # ------------------------------------------------------------- programs
    def count_and(self, a, b):
        return self._count_and_prog(a, b)

    def topn(self, matrix, filt, k: int):
        return self._topn_prog(matrix, filt, k)

    def bsi_sum(self, slices, filt):
        return self._bsi_sum_prog(slices, filt)

    def ingest_and_aggregate(self, matrix, delta, filt):
        return self._ingest_prog(matrix, delta, filt)

    @functools.cached_property
    def _count_and_prog(self):
        @jax.jit
        @functools.partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(P(AXIS_SHARDS, AXIS_WORDS), P(AXIS_SHARDS, AXIS_WORDS)),
            out_specs=P(),
        )
        def prog(a, b):
            local = ops.count_and(a, b)  # staged i32→i64 (see ops.popcount)
            return jax.lax.psum(jax.lax.psum(local, AXIS_WORDS), AXIS_SHARDS)

        return prog

    @functools.cached_property
    def _topn_prog(self):
        """(matrix [R,S,W], filt [S,W]) → per-row global counts int64[R]
        (psum over both axes; top_k happens on the replicated vector)."""

        @functools.partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(P(None, AXIS_SHARDS, AXIS_WORDS), P(AXIS_SHARDS, AXIS_WORDS)),
            out_specs=P(),
        )
        def counts_prog(matrix, filt):
            # [R, S_local] i32; i64 only past this point (layout: count_and)
            per = ops.popcount_rows(matrix & filt[None])
            local = jnp.sum(per.astype(jnp.int64), axis=1)
            return jax.lax.psum(jax.lax.psum(local, AXIS_WORDS), AXIS_SHARDS)

        @functools.partial(jax.jit, static_argnums=(2,))
        def prog(matrix, filt, k: int):
            counts = counts_prog(matrix, filt)
            k = min(k, counts.shape[0])
            vals, ids = jax.lax.top_k(counts, k)
            return vals, ids.astype(jnp.int32)

        return prog

    @functools.cached_property
    def _tanimoto_prog(self):
        """(matrix [R,S,W], query [S,W]) → (scores f32[k], ids i32[k]) —
        BASELINE config 5 (chemical-similarity search) as ONE SPMD
        program: per-device partial |a∩q| and |a| popcounts, psum over
        words-then-shards (the words hop rides the fast/ICI minor axis),
        Tanimoto on the replicated vectors, top_k replicated."""

        @functools.partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(P(None, AXIS_SHARDS, AXIS_WORDS), P(AXIS_SHARDS, AXIS_WORDS)),
            out_specs=(P(), P(), P()),
        )
        def counts_prog(matrix, query):
            inter = jnp.sum(
                ops.popcount_rows(matrix & query[None]).astype(jnp.int64),
                axis=1,
            )
            row_pop = jnp.sum(
                ops.popcount_rows(matrix).astype(jnp.int64), axis=1
            )
            q_pop = jnp.sum(ops.popcount_rows(query).astype(jnp.int64))
            red = lambda v: jax.lax.psum(
                jax.lax.psum(v, AXIS_WORDS), AXIS_SHARDS
            )
            return red(inter), red(row_pop), red(q_pop)

        @functools.partial(jax.jit, static_argnums=(2,))
        def prog(matrix, query, k: int):
            inter, row_pop, q_pop = counts_prog(matrix, query)
            inter = inter.astype(jnp.float32)
            union = row_pop.astype(jnp.float32) + q_pop.astype(jnp.float32) - inter
            scores = jnp.where(union > 0, inter / union, 0.0)
            k = min(k, scores.shape[0])
            vals, ids = jax.lax.top_k(scores, k)
            return vals, ids.astype(jnp.int32)

        return prog

    def tanimoto(self, matrix, query, k: int):
        return self._tanimoto_prog(matrix, query, k)

    @functools.cached_property
    def _cosine_prog(self):
        """(matrix [R,S,W], query [S,W]) → (scores f32[k], ids i32[k]) —
        the cosine twin of the Tanimoto search: same psum tree, scores
        |a∩q| / sqrt(|a|·|q|) on the replicated count vectors."""

        @functools.partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(P(None, AXIS_SHARDS, AXIS_WORDS), P(AXIS_SHARDS, AXIS_WORDS)),
            out_specs=(P(), P(), P()),
        )
        def counts_prog(matrix, query):
            inter = jnp.sum(
                ops.popcount_rows(matrix & query[None]).astype(jnp.int64),
                axis=1,
            )
            row_pop = jnp.sum(
                ops.popcount_rows(matrix).astype(jnp.int64), axis=1
            )
            q_pop = jnp.sum(ops.popcount_rows(query).astype(jnp.int64))
            return (
                self._psum_both(inter),
                self._psum_both(row_pop),
                self._psum_both(q_pop),
            )

        @functools.partial(jax.jit, static_argnums=(2,))
        def prog(matrix, query, k: int):
            inter, row_pop, q_pop = counts_prog(matrix, query)
            denom = jnp.sqrt(
                row_pop.astype(jnp.float32) * q_pop.astype(jnp.float32)
            )
            scores = jnp.where(
                denom > 0, inter.astype(jnp.float32) / denom, 0.0
            )
            k = min(k, scores.shape[0])
            vals, ids = jax.lax.top_k(scores, k)
            return vals, ids.astype(jnp.int32)

        return prog

    def cosine(self, matrix, query, k: int):
        return self._cosine_prog(matrix, query, k)

    # ------------------------------------------- all-pairs (MXU) programs
    # The paper's matmul-shaped workload (arXiv 2112.09017): pairwise
    # similarity between two fingerprint sets as ONE distributed matmul.
    # Bits unpack to {0,1} bf16 per device block, the per-block dot
    # rides the MXU, and the contraction over the split word axis is a
    # psum — rows of ``a`` stay sharded over the shards axis, so the
    # [N, M] score matrix never replicates.

    def place_allpairs(self, a: np.ndarray, b: np.ndarray):
        """(a uint32[N, W], b uint32[M, W]) → placed device pair: a rows
        sharded over the shards axis (words over words), b replicated
        over shards (every device row scores its a-slice against all of
        b). N must divide the shards axis and W the words axis."""
        if a.shape[0] % self.mesh.shape[AXIS_SHARDS]:
            raise ValueError(
                f"N={a.shape[0]} rows not divisible by the shards axis "
                f"({self.mesh.shape[AXIS_SHARDS]})"
            )
        if a.shape[-1] % self.mesh.shape[AXIS_WORDS]:
            raise ValueError(
                f"W={a.shape[-1]} words not divisible by the words axis "
                f"({self.mesh.shape[AXIS_WORDS]})"
            )
        a_dev = jax.device_put(
            a, NamedSharding(self.mesh, P(AXIS_SHARDS, AXIS_WORDS))
        )
        b_dev = jax.device_put(
            b, NamedSharding(self.mesh, P(None, AXIS_WORDS))
        )
        return a_dev, b_dev

    def _pairwise_prog(self, kind: str):
        from pilosa_tpu.ops.similarity import _unpack_bits_bf16

        @functools.partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(P(AXIS_SHARDS, AXIS_WORDS), P(None, AXIS_WORDS)),
            out_specs=P(AXIS_SHARDS, None),
        )
        def prog(a, b):
            a_bits = _unpack_bits_bf16(a)
            b_bits = _unpack_bits_bf16(b)
            inter = jax.lax.psum(
                jnp.dot(a_bits, b_bits.T, preferred_element_type=jnp.float32),
                AXIS_WORDS,
            )
            a_pop = jax.lax.psum(
                ops.popcount_rows(a).astype(jnp.float32), AXIS_WORDS
            )
            b_pop = jax.lax.psum(
                ops.popcount_rows(b).astype(jnp.float32), AXIS_WORDS
            )
            if kind == "tanimoto":
                union = a_pop[:, None] + b_pop[None, :] - inter
                return jnp.where(union > 0, inter / union, 0.0)
            denom = jnp.sqrt(a_pop[:, None] * b_pop[None, :])
            return jnp.where(denom > 0, inter / denom, 0.0)

        return jax.jit(prog)

    @functools.cached_property
    def _pairwise_tanimoto_prog(self):
        return self._pairwise_prog("tanimoto")

    @functools.cached_property
    def _pairwise_cosine_prog(self):
        return self._pairwise_prog("cosine")

    def pairwise_tanimoto(self, a, b):
        """All-pairs Tanimoto over a placed pair → f32[N, M], rows
        sharded (ops.similarity.tanimoto_matrix, distributed)."""
        return self._pairwise_tanimoto_prog(a, b)

    def pairwise_cosine(self, a, b):
        return self._pairwise_cosine_prog(a, b)

    @functools.cached_property
    def _bsi_sum_prog(self):
        """(slices [D,S,W], filt [S,W]) → (sum int64, count int64)."""

        @jax.jit
        @functools.partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(P(None, AXIS_SHARDS, AXIS_WORDS), P(AXIS_SHARDS, AXIS_WORDS)),
            out_specs=(P(), P()),
        )
        def prog(slices, filt):
            exists = slices[bsi_ops.EXISTS_ROW]
            sign = slices[bsi_ops.SIGN_ROW]
            mag = slices[bsi_ops.OFFSET_ROW :]
            pos = (exists & ~sign & filt)[None]
            neg = (exists & sign & filt)[None]
            depth = mag.shape[0]
            weights = jnp.asarray([1 << k for k in range(depth)], dtype=jnp.int64)
            pc = jnp.sum(ops.popcount_rows(mag & pos).astype(jnp.int64), axis=1)
            nc = jnp.sum(ops.popcount_rows(mag & neg).astype(jnp.int64), axis=1)
            local_sum = jnp.sum((pc - nc) * weights)
            local_n = ops.popcount(exists & filt)
            total = jax.lax.psum(jax.lax.psum(local_sum, AXIS_WORDS), AXIS_SHARDS)
            n = jax.lax.psum(jax.lax.psum(local_n, AXIS_WORDS), AXIS_SHARDS)
            return total, n

        return prog

    @functools.cached_property
    def _ingest_prog(self):
        """The full "step": apply a packed write delta to the row matrix
        (device-side ingest, the donated-buffer mutation path) then compute
        the standing aggregates — one compiled program, zero host round
        trips (reference analogue: fragment.bulkImport + executor pass).

        (matrix [R,S,W], delta [R,S,W], filt [S,W])
            → (new_matrix, per-row counts int64[R], total int64)
        """

        @functools.partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(
                P(None, AXIS_SHARDS, AXIS_WORDS),
                P(None, AXIS_SHARDS, AXIS_WORDS),
                P(AXIS_SHARDS, AXIS_WORDS),
            ),
            out_specs=(P(None, AXIS_SHARDS, AXIS_WORDS), P(), P()),
        )
        def prog(matrix, delta, filt):
            new_matrix = matrix | delta
            local_counts = jnp.sum(
                ops.popcount_rows(new_matrix & filt[None]).astype(jnp.int64),
                axis=1,
            )
            counts = jax.lax.psum(
                jax.lax.psum(local_counts, AXIS_WORDS), AXIS_SHARDS
            )
            total = jnp.sum(counts)
            return new_matrix, counts, total

        return jax.jit(prog, donate_argnums=(0,))


def stack_field_matrices(field, shards: list[int]) -> np.ndarray:
    """Stack a field's standard-view fragment matrices → uint32[R, S, W]
    (host-side, row-major; rows padded to the max across shards)."""
    from pilosa_tpu.core import VIEW_STANDARD
    from pilosa_tpu.executor.compile import stack_view_matrices

    return stack_view_matrices(field.view(VIEW_STANDARD), shards)[0]
