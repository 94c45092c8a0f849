"""Vectorized host (numpy) query engine — the below-crossover fast path.

Reference: executor.go mapperLocal never pays a dispatch it doesn't
need; PIMDAL (PAPERS.md) frames the same rule for analytics offload
generally.  Here, a query whose estimated work sits below the
calibrated host/device crossover (executor/router.py) executes entirely
on the host: numpy bitwise ops + ``np.bitwise_count`` over the SAME
packed ``uint32[R, S, W]`` stacks the device StackCache builds — so the
two engines read identical bits and must return identical results
(tests/test_routing.py asserts it for every PQL call type).

Why a second engine instead of jax-on-CPU: the device path pays
dispatch + readback per sync query (not measured on the v5e yet; see
PERF.md) plus scalar-operand uploads and the
``_Pending`` readback machinery.  A sub-millisecond query answers
faster than the device path can *ask*.  This engine strips all of it:

- host plans are compiled once and memoized per plan key (the call's
  structural repr + shard list) with field-identity and stack-version
  validation — a cache hit costs two dict lookups;
- popcounts run over uint64 views of the packed words (same bytes,
  half the elements — measured ~2x the uint32 chain) — this is how the
  host path beats the 1-core-numpy CPU baseline instead of merely
  matching it;
- no ``_Pending``, no device scalar upload, no readback wave: every
  result is a concrete Python value.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Callable

import numpy as np

from pilosa_tpu.core import (
    BSI_OFFSET,
    EXISTENCE_FIELD,
    FIELD_INT,
    FIELD_TIME,
    VIEW_BSI,
    VIEW_STANDARD,
    Field,
    Index,
)
from pilosa_tpu.core.timequantum import views_by_time_range
from pilosa_tpu.pql import Call, Condition, coerce_timestamp
from pilosa_tpu.shardwidth import WORDS_PER_SHARD

_ONES = np.uint32(0xFFFFFFFF)


class HostPlanError(ValueError):
    pass


def decode_container(
    kind: str, payload: np.ndarray, n_shards: int, n_words: int
) -> np.ndarray:
    """Host decode of a tiered-residency container payload → packed
    uint32[S, W] plane — the numpy inverse of residency.pack_container
    and the HOST equivalence branch for every container kind the device
    chooser can emit (ops/containers.py holds the device twins; the
    analyzer's parity rule pins the two surfaces together).  Used by the
    equivalence suite and the residency bench to prove bit-identical
    results across containers."""
    if kind == "dense":
        return np.asarray(payload, dtype=np.uint32).reshape(n_shards, n_words)
    bits = np.zeros(n_shards * n_words * 32, dtype=np.uint8)
    if kind == "sparse":
        ids = np.asarray(payload)
        bits[ids[ids >= 0]] = 1
    elif kind == "run":
        for lo, hi in np.asarray(payload).reshape(-1, 2):
            bits[lo:hi] = 1
    else:
        raise HostPlanError(f"unknown container kind {kind!r}")
    return (
        np.packbits(bits, bitorder="little")
        .view(np.uint32)
        .reshape(n_shards, n_words)
    )


def _popcount_sum(words: np.ndarray) -> int:
    # count through a uint64 view when possible: same bytes, half the
    # elements — measured ~2x faster than the uint32 chain, and the
    # margin that puts this engine ABOVE the 1-core numpy baseline
    # (which counts uint32) instead of tied with it
    if (
        words.dtype == np.uint32
        and words.flags.c_contiguous
        and words.nbytes % 8 == 0
    ):
        words = words.reshape(-1).view(np.uint64)
    return int(np.bitwise_count(words).sum())


# ------------------------------------------------------------- host stacks
class HostStacks:
    """Host-resident stacked (field, view) matrices — the numpy mirror of
    compile.StackCache, with the same (uid, version) token validation and
    the same whole-view ``view.version`` O(1) fast path, so a cache hit
    costs one dict lookup regardless of shard count.

    Entries share no memory with the device cache; they are built from
    the same fragment host matrices via ``stack_view_matrices``.  Point
    writes apply as in-place dirty-row scatters (numpy assignment —
    O(dirty rows), not O(stack)).  Fields whose stack would exceed the
    host budget are served in GATHER mode: ``matrix`` returns None and
    the caller assembles [S, W] planes row-by-row from the fragments.
    """

    MAX_ENTRIES = 32
    MAX_DELTA_ROWS = 4096

    def __init__(self):
        self._cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._lock = threading.Lock()

    @staticmethod
    def budget() -> int:
        env = os.environ.get("PILOSA_TPU_HOST_STACK_BUDGET")
        return int(env) if env else 8 << 30

    @staticmethod
    def _frag_token(view, shard: int) -> tuple:
        frag = view.fragment(shard) if view else None
        return (-1, -1) if frag is None else (frag.uid, frag.version)

    def matrix(
        self, idx: Index, field: Field, view_name: str, shards: list[int]
    ) -> tuple[np.ndarray | None, int]:
        """(np uint32[R, S, W], n_rows) — or (None, n_rows) when the
        stack would exceed the host budget (gather mode)."""
        from pilosa_tpu.executor.compile import StackCache, stack_view_matrices

        view = field.view(view_name)
        key = (idx.name, field.name, view_name, tuple(shards))
        view_ver = view.version if view is not None else None
        with self._lock:
            cached = self._cache.get(key)
            if (
                cached is not None
                and view_ver is not None
                and cached[3] == view_ver
            ):
                self._cache.move_to_end(key)
                return cached[1], cached[2]
        r_pad = StackCache._projected_rows(view, shards)
        if len(shards) * r_pad * WORDS_PER_SHARD * 4 > self.budget():
            return None, r_pad
        with self._lock:
            cached = self._cache.get(key)
            versions = tuple(self._frag_token(view, s) for s in shards)
            if cached is not None:
                if cached[0] == versions:
                    self._cache[key] = (versions, cached[1], cached[2], view_ver)
                    self._cache.move_to_end(key)
                    return cached[1], cached[2]
                entry = self._try_delta(cached, view, shards, versions, view_ver)
                if entry is not None:
                    self._cache[key] = entry
                    self._cache.move_to_end(key)
                    return entry[1], entry[2]
            stacked, max_rows = stack_view_matrices(view, shards)
            self._cache[key] = (versions, stacked, max_rows, view_ver)
            self._cache.move_to_end(key)
            while len(self._cache) > self.MAX_ENTRIES:
                self._cache.popitem(last=False)
            return stacked, max_rows

    def _try_delta(self, cached, view, shards, versions, view_ver):
        """In-place dirty-row application (caller holds the lock).  A
        query racing a write may read a row mid-assignment — the same
        last-writer-wins semantics the device scatter path has."""
        old_versions, mat, max_rows = cached[0], cached[1], cached[2]
        updates: list[tuple[int, int]] = []
        for i, s in enumerate(shards):
            old_uid, old_ver = old_versions[i]
            if (old_uid, old_ver) == versions[i]:
                continue
            if old_uid != versions[i][0]:
                return None
            frag = view.fragment(s)
            if frag is None:
                return None
            dirty = frag.dirty_rows_since(old_ver)
            if dirty is None:
                return None
            if len(updates) + len(dirty) > self.MAX_DELTA_ROWS:
                return None
            host_m, _n = frag.host_matrix()
            if host_m.shape[0] > max_rows:
                return None
            for r in sorted(dirty):
                if r >= max_rows:
                    return None
                updates.append((i, r))
        for i, r in updates:
            frag = view.fragment(shards[i])
            host_m, _n = frag.host_matrix()
            mat[r, i] = (
                host_m[r] if r < host_m.shape[0] else 0
            )
        return (versions, mat, max_rows, view_ver)

    def gather_row(
        self, field: Field, view_name: str, shards: list[int], row_id: int
    ) -> np.ndarray:
        """[S, W] plane for one row, assembled from fragments (gather
        mode — over-budget fields only)."""
        view = field.view(view_name)
        out = np.zeros((len(shards), WORDS_PER_SHARD), dtype=np.uint32)
        if view is None or row_id < 0:
            return out
        for i, s in enumerate(shards):
            frag = view.fragment(s)
            if frag is not None:
                out[i] = frag.row_packed(row_id)
        return out

    def invalidate(self) -> None:
        with self._lock:
            self._cache.clear()


# -------------------------------------------------------- numpy BSI kernels
def _magnitude_cmp(mag: np.ndarray, c_abs: int):
    """numpy port of ops.bsi._magnitude_cmp over [D, S, W] slices."""
    depth = mag.shape[0]
    shape = mag.shape[1:]
    eq = np.full(shape, _ONES, dtype=np.uint32)
    lt = np.zeros(shape, dtype=np.uint32)
    gt = np.zeros(shape, dtype=np.uint32)
    for k in range(depth - 1, -1, -1):
        bit = mag[k]
        if (c_abs >> k) & 1:
            lt |= eq & ~bit
            eq &= bit
        else:
            gt |= eq & bit
            eq &= ~bit
    return eq, lt, gt


def bsi_compare(slices: np.ndarray, op: str, value: int) -> np.ndarray:
    """numpy port of ops.bsi.compare — [2+D, S, W] → uint32[S, W]."""
    exists, sign, mag = slices[0], slices[1], slices[2:]
    pos = exists & ~sign
    neg = exists & sign
    c_abs = abs(value)
    if c_abs >= 1 << mag.shape[0]:
        shape = mag.shape[1:]
        eq_m = np.zeros(shape, dtype=np.uint32)
        gt_m = np.zeros(shape, dtype=np.uint32)
        lt_m = np.full(shape, _ONES, dtype=np.uint32)
    else:
        eq_m, lt_m, gt_m = _magnitude_cmp(mag, c_abs)
    if value >= 0:
        eq = pos & eq_m
        lt = neg | (pos & lt_m)
        gt = pos & gt_m
    else:
        eq = neg & eq_m
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
    raise HostPlanError(f"bad BSI comparison op {op!r}")


def bsi_between(slices: np.ndarray, lo: int, hi: int) -> np.ndarray:
    return bsi_compare(slices, ">=", lo) & bsi_compare(slices, "<=", hi)


def bsi_sum(slices: np.ndarray, filt: np.ndarray | None) -> tuple[int, int]:
    """Exact (sum, count) over [2+D, S, W] slices — the host mirror of
    the executor's _sum_fn + weigh_sum chain."""
    exists, sign, mag = slices[0], slices[1], slices[2:]
    pos = exists & ~sign
    neg = exists & sign
    if filt is not None:
        pos = pos & filt
        neg = neg & filt
    total = 0
    scratch = np.empty_like(pos)
    for k in range(mag.shape[0]):
        p = _popcount_sum(np.bitwise_and(mag[k], pos, out=scratch))
        q = _popcount_sum(np.bitwise_and(mag[k], neg, out=scratch))
        total += (p - q) << k
    return total, _popcount_sum(pos | neg)


def bsi_min_max(
    slices: np.ndarray, filt: np.ndarray | None, want_max: bool
) -> tuple[int, int]:
    """(value, count) of the global min/max — one MSB→LSB candidate walk
    over all shards at once (equivalent to the device per-shard walk +
    host combine: the surviving candidate set is exactly the columns
    holding the extreme value, so its popcount is the tie count)."""
    exists, sign, mag = slices[0], slices[1], slices[2:]
    depth = mag.shape[0]
    base = exists & filt if filt is not None else exists
    pos_cand = base & ~sign
    neg_cand = base & sign
    has_pos = bool(np.any(pos_cand))
    has_neg = bool(np.any(neg_cand))
    if not has_pos and not has_neg:
        return 0, 0

    def walk(cand: np.ndarray, prefer_set: bool) -> tuple[int, np.ndarray]:
        val = 0
        for k in range(depth - 1, -1, -1):
            t = cand & mag[k] if prefer_set else cand & ~mag[k]
            nonempty = bool(np.any(t))
            if nonempty:
                cand = t
            bit_is_one = nonempty if prefer_set else not nonempty
            if bit_is_one:
                val += 1 << k
        return val, cand

    if want_max:
        if has_pos:
            val, cand = walk(pos_cand, prefer_set=True)
        else:
            val, cand = walk(neg_cand, prefer_set=False)
            val = -val
    else:
        if has_neg:
            val, cand = walk(neg_cand, prefer_set=True)
            val = -val
        else:
            val, cand = walk(pos_cand, prefer_set=False)
    return val, _popcount_sum(cand)


def bsi_blocks(
    stacks: "HostStacks", idx: Index, field: Field, shards: list[int]
):
    """Yield ``(lo, hi, uint32[2+depth, hi-lo, W])`` slice blocks for an
    int field.  The resident host stack yields once, whole; gather-mode
    (over-budget) fields yield budget-bounded shard chunks assembled
    from the fragments — the full block the budget rejected is never
    allocated."""
    need = BSI_OFFSET + field.bit_depth
    mat, _n = stacks.matrix(idx, field, VIEW_BSI, shards)
    if mat is not None:
        if mat.shape[0] < need:
            mat = np.concatenate(
                [
                    mat,
                    np.zeros(
                        (need - mat.shape[0],) + mat.shape[1:],
                        dtype=np.uint32,
                    ),
                ]
            )
        yield 0, len(shards), mat[:need]
        return
    chunk = max(
        1, int(stacks.budget() // max(1, need * WORDS_PER_SHARD * 4))
    )
    for lo in range(0, len(shards), chunk):
        sub = shards[lo : lo + chunk]
        yield lo, lo + len(sub), np.stack(
            [
                stacks.gather_row(field, VIEW_BSI, sub, r)
                for r in range(need)
            ]
        )


def shift_words(words: np.ndarray, n: int) -> np.ndarray:
    """numpy port of ops.shift_words (per-shard word roll + carry)."""
    if n == 0:
        return words
    from pilosa_tpu.shardwidth import BITS_PER_WORD

    q, r = n // BITS_PER_WORD, n % BITS_PER_WORD
    w = words
    if q:
        w = np.roll(w, q, axis=-1)
        w[..., :q] = 0
    if r:
        up = w << np.uint32(r)
        carry = np.roll(w, 1, axis=-1) >> np.uint32(BITS_PER_WORD - r)
        carry[..., 0] = 0
        w = up | carry
    return w


# ------------------------------------------------------------- host planner
class HostPlanner:
    """Builds a zero-argument closure tree for one bitmap call.  The
    numpy mirror of compile._Planner: identical call-tree walk, identical
    error surface, but row ids bind statically (no traced scalars — there
    is nothing to compile).  Closures hold no mutable evaluation state:
    cached plans run concurrently on HTTP handler threads.

    ``cacheable`` turns False when the plan depended on state that a
    later write can change without changing the call's repr (string-key
    translation, time-range view resolution) — such plans are rebuilt
    per query, exactly like the device planner always is."""

    def __init__(
        self, idx: Index, shards: list[int], stacks: HostStacks, stats=None
    ):
        self.idx = idx
        self.shards = shards
        self.stacks = stacks
        self.stats = stats  # counts the BSI condition leaves planned
        self.cacheable = True
        self.fields: list[tuple[str, Field]] = []  # identity validation

    # ------------------------------------------------------------- leaves
    def _zeros(self) -> np.ndarray:
        return np.zeros((len(self.shards), WORDS_PER_SHARD), dtype=np.uint32)

    def _matrix_leaf(self, field: Field, view_name: str, row_id: int):
        self.fields.append((field.name, field))
        idx, shards, stacks = self.idx, self.shards, self.stacks

        def run() -> np.ndarray:
            mat, _n = stacks.matrix(idx, field, view_name, shards)
            if mat is None:
                return stacks.gather_row(field, view_name, shards, row_id)
            if 0 <= row_id < mat.shape[0]:
                return mat[row_id]
            return np.zeros(
                (len(shards), WORDS_PER_SHARD), dtype=np.uint32
            )

        return run

    def _existence(self):
        ef = self.idx.field(EXISTENCE_FIELD)
        if not self.idx.options.track_existence:
            raise HostPlanError(
                "query requires existence tracking (index created with "
                "track_existence=false)"
            )
        if ef is None:
            return self._zeros
        return self._matrix_leaf(ef, VIEW_STANDARD, 0)

    def _bsi_apply(
        self, field: Field, fn: Callable[[np.ndarray], np.ndarray]
    ) -> Callable[[], np.ndarray]:
        """closure() → uint32[S, W] = ``fn`` applied over the field's
        [2+depth, S, W] slice block.  Over-budget (gather-mode) fields
        apply ``fn`` per shard CHUNK — every BSI kernel here is
        shard-separable, so the full block that exceeded the budget is
        never materialized at once."""
        self.fields.append((field.name, field))
        idx, shards, stacks = self.idx, self.shards, self.stacks
        need = BSI_OFFSET + field.bit_depth

        def run() -> np.ndarray:
            out = None
            for lo, hi, block in bsi_blocks(stacks, idx, field, shards):
                part = fn(block)
                if lo == 0 and hi == len(shards):
                    return part
                if out is None:
                    out = np.zeros(
                        (len(shards), WORDS_PER_SHARD), dtype=np.uint32
                    )
                out[lo:hi] = part
            if out is None:
                out = np.zeros(
                    (len(shards), WORDS_PER_SHARD), dtype=np.uint32
                )
            return out

        return run

    # ---------------------------------------------------------- call tree
    def plan(self, call: Call) -> Callable[[], np.ndarray]:
        name = call.name
        if name in ("Row", "Range"):
            return self._plan_row(call)
        if name in ("Union", "Intersect", "Difference", "Xor"):
            if not call.children:
                if name == "Intersect":
                    raise HostPlanError("Intersect() needs at least one child")
                return self._zeros
            fns = [self.plan(ch) for ch in call.children]
            op = {
                "Union": np.bitwise_or,
                "Intersect": np.bitwise_and,
                "Xor": np.bitwise_xor,
            }.get(name)
            # NO shared scratch buffers: cached plans run concurrently
            # on HTTP handler threads, and numpy releases the GIL inside
            # elementwise ops — a per-node accumulator would be a data
            # race. Per-call allocation measures within noise of out=
            # reuse at these shapes; the uint64 popcount is where the
            # host path's speed edge lives (_popcount_sum).

            if name == "Difference":

                def run() -> np.ndarray:
                    out = fns[0]()
                    for fn in fns[1:]:
                        out = out & ~fn()
                    return out

                return run

            def run() -> np.ndarray:
                out = fns[0]()
                for fn in fns[1:]:
                    out = op(out, fn())
                return out

            return run
        if name == "Not":
            if len(call.children) != 1:
                raise HostPlanError("Not() takes exactly one call")
            sub = self.plan(call.children[0])
            ex = self._existence()
            return lambda: ex() & ~sub()
        if name == "All":
            return self._existence()
        if name == "Shift":
            if len(call.children) != 1:
                raise HostPlanError("Shift() takes exactly one call")
            n = call.arg("n", 1)
            if not isinstance(n, int) or n < 0:
                raise HostPlanError(
                    f"Shift() n must be a non-negative integer, got {n!r}"
                )
            sub = self.plan(call.children[0])
            return lambda: shift_words(np.array(sub()), n)
        raise HostPlanError(f"{name!r} is not a bitmap call")

    def _plan_row(self, call: Call):
        cond = call.condition()
        if cond is not None:
            return self._plan_condition(cond)
        fa = call.field_arg()
        if fa is None:
            raise HostPlanError(f"Row() needs a field argument: {call!r}")
        fname, row = fa
        field = self.idx.field(fname)
        if field is None:
            raise HostPlanError(f"field {fname!r} not found")
        row_id = self.resolve_row_id(field, row)

        ts_from, ts_to = call.arg("from"), call.arg("to")
        if ts_from is not None or ts_to is not None:
            self.cacheable = False  # view set depends on mutable bounds
            if field.options.field_type != FIELD_TIME:
                raise HostPlanError(f"field {fname!r} is not a time field")
            raw_from, raw_to = ts_from, ts_to
            ts_from = coerce_timestamp(ts_from) if ts_from is not None else None
            ts_to = coerce_timestamp(ts_to) if ts_to is not None else None
            if raw_from is not None and ts_from is None:
                raise HostPlanError(f"bad from= timestamp {raw_from!r}")
            if raw_to is not None and ts_to is None:
                raise HostPlanError(f"bad to= timestamp {raw_to!r}")
            bounds = field.time_bounds()
            if bounds is None:
                return self._zeros
            ts_from = ts_from if ts_from is not None else bounds[0]
            ts_to = ts_to if ts_to is not None else bounds[1]
            view_names = [
                v
                for v in views_by_time_range(
                    VIEW_STANDARD, ts_from, ts_to, field.options.time_quantum
                )
                if field.view(v) is not None
            ]
            if not view_names:
                return self._zeros
            fns = [self._matrix_leaf(field, v, row_id) for v in view_names]

            def run() -> np.ndarray:
                out = fns[0]()
                for fn in fns[1:]:
                    out = out | fn()
                return out

            return run
        return self._matrix_leaf(field, VIEW_STANDARD, row_id)

    def _plan_condition(self, cond: tuple[str, Condition]):
        fname, condition = cond
        field = self.idx.field(fname)
        if field is None:
            raise HostPlanError(f"field {fname!r} not found")
        if field.options.field_type != FIELD_INT:
            raise HostPlanError(f"field {fname!r} is not an int field")
        value, op = condition.value, condition.op
        if value is None:
            if op == "!=":
                return self._bsi_apply(field, lambda b: b[0])
            if op == "==":
                ex = self._existence()
                notnull = self._bsi_apply(field, lambda b: b[0])
                return lambda: ex() & ~notnull()
            raise HostPlanError(
                f"null only supports ==/!= comparisons, got {op!r}"
            )
        if self.stats is not None:
            self.stats.count("bsi_condition_leaves_total", tags={"op": op})
        if op == "between":
            lo, hi = int(value[0]), int(value[1])
            return self._bsi_apply(field, lambda b: bsi_between(b, lo, hi))
        v = int(value)
        return self._bsi_apply(field, lambda b: bsi_compare(b, op, v))

    def resolve_row_id(self, field: Field, row: Any) -> int:
        if isinstance(row, bool):
            return int(row)
        if isinstance(row, int):
            return row
        if isinstance(row, str):
            # translation state can change under a cached plan
            self.cacheable = False
            if not field.options.keys:
                raise HostPlanError(
                    f"field {field.name!r} does not use string keys"
                )
            rid = field.row_keys.translate_key(row, create=False)
            return rid if rid is not None else -1
        raise HostPlanError(f"bad row value {row!r}")


# --------------------------------------------------------------- the engine
class HostEngine:
    """Executes read calls on the host over HostStacks.  Owned by the
    QueryCompiler (compile.py) so both engines hang off one object; the
    Executor routes calls here when the router picks the host path."""

    MAX_PLANS = 1024
    # transient-tensor chunk bound for host GroupBy mask/count batches
    GB_CHUNK_BYTES = 256 << 20

    def __init__(self, stats=None):
        self.stats = stats
        self.stacks = HostStacks()
        self._plans: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._lock = threading.Lock()

    # ----------------------------------------------------------- plan cache
    def _bitmap_plan(
        self, idx: Index, call: Call, shards: list[int]
    ) -> Callable[[], np.ndarray]:
        # the structural repr is the plan key; cached on the Call object
        # so a multi-call request (or a bench loop reusing a parsed AST)
        # pays the string build once
        ckey = call.__dict__.get("_plan_repr")
        if ckey is None:
            ckey = call.__dict__["_plan_repr"] = repr(call)
        key = (idx.name, tuple(shards), ckey)
        with self._lock:
            hit = self._plans.get(key)
            if hit is not None:
                run, fields = hit
                if all(idx.field(n) is f for n, f in fields):
                    self._plans.move_to_end(key)
                    return run
                del self._plans[key]
        planner = HostPlanner(idx, shards, self.stacks, self.stats)
        run = planner.plan(call)
        if planner.cacheable:
            with self._lock:
                self._plans[key] = (run, planner.fields)
                self._plans.move_to_end(key)
                while len(self._plans) > self.MAX_PLANS:
                    self._plans.popitem(last=False)
        return run

    def bitmap_words(
        self, idx: Index, call: Call, shards: list[int]
    ) -> np.ndarray:
        """uint32[S, W] — may be a view of cached stack memory; callers
        that hand the words to a client copy first (the executor does)."""
        return self._bitmap_plan(idx, call, shards)()

    def filter_words(
        self, idx: Index, call: Call, shards: list[int]
    ) -> np.ndarray | None:
        """First-child filter words, or None when the call carries no
        filter (host ops skip the AND entirely — no all-ones filter)."""
        if not call.children:
            return None
        return self.bitmap_words(idx, call.children[0], shards)

    # ----------------------------------------------------------- aggregates
    def count(self, idx: Index, call: Call, shards: list[int]) -> int:
        return _popcount_sum(self.bitmap_words(idx, call, shards))

    def sum(
        self, idx: Index, field: Field, call: Call, shards: list[int]
    ) -> tuple[int, int]:
        filt = self.filter_words(idx, call, shards)
        total = n = 0
        for lo, hi, block in bsi_blocks(self.stacks, idx, field, shards):
            s, c = bsi_sum(block, filt[lo:hi] if filt is not None else None)
            total += s
            n += c
        return total, n

    def min_max(
        self,
        idx: Index,
        field: Field,
        call: Call,
        shards: list[int],
        want_max: bool,
    ) -> tuple[int, int]:
        filt = self.filter_words(idx, call, shards)
        best, count = None, 0
        for lo, hi, block in bsi_blocks(self.stacks, idx, field, shards):
            v, c = bsi_min_max(
                block, filt[lo:hi] if filt is not None else None, want_max
            )
            if c == 0:
                continue
            if best is None or (v > best if want_max else v < best):
                best, count = v, c
            elif v == best:
                count += c
        return (best if best is not None else 0), count

    def _rows_of_field(self, field: Field, shards: list[int]) -> list[int]:
        rows: set[int] = set()
        view = field.view(VIEW_STANDARD)
        if view is None:
            return []
        for s in shards:
            frag = view.fragment(s)
            if frag is not None:
                rows.update(frag.row_ids())
        return sorted(rows)

    def topn_pairs(
        self,
        idx: Index,
        field: Field,
        call: Call,
        shards: list[int],
        rows: list[int] | None,
    ) -> list[tuple[int, int]]:
        """Exact (row, count) pairs.  ``rows`` is the ids= subset (kept
        in input order, zero counts dropped — matching the device ids
        path); None scans every stack row, exactly like the device
        program (padding rows count 0 and drop), falling back to stored
        row ids only in gather mode."""
        filt = self.filter_words(idx, call, shards)
        mat, _n = self.stacks.matrix(idx, field, VIEW_STANDARD, shards)
        if rows is not None:
            want = rows
        elif mat is not None:
            want = range(mat.shape[0])
        else:
            want = self._rows_of_field(field, shards)
        pairs: list[tuple[int, int]] = []
        scratch: np.ndarray | None = None
        for r in want:
            if mat is not None and 0 <= r < mat.shape[0]:
                plane = mat[r]
            elif mat is not None:
                continue  # beyond the stack: no bits stored
            else:
                plane = self.stacks.gather_row(
                    field, VIEW_STANDARD, shards, r
                )
            if filt is not None:
                if scratch is None:
                    scratch = np.empty_like(plane)
                c = _popcount_sum(np.bitwise_and(plane, filt, out=scratch))
            else:
                c = _popcount_sum(plane)
            if c > 0:
                pairs.append((int(r), c))
        return pairs

    def includes_column(
        self, idx: Index, call: Call, shard: int, offset: int
    ) -> bool:
        words = self.bitmap_words(idx, call.children[0], [shard])[0]
        return bool((int(words[offset // 32]) >> (offset % 32)) & 1)

    # -------------------------------------------------------------- GroupBy
    def group_by(
        self,
        idx: Index,
        fields: list[Field],
        row_lists: list[list[int]],
        filter_call: Call | None,
        agg_field: Field | None,
        limit: int | None,
        shards: list[int],
    ) -> list[dict]:
        """Level-synchronous host GroupBy.  Emission order is g-major,
        k-minor per level (numpy argwhere order) — identical to both
        device paths, so ``limit`` cuts the same prefix."""
        n_s = len(shards)
        if filter_call is not None:
            base = np.array(self.bitmap_words(idx, filter_call, shards))
        else:
            base = np.full((n_s, WORDS_PER_SHARD), _ONES, dtype=np.uint32)
        def agg_sum(mask: np.ndarray) -> int:
            total = 0
            for lo, hi, block in bsi_blocks(
                self.stacks, idx, agg_field, shards
            ):
                total += bsi_sum(block, mask[lo:hi])[0]
            return total
        results: list[dict] = []
        # [K, S, W] per level: stack views when resident, gathers otherwise
        level_rows: list[list[np.ndarray]] = []
        for f, rows in zip(fields, row_lists):
            mat, _n = self.stacks.matrix(idx, f, VIEW_STANDARD, shards)
            planes = []
            for r in rows:
                if mat is not None:
                    planes.append(
                        mat[r]
                        if 0 <= r < mat.shape[0]
                        else np.zeros((n_s, WORDS_PER_SHARD), np.uint32)
                    )
                else:
                    planes.append(
                        self.stacks.gather_row(f, VIEW_STANDARD, shards, r)
                    )
            level_rows.append(planes)

        plane_bytes = n_s * WORDS_PER_SHARD * 4
        chunk_g = max(1, self.GB_CHUNK_BYTES // max(1, plane_bytes))

        def emit(groups: list[tuple], counts: list[int], masks) -> None:
            start = len(results)
            for grp, c in zip(groups, counts):
                results.append(
                    {
                        "group": [
                            {"field": f.name, "rowID": rid} for f, rid in grp
                        ],
                        "count": int(c),
                    }
                )
            if agg_field is not None:
                for i, m in enumerate(masks):
                    results[start + i]["sum"] = agg_sum(m)

        def expand(level: int, masks: list[np.ndarray], groups: list[tuple]):
            if limit is not None and len(results) >= limit:
                return
            rows_l = row_lists[level]
            planes = level_rows[level]
            counts = np.zeros((len(groups), len(rows_l)), dtype=np.int64)
            scratch = None
            for g, m in enumerate(masks):
                for k, p in enumerate(planes):
                    if scratch is None:
                        scratch = np.empty_like(p)
                    counts[g, k] = _popcount_sum(
                        np.bitwise_and(m, p, out=scratch)
                    )
            pairs = np.argwhere(counts > 0)
            last = level == len(fields) - 1
            if last and limit is not None:
                pairs = pairs[: limit - len(results)]
            for lo in range(0, pairs.shape[0], chunk_g):
                chunk = pairs[lo : lo + chunk_g]
                sub_groups = [
                    groups[g] + ((fields[level], rows_l[k]),)
                    for g, k in chunk.tolist()
                ]
                if last and agg_field is None:
                    emit(
                        sub_groups,
                        counts[chunk[:, 0], chunk[:, 1]].tolist(),
                        None,
                    )
                else:
                    sub_masks = [
                        masks[g] & planes[k] for g, k in chunk.tolist()
                    ]
                    if last:
                        emit(
                            sub_groups,
                            counts[chunk[:, 0], chunk[:, 1]].tolist(),
                            sub_masks,
                        )
                    else:
                        expand(level + 1, sub_masks, sub_groups)
                if limit is not None and len(results) >= limit:
                    return

        if all(row_lists):
            expand(0, [base], [()])
        return results
