"""Query compilation: PQL call tree → ONE jitted device program.

Reference: executor.go walks the AST per shard with Go hot loops and
reduces over HTTP. Here the whole read query becomes a single XLA
program over *stacked* field arrays:

- each (field, view) keeps a device-resident stacked matrix
  ``uint32[R, S, W]`` (R = padded rows, S = shards; row-major so a row
  gather reads one contiguous [S, W] plane — see stack_view_matrices)
  rebuilt only when a fragment version changes — uploads are amortized
  across queries;
- a call tree compiles to a closure over (matrix, row_id) leaf inputs;
  row IDs are traced scalars, so one compiled program serves every row
  of the same query shape (Count(Intersect(Row, Row)) compiles once);
- a shard mask input restricts execution to a query's shard subset
  without recompiling;
- the reduction (Count/Sum/TopN) happens inside the same program, so a
  query is one host→device dispatch and one scalar readback.

The structural cache key is the call tree's shape with row IDs
abstracted out; jax.jit's own shape cache handles S/R/W changes.
"""

from __future__ import annotations

import math
import os
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from pilosa_tpu import ops
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
from pilosa_tpu.core.fragment import _pad_rows
from pilosa_tpu.core.timequantum import views_by_time_range
from pilosa_tpu.pql import Call, Condition, coerce_timestamp
from pilosa_tpu.shardwidth import WORDS_PER_SHARD
from pilosa_tpu.utils import saturation
from pilosa_tpu.utils.tracing import GLOBAL_TRACER


class PlanError(ValueError):
    pass


class StackOverBudget(Exception):
    """A field's dense [R, S, W] stack would exceed the device budget.

    Raised EXPLICITLY instead of letting the allocation OOM (SURVEY §7
    hard part (e)). Callers fall back: Row() leaves go through the
    hot-row slot stack, TopN streams row chunks; anything else surfaces
    the clear error."""

    def __init__(self, field: str, rows: int, bytes_needed: int, budget: int):
        self.field, self.rows = field, rows
        self.bytes_needed, self.budget = bytes_needed, budget
        super().__init__(
            f"field {field!r}: dense stack of {rows} rows needs "
            f"{bytes_needed / 2**20:.0f} MiB on device (budget "
            f"{budget / 2**20:.0f} MiB); high-cardinality fields answer "
            "Row/Count/TopN via the hot-row path"
        )


def named_jit(name: str, fn: Callable) -> Callable:
    """``jax.jit(fn)`` under a program NAME: the XLA module is
    ``jit_<name>``, which is what the device trace's ``XLA Modules``
    line, ``JAX_LOG_COMPILES`` lines and the compile counter's
    ``program`` label (utils/xlaevents.py) then say ran or compiled —
    not ``jit__lambda`` for every program of the engine. Every program
    built under executor/ goes through here (tests/test_trace_clock.py
    walks the tree for a bare ``jax.jit``). ``name`` comes from the call
    type alone (``pilosa_topn``), never from data, so the label set
    stays small; program-cache keys are unchanged."""

    def program(*args):
        return fn(*args)

    program.__name__ = program.__qualname__ = name
    program.__wrapped__ = fn  # argument names and source line stay fn's
    return jax.jit(program)


def range_suffix(skey: str) -> str:
    """``"_range"`` where the plan behind the structure key ``skey`` has a
    BSI comparison leaf (``_Planner._plan_condition``), else ``""``: the
    programs that scan an int field's bit slices carry it in their name
    (``pilosa_count_range``), so the device trace and the compile
    counter tell the bit-sliced scan from the two-row popcount. Field
    names hold neither ``[`` nor ``(``, so only the leaf's own key
    matches."""
    return "_range" if "cmp[" in skey or "between(" in skey else ""


# --------------------------------------------------------------- stacking
def stack_view_matrices(view, shards: list[int]) -> tuple[np.ndarray, int]:
    """Stack a view's fragment host matrices → (np uint32[R, S, W], R).

    Shared by the query compiler's StackCache and the mesh engine
    (parallel/mesh.py). Reads fragment HOST matrices — no per-fragment
    device round trips; the caller does one upload for the whole stack.

    ROW-MAJOR ([R, S, W], not [S, R, W]) is load-bearing for query
    latency: TPU tiles the two minor dims, so with rows as a middle dim
    a tile spans all R rows of 128 words and gathering ONE row streams
    the ENTIRE stack through the VPU (measured 2026-07-30 at 10.7B
    columns: 29.9 ms/query ≈ whole-stack read at roofline). With rows
    leading, a row gather is a contiguous [S, W] plane — only the rows a
    query touches cross HBM.
    """
    mats, max_rows = [], 1
    for s in shards:
        frag = view.fragment(s) if view else None
        if frag is None:
            mats.append(None)
        else:
            m, _n = frag.host_matrix()
            mats.append(m)
            max_rows = max(max_rows, m.shape[0])
    stacked = np.zeros((max_rows, len(shards), WORDS_PER_SHARD), dtype=np.uint32)
    from pilosa_tpu import native

    if not native.stack_fill(mats, stacked):
        # numpy fallback — rows outer, shards inner: destination writes
        # land contiguously in each [S, W] row plane. Controlled A/B at
        # 10 GiB on the bench host (fresh destinations, alternating
        # reps): shard-inner strided fill 44.2/23.7 s vs this order
        # 20.2/11.7 s — consistently ~2× faster. The C path above
        # parallelizes the same row-plane order across threads (ctypes
        # releases the GIL), cutting the pod-scale stack build further.
        for r in range(max_rows):
            plane = stacked[r]
            for i, m in enumerate(mats):
                if m is not None and r < m.shape[0]:
                    plane[i] = m[r]
    return stacked, max_rows


# scatter index sentinel: out of bounds on any axis ⇒ mode="drop" skips it
_OOB = np.int32(2**30)

_budget_cache: list[int] = []
# explicit override installed from config (device-stack-budget-bytes);
# wins over the env var and the HBM probe
_budget_override: list[int] = []


def set_stack_budget(n: int | None) -> None:
    """Install the configured device stack budget (Config field
    ``device-stack-budget-bytes``; the server wires it at boot).  None
    or 0 clears back to env/HBM resolution.  Always resets the memo so
    tests and re-configuration see the change immediately."""
    _budget_override.clear()
    if n:
        _budget_override.append(int(n))
    _budget_cache.clear()


def reset_stack_budget_cache() -> None:
    """Drop the memoized resolution (tests re-resolve after changing
    PILOSA_TPU_STACK_BUDGET; the old cache was append-only)."""
    _budget_cache.clear()


def stack_budget_if_resolved() -> int | None:
    """The budget WITHOUT triggering resolution, or None while only the
    HBM path (which initializes the JAX backend) could answer.  The
    /debug/resources ledger reads through this: a control-plane scrape
    must never be the first jax call in the process (debug routes do
    not pass through the attach gate)."""
    if _budget_override:
        return _budget_override[0]
    if _budget_cache:
        return _budget_cache[0]
    env = os.environ.get("PILOSA_TPU_STACK_BUDGET")
    return int(env) if env else None


def _stack_budget() -> int:
    """See StackCache.STACK_BYTES_BUDGET. Cached after first resolution
    (device memory limits don't change mid-process)."""
    # override → cache → env, shared with the non-initializing ledger
    # accessor so /debug/resources and the enforced budget cannot drift
    resolved = stack_budget_if_resolved()
    if resolved is not None:
        if not _budget_cache and not _budget_override:
            _budget_cache.append(resolved)  # env path: memoize like HBM
        return resolved
    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        budget = 2 << 30  # the CPU backend reports no memory limit
    else:
        # an accelerator that cannot say how much memory it has is an
        # error to surface, not a 2 GiB guess to serve under
        limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
        if limit <= 0:
            raise RuntimeError(
                f"{dev.platform} device {dev.device_kind!r} reports no "
                "memory limit; set device-stack-budget-bytes"
            )
        # 70% of reported HBM even when that is below 2 GiB — the
        # headroom matters more on small devices, not less
        budget = int(limit * 0.7)
    _budget_cache.append(budget)
    return budget


def _scatter_rows(store, idx, rows):
    """Functional row scatter for the tiered container stores:
    ``store[idx[k]] = rows[k]`` for dense [H,S,W], sparse [H,K] and run
    [H,K,2] stores alike. _OOB padding indices drop. Not donated — a
    query snapshot may still hold the previous array."""
    return store.at[idx].set(rows, mode="drop")


_scatter_rows = named_jit("pilosa_scatter_rows", _scatter_rows)


def _apply_stack_delta(matrix, idx, rows):
    """Scatter ``rows[k]`` into ``matrix[idx[k,0], idx[k,1]]`` on device
    (row-major stacks: idx columns are (row, shard)). Padding entries use
    the _OOB sentinel and are dropped.
    Deliberately NOT donated: concurrent readers may still hold the old
    stack; the device-to-device copy rides HBM bandwidth, which is the
    point — the host→device upload is what O(dirty rows) avoids."""
    return matrix.at[idx[:, 0], idx[:, 1]].set(rows, mode="drop")


_apply_stack_delta = named_jit("pilosa_stack_delta", _apply_stack_delta)


class StackCache:
    """Device-resident stacked (field, view) matrices.

    Entries key on the exact shard list and invalidate via per-fragment
    (uid, version) tokens — a deleted-and-recreated index gets fresh
    fragment uids, so stale data can never be served. An LRU cap bounds
    device memory when workloads query many distinct shard subsets.

    Point writes between queries take the DELTA path: the fragments'
    dirty-row history yields the changed (shard, row) set, only those
    packed rows cross host→device, and a scatter updates the resident
    stack in place of a full O(S·R·W) re-upload (VERDICT r1 item 4;
    reference analogue: fragment.go bulkImport's incremental discipline).
    """

    MAX_ENTRIES = 64
    MAX_DELTA_ROWS = 1024  # beyond this a full restack is cheaper

    # one device's bytes cap for any one dense stack (on a mesh, the
    # device's share of it: see the byte ledger); larger fields take the
    # hot-row path. Resolution order: PILOSA_TPU_STACK_BUDGET env →
    # 70% of the device's reported HBM limit (a 16 GiB chip serves a
    # 10 GiB pod-scale stack out of the box); 2 GiB on the CPU backend,
    # which reports none. Lazy so importing
    # the module never initializes a backend; tests monkeypatch the
    # class attribute with a plain int, which shadows the property.
    @property
    def STACK_BYTES_BUDGET(self) -> int:  # noqa: N802 — historical name
        return _stack_budget()

    # How over-budget fields serve resident rows (docs/device-residency.md):
    # "tiered"  — per-row compressed containers (dense/sparse/run) with a
    #             hot/cold LRU tier and touch-driven promotion (default);
    # "slots"   — the legacy dense hot-row slot stack (tests pin it to
    #             exercise that path; no compression, no cold tier).
    RESIDENCY_MODE = "tiered"
    MAX_TIERED_ENTRIES = 4  # count cap; the byte ledger is the real bound

    def __init__(self, mesh_ctx=None, stats=None):
        from collections import OrderedDict

        self._cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._hot: "OrderedDict[tuple, dict]" = OrderedDict()
        # tiered compressed residency entries (executor/residency.py)
        self._tiered: "OrderedDict[tuple, Any]" = OrderedDict()
        self.mesh_ctx = mesh_ctx  # parallel.mesh.MeshContext | None
        self.stats = stats  # optional StatsClient for residency metrics
        # contention-counted (docs/profiling.md): /debug/saturation's
        # "stack-cache" lock family — every stack build/eviction and
        # route-time token check serializes here
        self._lock = saturation.ContendedLock("stack-cache")
        # shared byte ledger across BOTH caches: the budget is an
        # AGGREGATE resident cap, not just per-stack — a per-entry check
        # alone would let two near-budget stacks coexist and OOM the
        # device once the budget scales to 70% of HBM
        self._bytes: dict[tuple, int] = {}
        # projected bytes of builds in flight (admitted, not yet
        # installed): two concurrent builders of different keys must see
        # each other's claims or they co-allocate past the budget.
        # Keyed by a PER-BUILD token, not the stack key — two concurrent
        # builds of the SAME key must each hold a claim, or the first to
        # finish releases the second's bytes while it is still allocating
        self._reserved: dict[object, int] = {}
        self.resident_bytes = 0
        # observability: tests assert the write path stays incremental
        self.full_restacks = 0
        self.delta_updates = 0
        self.delta_rows_uploaded = 0
        self.hot_row_uploads = 0
        # tiered residency counters (satellite: eviction/tier
        # observability; /debug/vars deviceResidency reads these)
        self.rows_promoted = 0
        self.rows_demoted = 0
        self.cold_uploads = 0
        self.evictions = {"dense": 0, "hot": 0, "tiered": 0}
        self._container_bytes = {"dense": 0, "sparse": 0, "run": 0}
        if stats is not None:
            # a scrape tells "no eviction" from a program without the family
            for tier in self.evictions:
                stats.declare("stack_evictions_total", tags={"tier": tier})

    # ----------------------------------------------------- byte ledger
    # The ledger counts what ONE device holds: the whole array without a
    # mesh, a device's block of it on one (every stack is split evenly
    # over the mesh, or replicated), so the sum is what the fullest
    # device holds and the budget is one device's.
    def _device_bytes(self, shape: tuple) -> int:
        """Bytes on one device of a uint32 stack of ``shape`` [R, S, W]
        once placed — the projection before a build."""
        if self.mesh_ctx is None:
            return math.prod(shape) * 4
        return self.mesh_ctx.block_bytes(tuple(shape))

    @staticmethod
    def _placed_bytes(arr) -> int:
        """Bytes of a placed array's block on one device."""
        return math.prod(arr.sharding.shard_shape(arr.shape)) * arr.dtype.itemsize

    # callers hold self._lock
    def _account(self, key: tuple, nbytes: int) -> None:
        self.resident_bytes += nbytes - self._bytes.get(key, 0)
        self._bytes[key] = nbytes
        self._push_ledger_gauge()

    def _forget(self, key: tuple) -> None:
        self.resident_bytes -= self._bytes.pop(key, 0)
        self._push_ledger_gauge()

    def _push_ledger_gauge(self) -> None:
        if self.stats is not None:
            self.stats.gauge("stack_device_bytes", float(self.resident_bytes))

    def _evict_for(self, need: int, keep: tuple | None = None) -> None:
        """Evict LRU entries (dense stacks first, then hot slot stacks,
        then tiered container entries) until ``need`` more bytes fit
        under the budget. The entry being (re)built is exempt; if
        nothing evictable remains the admit proceeds anyway — the
        per-stack check already bounds any single entry."""
        budget = self.STACK_BYTES_BUDGET
        while (
            self.resident_bytes + sum(self._reserved.values()) + need > budget
        ):
            victim = next((k for k in self._cache if k != keep), None)
            if victim is not None:
                del self._cache[victim]
                self._forget(victim)
                self._note_eviction("dense")
                continue
            victim = next((k for k in self._hot if k != keep), None)
            if victim is not None:
                del self._hot[victim]
                self._forget(victim)
                self._note_eviction("hot")
                continue
            victim = next((k for k in self._tiered if k != keep), None)
            if victim is None:
                break
            self._forget_tiered(victim, self._tiered.pop(victim))
            self._note_eviction("tiered")

    def _note_eviction(self, tier: str) -> None:
        # caller holds self._lock
        self.evictions[tier] = self.evictions.get(tier, 0) + 1
        if self.stats is not None:
            self.stats.count("stack_evictions_total", tags={"tier": tier})

    @staticmethod
    def _projected_rows(view, shards: list[int]) -> int:
        """Padded stack height WITHOUT materializing any host matrix —
        the over-budget check must not itself allocate O(R·W)."""
        n = 1
        for s in shards:
            frag = view.fragment(s) if view else None
            if frag is not None:
                n = max(n, frag.n_rows())
        return _pad_rows(n)

    def matrix(self, idx: Index, field: Field, view_name: str, shards: list[int]):
        """(jnp uint32[R, S, W], n_rows int) for the given shard list.

        Raises StackOverBudget when the dense stack would exceed
        STACK_BYTES_BUDGET — callers use hot_slot()/hot_dev() or chunked
        scans instead."""
        view = field.view(view_name)
        key = (idx.name, field.name, view_name, tuple(shards))
        # whole-view mutation stamp read BEFORE the per-fragment tokens:
        # a mutation racing this read advances view.version, so an entry
        # stamped with the earlier value just re-validates next query
        view_ver = view.version if view is not None else None
        with self._lock:
            cached = self._cache.get(key)
            if (
                cached is not None
                and view_ver is not None
                and cached[3] == view_ver
            ):
                # O(1) fast path — no mutation anywhere in the view since
                # this entry was stamped, so BOTH O(S) scans (budget
                # projection + per-fragment tokens; 10k+ calls per leaf
                # per query at pod scale) are skipped. Over-budget fields
                # never enter the cache, so a hit implies within-budget.
                self._cache.move_to_end(key)
                return cached[1], cached[2]
        r_pad = self._projected_rows(view, shards)
        need = self._device_bytes((r_pad, len(shards), WORDS_PER_SHARD))
        if need > self.STACK_BYTES_BUDGET:
            raise StackOverBudget(
                field.name, r_pad, need, self.STACK_BYTES_BUDGET
            )
        with self._lock:
            # evict for the PROJECTED bytes BEFORE the build allocates on
            # device — evicting only at install would let the new stack
            # coexist with victims at ~2× budget peak (a same-key rebuild
            # still transiently holds old+new; concurrent readers may use
            # the old array, so it cannot be dropped early)
            self._evict_for(need - self._bytes.get(key, 0), keep=key)
            cached = self._cache.get(key)
            versions = tuple(self._frag_token(view, s) for s in shards)
            if cached is not None and cached[0] == versions:
                self._cache[key] = (versions, cached[1], cached[2], view_ver)
                self._cache.move_to_end(key)
                return cached[1], cached[2]
            # reserve the projection so a concurrent admit of a DIFFERENT
            # key can't also pass eviction and co-allocate past the
            # budget while both builds are in flight (ADVICE r3)
            build_token = object()
            self._reserved[build_token] = need
        # build OUTSIDE the lock: a slow restack/upload must not convoy
        # concurrent cache-hit readers. A racing write between the version
        # snapshot and the build just means the next query sees another
        # version mismatch and applies the remainder (delta application is
        # idempotent — rows carry full contents).
        try:
            entry = None
            if cached is not None:
                entry = self._try_delta(cached, view, shards, versions, view_ver)
            if entry is None:
                with GLOBAL_TRACER.span(
                    "stack.pack", field=field.name, shards=len(shards)
                ) as packed:
                    stacked, max_rows = stack_view_matrices(view, shards)
                    packed.tags.update(rows=max_rows, bytes=int(stacked.nbytes))
                with GLOBAL_TRACER.span(
                    "stack.upload",
                    devices=self.mesh_ctx.n_devices if self.mesh_ctx else 1,
                    device_bytes=self._device_bytes(stacked.shape),
                    **packed.tags,
                ) as uploaded:
                    if self.mesh_ctx is not None:
                        dev = self.mesh_ctx.place_stack(stacked)
                    else:
                        dev = jnp.asarray(stacked)
                if self.stats is not None:
                    # set-up lies outside the benchmark's traced slice,
                    # so packing and upload are also read as counters
                    self.stats.timing("stack_pack_seconds", packed.duration)
                    self.stats.timing("stack_upload_seconds", uploaded.duration)
                with self._lock:
                    self.full_restacks += 1
                entry = (versions, dev, max_rows, view_ver)
        except BaseException:
            with self._lock:
                self._reserved.pop(build_token, None)
            raise
        with self._lock:
            self._reserved.pop(build_token, None)
            # last-writer-wins install is self-healing: if a concurrent
            # builder installed a different entry, the next call re-reads
            # fragment versions and reconciles via the delta path
            nbytes = self._placed_bytes(entry[1])
            self._evict_for(nbytes - self._bytes.get(key, 0), keep=key)
            self._cache[key] = entry
            self._account(key, nbytes)
            self._cache.move_to_end(key)
            while len(self._cache) > self.MAX_ENTRIES:
                victim, _ = self._cache.popitem(last=False)
                self._forget(victim)
            return entry[1], entry[2]

    def _try_delta(self, cached, view, shards: list[int], versions: tuple, view_ver):
        """Apply changed fragments' dirty rows to the cached device stack;
        None ⇒ fall back to a full restack (unknown history, fragment
        replaced, row growth past the stack height, or too many rows)."""
        old_versions, dev, max_rows = cached[0], cached[1], cached[2]
        updates: list[tuple[int, int, np.ndarray]] = []
        for i, s in enumerate(shards):
            old_uid, old_ver = old_versions[i]
            new_uid, _new_ver = versions[i]
            if (old_uid, old_ver) == versions[i]:
                continue
            if old_uid != new_uid:
                return None  # fragment created or replaced under the key
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
                return None  # stack needs to grow — restack
            for r in sorted(dirty):
                if r >= max_rows:
                    return None
                words = (
                    host_m[r]
                    if r < host_m.shape[0]
                    else np.zeros(WORDS_PER_SHARD, dtype=np.uint32)
                )
                updates.append((i, r, words))
        if not updates:
            return (versions, dev, max_rows, view_ver)
        k_pad = 1 << (len(updates) - 1).bit_length()
        with GLOBAL_TRACER.span(
            "stack.delta",
            field=view.field,
            shards=len(shards),
            rows=len(updates),
            bytes=k_pad * WORDS_PER_SHARD * 4,
        ):
            idx_arr = np.full((k_pad, 2), _OOB, dtype=np.int32)  # OOB ⇒ drop
            row_arr = np.zeros((k_pad, WORDS_PER_SHARD), dtype=np.uint32)
            for k, (i, r, words) in enumerate(updates):
                idx_arr[k] = (r, i)
                row_arr[k] = words
            new_dev = _apply_stack_delta(dev, idx_arr, row_arr)
            if new_dev.sharding != dev.sharding:
                # the scatter must not silently demote the stack's SPMD layout
                new_dev = jax.device_put(new_dev, dev.sharding)
        with self._lock:
            self.delta_updates += 1
            self.delta_rows_uploaded += len(updates)
        return (versions, new_dev, max_rows, view_ver)

    @staticmethod
    def _frag_token(view, shard: int) -> tuple:
        frag = view.fragment(shard) if view else None
        return (-1, -1) if frag is None else (frag.uid, frag.version)

    def stats_snapshot(self) -> dict:
        """Counter view for /debug/vars (owns the field names so
        transport code never reads cache internals); increments happen
        under the same lock, so no update is lost."""
        with self._lock:
            return {
                "fullRestacks": self.full_restacks,
                "deltaUpdates": self.delta_updates,
                "deltaRowsUploaded": self.delta_rows_uploaded,
                "hotRowUploads": self.hot_row_uploads,
                "entries": len(self._cache),
                "hotEntries": len(self._hot),
                "tieredEntries": len(self._tiered),
                "residentBytes": self.resident_bytes,
                "budgetBytes": self.STACK_BYTES_BUDGET,
            }

    def placement_snapshot(self) -> dict:
        """/debug/resources: how the resident dense stacks sit on the
        local devices (a mesh must PARTITION them — replicated
        everywhere also "spans" n devices) and each device's own memory
        counters. Empty until a stack is resident, so a scrape is never
        the first jax call in the process."""
        with self._lock:
            arrays = [e[1] for e in self._cache.values()]
        if not arrays:
            return {}
        memory = []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}  # the CPU backend reports none
            memory.append(
                {
                    "id": d.id,
                    "bytesInUse": stats.get("bytes_in_use"),
                    "peakBytesInUse": stats.get("peak_bytes_in_use"),
                    "bytesLimit": stats.get("bytes_limit"),
                }
            )
        return {
            "stacks": len(arrays),
            "devicesSpanned": min(len(a.sharding.device_set) for a in arrays),
            "replicatedStacks": sum(
                1
                for a in arrays
                if len(a.sharding.device_set) > 1
                and a.sharding.is_fully_replicated
            ),
            "deviceMemory": memory,
        }

    def invalidate(self) -> None:
        with self._lock:
            self._bytes.clear()
            self.resident_bytes = 0
            self._cache.clear()
            self._hot.clear()
            self._tiered.clear()
            self._container_bytes = {"dense": 0, "sparse": 0, "run": 0}
            self._push_residency_gauges()
            self._push_ledger_gauge()

    # ----------------------------------------------------- hot-row stacks
    # High-cardinality fields (dense stack over STACK_BYTES_BUDGET) keep
    # only an LRU working set of rows on device: an [H, S, W] slot stack
    # plus a row→slot map. Cold rows live in the host roaring bitmaps and
    # are promoted on first touch with an O(S·W) scatter — never a full
    # host matrix (SURVEY §7 hard part (e)).

    def hot_capacity(self, n_shards: int) -> int:
        # HALF the aggregate budget: a full-budget slot stack would be
        # mutually exclusive with every dense stack, and a hybrid query
        # (dense field ∩ hot field) would evict one to admit the other
        # on every request — permanent restack/re-promotion thrash
        h = (self.STACK_BYTES_BUDGET // 2) // max(
            1, n_shards * WORDS_PER_SHARD * 4
        )
        return max(8, 1 << (int(h).bit_length() - 1)) if h >= 8 else 8

    MAX_HOT_ENTRIES = 4  # count cap; the byte ledger is the real bound

    def _hot_entry(self, idx: Index, field: Field, view_name: str, shards):
        view = field.view(view_name)
        key = ("hot", idx.name, field.name, view_name, tuple(shards))
        # same O(1) whole-view fast path as matrix(): stamp read before
        # tokens, so a racing mutation only costs a re-validation
        view_ver = view.version if view is not None else None
        entry = self._hot.get(key)
        h = self.hot_capacity(len(shards))
        if (
            entry is not None
            and entry["h"] == h
            and view_ver is not None
            and entry.get("view_ver") == view_ver
        ):
            self._hot.move_to_end(key)
            return entry, view
        versions = tuple(self._frag_token(view, s) for s in shards)
        if entry is None or entry["h"] != h:
            from collections import OrderedDict

            zeros = np.zeros((h, len(shards), WORDS_PER_SHARD), dtype=np.uint32)
            self._evict_for(self._device_bytes(zeros.shape) - self._bytes.get(key, 0), keep=key)
            dev = (
                self.mesh_ctx.place_stack(zeros)
                if self.mesh_ctx is not None
                else jnp.asarray(zeros)
            )
            entry = {
                "versions": versions,
                "dev": dev,
                "slots": OrderedDict(),
                "h": h,
                "view_ver": view_ver,
            }
            self._hot[key] = entry
            self._account(key, self._placed_bytes(dev))
            self._hot.move_to_end(key)
            while len(self._hot) > self.MAX_HOT_ENTRIES:
                victim, _ = self._hot.popitem(last=False)
                self._forget(victim)
            return entry, view
        self._hot.move_to_end(key)
        if entry["versions"] != versions:
            # reconcile resident rows against fragment mutations
            stale: set[int] | None = set()
            for i, s in enumerate(shards):
                old_uid, old_ver = entry["versions"][i]
                new_uid, new_ver = versions[i]
                if (old_uid, old_ver) == (new_uid, new_ver):
                    continue
                frag = view.fragment(s) if view else None
                if frag is None or old_uid != new_uid:
                    stale = None
                    break
                dirty = frag.dirty_rows_since(old_ver)
                if dirty is None:
                    stale = None
                    break
                stale |= dirty
            if stale is None:
                entry["slots"].clear()
            else:
                self._upload_hot_rows(
                    entry,
                    view,
                    shards,
                    [(r, entry["slots"][r]) for r in stale & set(entry["slots"])],
                )
            entry["versions"] = versions
        entry["view_ver"] = view_ver
        return entry, view

    def _upload_hot_rows(self, entry, view, shards, pairs: list[tuple[int, int]]):
        """One batched scatter for every (row_id, slot) pair — the slot
        stack is full-copied per scatter, so k rows must cost one copy,
        not k."""
        if not pairs:
            return
        n_s = len(shards)
        k = len(pairs)
        data = np.zeros((k * n_s, WORDS_PER_SHARD), dtype=np.uint32)
        idx_arr = np.empty((k * n_s, 2), dtype=np.int32)
        for j, (row_id, slot) in enumerate(pairs):
            for i, s in enumerate(shards):
                frag = view.fragment(s) if view else None
                if frag is not None:
                    data[j * n_s + i] = frag.row_packed(row_id)
                idx_arr[j * n_s + i] = (slot, i)
        new_dev = _apply_stack_delta(entry["dev"], idx_arr, data)
        if new_dev.sharding != entry["dev"].sharding:
            new_dev = jax.device_put(new_dev, entry["dev"].sharding)
        entry["dev"] = new_dev
        # no lock acquisition: every caller (hot_batch → _hot_entry →
        # here) already holds self._lock, which is non-reentrant
        self.hot_row_uploads += len(pairs)

    def hot_batch(
        self,
        idx: Index,
        field: Field,
        view_name: str,
        shards: list[int],
        row_ids: list[int],
    ):
        """Atomically ensure EVERY row in ``row_ids`` is device-resident
        and return ``(dev [H,S,W], {row_id: slot})`` captured in one
        critical section. The returned array object is immutable — later
        evictions by other queries scatter into a NEW array, so a
        program compiled against this (dev, slots) pair can never read a
        reassigned slot (code-review r2: plan-time slots must not go
        stale before dispatch)."""
        with self._lock:
            entry, view = self._hot_entry(idx, field, view_name, shards)
            slots = entry["slots"]
            need = [r for r in dict.fromkeys(row_ids) if r >= 0]
            if len(need) > entry["h"]:
                raise StackOverBudget(
                    field.name,
                    len(need),
                    len(need) * len(shards) * WORDS_PER_SHARD * 4,
                    self.STACK_BYTES_BUDGET,
                )
            # bump every needed resident row first so the LRU never
            # evicts one member of this batch to admit another
            for r in need:
                if r in slots:
                    slots.move_to_end(r)
            uploads: list[tuple[int, int]] = []
            for r in need:
                if r in slots:
                    continue
                if len(slots) < entry["h"]:
                    slot = len(slots)
                else:
                    _evicted, slot = slots.popitem(last=False)
                slots[r] = slot
                uploads.append((r, slot))
            self._upload_hot_rows(entry, view, shards, uploads)
            return entry["dev"], {r: slots[r] for r in need}

    # ------------------------------------------- tiered compressed residency
    # Over-budget fields in "tiered" mode keep a hot working set of rows
    # resident in per-row COMPRESSED containers — dense words, sparse
    # column ids, or run intervals (executor/residency.py chooses per
    # row; ops/containers.py evaluates directly over the payloads).
    # Cold rows live in the host roaring bitmaps: their first touch
    # serves via a one-shot host-packed upload (host-served, merged
    # exactly on device), repeated touches promote them into residency,
    # and LRU slot reuse demotes the coldest resident row back to host.

    def residency_mode(self) -> str:
        # multi-host meshes serve over-budget fields through the legacy
        # slot path: container payloads are packed from PROCESS-LOCAL
        # fragments in local-position space, which cannot be declared a
        # replicated global array (each process would hold different
        # bits) — the [H, S, W] slot stack, by contrast, shards along S
        # like every other stack
        if self.mesh_ctx is not None and getattr(
            self.mesh_ctx, "multihost", False
        ):
            return "slots"
        return self.RESIDENCY_MODE

    def is_over_budget(
        self, idx: Index, field: Field, view_name: str, shards: list[int]
    ) -> bool:
        """Would this field's dense stack exceed the budget (i.e. do its
        rows serve through the tiered/hot layer)?  No allocation — the
        router's residency probe, once per touched field per query.
        Over the index's own scope (``Index.shard_scope``, told by
        identity: it covers every fragment the view has) the height is
        the view's memoized ``max_rows`` and nothing is walked; an
        explicit shard list is scanned, O(its length)."""
        view = field.view(view_name)
        if view is not None and shards is idx.shard_scope():
            r_pad = _pad_rows(view.max_rows())
        else:
            r_pad = self._projected_rows(view, shards)
        return self._device_bytes((r_pad, len(shards), WORDS_PER_SHARD)) > self.STACK_BYTES_BUDGET

    def _pack_plane(self, view, shards: list[int], row_id) -> np.ndarray:
        """Host-packed [S, W] plane of one row, straight from fragments."""
        out = np.zeros((len(shards), WORDS_PER_SHARD), dtype=np.uint32)
        if view is None or row_id is None or row_id < 0:
            return out
        for i, s in enumerate(shards):
            frag = view.fragment(s)
            if frag is not None:
                out[i] = frag.row_packed(row_id)
        return out

    def _tiered_entry(self, idx: Index, field: Field, view_name: str, shards):
        """(key, entry, view), versions reconciled. Caller holds _lock.
        Stale resident rows are DROPPED (not re-uploaded): a write may
        change a row's container class, so the next touch re-chooses and
        re-packs; touch counts survive, so a hot row re-promotes on its
        very next query."""
        from pilosa_tpu.executor.residency import TieredEntry

        view = field.view(view_name)
        key = ("tier", idx.name, field.name, view_name, tuple(shards))
        view_ver = view.version if view is not None else None
        entry = self._tiered.get(key)
        if entry is None:
            entry = TieredEntry(len(shards), self.STACK_BYTES_BUDGET)
            self._tiered[key] = entry
            while len(self._tiered) > self.MAX_TIERED_ENTRIES:
                victim = next(k for k in self._tiered if k != key)
                self._forget_tiered(victim, self._tiered.pop(victim))
                self._note_eviction("tiered")
        # track the live budget: set_stack_budget() reconfiguration must
        # size NEW stores from the current value (existing stores keep
        # their allocation — the shared ledger evicts them under
        # pressure like anything else)
        entry.budget = self.STACK_BYTES_BUDGET
        self._tiered.move_to_end(key)
        if view_ver is not None and entry.view_ver == view_ver:
            return key, entry, view
        versions = tuple(self._frag_token(view, s) for s in shards)
        if entry.versions != versions:
            stale: set[int] | None = set()
            if entry.versions is not None:
                for i, s in enumerate(shards):
                    old_uid, old_ver = entry.versions[i]
                    new_uid, _nv = versions[i]
                    if (old_uid, old_ver) == versions[i]:
                        continue
                    frag = view.fragment(s) if view else None
                    if frag is None or old_uid != new_uid:
                        stale = None
                        break
                    dirty = frag.dirty_rows_since(old_ver)
                    if dirty is None:
                        stale = None
                        break
                    stale |= dirty
            else:
                stale = None
            if stale is None:
                entry.clear()
            else:
                rows_dropped = [
                    r
                    for r in stale
                    if any(r in st["slots"] for st in entry.stores.values())
                ]
                self.rows_demoted += len(rows_dropped)
                entry.drop_rows(stale)
            entry.versions = versions
        entry.view_ver = view_ver
        return key, entry, view

    def _tiered_store(self, entry, kind: str, key: tuple) -> dict:
        """Get-or-create one kind's fixed-capacity device store. Caller
        holds _lock; creation charges the byte ledger (evicting LRU
        entries first) and the per-container gauges."""
        from pilosa_tpu.executor.residency import RUN_MAX_INTERVALS, SPARSE_MAX_IDS

        st = entry.stores.get(kind)
        if st is not None:
            return st
        h, _k = entry.capacity(kind, entry.n_shards * WORDS_PER_SHARD)
        if kind == "dense":
            host = np.zeros(
                (h, entry.n_shards, WORDS_PER_SHARD), dtype=np.uint32
            )
        elif kind == "sparse":
            host = np.full((h, SPARSE_MAX_IDS), -1, dtype=np.int32)
        elif kind == "run":
            host = np.zeros((h, RUN_MAX_INTERVALS, 2), dtype=np.int32)
        else:
            raise ValueError(f"unknown container kind {kind!r}")
        # a dense store splits over a mesh like a stack; payload stores
        # are replicated, whole on every device
        nbytes = self._device_bytes(host.shape) if kind == "dense" else int(host.nbytes)
        self._evict_for(nbytes, keep=key)
        if self.mesh_ctx is not None:
            dev = (
                self.mesh_ctx.place_stack(host)
                if kind == "dense"
                else self.mesh_ctx.place_block(host)
            )
        else:
            dev = jnp.asarray(host)
        from collections import OrderedDict

        st = {
            "dev": dev,
            "slots": OrderedDict(),
            "free": [],
            "alloc": 0,
            "h": h,
            "nbytes": nbytes,
        }
        entry.stores[kind] = st
        self._account(key, self._bytes.get(key, 0) + nbytes)
        self._container_bytes[kind] += nbytes
        self._push_residency_gauges()
        return st

    def _forget_tiered(self, key: tuple, entry) -> None:
        # caller holds self._lock
        for kind, st in entry.stores.items():
            self._container_bytes[kind] -= st["nbytes"]
        self._forget(key)
        self._push_residency_gauges()

    def _push_residency_gauges(self) -> None:
        if self.stats is None:
            return
        for kind, v in self._container_bytes.items():
            self.stats.gauge(
                "residency_bytes", v, tags={"container": kind}
            )

    def tiered_plan(
        self,
        idx: Index,
        field: Field,
        view_name: str,
        shards: list[int],
        row_id: int,
    ) -> tuple[str, str]:
        """Plan-time residency decision for one row leaf →
        ``(container_kind, action)`` with action one of:

        - "resident" — already on device; the batch snapshot will bump it;
        - "promote"  — touch count reached the threshold; the batch will
          pack + upload it into its container store (rows_promoted);
        - "cold"     — below the threshold; serve via a one-shot
          host-packed plane upload, no residency churn.

        The chooser memoizes per (row, fragment versions); a miss costs
        one host row pack + an O(words) popcount scan."""
        from pilosa_tpu.executor import residency

        with self._lock:
            key, entry, view = self._tiered_entry(idx, field, view_name, shards)
            if row_id is None or row_id < 0:
                return "sparse", "cold"  # unknown key ⇒ all-zero plane
            kind = entry.kinds.get(row_id)
            if kind is not None:
                # LRU, not FIFO: without the bump, a constantly-queried
                # resident row's kind memo would age out behind one-shot
                # cold rows, making tiered_resident report it cold (and
                # re-analyzing its whole plane under the lock each plan)
                entry.kinds.move_to_end(row_id)
            else:
                plane = self._pack_plane(view, shards, row_id)
                nbits, nruns = residency.analyze_plane(plane)
                kind = residency.choose_container(
                    nbits, nruns, len(shards) * WORDS_PER_SHARD
                )
                entry.kinds[row_id] = kind
                while len(entry.kinds) > residency.MAX_TOUCH_ROWS:
                    entry.kinds.popitem(last=False)
            touches = entry.note_touch(row_id)
            if entry.resident(row_id, kind):
                return kind, "resident"
            if touches >= residency.PROMOTE_TOUCHES:
                return kind, "promote"
            return kind, "cold"

    def cold_plane(
        self, idx: Index, field: Field, view_name: str, shards, row_id: int
    ):
        """One-shot device upload of a host-packed row plane — the
        pre-promotion cold service (the host serves the row, the device
        program merges it exactly with resident-compressed rows)."""
        view = field.view(view_name)
        plane = self._pack_plane(view, shards, row_id)
        with self._lock:
            self.cold_uploads += 1
        if self.mesh_ctx is not None:
            return self.mesh_ctx.place_rows(plane)
        return jnp.asarray(plane)

    def tiered_batch(
        self,
        idx: Index,
        field: Field,
        view_name: str,
        shards: list[int],
        needs: "list[tuple[int, str]]",
    ):
        """Atomically ensure every (row, kind) pair is resident and
        return ``({kind: dev_store}, {row: slot})`` captured in one
        critical section — the same immutable-snapshot contract as
        hot_batch (functional scatters swap arrays, so a compiled
        program can never read a reassigned slot)."""
        from pilosa_tpu.executor import residency

        with self._lock:
            key, entry, view = self._tiered_entry(idx, field, view_name, shards)
            uniq = list(dict.fromkeys((r, k) for r, k in needs if r >= 0))
            by_kind: dict[str, list[int]] = {}
            for r, k in uniq:
                by_kind.setdefault(k, []).append(r)
            stores = {
                k: self._tiered_store(entry, k, key) for k in by_kind
            }
            for k, rows in by_kind.items():
                if len(rows) > stores[k]["h"]:
                    # atomic-batch contract: a query needing more rows of
                    # one container kind than its store holds fails
                    # EXPLICITLY — never a silently evicted slot mid-query
                    raise StackOverBudget(
                        f"{field.name} ({k} container store, "
                        f"{stores[k]['h']} slots)",
                        len(rows),
                        len(rows) * len(shards) * WORDS_PER_SHARD * 4,
                        self.STACK_BYTES_BUDGET,
                    )
            # bump resident batch members first so LRU reuse never
            # demotes one member of this batch to admit another
            for k, rows in by_kind.items():
                slots = stores[k]["slots"]
                for r in rows:
                    if r in slots:
                        slots.move_to_end(r)
            slot_map: dict[int, int] = {}
            for k, rows in by_kind.items():
                st = stores[k]
                missing = [r for r in rows if r not in st["slots"]]
                for r in rows:
                    if r in st["slots"]:
                        slot_map[r] = st["slots"][r]
                # pack + validate BEFORE any slot mutation: a payload
                # that no longer fits its planned kind (a racing write
                # changed the row's class) must fail with the slot maps
                # untouched, or later queries would read the assigned
                # but never-written slot as resident zeros
                payloads = {
                    r: self._pack_payload(k, st, view, shards, r)
                    for r in missing
                }
                uploads: list[tuple[int, int]] = []
                for r in missing:
                    if st["free"]:
                        slot = st["free"].pop()
                    elif st["alloc"] < st["h"]:
                        slot = st["alloc"]
                        st["alloc"] += 1
                    else:
                        demoted, slot = st["slots"].popitem(last=False)
                        entry.kinds.pop(demoted, None)
                        self.rows_demoted += 1
                        if self.stats is not None:
                            self.stats.count("rows_demoted")
                    st["slots"][r] = slot
                    slot_map[r] = slot
                    uploads.append((r, slot))
                if uploads:
                    self._upload_tiered_rows(st, k, payloads, uploads)
                    self.rows_promoted += len(uploads)
                    self.hot_row_uploads += len(uploads)
                    if self.stats is not None:
                        self.stats.count("rows_promoted", len(uploads))
            return {k: st["dev"] for k, st in stores.items()}, slot_map

    def _pack_payload(self, kind: str, st: dict, view, shards, row_id: int):
        """Pack one row for its planned container store, validating the
        fit (caller holds _lock and has not yet assigned a slot)."""
        from pilosa_tpu.executor import residency

        payload = residency.pack_container(
            kind, self._pack_plane(view, shards, row_id)
        )
        if kind != "dense" and payload.shape[0] > st["dev"].shape[1]:
            raise StackOverBudget(
                f"row {row_id} no longer fits its planned {kind!r} "
                "container (changed class mid-plan)",
                1,
                int(payload.nbytes),
                self.STACK_BYTES_BUDGET,
            )
        return payload

    def _upload_tiered_rows(
        self, st: dict, kind: str, payloads: dict, uploads
    ) -> None:
        """Scatter pre-packed, pre-validated payloads into one kind
        store (one functional scatter per batch, padded to pow2 so XLA
        retraces stay rare). Caller holds _lock."""
        k_pad = 1 << (len(uploads) - 1).bit_length()
        idx_arr = np.full(k_pad, _OOB, dtype=np.int32)
        rows_arr = np.zeros((k_pad,) + st["dev"].shape[1:], st["dev"].dtype)
        if kind == "sparse":
            rows_arr[:] = -1
        for j, (row_id, slot) in enumerate(uploads):
            payload = payloads[row_id]
            if kind == "dense":
                rows_arr[j] = payload
            else:
                rows_arr[j, : payload.shape[0]] = payload
            idx_arr[j] = slot
        new_dev = _scatter_rows(st["dev"], idx_arr, rows_arr)
        if new_dev.sharding != st["dev"].sharding:
            new_dev = jax.device_put(new_dev, st["dev"].sharding)
        st["dev"] = new_dev

    def tiered_resident(
        self, idx: Index, field: Field, view_name: str, shards, row_id: int
    ) -> bool:
        """Cheap residency probe (router cost model) — never creates
        entries, packs planes, or bumps touch counts."""
        key = ("tier", idx.name, field.name, view_name, tuple(shards))
        with self._lock:
            entry = self._tiered.get(key)
            if entry is None:
                return False
            kind = entry.kinds.get(row_id)
            if kind is None:
                return False
            return entry.resident(row_id, kind)

    def residency_snapshot(self) -> dict:
        """/debug/vars ``deviceResidency`` section + the ?profile=true
        residency block (owns the field names, like stats_snapshot)."""
        with self._lock:
            per_entry = []
            for key, entry in self._tiered.items():
                per_entry.append(
                    {
                        "field": key[2],
                        "view": key[3],
                        "shards": len(key[4]),
                        "rows": {
                            k: len(st["slots"])
                            for k, st in entry.stores.items()
                        },
                    }
                )
            return {
                "mode": self.RESIDENCY_MODE,
                "entries": len(self._tiered),
                "residentRows": sum(
                    e.resident_rows() for e in self._tiered.values()
                ),
                "rowsPromoted": self.rows_promoted,
                "rowsDemoted": self.rows_demoted,
                "coldUploads": self.cold_uploads,
                "evictions": dict(self.evictions),
                "bytesByContainer": dict(self._container_bytes),
                "budgetBytes": self.STACK_BYTES_BUDGET,
                "tiers": per_entry,
            }


# ------------------------------------------------------------------ plans
class _Planner:
    """Builds (closure, leaf inputs, structure key) for one call tree.

    ``block_shape`` is the [S, W] plane shape the closures trace against:
    the global (len(shards), WORDS_PER_SHARD) for single-program jit, or
    the per-device block when the closure will run inside a shard_map
    program (zero leaves must be block-shaped there — a global-shaped
    zeros would shape-mismatch every sharded operand)."""

    def __init__(
        self,
        idx: Index,
        shards: list[int],
        stacks: StackCache,
        block_shape: tuple[int, int] | None = None,
    ):
        self.idx = idx
        self.shards = shards
        self.stacks = stacks
        self.block_shape = block_shape or (len(shards), WORDS_PER_SHARD)
        self._builders: list[Callable[[], Any]] = []  # device-input thunks
        self.scalars: list = []  # traced row-id/slot inputs (int | thunk)
        self._array_keys: dict[tuple, int] = {}
        # over-budget fields: rows each query leaf needs, resolved to an
        # atomic (dev, slots) snapshot at materialize time
        self._hot_needs: dict[tuple, tuple[Field, str, list[int]]] = {}
        self._hot_resolved: dict[tuple, tuple] = {}
        # tiered-mode needs: (row, container kind) pairs per field,
        # resolved via ONE atomic tiered_batch snapshot each
        self._tiered_needs: dict[tuple, tuple[Field, str, list]] = {}
        self._tiered_resolved: dict[tuple, tuple] = {}
        # (leaf structure key, count closure) for sparse/run leaves —
        # Count(Row) over a compressed row skips the plane entirely
        self.direct_counts: list[tuple[str, Callable]] = []

    def _add_array(self, key: tuple, build: Callable[[], Any]) -> int:
        i = self._array_keys.get(key)
        if i is None:
            i = len(self._builders)
            self._array_keys[key] = i
            self._builders.append(build)
        return i

    def materialize(self) -> list[Any]:
        """Resolve device inputs AFTER planning finishes. Hot-row fields
        resolve here as ONE atomic hot_batch per field — plan-time slot
        binding could go stale if a concurrent query evicted a row
        between planning and dispatch; the batch snapshot cannot."""
        for fkey, (field, view_name, rows) in self._hot_needs.items():
            self._hot_resolved[fkey] = self.stacks.hot_batch(
                self.idx, field, view_name, self.shards, rows
            )
        for fkey, (field, view_name, needs) in self._tiered_needs.items():
            self._tiered_resolved[fkey] = self.stacks.tiered_batch(
                self.idx, field, view_name, self.shards, needs
            )
        return [b() for b in self._builders]

    def scalar_values(self) -> list[int]:
        """Concrete traced-scalar inputs; call AFTER materialize() (hot
        slots resolve there)."""
        return [s() if callable(s) else s for s in self.scalars]

    def _add_scalar(self, value: int) -> int:
        self.scalars.append(int(value))
        return len(self.scalars) - 1

    def _add_constant(self, value: int) -> int:
        """A BSI comparison constant as ``ops.bsi.CONSTANT_WORDS``
        consecutive scalars; → the index of the first."""
        first = len(self.scalars)
        self.scalars.extend(ops.bsi.constant_words(int(value)).tolist())
        return first

    def _matrix_leaf(self, field: Field, view_name: str, row_id: int):
        """closure(arrays, scalars) → uint32[S, W] for one stored row.

        Small fields read a slot of the full dense stack; over-budget
        fields promote the row into the hot slot stack and read that
        slot instead (same closure shape — only the traced index
        differs)."""
        try:
            # probing the budget up front keeps one compiled program per
            # (field mode); the check allocates nothing
            self.stacks.matrix(self.idx, field, view_name, self.shards)
            ai = self._add_array(
                ("m", field.name, view_name),
                lambda: self.stacks.matrix(
                    self.idx, field, view_name, self.shards
                )[0],
            )
            si = self._add_scalar(row_id)
            mode = "m"
        except StackOverBudget:
            if self.stacks.residency_mode() != "slots":
                return self._tiered_leaf(field, view_name, row_id)
            fkey = (field.name, view_name)
            need = self._hot_needs.setdefault(fkey, (field, view_name, []))
            if row_id >= 0:
                need[2].append(row_id)
            ai = self._add_array(
                ("hot",) + fkey, lambda: self._hot_resolved[fkey][0]
            )
            self.scalars.append(
                lambda: self._hot_resolved[fkey][1].get(row_id, -1)
            )
            si = len(self.scalars) - 1
            mode = "hot"

        def run(arrays, scalars):
            m = arrays[ai]
            row = scalars[si]
            # out-of-range / -1 rows read as zeros; axis 0 of the
            # row-major stack — a contiguous [S, W] plane, so the slice
            # reads only this row's bytes (see stack_view_matrices).
            # dynamic_index_in_dim + select rather than jnp.take: a
            # scalar take lowers to a gather HLO, which XLA may
            # materialize as its own HBM-sized temp before the consumer
            # op; dynamic-slice fuses into the consumer (the AND/popcount
            # chain), keeping a query's traffic at the rows it touches.
            r = jnp.clip(row, 0, m.shape[0] - 1)
            plane = jax.lax.dynamic_index_in_dim(m, r, axis=0, keepdims=False)
            valid = (row >= 0) & (row < m.shape[0])
            return jnp.where(valid, plane, jnp.uint32(0))

        return run, f"row({mode}:{field.name}/{view_name})"

    def _tiered_leaf(self, field: Field, view_name: str, row_id: int):
        """Row leaf of an over-budget field in tiered residency mode
        (docs/device-residency.md): the closure decodes the row's
        COMPRESSED container inside the consuming program — the kind is
        static (it is part of the structure key, so each kind combination
        compiles once) and the traced scalar is the container-store slot.
        Cold (pre-promotion) rows serve via a one-shot host-packed plane
        input instead — host-served, merged exactly on device."""
        kind, action = self.stacks.tiered_plan(
            self.idx, field, view_name, self.shards, row_id
        )
        n_s, n_w = len(self.shards), WORDS_PER_SHARD
        if action == "cold":
            ai = self._add_array(
                ("cold", field.name, view_name, row_id),
                lambda: self.stacks.cold_plane(
                    self.idx, field, view_name, self.shards, row_id
                ),
            )
            # the array ORDINAL must be part of the structure key: cold
            # arrays are per-row inputs (unlike the shared dense/tiered
            # stores), so Union(Row(7), Row(7)) — one deduped input —
            # and Union(Row(8), Row(9)) — two — are different program
            # structures that a row-blind key would alias
            return (
                lambda arrays, scalars: arrays[ai]
            ), f"row(cold{ai}:{field.name}/{view_name})"
        fkey = (field.name, view_name)
        need = self._tiered_needs.setdefault(fkey, (field, view_name, []))
        need[2].append((row_id, kind))
        ai = self._add_array(
            ("tier", kind) + fkey,
            lambda: self._tiered_resolved[fkey][0][kind],
        )
        self.scalars.append(
            lambda: self._tiered_resolved[fkey][1].get(row_id, -1)
        )
        si = len(self.scalars) - 1
        skey = f"row(tier-{kind}:{field.name}/{view_name})"

        def gather(arrays, scalars):
            st = arrays[ai]
            slot = scalars[si]
            s = jnp.clip(slot, 0, st.shape[0] - 1)
            payload = jax.lax.dynamic_index_in_dim(
                st, s, axis=0, keepdims=False
            )
            return payload, slot >= 0

        if kind == "dense":

            def run(arrays, scalars):
                plane, valid = gather(arrays, scalars)
                return jnp.where(valid, plane, jnp.uint32(0))

        elif kind == "sparse":

            def run(arrays, scalars):
                ids, valid = gather(arrays, scalars)
                ids = jnp.where(valid, ids, jnp.int32(-1))
                return ops.containers.sparse_plane(ids, n_s, n_w)

            self.direct_counts.append(
                (
                    skey,
                    lambda arrays, scalars: ops.containers.sparse_count(
                        jnp.where(
                            gather(arrays, scalars)[1],
                            gather(arrays, scalars)[0],
                            jnp.int32(-1),
                        )
                    ),
                )
            )
        elif kind == "run":

            def run(arrays, scalars):
                runs, valid = gather(arrays, scalars)
                runs = jnp.where(valid, runs, jnp.int32(0))
                return ops.containers.run_plane(runs, n_s, n_w)

            self.direct_counts.append(
                (
                    skey,
                    lambda arrays, scalars: ops.containers.run_count(
                        jnp.where(
                            gather(arrays, scalars)[1],
                            gather(arrays, scalars)[0],
                            jnp.int32(0),
                        )
                    ),
                )
            )
        else:
            raise PlanError(f"unknown container kind {kind!r}")
        return run, skey

    def _existence(self):
        ef = self.idx.field(EXISTENCE_FIELD)
        if not self.idx.options.track_existence:
            raise PlanError(
                "query requires existence tracking (index created with "
                "track_existence=false)"
            )
        if ef is None:
            return (lambda arrays, scalars: jnp.zeros(
                self.block_shape, jnp.uint32
            )), "exists(empty)"
        return self._matrix_leaf(ef, VIEW_STANDARD, 0)

    def _bsi(self, field: Field):
        """closure → uint32[D, S, W] bit-slice block (row-major stack).

        Over-budget BSI stacks (huge shard lists) serve through the
        tiered residency layer in tiered mode: each slice row is its own
        container leaf — sign/existence slices tend to pack as runs,
        high-significance slices as sparse ids — and the closure stacks
        the decoded planes into the [D, S, W] block the BSI kernels
        expect (a transient inside the program, never a resident copy)."""
        need = BSI_OFFSET + field.bit_depth
        try:
            self.stacks.matrix(self.idx, field, VIEW_BSI, self.shards)
        except StackOverBudget:
            if self.stacks.residency_mode() == "slots":
                raise
            subs = [
                self._matrix_leaf(field, VIEW_BSI, d) for d in range(need)
            ]
            fns = [s[0] for s in subs]
            keys = ",".join(s[1] for s in subs)

            def run_tiered(arrays, scalars):
                return jnp.stack([fn(arrays, scalars) for fn in fns])

            return run_tiered, f"bsitier({field.name}:{keys})"
        ai = self._add_array(
            ("bsi", field.name),
            lambda: self.stacks.matrix(self.idx, field, VIEW_BSI, self.shards)[0],
        )

        def run(arrays, scalars):
            return ops.bsi.block(arrays[ai], need)

        return run, f"bsi({field.name}:{field.bit_depth})"

    # ---------------------------------------------------------- call tree
    def plan(self, call: Call):
        """→ (closure(arrays, scalars) → uint32[S, W], structure key)"""
        name = call.name
        if name in ("Row", "Range"):
            return self._plan_row(call)
        if name in ("Union", "Intersect", "Difference", "Xor"):
            subs = [self.plan(ch) for ch in call.children]
            if not subs:
                if name == "Intersect":
                    raise PlanError("Intersect() needs at least one child")
                zero = lambda arrays, scalars: jnp.zeros(
                    self.block_shape, jnp.uint32
                )
                return zero, f"{name}()"
            fns = [s[0] for s in subs]
            keys = ",".join(s[1] for s in subs)
            op = {
                "Union": jnp.bitwise_or,
                "Intersect": jnp.bitwise_and,
                "Xor": jnp.bitwise_xor,
                "Difference": lambda a, b: a & ~b,
            }[name]

            def run(arrays, scalars):
                out = fns[0](arrays, scalars)
                for fn in fns[1:]:
                    out = op(out, fn(arrays, scalars))
                return out

            return run, f"{name}({keys})"
        if name == "Not":
            if len(call.children) != 1:
                raise PlanError("Not() takes exactly one call")
            sub, key = self.plan(call.children[0])
            ex, exkey = self._existence()
            return (
                lambda arrays, scalars: ex(arrays, scalars) & ~sub(arrays, scalars)
            ), f"Not({key},{exkey})"
        if name == "All":
            ex, exkey = self._existence()
            return ex, f"All({exkey})"
        if name == "Shift":
            if len(call.children) != 1:
                raise PlanError("Shift() takes exactly one call")
            n = call.arg("n", 1)
            if not isinstance(n, int) or n < 0:
                raise PlanError(f"Shift() n must be a non-negative integer, got {n!r}")
            sub, key = self.plan(call.children[0])
            return (
                lambda arrays, scalars: ops.shift_words(sub(arrays, scalars), n)
            ), f"Shift{n}({key})"
        raise PlanError(f"{name!r} is not a bitmap call")

    def _plan_row(self, call: Call):
        cond = call.condition()
        if cond is not None:
            return self._plan_condition(call, cond)
        fa = call.field_arg()
        if fa is None:
            raise PlanError(f"Row() needs a field argument: {call!r}")
        fname, row = fa
        field = self.idx.field(fname)
        if field is None:
            raise PlanError(f"field {fname!r} not found")
        row_id = self._row_id(field, row)

        ts_from, ts_to = call.arg("from"), call.arg("to")
        if ts_from is not None or ts_to is not None:
            if field.options.field_type != FIELD_TIME:
                raise PlanError(f"field {fname!r} is not a time field")
            raw_from, raw_to = ts_from, ts_to
            ts_from = coerce_timestamp(ts_from) if ts_from is not None else None
            ts_to = coerce_timestamp(ts_to) if ts_to is not None else None
            if raw_from is not None and ts_from is None:
                raise PlanError(f"bad from= timestamp {raw_from!r}")
            if raw_to is not None and ts_to is None:
                raise PlanError(f"bad to= timestamp {raw_to!r}")
            bounds = field.time_bounds()
            if bounds is None:
                zero = lambda arrays, scalars: jnp.zeros(
                    self.block_shape, jnp.uint32
                )
                return zero, "time(empty)"
            ts_from = ts_from if ts_from is not None else bounds[0]
            ts_to = ts_to if ts_to is not None else bounds[1]
            view_names = [
                v
                for v in views_by_time_range(
                    VIEW_STANDARD, ts_from, ts_to, field.options.time_quantum
                )
                if field.view(v) is not None
            ]
            subs = [self._matrix_leaf(field, v, row_id) for v in view_names]
            if not subs:
                zero = lambda arrays, scalars: jnp.zeros(
                    self.block_shape, jnp.uint32
                )
                return zero, "time(empty)"
            fns = [s[0] for s in subs]
            keys = ",".join(s[1] for s in subs)

            def run(arrays, scalars):
                out = fns[0](arrays, scalars)
                for fn in fns[1:]:
                    out = out | fn(arrays, scalars)
                return out

            return run, f"timeunion({keys})"
        return self._matrix_leaf(field, VIEW_STANDARD, row_id)

    def _plan_condition(self, call: Call, cond: tuple[str, Condition]):
        fname, condition = cond
        field = self.idx.field(fname)
        if field is None:
            raise PlanError(f"field {fname!r} not found")
        if field.options.field_type != FIELD_INT:
            raise PlanError(f"field {fname!r} is not an int field")
        bsi, bkey = self._bsi(field)
        ex, _ = self._existence() if condition.value is None and condition.op == "==" else (None, None)

        value = condition.value
        op = condition.op
        if value is None:
            if op == "!=":
                return (
                    lambda arrays, scalars: bsi(arrays, scalars)[0]
                ), f"notnull({bkey})"
            if op == "==":
                return (
                    lambda arrays, scalars: ex(arrays, scalars)
                    & ~bsi(arrays, scalars)[0]
                ), f"isnull({bkey})"
            raise PlanError(f"null only supports ==/!= comparisons, got {op!r}")

        # The constants are traced operands, like a row id: the structure
        # key holds the operator and never a value, so every threshold
        # runs the one compiled program (ops.bsi.constant_words).
        at = [
            self._add_constant(b)
            for b in (value if op == "between" else [value])
        ]
        if self.stacks.stats is not None:
            self.stacks.stats.count(
                "bsi_condition_leaves_total", tags={"op": op}
            )

        def words(scalars, k):
            return scalars[at[k] : at[k] + ops.bsi.CONSTANT_WORDS]

        if op == "between":
            return (
                lambda arrays, scalars: ops.bsi.between(
                    bsi(arrays, scalars), words(scalars, 0), words(scalars, 1)
                )
            ), f"between({bkey})"
        return (
            lambda arrays, scalars: ops.bsi.compare(
                bsi(arrays, scalars), op, words(scalars, 0)
            )
        ), f"cmp[{op}]({bkey})"

    def _row_id(self, field: Field, row: Any) -> int:
        if isinstance(row, bool):
            return int(row)
        if isinstance(row, int):
            return row
        if isinstance(row, str):
            if not field.options.keys:
                raise PlanError(f"field {field.name!r} does not use string keys")
            rid = field.row_keys.translate_key(row, create=False)
            return rid if rid is not None else -1
        raise PlanError(f"bad row value {row!r}")


# ----------------------------------------------------------- compiled API
class QueryCompiler:
    """Caches jitted programs keyed by (index, structure, mode).

    The stacked arrays are built for the exact shard list of each query
    (the stack cache keys on it), so programs need no shard mask — two
    different shard subsets of the same length share one compiled program
    and differ only in their inputs.
    """

    def __init__(self, mesh_ctx=None, stats=None):
        self.stacks = StackCache(mesh_ctx, stats=stats)
        self.mesh_ctx = mesh_ctx
        self._programs: dict[tuple, Callable] = {}
        self._ones: dict[int, Any] = {}
        self._scalar_arrays: dict[tuple, Any] = {}
        # the HOST compilation layer: numpy plans over host-resident
        # stacks, memoized per plan key (executor/hostpath.py). Hangs off
        # the compiler so both engines — and their caches — share one
        # owner; the executor's router picks which one a call runs on.
        from pilosa_tpu.executor.hostpath import HostEngine

        self.host = HostEngine(stats=stats)
        # the MESH compilation layer: explicit shard_map programs with
        # psum reduction trees over the (shards × words) mesh — the
        # router's third path (docs/spmd.md). Only attached for a real
        # multi-device mesh; a 1-device mesh compiles to the identical
        # program with placement overhead on top.
        self.mesh_engine = None
        if mesh_ctx is not None and getattr(mesh_ctx, "n_devices", 1) > 1:
            from pilosa_tpu.parallel.mesh import MeshQueryEngine

            self.mesh_engine = MeshQueryEngine(mesh_ctx.mesh, stats=stats)

    def device_scalars(self, values: list[int]):
        """Device-resident int32 operand vector, cached by VALUE.

        Dispatching with a fresh numpy array uploads it host→device on
        every call — pure overhead next to the compute. Repeated
        queries, the common serving case, hit this cache and dispatch
        with zero transfers. Where the values do NOT repeat (row ids of
        a wide field, or BSI comparison constants, which travel here
        since they became operands: a fare slider sends another
        threshold every time) nearly every call misses and pays one
        small ``device_put`` on the calling thread, the wave leader's
        under the scheduler, and the cache turns over every 4,096
        queries. Each miss is counted (``device_scalar_uploads_total``);
        over ``queries_routed`` that is uploads per query."""
        key = tuple(values)
        cached = self._scalar_arrays.get(key)
        if cached is None:
            if self.stacks.stats is not None:
                self.stacks.stats.count("device_scalar_uploads_total")
            if len(self._scalar_arrays) >= 4096:
                # tiny (≤ a few hundred bytes each); drop-all beats LRU
                # bookkeeping on the hot path, rebuild is one upload
                self._scalar_arrays.clear()
            host = np.asarray(key, dtype=np.int32)
            if self.mesh_ctx is not None:
                # replicate explicitly (and in ONE placement — not
                # asarray-then-re-place) so SPMD programs see a committed
                # sharding instead of inferring one per call
                cached = jax.device_put(
                    host,
                    jax.sharding.NamedSharding(
                        self.mesh_ctx.mesh, jax.sharding.PartitionSpec()
                    ),
                )
            else:
                cached = jnp.asarray(host)
            self._scalar_arrays[key] = cached
        return cached

    def cache_snapshot(self) -> dict:
        """/debug/vars ``stackCache``: the stack cache's counters and,
        beside them, the number of programs this compiler holds. The
        program cache has no bound: a key that varies with the data
        (as a BSI constant did until it became an operand) shows here as
        a number that climbs with the traffic."""
        out = self.stacks.stats_snapshot()
        out["programs"] = len(self._programs)
        return out

    def program(self, key: tuple, build: Callable[[], Callable]) -> Callable:
        """Generic compiled-program cache (used by the executor for its
        aggregate programs as well)."""
        prog = self._programs.get(key)
        if prog is None:
            prog = build()
            self._programs[key] = prog
        return prog

    def run_program(self, key: tuple, build: Callable[[], Callable], *args):
        """program() + call in one step — the call-site sugar the
        executor uses for its aggregate programs. jit compiles on the
        first call per argument shape and reuses the executable after
        (through the persistent compilation cache across restarts)."""
        return self.program(key, build)(*args)

    def ones(self, n_shards: int):
        """Cached all-ones filter [S, W] on device."""
        cached = self._ones.get(n_shards)
        if cached is None:
            cached = jnp.full(
                (n_shards, WORDS_PER_SHARD), 0xFFFFFFFF, dtype=jnp.uint32
            )
            if self.mesh_ctx is not None:
                cached = self.mesh_ctx.place_rows(cached)
            self._ones[n_shards] = cached
        return cached

    def _plan(self, idx: Index, call: Call, shards: list[int]):
        planner = _Planner(idx, shards, self.stacks)
        run, skey = planner.plan(call)
        return planner, run, skey

    def bitmap_device(self, idx: Index, call: Call, shards: list[int]):
        """Evaluate a bitmap call for all shards in one program →
        device uint32[S, W]."""
        planner, run, skey = self._plan(idx, call, shards)
        key = (idx.name, len(shards), skey, "words")
        prog = self.program(key, lambda: named_jit("pilosa_words", run))
        arrays = planner.materialize()
        return prog(arrays, self.device_scalars(planner.scalar_values()))

    def bitmap_words(self, idx: Index, call: Call, shards: list[int]) -> np.ndarray:
        return np.asarray(self.bitmap_device(idx, call, shards))

    def count_async(self, idx: Index, call: Call, shards: list[int]):
        """Device int64 scalar (not synced) — lets callers pipeline many
        queries before paying the device→host readback latency.

        When the whole tree is ONE sparse/run container leaf (tiered
        residency), the count reads the compressed payload directly —
        O(payload) values, no [S, W] plane even transiently."""
        planner, run, skey = self._plan(idx, call, shards)
        direct = None
        if len(planner.direct_counts) == 1 and planner.direct_counts[0][0] == skey:
            direct = planner.direct_counts[0][1]
        key = (
            idx.name,
            len(shards),
            skey,
            "count-direct" if direct is not None else "count",
        )

        def build():
            if direct is not None:
                return named_jit("pilosa_count_direct", direct)

            def count(arrays, scalars):
                words = run(arrays, scalars)
                return jnp.sum(ops.popcount_rows(words).astype(jnp.int64))

            return named_jit("pilosa_count" + range_suffix(skey), count)

        prog = self.program(key, build)
        arrays = planner.materialize()
        return prog(arrays, self.device_scalars(planner.scalar_values()))

    def tiered_bsi_block(self, idx: Index, field: Field, shards: list[int]):
        """[D, S, W] bit-slice block of an over-budget int field,
        assembled on device from tiered compressed slice rows (the
        executor's aggregate paths feed it to their Sum/Min/Max/TopN
        programs; the block is a program OUTPUT, not a resident stack)."""
        planner = _Planner(idx, shards, self.stacks)
        run, skey = planner._bsi(field)
        key = (idx.name, len(shards), skey, "bsi_block")
        prog = self.program(key, lambda: named_jit("pilosa_bsi_block", run))
        arrays = planner.materialize()
        return prog(arrays, self.device_scalars(planner.scalar_values()))

    # ------------------------------------------------------ mesh programs
    # The explicit-SPMD (shard_map) compile path. Planner closures are the
    # SAME ones the single-program path uses — planned against the mesh's
    # per-device block shape so zero leaves trace block-shaped — and the
    # MeshQueryEngine wraps them in shard_map with the psum reduction
    # trees. Program caching rides the same cache as every other
    # program ("mesh" + spec mode in the key).

    def mesh_mode(self, n_shards: int) -> str | None:
        """The mesh placement mode serving this shard count, or None when
        no mesh is attached / the shapes only replicate (no mesh program)."""
        if self.mesh_engine is None:
            return None
        return self.mesh_engine.spec_mode(n_shards, WORDS_PER_SHARD)

    def mesh_plan(self, idx: Index, call: Call, shards: list[int], mode: str):
        """(planner, run, skey) with block-shaped zero leaves for ``mode``."""
        planner = _Planner(
            idx,
            shards,
            self.stacks,
            block_shape=self.mesh_engine.block_shape(
                len(shards), WORDS_PER_SHARD, mode
            ),
        )
        run, skey = planner.plan(call)
        return planner, run, skey

    def _mesh_dispatch(self, name: str, prog, *args):
        """Issue one mesh program: spanned per program (the
        ``mesh.dispatch`` trace surface) and counted for /debug/vars."""
        eng = self.mesh_engine
        eng.note_call(name)
        with GLOBAL_TRACER.span(
            "mesh.dispatch", program=name, devices=eng.n_devices
        ):
            return prog(*args)

    def mesh_bitmap_device(self, idx: Index, call: Call, shards: list[int]):
        """Bitmap call tree as ONE shard_map program → sharded
        uint32[S, W] (elementwise per device block; no collectives)."""
        mode = self.mesh_mode(len(shards))
        planner, run, skey = self.mesh_plan(idx, call, shards, mode)
        key = (idx.name, len(shards), skey, "mesh", mode, "words")
        prog = self.program(
            key, lambda: self.mesh_engine.bitmap_tree(run, mode)
        )
        arrays = planner.materialize()
        return self._mesh_dispatch(
            "bitmap",
            prog,
            arrays,
            self.device_scalars(planner.scalar_values()),
        )

    def mesh_bitmap_words(self, idx: Index, call: Call, shards: list[int]) -> np.ndarray:
        """Synchronous mesh bitmap: the gather of the sharded result IS a
        collective readback — spanned as ``mesh.collective`` so the query
        trace shows where the cross-chip transfer happened."""
        dev = self.mesh_bitmap_device(idx, call, shards)
        with GLOBAL_TRACER.span(
            "mesh.collective", program="bitmap",
            devices=self.mesh_engine.n_devices,
        ):
            return np.asarray(dev)

    def mesh_count_async(self, idx: Index, call: Call, shards: list[int]):
        """Count as one shard_map program → replicated int64 (not
        synced); rides the same readback wave as every other pending."""
        mode = self.mesh_mode(len(shards))
        planner, run, skey = self.mesh_plan(idx, call, shards, mode)
        key = (idx.name, len(shards), skey, "mesh", mode, "count")
        prog = self.program(
            key, lambda: self.mesh_engine.count_tree(run, mode)
        )
        arrays = planner.materialize()
        return self._mesh_dispatch(
            "count",
            prog,
            arrays,
            self.device_scalars(planner.scalar_values()),
        )

    def mesh_snapshot(self) -> dict:
        """/debug/vars ``meshExecution`` section."""
        if self.mesh_engine is None:
            return {"attached": False}
        out = {"attached": True}
        out.update(self.mesh_engine.snapshot())
        return out

