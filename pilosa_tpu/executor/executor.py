"""Query executor: PQL AST → compiled device programs over sharded
fragments.

Reference: executor.go (executor.Execute, executeCall, executeBitmapCall,
executeCount, executeTopN, executeSum/Min/Max, executeGroupBy,
executeRows, executeSet/Clear…, mapReduce, mapperLocal/mapperRemote).
Redesigned for TPU:

- every read query executes as ONE jitted program over stacked
  ``uint32[R, S, W]`` field arrays (row-major; see executor/compile.py) — the
  reference's per-shard goroutine fan-out and HTTP reduce collapse into a
  single XLA dispatch with on-device reductions;
- aggregates (Count/Sum/Min/Max/TopN) reduce on device; only scalars (or
  a [rows] count vector for TopN) cross back to the host;
- TopN is EXACT in one pass (per-row masked popcount + sort) instead of
  the reference's approximate cache-fed phase 1; the two-phase recount
  survives only for the ids= form;
- the cluster layer (pilosa_tpu.parallel) fans out non-local shards and
  reduces typed partials; this executor always runs the local portion.
"""

from __future__ import annotations

import bisect
import itertools
import math
import operator
import os
import threading
import time
from datetime import datetime
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from pilosa_tpu import ops
from pilosa_tpu.core import (
    BSI_OFFSET,
    FIELD_INT,
    VIEW_BSI,
    VIEW_STANDARD,
    Field,
    Holder,
    Index,
)
from pilosa_tpu.executor.compile import (
    PlanError,
    QueryCompiler,
    StackOverBudget,
    _stack_budget,
    named_jit,
    stack_budget_if_resolved,
    range_suffix,
)
from pilosa_tpu.executor.hostpath import HostPlanError
from pilosa_tpu.executor.router import QueryRouter, estimate_words
from pilosa_tpu.executor.row import RowResult
from pilosa_tpu.pql import Call, coerce_timestamp, parse
from pilosa_tpu.roaring import unpack_words
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.stats import NopStats
from pilosa_tpu.utils.tracing import GLOBAL_TRACER

def apply_options(idx: "Index", call: "Call", res: Any) -> Any:
    """Apply an Options() wrapper's result-shaping args (reference:
    QueryRequest ColumnAttrs/ExcludeColumns/ExcludeRowAttrs). Shared by
    the local executor and the cluster coordinator (which re-applies
    after merging per-node partials)."""
    if isinstance(res, RowResult):
        if call.arg("excludeColumns"):
            res.exclude_columns = True
        if call.arg("excludeRowAttrs"):
            res.exclude_row_attrs = True
        if call.arg("columnAttrs"):
            sets = []
            for col in res.columns().tolist():
                attrs = idx.column_attrs.attrs(int(col))
                if attrs:
                    entry: dict = {"id": int(col), "attrs": attrs}
                    if idx.options.keys:
                        key = idx.column_keys.translate_id(int(col))
                        if key is not None:
                            entry["key"] = key
                    sets.append(entry)
            res.column_attr_sets = sets
    return res


BITMAP_CALLS = {
    "Row",
    "Range",
    "Union",
    "Intersect",
    "Difference",
    "Xor",
    "Not",
    "All",
    "Shift",
}
WRITE_CALLS = {
    "Set",
    "Clear",
    "ClearRow",
    "Store",
    "SetRowAttrs",
    "SetColumnAttrs",
}


def unwrap_options(call: Call) -> Call:
    """Innermost call of an Options() wrapper chain — THE write/read
    classification rule; the cluster router and the max_writes limit must
    agree on it."""
    while call.name == "Options" and len(call.children) == 1:
        call = call.children[0]
    return call


class ExecutionError(ValueError):
    pass


def finalize(results: list) -> list:
    """Dispatched results → client-facing values (resolved pendings
    replaced by their finished values). Shared by Executor.execute and
    the wave scheduler's per-query completion."""
    return [r.value if isinstance(r, _Pending) else r for r in results]


class _Pending:
    """Deferred on-device aggregate values. execute() resolves EVERY
    pending result in one readback wave after all calls have dispatched:
    the device arrays' device→host copies are started together and
    awaited once, then cast to int64 on the host — an N-aggregate
    request pays about one transport RTT, not N (VERDICT r3 weak #3:
    with only Count pipelined, sync TopN ran at ~1/RTT and GroupBy below
    the CPU baseline), and no device program joins them, so a new
    sequence of result sizes compiles nothing. The same mechanism
    settles CROSS-QUERY waves: the dispatch scheduler
    (executor/scheduler.py) settles the pendings of many concurrent
    requests in one such wave. `finish` turns the fetched host arrays
    (int64, original shapes) into the final result; ``fetched`` holds
    them between the settlement (scheduler.fetch_wave) and the
    per-query resolve so one query's finish() failure cannot strand its
    wave-mates."""

    __slots__ = ("arrays", "finish", "value", "fetched", "route", "audit")

    def __init__(
        self,
        arrays: list,
        finish: "Callable[[list], Any]",
        route: str = "device",
    ) -> None:
        self.arrays = list(arrays)
        self.finish = finish
        self.value = None
        self.fetched: list | None = None
        # which engine produced the arrays ("device" | "mesh") — the
        # readback wave attributes its measured latency to the matching
        # router EWMA so the two paths calibrate independently
        self.route = route
        # settle-time router-audit record ({route, estimates,
        # dispatch_s}), completed when the readback wave lands and the
        # call's full measured cost is known; popped on first use so a
        # per-query fallback fetch after a poisoned joint readback
        # cannot double-score the call
        self.audit: dict | None = None

    def resolve_now(self) -> Any:
        self.value = self.finish([np.asarray(a) for a in self.arrays])
        return self.value

    def resolve_fetched(self) -> Any:
        """Finish from host arrays a prior fetch_wave stored — no device
        access; safe to call per query with per-query error isolation."""
        assert self.fetched is not None, "resolve_fetched before fetch"
        self.value = self.finish(self.fetched)
        return self.value


# GroupBy's three device bodies live in ops/groupby.py; the mesh engine
# runs the same three inside its shard_map trees.
_gb_counts = named_jit("pilosa_groupby_counts", ops.groupby.level_counts)
_gb_masks = named_jit("pilosa_groupby_masks", ops.groupby.pair_masks)
_gb_chains = named_jit("pilosa_groupby_chains", ops.groupby.chain_counts)

# The most chunks of ``chunk_cap`` pairs that a GroupBy expands UNPRUNED,
# every (parent, real row) pair with no level read back
# (``Executor._groupby_deferred``), from its row lists as they are or, on
# the level walk, from the rows its first read found the filter to hold
# (``path="pruned"``). An unpruned chunk costs the device at
# most what a full chunk of the level path costs (7.1 ms of masks and
# 13.7 ms of counts for 64 masks by 32 rows at 128 shards; my chip runs,
# PR 34), and a level's round trip costs 5-8 ms of an idle device and the
# wave's leader (ledger, PR 34: ``groupby_readback_wait_ms`` 7.30): inside
# two chunks the reads cost more than they could prune away; beyond, a
# sparse expansion would have pruned more than its reads cost.
DEFERRED_CHUNKS = 2


class _Held:
    """One GroupBy's reservation in the transient ledger. ``done`` is the
    device array whose readiness means the device has finished with the
    query's masks (the last output of a deferred GroupBy); None while the
    query frees its reservation itself (the level-synchronous path)."""

    __slots__ = ("nbytes", "done")

    def __init__(self, nbytes: int):
        self.nbytes = nbytes
        self.done = None


class GroupByLedger:
    """Device bytes that GroupBys in flight hold beside the resident
    stacks: the filter's plane, the group masks of every level and the
    programs' temporaries (``ops.groupby.TEMP_PLANES``). The stack budget
    caps what is RESIDENT; this is the account of the rest (PR 21:
    ``RESOURCE_EXHAUSTED`` at 512 shards with every stack inside its
    budget), and ``Executor._gb_budget()`` is what it is held to.

    A GroupBy reserves its whole need once, before its first program, and
    so never waits while holding: no two can wait for each other. A
    request that does not fit first retires the deferred GroupBys that
    are still in flight, oldest first, by waiting for the device to finish
    each (their masks are freed with the program that read them), then
    waits for the level-synchronous ones of other threads to release. A
    query whose least need is over the whole budget (a pinned budget of a
    few bytes) runs once nothing else is held: the mark then shows it.
    ``/debug/resources`` row ``groupbyTransient``; gauge
    ``groupby_transient_high_water_bytes``."""

    def __init__(self, stats):
        self.stats = stats
        self._cond = threading.Condition()
        self.held = 0
        self.high_water = 0
        self._in_flight: list[_Held] = []

    def admit(self, nbytes: int, budget: int, **tags) -> _Held:
        """Reserve ``nbytes`` of one device against ``budget``; ``tags``
        (the route's devices, the planes reserved) label the wait's span."""
        token = _Held(int(nbytes))
        with self._cond:
            while self.held and self.held + token.nbytes > budget:
                # a span only where the call waits (the leader's thread,
                # under the scheduler): what fits at once pays nothing
                with GLOBAL_TRACER.span(
                    "executor.groupby.admit", bytes=token.nbytes, **tags
                ):
                    if self._in_flight:
                        oldest = self._in_flight[0]
                        done = oldest.done  # a release elsewhere clears the token's
                        self._cond.release()
                        try:
                            done.block_until_ready()  # a wait for the device, no transfer
                        finally:
                            self._cond.acquire()
                        self._drop(oldest)
                    else:
                        self._cond.wait(0.05)
            self.held += token.nbytes
            if self.held > self.high_water:
                self.high_water = self.held
                self.stats.gauge(
                    "groupby_transient_high_water_bytes", float(self.held)
                )
        return token

    def in_flight(self, token: _Held, done) -> None:
        """The query's programs are issued and nothing on the host holds
        its masks any longer: ``token`` is spent once ``done`` is ready."""
        with self._cond:
            token.done = done
            self._in_flight.append(token)

    def shrink(self, token: _Held, nbytes: int) -> None:
        """Hand back what ``token`` holds over ``nbytes``, waking the
        requests that wait for room; a reservation never grows."""
        with self._cond:
            surplus = token.nbytes - int(nbytes)
            if surplus > 0:
                token.nbytes -= surplus
                self.held -= surplus
                self._cond.notify_all()

    def release(self, token: _Held) -> None:
        with self._cond:
            self._drop(token)

    def _drop(self, token: _Held) -> None:
        """Idempotent; the caller holds the condition."""
        if token.nbytes:
            self.held -= token.nbytes
            token.nbytes = 0
            self._cond.notify_all()
        if token.done is not None:
            token.done = None
            if token in self._in_flight:
                self._in_flight.remove(token)

    def snapshot(self) -> dict:
        with self._cond:
            for token in [t for t in self._in_flight if t.done.is_ready()]:
                self._drop(token)
            return {
                "heldBytes": self.held,
                "highWaterBytes": self.high_water,
                "fusedInFlight": len(self._in_flight),
            }


class SumCount(dict):
    """Sum/Min/Max result: {"value": v, "count": n} (reference: ValCount)."""

    def __init__(self, value: int, count: int):
        super().__init__(value=int(value), count=int(count))


def _pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def _chain_table(lens: list[int]) -> np.ndarray:
    """Every chain of one place a level in row lists of ``lens`` rows, in
    (g-major, k-minor) order -> int32 ``[P_pad, levels]``, padded with -1
    rows to a power of two (``ops.groupby.chain_counts``' table)."""
    places = np.indices(lens, dtype=np.int32).reshape(len(lens), -1).T
    table = np.full((_pow2(len(places)), len(lens)), -1, dtype=np.int32)
    table[: len(places)] = places
    return table


def _pad_row_ids(rows: list[int], k_pad: int) -> np.ndarray:
    """Row ids padded to k_pad with -1: jnp.take(mode="fill") turns the
    padding into all-zero rows, so padded slots count 0 and prune."""
    arr = np.full(k_pad, -1, dtype=np.int32)
    arr[: len(rows)] = rows
    return arr


class Executor:
    # device-memory cap for GroupBy's [G, S, W] group-mask tensor; levels
    # surviving more groups than fit are processed in chunks (see
    # _execute_group_by). None ⇒ resolved lazily from device HBM in
    # _gb_budget(); tests pin an int (class or instance) to force paths.
    GROUPBY_MASK_BUDGET = None

    def _gb_budget(self, resolve: bool = True) -> int | None:
        """GroupBy transient-mask budget, of ONE device (on the mesh route
        a GroupBy reserves a device's share of its planes, as the stack
        ledger does of its stacks): a pinned GROUPBY_MASK_BUDGET
        wins; else PILOSA_TPU_GROUPBY_BUDGET env; else 1/8 of the stack
        budget (~70% of HBM), floored at 256 MiB. Sized so a realistic
        GroupBy expands in one or two chunks on a real chip, with one
        deferred readback, instead of paying a sync round trip per level.
        Lazy: resolving device memory must never happen at construction
        (backend init)."""
        if self.GROUPBY_MASK_BUDGET is not None:
            return self.GROUPBY_MASK_BUDGET
        env = os.environ.get("PILOSA_TPU_GROUPBY_BUDGET")
        if env:
            return int(env)
        stack = _stack_budget() if resolve else stack_budget_if_resolved()
        return None if stack is None else max(256 << 20, stack // 8)

    def __init__(
        self,
        holder: Holder,
        mesh_ctx=None,
        stats=None,
        route_mode: str | None = None,
        router: QueryRouter | None = None,
    ):
        self.holder = holder
        self.stats = stats  # optional StatsClient for per-call histograms
        self.compiler = QueryCompiler(mesh_ctx, stats=stats)
        # GroupBy's counters and ledger, whether or not a registry is behind them
        self._gb_stats = stats if stats is not None else NopStats()
        self.gb_ledger = GroupByLedger(self._gb_stats)
        # the counts of what a GroupBy PAID, and of the GroupBys a chain
        # count answered, stand at 0 from the start: a scrape then says
        # "never", not "a program without the family"
        for family in (
            "groupby_level_readbacks_total", "groupby_chunk_waits_total",
            "groupby_chain_queries_total", "groupby_groups_summed_total",
            "groupby_streamed_launches_total",
        ):
            self._gb_stats.declare(family)
        for stage in ("counted", "kept"):
            self._gb_stats.declare("groupby_level_pairs_total", tags={"stage": stage})
        # per-call host/device routing (executor/router.py). Passing an
        # existing router preserves its calibration across executor
        # rebuilds (the server's mesh re-attach swaps the Executor but
        # the measured crossover must not reset to seeds).
        self.router = (
            router
            if router is not None
            else QueryRouter(mode=route_mode, stats=stats)
        )
        # the router's mesh path exists only while a multi-device mesh
        # is attached; a rebuild WITHOUT one (failed attach, CPU pin)
        # must also reset it or a persistent router would keep routing
        # to an engine the new executor doesn't have
        self.router.mesh_devices = (
            self.compiler.mesh_engine.n_devices
            if self.compiler.mesh_engine is not None
            else 1
        )
        # per-query-string route cache: the expensive half of routing is
        # building the decision INPUTS (structural repr for the memo
        # key, the work estimate's tree walk, the residency cold-row
        # probe) — all re-derived per request even though decisions are
        # stable. Entries revalidate every _ROUTE_CACHE_HITS hits, so
        # calibration drift, data growth, and tier promotion re-route
        # within a bounded number of queries (see _routes_for for why
        # the drift generation is deliberately NOT part of the key).
        from collections import OrderedDict

        self._route_cache: "OrderedDict[tuple, list]" = OrderedDict()
        # OrderedDict's relink on move_to_end/popitem is not safe under
        # concurrent HTTP worker threads; the critical section is a few
        # dict ops, so one uncontended lock costs ~nothing per query
        self._route_cache_lock = threading.Lock()

    _ROUTE_CACHE_HITS = 64
    _ROUTE_CACHE_MAX = 512

    def _routes_for(
        self,
        idx: Index,
        index_name: str,
        query,
        calls: "list[Call]",
        shards: list[int] | None,
    ) -> "list[tuple[str | None, int, bool, int]]":
        """One route spec — ``(route, work, mesh_ok, cold_words)``, the
        _route tuple — per call, via the revalidating cache when the
        query arrived as a raw string (the serving hot path)."""
        if not isinstance(query, str):
            return [self._route(idx, c, shards) for c in calls]
        # deliberately NOT keyed on the router's drift generation: the
        # bounded hit count IS the staleness limit — calibration drift
        # re-routes within _ROUTE_CACHE_HITS queries, while keying on
        # the generation would invalidate the whole cache on every EWMA
        # wiggle and hand the hot path the full probe cost back
        key = (
            index_name,
            query,
            tuple(shards) if shards is not None else None,
            self.router.mode,
        )
        with self._route_cache_lock:
            ent = self._route_cache.get(key)
            if ent is not None and ent[0] > 0 and len(ent[1]) == len(calls):
                ent[0] -= 1
                self._route_cache.move_to_end(key)
                return ent[1]
        routes = [self._route(idx, c, shards) for c in calls]
        with self._route_cache_lock:
            self._route_cache[key] = [self._ROUTE_CACHE_HITS, routes]
            self._route_cache.move_to_end(key)
            while len(self._route_cache) > self._ROUTE_CACHE_MAX:
                self._route_cache.popitem(last=False)
        return routes

    # ------------------------------------------------------------ entry
    def execute(
        self,
        index_name: str,
        query: str | list[Call],
        shards: list[int] | None = None,
        routes: "list[tuple[str | None, int, bool, int]] | None" = None,
    ) -> list[Any]:
        results = self.dispatch(index_name, query, shards, routes=routes)
        pending = [r for r in results if isinstance(r, _Pending)]
        if pending:
            elapsed = self.settle(pending)
            prof = tracing.current_profile()
            if prof is not None:
                # the one device→host sync the whole request pays
                prof.add_call("_readback", elapsed, None)
        return finalize(results)

    def dispatch(
        self,
        index_name: str,
        query: str | list[Call],
        shards: list[int] | None = None,
        routes: "list[tuple[str | None, int, bool, int]] | None" = None,
    ) -> list[Any]:
        """Issue every call WITHOUT the readback wave — aggregates come
        back as unresolved ``_Pending``s. This is the enqueue half the
        cross-query scheduler shares: a wave dispatches many queries
        through here, then settles ALL their pendings in one settlement
        (settle / scheduler.fetch_wave). Aggregates dispatch ASYNC
        (device arrays, not yet synced) in program order, so an
        aggregate preceding a write still reads pre-write state —
        exactly the sequential semantics. Per-call dispatch is spanned +
        histogram-timed (the readback wave is timed separately:
        pipelining means a call's device time is not attributable to its
        own dispatch).  ``routes`` optionally carries per-call
        ``(route, work, mesh_ok, cold_words)`` specs a caller (the wave
        scheduler's batchability check) already computed, so the hot
        path doesn't pay the work estimation twice; the trailing
        elements feed the settle-time router audit."""
        idx = self.holder.index(index_name)
        if idx is None:
            raise ExecutionError(f"index {index_name!r} not found")
        calls = parse(query) if isinstance(query, str) else query
        prof = tracing.current_profile()
        prof_shards: tuple[int, ...] | None = None
        if routes is None:
            routes = self._routes_for(idx, index_name, query, calls, shards)
        results = []
        for i, c in enumerate(calls):
            t0 = time.perf_counter()
            route, work = routes[i][0], routes[i][1]
            with GLOBAL_TRACER.span(f"executor.{c.name}", index=index_name):
                results.append(
                    self._execute_call(idx, c, shards, lazy=True, route=route)
                )
            elapsed = time.perf_counter() - t0
            if route in ("host", "device", "mesh"):
                self.router.record(route)
                if (
                    route == "device"
                    and self.router.mode == "mesh"
                    and self.compiler.mesh_engine is not None
                ):
                    # a pinned mesh route reports every read it handed
                    # to the device path (mesh_fallbacks_total)
                    self.compiler.mesh_engine.note_fallback()
                if work > 0:
                    # feed the calibration: host samples refine host
                    # throughput/overhead, device/mesh samples their
                    # respective dispatch costs
                    self.router.observe(route, work, elapsed)
                if work > 0 and self.router.audit.enabled:
                    # settle-time decision audit: snapshot every
                    # candidate's estimate NOW (the decision's inputs);
                    # host calls score immediately — their elapsed IS
                    # the full cost — while device/mesh pendings carry
                    # the record to the readback wave, where the
                    # measured cost completes (Executor.fetch)
                    spec = routes[i]
                    est = self._candidate_costs(
                        route,
                        work,
                        spec[2] if len(spec) > 2 else False,
                        spec[3] if len(spec) > 3 else 0,
                    )
                    res = results[-1]
                    if isinstance(res, _Pending):
                        res.audit = {
                            "route": route,
                            "estimates": est,
                            "dispatch_s": elapsed,
                        }
                    else:
                        self.router.audit.record(route, est, elapsed)
                if self.stats is not None:
                    self.stats.count("queries_routed", tags={"path": route})
                if route == "mesh" and prof is not None:
                    # ?profile=true names the mesh route per call (the
                    # entry's route tag) AND the mesh geometry once
                    prof.mesh = self.compiler.mesh_snapshot()
            if self.stats is not None:
                self.stats.timing(
                    "executor_call_seconds", elapsed, tags={"call": c.name}
                )
            if prof is not None:
                if prof_shards is None:
                    prof_shards = self._shards(idx, shards)
                prof.add_call(c.name, elapsed, prof_shards, route=route)
        if prof is not None and self.compiler.stacks._tiered:
            # residency block in ?profile=true: which container tiers
            # served this query's over-budget fields and the promotion /
            # demotion counters at the time it ran
            prof.residency = self.compiler.stacks.residency_snapshot()
        return results

    def fetch(self, pending: "list[_Pending]") -> float:
        """One settlement for every pending's arrays: their device→host
        copies started together, awaited once (the settlement layer
        lives in executor/scheduler.py — fetch_wave is the ONLY
        sanctioned readback site, per the readback analyzer rule).
        Leaves each pending's host arrays on ``p.fetched``; callers
        resolve per query so one finish() failure can't poison
        wave-mates. Records the readback histogram + router calibration."""
        if not pending:
            return 0.0
        from pilosa_tpu.executor.scheduler import fetch_wave

        t0 = time.perf_counter()
        fetch_wave(pending)
        elapsed = time.perf_counter() - t0
        # attribute the wave's measured latency to every path that rode
        # it — mesh and device pendings calibrate separate EWMAs, and a
        # shared wave's cost is what each path's queries actually paid
        for path in {p.route for p in pending}:
            self.router.observe_readback(elapsed, path=path)
        # complete the settle-time audit records: each pending call's
        # measured cost is its own dispatch plus its share of the one
        # settlement the wave paid (mirroring the cost model's amortized
        # readback term). Records pop on first use so the per-query
        # fallback fetch after a poisoned joint readback can't
        # double-score a call.
        share = elapsed / len(pending)
        for p in pending:
            rec = p.audit
            if rec is not None:
                p.audit = None
                self.router.audit.record(
                    rec["route"], rec["estimates"], rec["dispatch_s"] + share
                )
        if self.stats is not None:
            self.stats.timing("executor_readback_seconds", elapsed)
        return elapsed

    def settle(self, pending: "list[_Pending]") -> float:
        """Fetch + resolve a pending set (one query's, or a whole wave's
        when the caller doesn't need per-query error isolation)."""
        elapsed = self.fetch(pending)
        for p in pending:
            p.resolve_fetched()
        return elapsed

    def _shards(
        self, idx: Index, shards: "list[int] | tuple[int, ...] | None"
    ) -> tuple[int, ...]:
        """The shard scope of one call: an explicit list (``shards=``,
        ``Options(shards=...)``) sorted, else the index's own memoized
        tuple (``Index.shard_scope``: the same object until a write
        moves the index's stamp, so this costs one comparison whatever
        the shard count).  Read-only by type."""
        if shards is not None:
            return tuple(sorted(shards))
        return idx.shard_scope() or (0,)

    # ------------------------------------------------------------ routing
    def _route(self, idx: Index, call: Call, shards: list[int] | None):
        """(route, estimated_work_words, mesh_ok, cold_upload_words)
        for one top-level call.  Writes route None (no engine choice to
        make); Rows is metadata-only and always serves host-side.
        Reads go through the cost router — decision memoized per plan
        key (executor/router.py) — which picks among host, the
        single-program device path, and (when a multi-device
        MeshContext is attached and the call tree compiles to mesh
        programs) the explicit-SPMD mesh path.  The trailing elements
        carry the decision INPUTS forward so the settle-time audit and
        EXPLAIN can rebuild every candidate's cost without re-walking
        the tree.

        Cost: the call tree alone.  The shard count is the length of the
        index's memoized scope and a TopN's row count its view's
        memoized ``max_rows``; neither walks a fragment until a write
        moves the index's stamp, so a route costs the same at 8 shards
        and at 954."""
        c, sh = call, shards
        while c.name == "Options" and len(c.children) == 1:
            sh = c.arg("shards", sh)
            c = c.children[0]
        if c.name in WRITE_CALLS:
            return None, 0, False, 0
        if c.name == "Rows":
            return "host", 0, False, 0
        n = len(sh) if sh is not None else max(1, len(idx.shard_scope()))
        work = estimate_words(idx, c, n)
        if self.router.mode in ("host", "device"):
            # pinned modes never consult mesh eligibility or the cold-row
            # cost term — skip the residency walk on their hot path
            tiered, cold_words = False, 0
        else:
            tiered, cold_words = self._residency_info(idx, c, sh)
        # tiered container stores hold payloads in GLOBAL position space,
        # which a shard_map program's per-device block cannot decode —
        # tiered-touched trees stay on the single-program device path
        # (the stores themselves are mesh-placed, so SPMD reads of the
        # decoded planes keep working)
        mesh_ok = self._mesh_ok(c, n) and not tiered
        if self.router.mode != "auto":
            mode = self.router.mode
            if mode == "mesh" and not mesh_ok:
                # fallback-annotated call type (parallel.mesh), a
                # replicate-only shape or a tiered (over-budget) field:
                # the single-program device path serves it (still SPMD
                # via the stacks' NamedSharding). dispatch() counts each
                # read served so; this spec may be cached
                mode = "device"
            return mode, work, mesh_ok, cold_words
        return (
            self.router.decide(
                (idx.name, n, repr(c)),
                work,
                mesh_ok=mesh_ok,
                device_extra_words=cold_words,
            ),
            work,
            mesh_ok,
            cold_words,
        )

    def _candidate_costs(
        self, route: str, work: int, mesh_ok: bool, cold_words: int
    ) -> dict:
        """Modeled cost in seconds for every candidate path of one call
        — the decision's inputs, snapshotted for the settle-time audit
        and the EXPLAIN cost table.  Mesh appears only when it was a
        real candidate (eligible and multi-device) or was actually
        chosen (pinned mode)."""
        r = self.router
        extra_s = cold_words / r._host_wps() if cold_words else 0.0
        costs = {
            "host": r.host_cost(work),
            "device": r.device_cost(work) + extra_s,
        }
        if (mesh_ok and r.mesh_devices > 1) or route == "mesh":
            costs["mesh"] = r.mesh_cost(work) + extra_s
        return costs

    def _residency_info(
        self, idx: Index, call: Call, shards: list[int] | None,
        detail: list | None = None,
    ) -> tuple[bool, int]:
        """(touches_tiered_field, cold_upload_words) for one call tree.

        Every COLD row of a tiered (over-budget) field costs the device
        path roughly one host-packed [S, W] plane upload — the router
        charges that against the device route so a one-shot scan of a
        cold working set serves host-side, while a re-touched (promoted)
        set routes back to the device.  Promotion itself is driven by
        the touch counts the tiered layer keeps; this probe never
        mutates them."""
        stacks = self.compiler.stacks
        if stacks.residency_mode() == "slots":
            return False, 0
        shard_list = self._shards(idx, shards)
        unit = len(shard_list) * WORDS_PER_SHARD
        over_budget: dict[tuple, bool] = {}

        def over(field: Field, view_name: str) -> bool:
            k = (field.name, view_name)
            got = over_budget.get(k)
            if got is None:
                got = stacks.is_over_budget(idx, field, view_name, shard_list)
                over_budget[k] = got
            return got

        tiered = False
        cold = 0

        def leaf(field: Field, view_name: str, row_id) -> None:
            nonlocal tiered, cold
            if not over(field, view_name):
                if detail is not None:
                    detail.append(
                        {
                            "field": field.name,
                            "view": view_name,
                            "row": row_id,
                            "class": "in-budget",
                        }
                    )
                return
            tiered = True
            resident = stacks.tiered_resident(
                idx, field, view_name, shard_list, row_id
            )
            if not resident:
                cold += unit
            if detail is not None:
                detail.append(
                    {
                        "field": field.name,
                        "view": view_name,
                        "row": row_id,
                        "class": "resident" if resident else "cold",
                    }
                )

        def walk(c: Call) -> None:
            nonlocal tiered, cold
            if c.name in ("Row", "Range"):
                cond = c.condition()
                if cond is not None:
                    f = idx.field(cond[0])
                    if f is not None and over(f, VIEW_BSI):
                        tiered = True
                        need = BSI_OFFSET + f.bit_depth
                        cold_slices = 0
                        for d in range(need):
                            if not stacks.tiered_resident(
                                idx, f, VIEW_BSI, shard_list, d
                            ):
                                cold += unit
                                cold_slices += 1
                        if detail is not None:
                            detail.append(
                                {
                                    "field": f.name,
                                    "view": VIEW_BSI,
                                    "slices": need,
                                    "coldSlices": cold_slices,
                                    "class": (
                                        "cold" if cold_slices else "resident"
                                    ),
                                }
                            )
                    return
                fa = c.field_arg()
                if fa is not None:
                    f = idx.field(fa[0])
                    if f is not None:
                        row = fa[1]
                        if isinstance(row, bool):
                            row = int(row)
                        if isinstance(row, int):
                            leaf(f, VIEW_STANDARD, row)
                return
            if c.name in ("Sum", "Min", "Max"):
                # the aggregate's own BSI block is read too — an
                # over-budget one serves via tiered slice containers,
                # which the mesh programs cannot consume
                fname = c.arg("field") or (
                    c.pos_args[0] if c.pos_args else None
                )
                f = idx.field(fname) if isinstance(fname, str) else None
                if f is not None and f.options.field_type == FIELD_INT and over(
                    f, VIEW_BSI
                ):
                    tiered = True
                    need = BSI_OFFSET + f.bit_depth
                    cold_slices = 0
                    for d in range(need):
                        if not stacks.tiered_resident(
                            idx, f, VIEW_BSI, shard_list, d
                        ):
                            cold += unit
                            cold_slices += 1
                    if detail is not None:
                        detail.append(
                            {
                                "field": f.name,
                                "view": VIEW_BSI,
                                "slices": need,
                                "coldSlices": cold_slices,
                                "class": (
                                    "cold" if cold_slices else "resident"
                                ),
                            }
                        )
            for ch in c.children:
                walk(ch)
            filt = c.arg("filter")
            if isinstance(filt, Call):
                walk(filt)
            agg = c.arg("aggregate")
            if isinstance(agg, Call):
                walk(agg)

        walk(call)
        return tiered, cold

    def _mesh_ok(self, call: Call, n_shards: int) -> bool:
        """Can this call run as explicit mesh programs right now — a mesh
        engine is attached, the shard/word shapes actually shard onto it,
        and every node of the tree has a mesh program (no fallback
        annotations)?  Deferred import: executor modules must not pull
        parallel/ in at import time."""
        if self.compiler.mesh_engine is None:
            return False
        if self.compiler.mesh_mode(n_shards) is None:
            return False
        from pilosa_tpu.parallel.mesh import mesh_supported

        return mesh_supported(call)

    def route_for(
        self,
        index_name: str,
        query: "str | Call | list[Call]",
        shards: list[int] | None = None,
    ) -> str:
        """The route ("host", "device", "mesh" or "write") a query's
        first call would take right now, without running it."""
        idx = self.holder.index(index_name)
        if idx is None:
            raise ExecutionError(f"index {index_name!r} not found")
        calls = parse(query) if isinstance(query, str) else query
        first = calls[0] if isinstance(calls, list) else calls
        route = self._route(idx, first, shards)[0]
        return route or "write"

    def explain_call(
        self, idx: Index, call: Call, shards: list[int] | None
    ) -> dict:
        """The EXPLAIN plan for one top-level call — every decision the
        serving path would make, WITHOUT executing anything: the router
        cost table per candidate path, the residency classification of
        every touched row range, the mesh supportability verdict, and
        the work estimate behind them all.  Metadata-only by
        construction (the same fragment/schema probes the router's hot
        path uses); nothing here touches JAX."""
        c, sh = call, shards
        while c.name == "Options" and len(c.children) == 1:
            sh = c.arg("shards", sh)
            c = c.children[0]
        if c.name in WRITE_CALLS:
            return {"call": c.name, "route": "write"}
        if c.name == "Rows":
            return {
                "call": c.name,
                "route": "host",
                "note": "metadata-only call; always served host-side",
            }
        n = len(sh) if sh is not None else max(1, len(idx.shard_scope()))
        work = estimate_words(idx, c, n)
        res_detail: list = []
        tiered, cold_words = self._residency_info(idx, c, sh, detail=res_detail)
        # mesh supportability, verdict + reason (docs/spmd.md)
        mesh_attached = self.compiler.mesh_engine is not None
        geometry_ok = mesh_attached and self.compiler.mesh_mode(n) is not None
        programs_ok = False
        if geometry_ok:
            from pilosa_tpu.parallel.mesh import mesh_supported

            programs_ok = mesh_supported(c)
        multi_device = self.router.mesh_devices > 1
        mesh_ok = geometry_ok and programs_ok and not tiered and multi_device
        if not mesh_attached:
            mesh_reason = "no mesh engine attached"
        elif not multi_device:
            mesh_reason = "single device — mesh path disabled"
        elif not geometry_ok:
            mesh_reason = "shard/word geometry does not place onto the mesh"
        elif not programs_ok:
            mesh_reason = "call tree contains mesh-fallback calls"
        elif tiered:
            mesh_reason = (
                "tiered residency pins to the single-program device path"
            )
        else:
            mesh_reason = "supported"
        # the route the router takes RIGHT NOW — same decision inputs
        # and memo path as _route, but WITHOUT re-running the residency
        # and mesh-supportability walks this function already did (and
        # nothing is counted: dispatch() counts real serving fallbacks)
        if self.router.mode != "auto":
            route = self.router.mode
            if route == "mesh" and not mesh_ok:
                route = "device"
        else:
            route = self.router.decide(
                (idx.name, n, repr(c)),
                work,
                mesh_ok=mesh_ok,
                device_extra_words=cold_words,
            )
        costs = self._candidate_costs(route, work, mesh_ok, cold_words)
        return {
            "call": c.name,
            "route": route,
            "routeMode": self.router.mode,
            "estimatedWorkWords": work,
            "crossoverWords": self.router.crossover_words(),
            "candidates": {
                path: {"estimatedSeconds": s, "chosen": path == route}
                for path, s in sorted(costs.items())
            },
            "residency": {
                "mode": self.compiler.stacks.residency_mode(),
                "tiered": tiered,
                "coldUploadWords": cold_words,
                "rowRanges": res_detail,
            },
            "mesh": {
                "supported": mesh_ok,
                "reason": mesh_reason,
                "meshDevices": self.router.mesh_devices,
            },
        }

    def _execute_call(
        self,
        idx: Index,
        call: Call,
        shards: list[int] | None,
        lazy: bool = False,
        route: str | None = "device",
    ) -> Any:
        name = call.name
        if name == "Options":
            if len(call.children) != 1:
                raise ExecutionError("Options() takes exactly one call")
            opt_shards = call.arg("shards", shards)
            res = self._execute_call(
                idx, call.children[0], opt_shards, lazy=lazy, route=route
            )
            if isinstance(res, _Pending):
                # shape at resolve time so Options() args still apply
                inner = res.finish
                res.finish = lambda a: apply_options(idx, call, inner(a))
                return res
            return apply_options(idx, call, res)
        if name in WRITE_CALLS:
            return self._execute_write(idx, call)
        shard_list = self._shards(idx, shards)
        host = route == "host"
        # trust-but-verify the mesh route: the decision was made with
        # _mesh_ok, but a direct caller may pass route="mesh" blindly
        mesh = route == "mesh" and self.compiler.mesh_engine is not None
        try:
            if name in BITMAP_CALLS:
                if host:
                    # np.array: the host engine may hand back views of
                    # live stack memory; the result a client keeps must
                    # not alias storage a later write scatters into
                    words = np.array(
                        self.compiler.host.bitmap_words(idx, call, shard_list)
                    )
                elif mesh:
                    words = self.compiler.mesh_bitmap_words(
                        idx, call, shard_list
                    )
                else:
                    words = self._bitmap_words(idx, call, shard_list)
                res = RowResult(
                    {s: words[i] for i, s in enumerate(shard_list)}
                )
                self._attach_keys(idx, res)
                self._attach_row_attrs(idx, call, res)
                return res
            if name == "Count":
                if len(call.children) != 1:
                    raise ExecutionError("Count() takes exactly one call")
                if host:
                    # concrete scalar, no _Pending, no readback wave
                    return self.compiler.host.count(
                        idx, call.children[0], shard_list
                    )
                if mesh:
                    pend = _Pending(
                        [
                            self.compiler.mesh_count_async(
                                idx, call.children[0], shard_list
                            )
                        ],
                        lambda a: int(a[0]),
                        route="mesh",
                    )
                else:
                    pend = _Pending(
                        [
                            self.compiler.count_async(
                                idx, call.children[0], shard_list
                            )
                        ],
                        lambda a: int(a[0]),
                    )
                return pend if lazy else pend.resolve_now()
            if name == "Sum":
                return self._execute_sum(
                    idx, call, shard_list, lazy=lazy, host=host, mesh=mesh
                )
            if name in ("Min", "Max"):
                return self._execute_min_max(
                    idx, call, shard_list, name == "Max", lazy=lazy,
                    host=host, mesh=mesh,
                )
            if name == "TopN":
                return self._execute_topn(
                    idx, call, shard_list, lazy=lazy, host=host, mesh=mesh
                )
            if name == "Rows":
                return self._execute_rows(idx, call, shard_list)
            if name == "GroupBy":
                return self._execute_group_by(
                    idx, call, shard_list, lazy=lazy, host=host, mesh=mesh
                )
            if name == "IncludesColumn":
                return self._execute_includes_column(
                    idx, call, shard_list, host=host
                )
        except (PlanError, StackOverBudget, HostPlanError) as e:
            raise ExecutionError(str(e)) from e
        raise ExecutionError(f"unknown call {name!r}")

    # ----------------------------------------------------------- helpers
    def _bitmap_words(self, idx: Index, call: Call, shards: list[int]) -> np.ndarray:
        try:
            return self.compiler.bitmap_words(idx, call, shards)
        except PlanError as e:
            raise ExecutionError(str(e)) from e

    def _field(self, idx: Index, name: str) -> Field:
        f = idx.field(name)
        if f is None:
            raise ExecutionError(f"field {name!r} not found")
        return f

    def _row_id(self, field: Field, row: Any, create: bool = False) -> int | None:
        if isinstance(row, bool):
            return int(row)
        if isinstance(row, int):
            return row
        if isinstance(row, str):
            if not field.options.keys:
                raise ExecutionError(
                    f"field {field.name!r} does not use string keys"
                )
            return field.row_keys.translate_key(row, create=create)
        raise ExecutionError(f"bad row value {row!r}")

    def _col_id(self, idx: Index, col: Any, create: bool = False) -> int | None:
        if isinstance(col, int) and not isinstance(col, bool):
            return col
        if isinstance(col, str):
            if not idx.options.keys:
                raise ExecutionError(f"index {idx.name!r} does not use string keys")
            return idx.column_keys.translate_key(col, create=create)
        raise ExecutionError(f"bad column value {col!r}")

    def _attach_row_attrs(self, idx: Index, call: Call, res: RowResult) -> None:
        """Direct Row(field=row) results carry the row's attributes
        (reference: QueryResult Row.Attrs)."""
        if call.name != "Row" or call.condition() is not None:
            return
        fa = call.field_arg()
        if fa is None:
            return
        field = idx.field(fa[0])
        if field is None:
            return
        row_id = fa[1]
        if isinstance(row_id, str):
            if not field.options.keys:
                return
            row_id = field.row_keys.translate_key(row_id, create=False)
            if row_id is None:
                return
        if isinstance(row_id, bool):
            row_id = int(row_id)
        if isinstance(row_id, int):
            res.attrs = field.row_attrs.attrs(row_id)

    def _attach_keys(self, idx: Index, res: RowResult) -> None:
        if idx.options.keys:
            cols = res.columns().tolist()
            res.keys = [idx.column_keys.translate_id(c) or str(c) for c in cols]

    def _call_field_name(self, call: Call) -> str:
        fname = call.arg("field")
        if fname is None and call.pos_args:
            fname = call.pos_args[0]
        if fname is None:
            raise ExecutionError(f"{call.name}() needs a field argument")
        return fname

    def _agg_field(self, idx: Index, call: Call) -> Field:
        field = self._field(idx, self._call_field_name(call))
        if field.options.field_type != FIELD_INT:
            raise ExecutionError(f"field {field.name!r} is not an int field")
        return field

    def _filter_device(self, idx: Index, call: Call, shards: list[int]):
        """Child-call filter as a device array [S, W]; all-ones when
        absent (cached per shard count)."""
        if call.children:
            try:
                return self.compiler.bitmap_device(idx, call.children[0], shards)
            except PlanError as e:
                raise ExecutionError(str(e)) from e
        return self.compiler.ones(len(shards))

    def _filter_plan(
        self,
        idx: Index,
        call: Call,
        shards: list[int],
        mesh_mode: str | None = None,
    ):
        """Plan a filter child for IN-PROGRAM fusion: (run, arrays,
        scalars, skey), or None when the call has no filter. The filter
        expression computes inside the aggregate's own XLA program, so
        the [S, W] filter never materializes to HBM between two
        dispatches (VERDICT r3 weak #2: the separate filter program was
        part of the executor-vs-raw-kernel bandwidth gap).  With
        ``mesh_mode`` the closure traces against the mesh's per-device
        block shape so it can fuse into a shard_map program."""
        if not call.children:
            return None
        try:
            if mesh_mode is not None:
                planner, run, skey = self.compiler.mesh_plan(
                    idx, call.children[0], shards, mesh_mode
                )
            else:
                planner, run, skey = self.compiler._plan(
                    idx, call.children[0], shards
                )
        except PlanError as e:
            raise ExecutionError(str(e)) from e
        arrays = planner.materialize()
        scalars = self.compiler.device_scalars(planner.scalar_values())
        return run, arrays, scalars, skey

    def _bsi_stacked(self, idx: Index, field: Field, shards: list[int]):
        """uint32[R, S, W] bit-slice stack of an int field AS IT LIES IN
        DEVICE MEMORY (row-major like every stack): R is the resident
        stack's padded height, not the field's declared depth, and no
        device operation runs here. The depth rule is ``ops.bsi.block``,
        applied inside the program that reads the stack (``_sum_fn``,
        ``_minmax_fn``). Over-budget BSI stacks assemble from tiered
        compressed slice rows in tiered residency mode
        (docs/device-residency.md); the legacy slots mode surfaces the
        budget error clearly as before."""
        try:
            return self.compiler.stacks.matrix(idx, field, VIEW_BSI, shards)[0]
        except StackOverBudget as e:
            if self.compiler.stacks.residency_mode() == "slots":
                raise ExecutionError(str(e)) from e
            try:
                return self.compiler.tiered_bsi_block(idx, field, shards)
            except StackOverBudget as e2:
                raise ExecutionError(str(e2)) from e2

    # ------------------------------------------------------- aggregates
    @staticmethod
    def _sum_fn(field: Field):
        """→ ``(stack [R,S,W], filt [S,W]) → (pos[D], neg[D], n)``, the
        ONE BSI-sum reduction body of ``field``: Sum jits it directly,
        GroupBy's aggregate wraps it in a group vmap and the mesh trees
        run it inside their shard_map, so all stay in sync. It takes the
        resident stack and applies the depth rule itself; D is the depth
        of the planes it read, which ``weigh_sum`` takes from the
        arrays. vmap over the shard axis (axis 1 of the row-major
        block)."""
        need = BSI_OFFSET + field.bit_depth

        def sum_fn(s, f):
            return tuple(
                x.astype(jnp.int64).sum(axis=0)
                for x in jax.vmap(ops.bsi.sum_counts, in_axes=(1, 0))(
                    ops.bsi.block(s, need), f
                )
            )

        return sum_fn

    @staticmethod
    def _minmax_fn(field: Field, want_max: bool):
        """→ ``(stack [R,S,W], filt [S,W]) → (values[S], counts[S])``,
        the per-shard Min/Max body, on the same terms as ``_sum_fn``."""
        need = BSI_OFFSET + field.bit_depth

        def minmax_fn(s, f):
            return jax.vmap(
                lambda ss, ff: ops.bsi.min_max(ss, ff, want_max=want_max),
                in_axes=(1, 0),
            )(ops.bsi.block(s, need), f)

        return minmax_fn

    def _sum_program(self, field: Field, n_shards: int):
        return self.compiler.program(
            ("sum", n_shards, field.bit_depth),
            lambda: named_jit("pilosa_sum", self._sum_fn(field)),
        )

    @staticmethod
    def _grouped_sum_fn(field: Field):
        """(stack [R,S,W], masks [G,S,W]) → (pos[G,D], neg[G,D], n[G]):
        the filtered Sum of every group mask, ``ops.groupby.grouped_sums``
        on the planes the field's depth rule keeps (as ``_sum_fn``'s).
        Shared with the mesh tree."""
        need = BSI_OFFSET + field.bit_depth
        return lambda s, masks: ops.groupby.grouped_sums(ops.bsi.block(s, need), masks)

    def _grouped_sum_program(self, field: Field, n_shards: int):
        return self.compiler.program(
            ("gb_sums", n_shards, field.bit_depth),
            lambda: named_jit("pilosa_sum_groups", self._grouped_sum_fn(field)),
        )

    def _execute_sum(
        self, idx: Index, call: Call, shards: list[int], lazy: bool = False,
        host: bool = False, mesh: bool = False,
    ):
        field = self._agg_field(idx, call)
        if host:
            value, n = self.compiler.host.sum(idx, field, call, shards)
            return SumCount(value, n)
        slices = self._bsi_stacked(idx, field, shards)
        if mesh:
            mode = self.compiler.mesh_mode(len(shards))
            eng = self.compiler.mesh_engine
            fplan = self._filter_plan(idx, call, shards, mesh_mode=mode)
            if fplan is not None:
                frun, farrays, fscalars, fskey = fplan
                key = ("mesh_sum", len(shards), field.bit_depth, mode, fskey)
                prog = self.compiler.program(
                    key,
                    lambda: eng.sum_tree(self._sum_fn(field), mode, frun=frun),
                )
                pos, neg, n = self.compiler._mesh_dispatch(
                    "sum", prog, slices, farrays, fscalars
                )
            else:
                key = ("mesh_sum", len(shards), field.bit_depth, mode)
                prog = self.compiler.program(
                    key, lambda: eng.sum_tree(self._sum_fn(field), mode)
                )
                pos, neg, n = self.compiler._mesh_dispatch(
                    "sum", prog, slices, self.compiler.ones(len(shards))
                )
        else:
            fplan = self._filter_plan(idx, call, shards)
            if fplan is not None:
                frun, farrays, fscalars, fskey = fplan

                def build():
                    sum_fn = self._sum_fn(field)
                    return named_jit(
                        "pilosa_sum_filtered" + range_suffix(fskey),
                        lambda s, fa, fs: sum_fn(s, frun(fa, fs)),
                    )

                pos, neg, n = self.compiler.run_program(
                    ("sum", len(shards), field.bit_depth, fskey),
                    build,
                    slices,
                    farrays,
                    fscalars,
                )
            else:
                filt = self.compiler.ones(len(shards))
                pos, neg, n = self._sum_program(field, len(shards))(
                    slices, filt
                )
        pend = _Pending(
            [pos, neg, n],
            lambda a: SumCount(ops.bsi.weigh_sum(a[0], a[1]), int(a[2])),
            route="mesh" if mesh else "device",
        )
        return pend if lazy else pend.resolve_now()

    def _execute_min_max(
        self, idx: Index, call: Call, shards: list[int], want_max: bool,
        lazy: bool = False, host: bool = False, mesh: bool = False,
    ):
        field = self._agg_field(idx, call)
        if host:
            value, n = self.compiler.host.min_max(
                idx, field, call, shards, want_max
            )
            return SumCount(value, n)
        slices = self._bsi_stacked(idx, field, shards)
        if mesh:
            # per-device-block extremes, all-gathered: finish() below
            # merges them exactly like per-shard partials (min/max with
            # count merges associatively over disjoint column blocks)
            mode = self.compiler.mesh_mode(len(shards))
            eng = self.compiler.mesh_engine
            fplan = self._filter_plan(idx, call, shards, mesh_mode=mode)
            if fplan is not None:
                frun, farrays, fscalars, fskey = fplan
                key = (
                    "mesh_minmax", len(shards), field.bit_depth, want_max,
                    mode, fskey,
                )
                prog = self.compiler.program(
                    key,
                    lambda: eng.minmax_tree(
                        self._minmax_fn(field, want_max), mode, frun=frun
                    ),
                )
                values, counts = self.compiler._mesh_dispatch(
                    "minmax", prog, slices, farrays, fscalars
                )
            else:
                key = (
                    "mesh_minmax", len(shards), field.bit_depth, want_max,
                    mode,
                )
                prog = self.compiler.program(
                    key,
                    lambda: eng.minmax_tree(
                        self._minmax_fn(field, want_max), mode
                    ),
                )
                values, counts = self.compiler._mesh_dispatch(
                    "minmax", prog, slices,
                    self.compiler.ones(len(shards)),
                )
        else:
            vmapped = self._minmax_fn(field, want_max)
            fplan = self._filter_plan(idx, call, shards)
            if fplan is not None:
                frun, farrays, fscalars, fskey = fplan
                values, counts = self.compiler.run_program(
                    ("minmax", len(shards), field.bit_depth, want_max, fskey),
                    lambda: named_jit(
                        "pilosa_minmax_filtered",
                        lambda s, fa, fs: vmapped(s, frun(fa, fs)),
                    ),
                    slices,
                    farrays,
                    fscalars,
                )
            else:
                values, counts = self.compiler.run_program(
                    ("minmax", len(shards), field.bit_depth, want_max),
                    lambda: named_jit("pilosa_minmax", vmapped),
                    slices,
                    self.compiler.ones(len(shards)),
                )

        def finish(a):
            best, best_count = None, 0
            for v, n in zip(a[0].tolist(), a[1].tolist()):
                if n == 0:
                    continue
                if best is None or (v > best if want_max else v < best):
                    best, best_count = v, n
                elif v == best:
                    best_count += n
            return SumCount(best if best is not None else 0, best_count)

        pend = _Pending(
            [values, counts], finish, route="mesh" if mesh else "device"
        )
        return pend if lazy else pend.resolve_now()

    def _execute_topn(
        self, idx: Index, call: Call, shards: list[int], lazy: bool = False,
        host: bool = False, mesh: bool = False,
    ):
        field = self._field(idx, self._call_field_name(call))
        n = call.arg("n")
        ids = call.arg("ids")
        # internal (cluster fan-out) arg: return only rows whose LOCAL
        # count reaches the floor — the coordinator's bounded final TopN
        # pass (cluster._topn_two_phase) uses it so the worst-case
        # cross-node transfer is O(rows above the proven cutoff), never
        # every nonzero row
        min_count = call.arg("minCount")
        attr_name = call.arg("attrName")
        attr_values = call.arg("attrValues")
        if attr_name is not None and not attr_values:
            raise ExecutionError("TopN() attrName requires attrValues")

        if host:
            pairs = self.compiler.host.topn_pairs(
                idx, field, call, shards,
                list(ids) if ids is not None else None,
            )
            return self._topn_finish(
                field, pairs, n, attr_name, attr_values, min_count
            )
        try:
            matrix, n_rows = self.compiler.stacks.matrix(
                idx, field, VIEW_STANDARD, shards
            )
        except StackOverBudget:
            # streamed (over-budget) path: chunk readbacks are the
            # streaming discipline itself, so it stays synchronous; the
            # filter materializes ONCE and is reused across every chunk
            # (mesh route included — the stream IS the fallback, and is
            # counted as one)
            if mesh:
                self.compiler.mesh_engine.note_fallback()
            filt = self._filter_device(idx, call, shards)
            pairs = self._topn_chunked(
                idx, field, shards, filt, ids=ids
            )
            return self._topn_finish(
                field, pairs, n, attr_name, attr_values, min_count
            )
        mesh_mode = self.compiler.mesh_mode(len(shards)) if mesh else None
        fplan = self._filter_plan(idx, call, shards, mesh_mode=mesh_mode)
        if ids is not None:
            row_ids = jnp.asarray(ids, jnp.int32)
            if mesh:
                eng = self.compiler.mesh_engine
                filtered = fplan is not None
                key = ("mesh_topn_ids", len(shards), mesh_mode) + (
                    (fplan[3],) if filtered else ()
                )
                prog = self.compiler.program(
                    key,
                    lambda: eng.topn_tree(
                        mesh_mode,
                        filtered,
                        True,
                        frun=fplan[0] if filtered else None,
                    ),
                )
                if filtered:
                    counts = self.compiler._mesh_dispatch(
                        "topn", prog, matrix, row_ids, fplan[1], fplan[2]
                    )
                else:
                    counts = self.compiler._mesh_dispatch(
                        "topn", prog, matrix, row_ids
                    )
            elif fplan is not None:
                frun, farrays, fscalars, fskey = fplan
                counts = self.compiler.run_program(
                    ("topn_ids", len(shards), fskey),
                    lambda: named_jit(
                        "pilosa_topn_ids_filtered",
                        lambda m, r, fa, fs: jax.vmap(
                            ops.topn.candidate_counts, in_axes=(1, None, 0)
                        )(m, r, frun(fa, fs))
                        .astype(jnp.int64)
                        .sum(axis=0),
                    ),
                    matrix,
                    row_ids,
                    farrays,
                    fscalars,
                )
            else:
                counts = self.compiler.run_program(
                    ("topn_ids", len(shards)),
                    lambda: named_jit(
                        "pilosa_topn_ids",
                        lambda m, r: jnp.sum(
                            ops.popcount_rows(
                                jnp.take(
                                    m, r, axis=0, mode="fill", fill_value=0
                                )
                            ).astype(jnp.int64),
                            axis=1,
                        ),
                    ),
                    matrix,
                    row_ids,
                )

            def finish(a):
                pairs = [
                    (int(r), int(c)) for r, c in zip(ids, a[0].tolist()) if c > 0
                ]
                return self._topn_finish(
                    field, pairs, n, attr_name, attr_values, min_count
                )

        else:
            if mesh:
                eng = self.compiler.mesh_engine
                filtered = fplan is not None
                key = ("mesh_topn", len(shards), mesh_mode) + (
                    (fplan[3],) if filtered else ()
                )
                prog = self.compiler.program(
                    key,
                    lambda: eng.topn_tree(
                        mesh_mode,
                        filtered,
                        False,
                        frun=fplan[0] if filtered else None,
                    ),
                )
                if filtered:
                    counts = self.compiler._mesh_dispatch(
                        "topn", prog, matrix, fplan[1], fplan[2]
                    )
                else:
                    counts = self.compiler._mesh_dispatch(
                        "topn", prog, matrix
                    )
            elif fplan is not None:
                frun, farrays, fscalars, fskey = fplan
                # filter computes INSIDE this program — no separate
                # dispatch, no [S, W] HBM round trip
                counts = self.compiler.run_program(
                    ("topn", len(shards), fskey),
                    lambda: named_jit(
                        "pilosa_topn_filtered" + range_suffix(fskey),
                        lambda m, fa, fs: ops.popcount_rows(
                            m & frun(fa, fs)[None]
                        )
                        .astype(jnp.int64)
                        .sum(axis=1),
                    ),
                    matrix,
                    farrays,
                    fscalars,
                )
            else:
                # no filter ⇒ no AND at all (the old path ANDed a
                # materialized all-ones array — pure HBM traffic)
                counts = self.compiler.run_program(
                    ("topn", len(shards)),
                    lambda: named_jit(
                        "pilosa_topn",
                        lambda m: ops.popcount_rows(m)
                        .astype(jnp.int64)
                        .sum(axis=1),
                    ),
                    matrix,
                )

            def finish(a):
                nz = np.flatnonzero(a[0])
                pairs = [(int(r), int(a[0][r])) for r in nz.tolist()]
                return self._topn_finish(
                    field, pairs, n, attr_name, attr_values, min_count
                )

        pend = _Pending([counts], finish, route="mesh" if mesh else "device")
        return pend if lazy else pend.resolve_now()

    @staticmethod
    def _topn_finish(
        field: Field, pairs: list, n, attr_name, attr_values, min_count=None
    ) -> list[dict]:
        if min_count is not None:
            pairs = [(r, c) for r, c in pairs if c >= min_count]
        if attr_name is not None:
            allowed = set(attr_values)
            pairs = [
                (r, c)
                for r, c in pairs
                if field.row_attrs.attrs(r).get(attr_name) in allowed
            ]
        pairs.sort(key=lambda rc: (-rc[1], rc[0]))
        if n is not None:
            pairs = pairs[:n]
        out = []
        for rid, c in pairs:
            entry = {"id": rid, "count": c}
            if field.options.keys:
                entry["key"] = field.row_keys.translate_id(rid) or str(rid)
            out.append(entry)
        return out

    def _topn_chunked(
        self, idx: Index, field: Field, shards: list[int], filt, ids=None
    ) -> list:
        """TopN for over-budget (high-cardinality) fields: stream row
        chunks host-roaring → device, count, discard — device memory stays
        within the hot budget while every row is still counted EXACTLY
        (SURVEY §7 hard part (e); reference: fragment.go top full scan)."""
        view = field.view(VIEW_STANDARD)
        rows = list(ids) if ids is not None else self._rows_of_field(field, shards)
        if not rows:
            return []
        stacks = self.compiler.stacks
        chunk = stacks.hot_capacity(len(shards))
        frags = [view.fragment(s) if view else None for s in shards]
        prog = self.compiler.program(
            ("topn_chunk", len(shards)),
            lambda: named_jit(
                "pilosa_topn_chunk",
                # g [C,S,W] row-major chunk, f [S,W] → int64[C]
                lambda g, f: jnp.sum(
                    ops.popcount_rows(g & f[None]).astype(jnp.int64),
                    axis=1,
                ),
            ),
        )
        pairs: list = []
        for lo in range(0, len(rows), chunk):
            sub = rows[lo : lo + chunk]
            host = np.zeros(
                (len(sub), len(shards), WORDS_PER_SHARD), dtype=np.uint32
            )
            for i, frag in enumerate(frags):
                if frag is None:
                    continue
                for j, r in enumerate(sub):
                    host[j, i] = frag.row_packed(r)
            counts = np.asarray(prog(jnp.asarray(host), filt))
            for j, r in enumerate(sub):
                if counts[j] > 0:
                    pairs.append((int(r), int(counts[j])))
        return pairs

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

    def _execute_rows(self, idx: Index, call: Call, shards: list[int]) -> dict:
        field = self._field(idx, self._call_field_name(call))
        rows = self._rows_of_field(field, shards)
        rids = call.arg("ids")
        if rids is not None:
            want = set(rids)
            rows = [r for r in rows if r in want]
        col = call.arg("column")
        if col is not None:
            col_id = self._col_id(idx, col)
            shard = col_id // SHARD_WIDTH
            view = field.view(VIEW_STANDARD)
            frag = view.fragment(shard) if view else None
            rows = [
                r for r in rows if frag is not None and frag.contains(r, col_id)
            ]
        previous = call.arg("previous")
        if previous is not None:
            prev_id = self._row_id(field, previous)
            rows = [r for r in rows if r > (prev_id if prev_id is not None else -1)]
        limit = call.arg("limit")
        if limit is not None:
            rows = rows[:limit]
        if field.options.keys:
            return {
                "rows": rows,
                "keys": [field.row_keys.translate_id(r) or str(r) for r in rows],
            }
        return {"rows": rows}

    def _gb_programs(self, mesh_mode: str | None):
        """(gb_counts, gb_masks, gb_chains) program callables for one
        GroupBy execution: the single-program jitted ones, or the mesh
        engine's shard_map ones (same bodies, psum merge tree) when the
        query routed mesh — every call site below stays engine-agnostic."""
        if mesh_mode is None:
            return _gb_counts, _gb_masks, _gb_chains
        eng = self.compiler.mesh_engine
        ckey = ("mesh_gb_counts", mesh_mode)
        cprog = self.compiler.program(
            ckey, lambda: eng.groupby_counts_tree(mesh_mode)
        )
        mkey = ("mesh_gb_masks", mesh_mode)
        mprog = self.compiler.program(
            mkey, lambda: eng.groupby_masks_tree(mesh_mode)
        )
        gbc = lambda masks, m, rows: self.compiler._mesh_dispatch(
            "groupby", cprog, masks, m, rows
        )
        gbm = lambda masks, m, g_idx, row_sel: self.compiler._mesh_dispatch(
            "groupby", mprog, masks, m, g_idx, row_sel
        )
        chprog = self.compiler.program(
            ("mesh_gb_chains", mesh_mode), lambda: eng.groupby_chains_tree(mesh_mode)
        )
        gbch = lambda *args: self.compiler._mesh_dispatch("groupby", chprog, *args)
        return gbc, gbm, gbch

    def _gb_launch(self, what: str, prog, *args):
        """Issue one device program of a GroupBy under its span
        (``executor.groupby.filter|counts|masks|chains|sums``), counted in
        ``groupby_launches_total``; a counts launch (masks, matrix, rows)
        that is one pass over the whole stack also in
        ``groupby_streamed_launches_total``."""
        self._gb_stats.count("groupby_launches_total")
        if what == "counts" and ops.groupby.whole_stack(*args):
            self._gb_stats.count("groupby_streamed_launches_total")
        with GLOBAL_TRACER.span(f"executor.groupby.{what}"):
            return prog(*args)

    def _gb_read(self, arrays):
        """A synchronous device→host read INSIDE the dispatch: the
        level-synchronous path needs a level's counts on the host before
        it can issue the next level. The calling thread, the wave's
        leader under the scheduler, waits here for the device."""
        self._gb_stats.count("groupby_level_readbacks_total")
        with GLOBAL_TRACER.span("executor.groupby.readback"):
            return jax.device_get(arrays)

    def _gb_wait(self, done) -> None:
        """A walk's one kind of wait: for the device to finish the
        query's OWN last program, so that the chunk of masks it read is
        freed before the next chunk's are made. No transfer, no host
        work behind it, the interpreter lock released."""
        self._gb_stats.count("groupby_chunk_waits_total")
        with GLOBAL_TRACER.span("executor.groupby.wait"):
            done.block_until_ready()

    def _gb_device_bytes(self, n_shards: int, mesh_mode: str | None) -> tuple[int, int]:
        """(a plane, the programs' temporaries) of a GroupBy on ONE device,
        the transient ledger's units. A plane is the whole [S, W] plane on
        the device route and a device's block of it on the mesh route; the
        temporaries are ``TEMP_PLANES`` planes, and on the mesh route the
        rows of one tile of the count pass too (``mesh_tile_bytes``)."""
        if mesh_mode is None:
            plane = n_shards * WORDS_PER_SHARD * 4
            return plane, ops.groupby.TEMP_PLANES * plane
        s, w = self.compiler.mesh_engine.block_shape(n_shards, WORDS_PER_SHARD, mesh_mode)
        return s * w * 4, ops.groupby.TEMP_PLANES * s * w * 4 + ops.groupby.mesh_tile_bytes(s, w)

    def _gb_make_masks(self, gb_masks_call, masks, matrix, parents, row_sel, plane_bytes):
        """One masks launch: pair p is parent ``parents[p]`` of ``masks``
        AND row ``row_sel[p]`` of ``matrix``. Padded to a power of two of
        pairs: a padding entry is an all-zero mask (parent 0 & row -1),
        and a stable shape avoids a compile per pair count."""
        p_pad = _pow2(len(parents))
        g_idx = np.zeros(p_pad, dtype=np.int32)
        g_idx[: len(parents)] = parents
        sel = np.full(p_pad, -1, dtype=np.int32)
        sel[: len(parents)] = row_sel
        self._gb_stats.count("groupby_mask_bytes_total", p_pad * plane_bytes)
        return self._gb_launch("masks", gb_masks_call, masks, matrix, g_idx, sel)

    def _gb_sum_program(self, agg_field: Field, n_shards: int, mesh_mode: str | None):
        """``(slices, masks [G, S, W]) → (pos [G, D], neg [G, D], n [G])``:
        the grouped sum of a GroupBy's aggregate on the query's route."""
        if mesh_mode is None:
            return self._grouped_sum_program(agg_field, n_shards)
        eng = self.compiler.mesh_engine
        gsp = self.compiler.program(
            ("mesh_gb_sums", n_shards, agg_field.bit_depth, mesh_mode),
            lambda: eng.grouped_sum_tree(self._grouped_sum_fn(agg_field), mesh_mode),
        )
        return lambda s, m: self.compiler._mesh_dispatch("groupby", gsp, s, m)

    def _execute_group_by(
        self, idx: Index, call: Call, shards: list[int], lazy: bool = False,
        host: bool = False, mesh: bool = False,
    ):
        if not call.children or any(ch.name != "Rows" for ch in call.children):
            raise ExecutionError("GroupBy() takes Rows() calls")
        limit = call.arg("limit")
        filter_call = call.arg("filter")
        if filter_call is not None and not isinstance(filter_call, Call):
            raise ExecutionError("GroupBy filter must be a call")
        aggregate = call.arg("aggregate")
        if aggregate is not None and not (
            isinstance(aggregate, Call) and aggregate.name == "Sum"
        ):
            raise ExecutionError("GroupBy aggregate must be Sum(field=...)")
        agg_field = self._agg_field(idx, aggregate) if aggregate is not None else None

        fields: list[Field] = []
        row_lists: list[list[int]] = []
        for ch in call.children:
            f = self._field(idx, self._call_field_name(ch))
            fields.append(f)
            rows = self._rows_of_field(f, shards)
            rids = ch.arg("ids")
            if rids is not None:
                # explicit row universe — the cluster coordinator pins the
                # GLOBAL first-L rows here so per-node expansion agrees
                # (see cluster._pin_groupby_rows)
                want = set(rids)
                rows = [r for r in rows if r in want]
            prev = ch.arg("previous")
            if prev is not None:
                prev_id = self._row_id(f, prev)
                rows = [r for r in rows if r > (prev_id if prev_id is not None else -1)]
            rlimit = ch.arg("limit")
            if rlimit is not None:
                rows = rows[:rlimit]
            row_lists.append(rows)

        if host:
            # one engine, same spec: identical row universes and emission
            # order, so host/device results match entry for entry
            self._gb_stats.count("groupby_queries_total", tags={"path": "host"})
            return self.compiler.host.group_by(
                idx, fields, row_lists, filter_call, agg_field, limit, shards
            )
        if not all(row_lists):
            return []  # a level without rows: no group, nothing to launch

        agg_slices = (
            self._bsi_stacked(idx, agg_field, shards) if agg_field is not None else None
        )
        matrices = []
        for f in fields:
            try:
                matrices.append(
                    self.compiler.stacks.matrix(idx, f, VIEW_STANDARD, shards)[0]
                )
            except StackOverBudget:
                # over-budget (high-cardinality) level: no resident stack —
                # counts and masks stream row chunks host→device instead
                # (same discipline as _topn_chunked; VERDICT r2 item 4)
                matrices.append(None)

        # What the query will hold on the device beside the stacks, in
        # [S, W] planes, reserved in the transient ledger BEFORE its first
        # program: the filter's plane, the masks of every level that
        # materialises them (all but the last; the last too under an
        # aggregate) and the programs' temporaries. Level l never holds
        # more masks than its padded pairs (``fold[l]``), so the small
        # first levels of a deep GroupBy leave the budget to the last.
        n_shards = len(shards)
        mesh_mode = self.compiler.mesh_mode(n_shards) if mesh else None
        plane_bytes, temp_bytes = self._gb_device_bytes(n_shards, mesh_mode)
        budget = self._gb_budget()

        def plan(lists: list[list[int]], ceiling: int) -> tuple[int, bool, bool, int]:
            """The walk of row lists ``lists`` within ``ceiling`` bytes:
            (chunk_cap, deferred, chain, the bytes it holds)."""
            kp = [_pow2(len(r)) for r in lists]
            fold = list(itertools.accumulate(kp, operator.mul))  # pairs down to each level
            mask_levels = fold if agg_field is not None else fold[:-1]
            # a level without a resident stack streams its rows host→device,
            # never more of them at a time than a level holds masks
            streamed = max(
                (k for k, m in zip(kp, matrices) if m is None), default=0
            )

            def need(cap: int) -> int:
                """Bytes held when no level holds more than ``cap`` masks."""
                planes = 1 + sum(min(cap, g) for g in mask_levels) + min(cap, streamed)
                return planes * plane_bytes + temp_bytes

            # the largest power of two of masks a level that fits beside the
            # rest: padded chunks never exceed it, and pow2 shapes keep XLA
            # retraces to one compile per bucket
            chunk_cap = _pow2(max(mask_levels + [1]))
            while chunk_cap > 1 and need(chunk_cap) > ceiling:
                chunk_cap //= 2
            # Which walk, from the shapes in hand: the (parent, real row)
            # pairs of the deepest level that materialises masks, in chunks
            # of chunk_cap. Few chunks over resident stacks: all pairs,
            # nothing read back (DEFERRED_CHUNKS). Under a limit only where
            # one chunk holds them all, since the level path stops early.
            pairs = math.prod(len(r) for r in lists[: len(mask_levels)])
            chunks = -(-pairs // chunk_cap)
            deferred = (
                not streamed
                and chunks <= DEFERRED_CHUNKS
                and (limit is None or chunks == 1)
            )
            # Of those, a GroupBy of several levels without an aggregate
            # needs no mask at all: one chain count ANDs the levels above
            # the last inside the program, so it holds the filter's plane
            # and the temporaries only
            chain = deferred and len(fields) > 1 and agg_field is None
            return chunk_cap, deferred, chain, (
                plane_bytes + temp_bytes if chain else need(chunk_cap)
            )

        chunk_cap, deferred, chain, reserved = plan(row_lists, budget)
        # the level walk over resident stacks reads the filter's counts
        # against every level's rows first, and chooses again from them
        first_read = (
            not deferred and len(fields) > 1 and all(m is not None for m in matrices)
        )
        held = self.gb_ledger.admit(
            reserved, budget,
            devices=self.compiler.mesh_engine.n_devices if mesh_mode is not None else 1,
            planes=reserved // plane_bytes,
        )
        try:
            gb_counts_call, gb_masks_call, gb_chains_call = self._gb_programs(mesh_mode)
            sum_prog = (
                self._gb_sum_program(agg_field, n_shards, mesh_mode)
                if agg_field is not None
                else None
            )
            if filter_call is None:
                base_mask = self.compiler.ones(n_shards)
            elif mesh_mode is not None:
                base_mask = self._gb_launch(
                    "filter", self.compiler.mesh_bitmap_device,
                    idx, filter_call, shards,
                )
            else:
                base_mask = self._gb_launch(
                    "filter", self._filter_device,
                    idx, Call("_", {}, [filter_call]), shards,
                )
            if mesh_mode is not None:
                base_mask = base_mask[None]  # the mesh trees' specs are [G, S, W]
            path, root = ("fused" if deferred else "levels"), None
            if first_read:
                root, row_lists = self._gb_first_read(
                    gb_counts_call, base_mask, matrices, row_lists
                )
                # the walk again, on the rows the filter holds and within
                # what was admitted: where their pairs fit the deferred
                # walk, nothing more is read and the rest goes back
                cap, deferred, chain, nbytes = plan(row_lists, reserved)
                if deferred:
                    path, chunk_cap = "pruned", cap
                    self.gb_ledger.shrink(held, nbytes)
            self._gb_stats.count("groupby_queries_total", tags={"path": path})
            if not all(row_lists):
                return []  # the filter holds no row of a level: no group
            if chain:
                self._gb_stats.count("groupby_chain_queries_total")
            if deferred:
                pend = self._groupby_deferred(
                    fields, row_lists, matrices, base_mask, limit, plane_bytes,
                    chunk_cap, sum_prog, agg_slices, gb_counts_call, gb_masks_call,
                    gb_chains_call if chain else None,
                    held, route="mesh" if mesh_mode is not None else "device",
                )
                held = None  # the pending result frees it
                return pend if lazy else pend.resolve_now()
            out = self._groupby_levels(
                fields, row_lists, root, matrices, base_mask, limit, shards,
                plane_bytes, chunk_cap, sum_prog, agg_slices, gb_counts_call,
                gb_masks_call, held, route="mesh" if mesh_mode is not None else "device",
            )
            held = None  # the walk released it, or its pending result does
            return out.resolve_now() if isinstance(out, _Pending) and not lazy else out
        finally:
            if held is not None:
                self.gb_ledger.release(held)

    def _gb_first_read(self, gb_counts_call, base_mask, matrices, row_lists):
        """The level walk's first read over resident stacks: the filter's
        counts against the rows of EVERY level, one counts launch a level
        and one read. A group lies inside the filter, so a row the filter
        does not hold is in none, and each level keeps the rows it holds
        (``groupby_level_pairs_total``: all counted, those kept).
        -> (level 0's counts of its kept rows [1, k], the kept row lists)."""
        got = self._gb_read([
            self._gb_launch(
                "counts", gb_counts_call, base_mask, m, _pad_row_ids(rows, _pow2(len(rows)))
            )
            for m, rows in zip(matrices, row_lists)
        ])
        kept = []
        for counts, rows in zip(got, row_lists):
            holds = counts[0, : len(rows)] > 0
            self._gb_stats.count("groupby_level_pairs_total", len(rows), tags={"stage": "counted"})
            self._gb_stats.count("groupby_level_pairs_total", int(holds.sum()), tags={"stage": "kept"})
            kept.append([r for r, h in zip(rows, holds.tolist()) if h])
        c0 = got[0][0, : len(row_lists[0])]
        return c0[c0 > 0][None], kept

    def _groupby_levels(
        self, fields, row_lists, root, matrices, base_mask, limit, shards,
        plane_bytes, chunk_cap, sum_prog, agg_slices, gb_counts_call,
        gb_masks_call, held, route: str,
    ) -> "list[dict] | _Pending":
        """Level-synchronous evaluation: a whole nesting level runs in TWO
        device dispatches — (1) counts of every (surviving group ×
        candidate row) pair, (2) materialization of the surviving
        groups' masks — instead of the reference's one-executor-pass-
        per-group (executor.go executeGroupBy). Each level's counts are
        READ on the host before the next level is issued
        (``_gb_read``), so only surviving pairs are expanded. A level
        that survives more pairs than ``chunk_cap`` is processed in
        chunks depth-first (order — and therefore limit semantics — is
        preserved because chunks run in pair order). Shapes pad to
        powers of two so recompiles stay rare. The walk of streamed
        levels, and of expansions over ``DEFERRED_CHUNKS`` chunks or
        chunked under a ``limit`` even on the rows the filter holds; the
        rest never read a level back (``_groupby_deferred``).

        Over resident stacks the first read (``_gb_first_read``) has
        already counted the filter against the rows of EVERY level:
        ``row_lists`` are the rows it holds and ``root`` level 0's counts
        of them, so the walk reads again from level 1 on; elsewhere
        ``root`` is None and level 0 is read like the rest. The grouped sums
        are never read inside the walk: they ride the wave's readback as
        a ``_Pending``, and before a chunk's masks are made the walk waits
        for the device to finish the last sums (``_gb_wait``), so a level
        still holds one chunk of masks, which is what ``held`` reserved.
        Without sums the walk releases ``held`` and returns the groups.
        ``plane_bytes`` is the ledger's plane on the query's route."""
        n_shards = len(shards)
        results: list[dict] = []
        arrays: list = []  # the pending result's: (pos, neg) pairs
        sums: list[tuple[int, int, int]] = []  # (first result, groups, pos slot)

        def emit(groups: list[tuple], counts: np.ndarray, masks) -> None:
            start = len(results)
            for grp, c in zip(groups, counts.tolist()):
                results.append(
                    {
                        "group": [
                            {"field": f.name, "rowID": rid} for f, rid in grp
                        ],
                        "count": int(c),
                    }
                )
            if sum_prog is not None:
                self._gb_stats.count("groupby_groups_summed_total", len(groups))
                pos, neg, _n = self._gb_launch("sums", sum_prog, agg_slices, masks)
                sums.append((start, len(groups), len(arrays)))
                arrays.extend((pos, neg))

        waited = [0]  # len(arrays) at the last wait

        def settled() -> None:
            """Wait for the last sums before masks are made: the chunk
            they read is then freed."""
            if len(arrays) > waited[0]:
                self._gb_wait(arrays[-1])
                waited[0] = len(arrays)

        def _level_frags(level: int) -> list:
            view = fields[level].view(VIEW_STANDARD)
            return [view.fragment(s) if view else None for s in shards]

        # per-execution LRU of host-packed rows: the counts pass and the
        # mask pass both need a streamed level's rows, and a row recurs
        # across pair chunks once per surviving parent group — entries are
        # bounded to chunk_cap so the cache stays within the same budget
        # as the mask tensor itself
        from collections import OrderedDict

        pack_cache: OrderedDict[tuple[int, int], np.ndarray] = OrderedDict()
        # a streamed level's rows split over the mesh as a stack does, so
        # that each device holds the share of them the ledger reserved
        upload = self.compiler.stacks.mesh_ctx.place_stack if route == "mesh" else jnp.asarray

        def _pack_rows(level: int, frags: list, rows: list[int], k_pad: int) -> np.ndarray:
            """Host-pack [k_pad, S, W] (row-major, like resident stacks)
            for a streamed level's row subset; padding rows stay zero so
            their counts/masks are zero."""
            host = np.zeros((k_pad, n_shards, WORDS_PER_SHARD), dtype=np.uint32)
            for j, r in enumerate(rows):
                key = (level, r)
                got = pack_cache.get(key)
                if got is None:
                    got = np.stack(
                        [
                            frag.row_packed(r)
                            if frag is not None
                            else np.zeros(WORDS_PER_SHARD, dtype=np.uint32)
                            for frag in frags
                        ]
                    )
                    pack_cache[key] = got
                    while len(pack_cache) > chunk_cap:
                        pack_cache.popitem(last=False)
                else:
                    pack_cache.move_to_end(key)
                host[j] = got
            return host

        def _level_counts(level: int, masks, n_groups: int) -> np.ndarray:
            """int64[n_groups, len(rows_l)] — resident stack when the level
            fits the budget, streamed row chunks otherwise (exactness and
            (g, k) output order are identical either way). The row ids go
            into the program as they are, numpy: an upload of their own
            would be one more device call a level."""
            rows_l = row_lists[level]
            m = matrices[level]
            if m is not None:
                k_pad = _pow2(len(rows_l))
                rows_arr = _pad_row_ids(rows_l, k_pad)
                return self._gb_read(
                    self._gb_launch("counts", gb_counts_call, masks, m, rows_arr)
                )[:n_groups, : len(rows_l)]
            frags = _level_frags(level)
            step = min(self.compiler.stacks.hot_capacity(n_shards), chunk_cap)
            parts = []
            for lo in range(0, len(rows_l), step):
                sub = rows_l[lo : lo + step]
                k_pad = _pow2(len(sub))
                host = _pack_rows(level, frags, sub, k_pad)
                parts.append(
                    self._gb_read(
                        self._gb_launch(
                            "counts", gb_counts_call, masks, upload(host),
                            np.arange(k_pad, dtype=np.int32),
                        )
                    )[:n_groups, : len(sub)]
                )
            return np.concatenate(parts, axis=1)

        def _pair_masks(level: int, masks, chunk: np.ndarray):
            """Materialize one pair-chunk's group masks. Streamed levels
            pack only the chunk's distinct rows (≤ chunk_cap ≤ the mask
            budget) and select them by local index."""
            settled()
            rows_l = row_lists[level]
            m = matrices[level]
            if m is None:
                uniq_k = np.unique(chunk[:, 1])
                m = upload(
                    _pack_rows(
                        level,
                        _level_frags(level),
                        [rows_l[k] for k in uniq_k.tolist()],
                        _pow2(uniq_k.size),
                    )
                )
                row_sel = np.searchsorted(uniq_k, chunk[:, 1])
            else:
                row_sel = [rows_l[k] for k in chunk[:, 1]]
            return self._gb_make_masks(
                gb_masks_call, masks, m, chunk[:, 0], row_sel, plane_bytes
            )

        def expand(level: int, masks, groups: list[tuple]) -> None:
            if limit is not None and len(results) >= limit:
                return
            rows_l = row_lists[level]
            last = level == len(fields) - 1
            cnp = root if level == 0 and root is not None else _level_counts(level, masks, len(groups))
            pairs = np.argwhere(cnp > 0)  # (g-major, k-minor) = lexicographic
            if last and limit is not None:
                pairs = pairs[: limit - len(results)]
            if cnp is not root:  # the first read counted its own
                # how much the read prunes: the real pairs the launch
                # counted, and those the walk goes on with
                self._gb_stats.count("groupby_level_pairs_total", cnp.size, tags={"stage": "counted"})
                self._gb_stats.count("groupby_level_pairs_total", len(pairs), tags={"stage": "kept"})
            for lo in range(0, pairs.shape[0], chunk_cap):
                chunk = pairs[lo : lo + chunk_cap]
                self._gb_stats.count("groupby_chunks_total")
                sub_groups = [
                    groups[g] + ((fields[level], rows_l[k]),)
                    for g, k in chunk.tolist()
                ]
                if last and sum_prog is None:
                    # counts suffice — skip materializing final masks
                    emit(sub_groups, cnp[chunk[:, 0], chunk[:, 1]], None)
                else:
                    sub_masks = _pair_masks(level, masks, chunk)
                    if last:
                        emit(
                            sub_groups, cnp[chunk[:, 0], chunk[:, 1]], sub_masks
                        )
                    else:
                        expand(level + 1, sub_masks, sub_groups)
                    # freed before the next chunk's are made: a level holds
                    # one chunk of masks, which is what was reserved
                    del sub_masks
                if limit is not None and len(results) >= limit:
                    return

        expand(0, base_mask, [()])
        if not arrays:
            self.gb_ledger.release(held)
            return results
        self.gb_ledger.in_flight(held, arrays[-1])

        def finish(a):
            self.gb_ledger.release(held)
            for start, n, slot in sums:
                values = ops.bsi.weigh_sums(a[slot][:n], a[slot + 1][:n])
                for i, value in enumerate(values):
                    results[start + i]["sum"] = value
            return results

        return _Pending(arrays, finish, route=route)

    def _groupby_deferred(
        self, fields, row_lists, matrices, base_mask, limit, plane_bytes,
        chunk_cap, sum_prog, agg_slices, gb_counts_call, gb_masks_call,
        gb_chains_call, held, route: str,
    ):
        """All-pairs GroupBy over resident stacks: the level path's walk
        with nothing read back. On every level but the last, every
        (parent, REAL row) pair is expanded, in (g-major, k-minor) order
        and in chunks of ``chunk_cap`` masks, with no counts launch; on
        the last level the counts stay on the device, under an aggregate
        with the grouped sums of the last level's masks. The whole query
        is one chain of launches ending in ONE deferred readback
        (_Pending) that rides the wave's, so a GroupBy costs the one
        transport RTT a Count costs.

        Pruning falls out of the algebra instead of host control flow: an
        empty parent gives all-zero masks, so every empty combination
        surfaces as count 0 and ``finish`` drops it. The chunks of a level
        are consecutive ranges of the lexicographic enumeration of the
        levels' real rows above it, so the emission order is nested
        ascending row order and ``limit`` cuts as on the level path.

        The caller has reserved ``need(chunk_cap)`` in the transient
        ledger (``held``): one chunk of masks a level. Before a level's
        next chunk is made, the reference to the last one goes and the
        walk waits for the device to finish the query's own last program
        (``_gb_wait``). The reservation is spent when the device has
        produced the last output (``GroupByLedger.in_flight``) or, at the
        latest, with the readback.

        With ``gb_chains_call`` (several levels, no aggregate) there is no
        walk: ONE launch counts every chain of real rows of the levels
        above the last against the last level's rows, in the same
        enumeration, and no mask is made."""
        last = len(fields) - 1
        lens = [len(r) for r in row_lists]
        rows_np = [np.asarray(r, dtype=np.int32) for r in row_lists]  # a masks launch's row ids
        arrays: list = []  # the pending result's: counts, then (pos, neg) pairs
        parts: list[tuple[int, int, int]] = []  # (first parent, parents, counts slot)
        sums: list[tuple[int, int]] = []  # (first pair, pos slot), in pair order

        def expand(level: int, masks, start: int, n: int) -> None:
            """``masks``: the ``n`` parents from ``start`` on of the
            enumeration of the levels above ``level``."""
            k_l = lens[level]
            if level == last:
                parts.append((start, n, len(arrays)))
                arrays.append(
                    self._gb_launch(
                        "counts", gb_counts_call, masks, matrices[level],
                        _pad_row_ids(row_lists[level], _pow2(k_l)),
                    )
                )
                if sum_prog is None:
                    return  # counts suffice: no masks of the last level
            end = (start + n) * k_l
            for lo in range(start * k_l, end, chunk_cap):
                if lo > start * k_l:
                    self._gb_wait(arrays[-1])
                g, k = np.divmod(np.arange(lo, min(lo + chunk_cap, end)), k_l)
                sub_masks = self._gb_make_masks(
                    gb_masks_call, masks, matrices[level], g - start,
                    rows_np[level][k], plane_bytes,
                )
                if level == last:
                    self._gb_stats.count("groupby_groups_summed_total", g.size)
                    pos, neg, _n = self._gb_launch("sums", sum_prog, agg_slices, sub_masks)
                    sums.append((lo, len(arrays)))
                    arrays.extend((pos, neg))
                else:
                    expand(level + 1, sub_masks, lo, g.size)
                # the reference goes before the next chunk's are made: a
                # level holds one chunk of masks, which is what was reserved
                del sub_masks

        if gb_chains_call is None:
            expand(0, base_mask, 0, 1)
        else:
            ids = [_pad_row_ids(r, _pow2(len(r))) for r in row_lists]
            n_chains = math.prod(lens[:last])
            parts.append((0, n_chains, 0))
            arrays.append(
                self._gb_launch(
                    "chains", gb_chains_call, base_mask, tuple(matrices[:last]),
                    tuple(ids[:last]), _chain_table(lens[:last]), np.int32(n_chains),
                    matrices[last], ids[last],
                )
            )
        ledger = self.gb_ledger
        ledger.in_flight(held, arrays[-1])
        sum_starts = [lo for lo, _slot in sums]

        def finish(a):
            """The surviving groups of every part, in numpy up to the reply's
            own dicts: this runs on the wave's leader before the wave's
            waiters are woken, with the device idle. A (field, row) cell
            is ONE dict, shared by the groups of this reply that hold it:
            two allocations a group instead of one a level more."""
            ledger.release(held)
            cells = [
                [{"field": f.name, "rowID": r} for r in rows]
                for f, rows in zip(fields, row_lists)
            ]
            results: list[dict] = []
            for start, n, slot in parts:
                cnt = a[slot][:n, : lens[last]]  # the padding of both axes cut off
                g, k = np.nonzero(cnt)  # row-major: (g-major, k-minor)
                if limit is not None:
                    g, k = g[: limit - len(results)], k[: limit - len(results)]
                # a group's cell a level, from its parent's place in the enumeration
                cols = [[cells[last][j] for j in k.tolist()]]
                rem = g + start
                for lvl in range(last - 1, -1, -1):
                    rem, j = np.divmod(rem, lens[lvl])
                    cols.append([cells[lvl][i] for i in j.tolist()])
                cols.reverse()
                first = len(results)
                results.extend(
                    {"group": group, "count": c}
                    for *group, c in zip(*cols, cnt[g, k].tolist())
                )
                if sums:
                    pairs = ((g + start) * lens[last] + k).tolist()
                    for entry, pair in zip(results[first:], pairs):
                        lo, at = sums[bisect.bisect_right(sum_starts, pair) - 1]
                        entry["sum"] = ops.bsi.weigh_sum(a[at][pair - lo], a[at + 1][pair - lo])
            return results

        return _Pending(arrays, finish, route=route)

    # ------------------------------------------------------------ writes
    def _execute_includes_column(
        self, idx: Index, call: Call, shards: list[int], host: bool = False
    ) -> bool:
        """IncludesColumn(bitmap, column=N) → bool (reference:
        executor.go executeIncludesColumnCall). Only the column's own
        shard is evaluated — one [1, W] program instead of a full scan."""
        if len(call.children) != 1:
            raise ExecutionError("IncludesColumn() takes exactly one call")
        col = call.arg("column")
        if col is None:
            raise ExecutionError("IncludesColumn() requires a column argument")
        col_id = self._col_id(idx, col, create=False)
        if col_id is None:
            return False
        shard = col_id // SHARD_WIDTH
        if shard not in shards:
            return False
        offset = col_id % SHARD_WIDTH
        if host:
            return self.compiler.host.includes_column(idx, call, shard, offset)
        words = self._bitmap_words(idx, call.children[0], [shard])[0]
        return bool((int(words[offset // 32]) >> (offset % 32)) & 1)

    def _execute_write(self, idx: Index, call: Call) -> Any:
        name = call.name
        if name == "Set":
            return self._execute_set(idx, call)
        if name == "Clear":
            return self._execute_clear(idx, call)
        if name == "ClearRow":
            return self._execute_clear_row(idx, call)
        if name == "Store":
            return self._execute_store(idx, call)
        if name == "SetRowAttrs":
            return self._execute_set_row_attrs(idx, call)
        if name == "SetColumnAttrs":
            return self._execute_set_column_attrs(idx, call)
        raise ExecutionError(f"unknown write call {name!r}")

    def _set_args(self, idx: Index, call: Call) -> tuple[int, Field, Any, datetime | None]:
        if not call.pos_args:
            raise ExecutionError(f"{call.name}() needs a column argument")
        col = self._col_id(idx, call.pos_args[0], create=call.name == "Set")
        ts = None
        for extra in call.pos_args[1:]:
            coerced = coerce_timestamp(extra)
            if coerced is not None:
                ts = coerced
            else:
                raise ExecutionError(f"unexpected argument {extra!r}")
        fa = call.field_arg()
        if fa is None:
            raise ExecutionError(f"{call.name}() needs a field=row argument")
        fname, row = fa
        return col, self._field(idx, fname), row, ts

    def _execute_set(self, idx: Index, call: Call) -> bool:
        col, field, row, ts = self._set_args(idx, call)
        if field.options.field_type == FIELD_INT:
            if not isinstance(row, int) or isinstance(row, bool):
                raise ExecutionError("int field Set() needs an integer value")
            changed = field.set_value(col, row)
        else:
            row_id = self._row_id(field, row, create=True)
            changed = field.set_bit(row_id, col, timestamp=ts)
        idx.mark_columns_exist(np.array([col], dtype=np.uint64))
        return changed

    def _execute_clear(self, idx: Index, call: Call) -> bool:
        col, field, row, _ts = self._set_args(idx, call)
        if field.options.field_type == FIELD_INT:
            return field.clear_value(col)
        row_id = self._row_id(field, row)
        if row_id is None:
            return False
        return field.clear_bit(row_id, col)

    def _execute_clear_row(self, idx: Index, call: Call) -> bool:
        fa = call.field_arg()
        if fa is None:
            raise ExecutionError("ClearRow() needs a field=row argument")
        fname, row = fa
        field = self._field(idx, fname)
        if field.options.field_type in (FIELD_INT,):
            raise ExecutionError("ClearRow() is not supported on int fields")
        row_id = self._row_id(field, row)
        if row_id is None:
            return False
        changed = False
        for view in field.views.values():
            for frag in view.fragments.values():
                changed |= frag.clear_row(row_id)
        return changed

    def _execute_store(self, idx: Index, call: Call) -> bool:
        if len(call.children) != 1:
            raise ExecutionError("Store() takes exactly one row call")
        fa = call.field_arg()
        if fa is None:
            raise ExecutionError("Store() needs a field=row argument")
        fname, row = fa
        field = self._field(idx, fname)
        row_id = self._row_id(field, row, create=True)
        shards = self._shards(idx, None)
        words = self._bitmap_words(idx, call.children[0], shards)
        for i, s in enumerate(shards):
            positions = unpack_words(words[i])
            frag = field.create_view_if_not_exists(
                VIEW_STANDARD
            ).create_fragment_if_not_exists(s)
            frag.set_row(row_id, positions.astype(np.uint64))
        return True

    def _execute_set_row_attrs(self, idx: Index, call: Call) -> None:
        if len(call.pos_args) < 2:
            raise ExecutionError("SetRowAttrs(field, row, attrs...) needs 2 args")
        field = self._field(idx, call.pos_args[0])
        row_id = self._row_id(field, call.pos_args[1], create=True)
        field.row_attrs.set_attrs(row_id, dict(call.args))
        return None

    def _execute_set_column_attrs(self, idx: Index, call: Call) -> None:
        if len(call.pos_args) < 1:
            raise ExecutionError("SetColumnAttrs(col, attrs...) needs a column")
        col = self._col_id(idx, call.pos_args[0], create=True)
        idx.column_attrs.set_attrs(col, dict(call.args))
        return None
