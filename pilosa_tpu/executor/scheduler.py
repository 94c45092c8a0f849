"""Cross-query wave coalescing: the device dispatch scheduler.

The r05 TPU artifacts pinned sync query throughput at ~1/RTT of the
transport (sync TopN 14.2 q/s vs 117.6 q/s for the SAME work submitted
as an explicit batch): every HTTP thread dispatched its own readback
wave, so N concurrent users paid N transport RTTs where the executor's
one-readback ``_Pending`` wave would pay one.  This module closes that
gap for *independent concurrent* queries: request threads enqueue work
items, one of them becomes the wave leader, drains the queue (plus a
short adaptive window for stragglers), dispatches every query through
the existing compile/dispatch layer (``Executor.dispatch`` — the
parity-covered entry), and settles ALL queries' pending aggregates in
ONE settlement (``fetch_wave``: every result array's device→host copy
started together, awaited once, joined on the host — no device program,
so nothing to compile per wave).  Under sustained concurrency
the group-commit effect alone coalesces waves (while one wave executes,
the next one's queries accumulate); the window only adds burst
alignment.

Semantics guardrails:

- writes, and queries containing writes, are NEVER coalesced across
  requests — they run direct, preserving per-request program order;
- host-routed queries bypass the window entirely (no readback to
  share; queueing would be pure added latency);
- error isolation: one query failing — at dispatch, at readback, or in
  its finish() — errors only that query, never its wave-mates;
- single-flight dedup: identical concurrent queries (same index, same
  calls, same shards, same stack token) share one execution; the stack
  token (a globally monotone mutation stamp, core/view.py) guarantees a
  query enqueued after a write never joins a pre-write execution.

Modes (config ``batch-mode`` / env ``PILOSA_TPU_BATCH_MODE``):
``off`` — every query runs direct (the pre-scheduler path);
``adaptive`` — solo traffic pays no window (the wave occupancy EWMA
gates it), concurrent traffic waits min(batch-window-us, readback-RTT
EWMA / 2) for stragglers; ``always`` — every wave waits the full
configured window.  See docs/query-batching.md.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any, Callable

import numpy as np

import jax

from pilosa_tpu.executor.executor import (
    WRITE_CALLS,
    ExecutionError,
    _Pending,
    finalize,
    unwrap_options,
)
from pilosa_tpu.pql import Call, parse
from pilosa_tpu.utils import sanitize, saturation, tracing
from pilosa_tpu.utils.tracing import GLOBAL_TRACER

BATCH_MODES = ("off", "adaptive", "always")

# adaptive window opens only once waves actually coalesce: below this
# occupancy EWMA the traffic is effectively solo and the window would be
# pure added latency at c1
_SOLO_OCCUPANCY = 1.25

WAKEUP_REASONS = ("done", "lead", "again")


def fetch_wave(pending: "list[_Pending]") -> None:
    """THE settlement layer — the one sanctioned device→host readback
    site (the analyzer's readback rule names this function, not the
    whole file): every pending's device arrays, across every query of a
    wave, start their device→host copies together and are awaited once,
    so a transport with a long round trip pays about one, not one per
    array — and no XLA program runs, so a new sequence of result sizes
    compiles nothing.  The join is on the host: each array lands on
    ``p.fetched`` as int64 in its original shape; resolving finish() is
    the caller's job so per-query error isolation stays possible.  A
    sharded or replicated mesh result comes back whole (jax gathers its
    shards; a replicated one crosses once), a host array passes through
    the same cast.

    Two spans split what the readback histogram times as one:
    ``readback.join`` starts the copies, ``readback.transfer`` is the
    wait for the device and the gather (``bytes``: what crossed, in the
    arrays' own dtypes)."""
    arrays = [a for p in pending for a in p.arrays]
    with GLOBAL_TRACER.span("readback.join") as sp:
        on_device = [a for a in arrays if isinstance(a, jax.Array)]
        for a in on_device:
            a.copy_to_host_async()
        sp.set_tag("arrays", len(arrays))
    with GLOBAL_TRACER.span("readback.transfer") as sp:
        # device_get: the explicit transfer (a transfer guard lets it
        # through) and the sync the analyzer's rule sees by name
        host = iter(jax.device_get(arrays))
        sp.set_tag("bytes", sum(a.nbytes for a in on_device))
    for p in pending:
        p.fetched = [
            np.asarray(next(host)).astype(np.int64, copy=False)
            for _ in p.arrays
        ]


def stack_token(idx) -> int:
    """Mutation stamp for single-flight dedup: two identical queries may
    share one execution ONLY while their tokens agree — a mutation
    between them forces the later query onto its own execution
    (read-your-writes across the dedup).

    The token is the index's own stamp (``Index.stamp``,
    core/view.py): every ``View._bump_version`` — every fragment
    mutation, creation and removal — raises it before the write
    returns, with a value drawn from the views' global counter
    (monotone, never replayed by a recreated index).  Nothing changes
    an index's answers without a bump: a field CANNOT be deleted
    without one (``Index.delete_field`` raises the stamp itself), and a
    field or view just created holds no fragment, so no view count rides
    in the token.

    Cost: one attribute read, whatever the schema or the shard count."""
    return idx.stamp.value


def canonical_calls(calls) -> tuple:
    """The canonical call-repr tuple of ``dedup_key``, rendered at most
    once per parsed call object (cached on the Call): within one
    request the result cache's memoize and fill legs plus this
    scheduler's single-flight key would otherwise each re-render the
    same tree — ~10µs a pass — on the query's critical path.  Safe
    because call trees are treated immutable after parse."""
    out = []
    for c in calls:
        canon = getattr(c, "_canon", None)
        if canon is None:
            canon = repr(c)
            try:
                c._canon = canon
            except AttributeError:
                pass  # a slotted/foreign call type: render every time
        out.append(canon)
    return tuple(out)


def dedup_key(index: str, calls, shards, idx) -> tuple:
    """The single-flight identity: ``(index, canonical calls, shard
    scope, mutation stamp)``.  Two queries may share one answer exactly
    when these keys are equal — the law the wave dedup below applies to
    in-flight executions and the cross-request result cache
    (utils/resultcache.py) applies to settled ones, so the key shape
    MUST stay shared: a drift between them would let the cache serve
    across a boundary dedup would not."""
    return (
        index,
        canonical_calls(calls),
        tuple(shards) if shards is not None else None,
        stack_token(idx),
    )


class _Waiter:
    """What ONE submitting call sleeps on (``execute``: one item;
    ``execute_many``: all its items, one thread).  ``event`` is set for
    exactly two reasons, both under the scheduler's lock: the call's
    last item completed (``pending`` reached 0), or the call was handed
    the leadership of the next wave (``WaveScheduler._heir``);
    ``handed`` is then the scheduler's clock at that ``set()``."""

    __slots__ = ("event", "pending", "handed")

    def __init__(self) -> None:
        self.event = threading.Event()
        self.pending = 0  # items of this call not completed yet
        self.handed = 0.0


class _WorkItem:
    __slots__ = (
        "index",
        "calls",
        "shards",
        "routes",
        "key",
        "waiter",
        "done",
        "raw",
        "pendings",
        "results",
        "error",
        "trace_ctx",
        "profile",
        "followers",
        "sealed",
    )

    def __init__(
        self, index: str, calls: list[Call], shards, waiter, routes=None
    ):
        self.index = index
        self.calls = calls
        self.shards = shards
        self.routes = routes  # per-call (route, work) from _batchable
        self.key: tuple | None = None
        self.waiter = waiter
        self.done = False
        self.raw: list[Any] = []
        self.pendings: list[_Pending] = []
        self.results: list[Any] | None = None
        self.error: BaseException | None = None
        self.trace_ctx: tuple | None = None
        self.profile = None
        self.followers: list["_WorkItem"] = []
        self.sealed = False


class WaveScheduler:
    """One scheduler per API façade, shared across HTTP threads.  Takes
    an ``executor_fn`` (not an Executor) so the late mesh attach — which
    rebuilds the Executor — never strands the scheduler on a stale
    engine; the persistent QueryRouter rides along automatically."""

    def __init__(
        self,
        executor_fn: "Callable[[], Any]",
        stats=None,
        mode: str | None = None,
        window_us: float | None = None,
        max_queries: int | None = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        if mode is None:
            mode = os.environ.get("PILOSA_TPU_BATCH_MODE", "") or "adaptive"
        if mode not in BATCH_MODES:
            raise ValueError(
                f"batch-mode must be one of {BATCH_MODES}, got {mode!r}"
            )
        if window_us is None:
            window_us = float(
                os.environ.get("PILOSA_TPU_BATCH_WINDOW_US", "") or 250.0
            )
        if max_queries is None:
            max_queries = int(
                os.environ.get("PILOSA_TPU_BATCH_MAX_QUERIES", "") or 64
            )
        self.mode = mode
        self.window_s = float(window_us) / 1e6
        self.max_queries = max(1, int(max_queries))
        self._executor_fn = executor_fn
        self.stats = stats
        self._clock = clock
        # contention-counted (docs/profiling.md): /debug/saturation's
        # "scheduler" lock family.
        self._lock = sanitize.make_lock(
            "WaveScheduler._lock", inner=saturation.ContendedLock("scheduler")
        )
        # the ONE thread that ever waits for arrivals is the leader in
        # its window; every other waiter sleeps on its own _Waiter.event
        self._arrival = threading.Condition(self._lock)
        self._queue: deque[_WorkItem] = deque()
        self._inflight: dict[tuple, _WorkItem] = {}
        # True while a leader runs its wave AND across a hand-over (the
        # heir is woken, not yet running): queue non-empty ⇒ True
        self._leader_active = False
        self._heir: _Waiter | None = None
        # lazy pool for execute_many's DIRECT (host-routed) entries:
        # the multi-query RPC coalesces legs before the remote routing
        # decision is known, so host-routed legs that used to arrive as
        # N parallel /internal/query requests must not serialize on the
        # one batch-handler thread
        self._direct_pool = None
        self.waves = 0
        self.batched_queries = 0
        self.deduped_queries = 0
        self.direct_queries = 0
        # returns of a waiter's event.wait(), by what it found: its call
        # done, the leadership handed to it, or neither ("again": must
        # stay 0 — nothing wakes a thread that has nothing to do)
        self.wakeups = dict.fromkeys(WAKEUP_REASONS, 0)
        if stats is not None:
            for why in WAKEUP_REASONS:
                stats.count("scheduler_wakeups_total", 0, tags={"why": why})

    # ------------------------------------------------------------- entry
    def execute(
        self,
        index: str,
        query: "str | list[Call]",
        shards: list[int] | None = None,
    ) -> list[Any]:
        """Drop-in for Executor.execute: same signature, same results,
        same exceptions — batchable device-routed queries ride a shared
        wave, everything else runs direct."""
        # per-query deadline (docs/fault-tolerance.md): a query whose
        # budget is already spent must fail with the labeled 504 error
        # BEFORE enqueueing — joining a wave it can no longer wait for
        # would burn device work on an answer nobody is listening to.
        # Deferred import: parallel.resilience is a leaf over client.py,
        # but executor modules must not pull parallel/ in at import time.
        from pilosa_tpu.parallel.resilience import current_deadline

        deadline = current_deadline()
        if deadline is not None and deadline.expired():
            raise deadline.exceeded("scheduler enqueue")
        executor = self._executor_fn()
        calls = parse(query) if isinstance(query, str) else query
        batchable, routes = self._batchable(executor, index, calls, shards)
        # re-fetch under the key build: a concurrent index deletion
        # between the batchability check and here must surface as the
        # canonical ExecutionError (the direct path raises it), never
        # an AttributeError from stack_token(None)
        idx = executor.holder.index(index) if batchable else None
        if not batchable or idx is None:
            with self._lock:
                self.direct_queries += 1
            return executor.execute(index, calls, shards=shards, routes=routes)
        waiter = _Waiter()
        item = _WorkItem(index, calls, shards, waiter, routes=routes)
        item.key = dedup_key(index, calls, shards, idx)
        item.trace_ctx = GLOBAL_TRACER.current_context()
        item.profile = tracing.current_profile()
        with self._lock:
            lead, deduped = self._enqueue([item])
        if deduped and self.stats is not None:
            self.stats.count("queries_deduped")
        self._await(waiter, lead)
        if item.error is not None:
            raise item.error
        return item.results  # type: ignore[return-value]

    def execute_many(
        self,
        requests: "list[tuple[str, str | list[Call], list[int] | None, tuple | None]]",
    ) -> list[Any]:
        """Execute several independent queries as ONE enqueue — the
        multi-query /internal RPC hands its coalesced legs here so they
        share a single device readback wave on this node too.  Each
        request is ``(index, query, shards, trace_ctx)``; the trace
        context (one per leg, propagated in the RPC body) replaces the
        submitter-thread capture ``execute()`` does.  Returns one
        element per request: the result list, or the exception that
        query raised (per-entry error isolation — callers must answer
        every leg)."""
        executor = self._executor_fn()
        out: list[Any] = [None] * len(requests)
        waiter = _Waiter()
        wave_items: list[tuple[int, _WorkItem]] = []
        futures: list[tuple[int, Any]] = []

        def run_direct(index, calls, shards, ctx, routes=None):
            try:
                dctx = ctx or (None, None)
                with GLOBAL_TRACER.detached(dctx[0], dctx[1]):
                    return executor.execute(
                        index, calls, shards=shards, routes=routes
                    )
            except Exception as exc:  # noqa: BLE001 — per-entry
                # isolation: the exception IS this slot's answer
                return exc

        for i, (index, query, shards, ctx) in enumerate(requests):
            try:
                calls = parse(query) if isinstance(query, str) else query
                batchable, _routes = self._batchable(
                    executor, index, calls, shards
                )
                idx = executor.holder.index(index) if batchable else None
                if not batchable or idx is None:
                    with self._lock:
                        self.direct_queries += 1
                    if len(requests) == 1:
                        out[i] = run_direct(index, calls, shards, ctx, _routes)
                    else:
                        # concurrent: these entries were independent
                        # RPCs before leg coalescing merged them into
                        # one envelope — they must stay parallel here
                        # (numpy/XLA release the GIL)
                        futures.append(
                            (
                                i,
                                self._pool().submit(
                                    run_direct,
                                    index,
                                    calls,
                                    shards,
                                    ctx,
                                    _routes,
                                ),
                            )
                        )
                    continue
                item = _WorkItem(index, calls, shards, waiter, routes=_routes)
                item.key = dedup_key(index, calls, shards, idx)
                item.trace_ctx = ctx
                wave_items.append((i, item))
            except Exception as e:  # noqa: BLE001 — per-entry isolation:
                # a parse/validation failure answers its own slot only
                out[i] = e
        if wave_items:
            with self._lock:
                lead, deduped = self._enqueue([it for _i, it in wave_items])
            if deduped and self.stats is not None:
                self.stats.count("queries_deduped", deduped)
            self._await(waiter, lead)
            for i, item in wave_items:
                out[i] = item.error if item.error is not None else item.results
        for i, fut in futures:
            out[i] = fut.result()  # run_direct never raises
        return out

    def _pool(self):
        if self._direct_pool is None:
            with self._lock:
                if self._direct_pool is None:
                    from concurrent.futures import ThreadPoolExecutor

                    # sized to the leg batcher's MAX_LEGS (64): a full
                    # coalesced envelope of host-routed legs ran as 64
                    # parallel handler threads pre-batching and must
                    # not queue behind a smaller pool here
                    self._direct_pool = ThreadPoolExecutor(
                        max_workers=64, thread_name_prefix="batch-direct"
                    )
        return self._direct_pool

    def close(self) -> None:
        """Release the direct-entry pool (Server.close reaches here;
        embedded multi-server rigs must not leak 64 idle threads per
        scheduler that ever served a mixed batch envelope)."""
        pool = self._direct_pool
        if pool is not None:
            pool.shutdown(wait=False)

    # ------------------------------------------------------ wave harness
    def _batchable(
        self, executor, index, calls, shards
    ) -> "tuple[bool, list | None]":
        """(batchable, routes): routes carries the per-call (route,
        work) pairs this check computed, handed to Executor.dispatch/
        execute so the hot path never pays the work estimation twice.
        Routes come back None when the query contains a write (dispatch
        must classify those itself)."""
        if self.mode == "off":
            return False, None
        idx = executor.holder.index(index)
        if idx is None:
            return False, None  # direct path raises the canonical error
        any_device = False
        routes: list = []
        for c in calls:
            if unwrap_options(c).name in WRITE_CALLS:
                # writes keep strict per-request program order with
                # their neighbouring reads — never coalesced
                return False, None
            rw = executor._route(idx, c, shards)
            routes.append(rw)
            if rw[0] in ("device", "mesh"):
                # mesh-routed queries batch too: their pendings ride the
                # same readback wave, so chip parallelism compounds with
                # cross-query coalescing (docs/spmd.md)
                any_device = True
        # host-routed calls bypass the window: no readback wave to
        # share, so queueing would only add latency (docs/query-batching.md)
        return any_device, routes

    def _enqueue(self, items: "list[_WorkItem]") -> "tuple[bool, int]":
        """Called holding ``_lock``, with all the items of one call
        (one waiter).  Each item joins an identical unsealed in-flight
        prime as its follower, or goes on the queue.  Returns (lead,
        deduped): ``lead`` says the caller found no leader and IS now
        the leader — it runs the next wave at once, with no wake-up in
        between (the solo path never sleeps).  With a leader there, the
        only thread an arrival can matter to is that leader in its
        window: one notify, for at most one sleeper."""
        waiter = items[0].waiter
        waiter.pending += len(items)
        deduped = 0
        for item in items:
            prime = self._inflight.get(item.key)
            if prime is not None and not prime.sealed:
                prime.followers.append(item)
                deduped += 1
            else:
                self._inflight[item.key] = item
                self._queue.append(item)
        self.deduped_queries += deduped
        if deduped == len(items):
            return False, deduped  # nothing queued: its primes' waves serve it
        if self._leader_active:
            self._arrival.notify()
            return False, deduped
        self._leader_active = True
        return True, deduped

    def _await(self, waiter: _Waiter, lead: bool) -> None:
        """Block until every item of ``waiter``'s call completed,
        leading a wave whenever the leadership is this call's.

        Who sleeps on what: every submitting call on its own
        ``waiter.event``; the leader in its window, alone, on
        ``_arrival``.  Who wakes whom: ``_finish`` sets the event of a
        call whose last item it completed; a leader that releases with
        work queued sets the event of the queue's head, the oldest
        waiter, and of nobody else.  So an ``event.wait()`` returns
        exactly when there is something for this thread to do.

        A leader runs exactly ONE wave and then hands the leadership
        on: if it kept it, the first arrival would serve everyone
        else's waves while its own finished response sat undelivered —
        measured as c8 throughput BELOW c1 on the first cut of this
        scheduler.  (It leads again only while it heads the queue
        itself: an ``execute_many`` call larger than a wave.)"""
        with GLOBAL_TRACER.span("scheduler.await"):
            while True:
                while lead:
                    try:
                        self._run_one_wave()
                    finally:
                        lead = self._release(waiter)
                if not waiter.pending:
                    return
                waiter.event.wait()
                # under the lock, which every set() is made under too:
                # a set between the wake-up and this clear is not lost,
                # because what it announced is read after the clear
                with self._lock:
                    waiter.event.clear()
                    lead = self._heir is waiter
                    if lead:
                        self._heir = None
                        why = "lead"
                        # from the old leader's set() to this thread
                        # running again: the hand-over of the leadership
                        handover = self._clock() - waiter.handed
                    else:
                        why = "again" if waiter.pending else "done"
                    self.wakeups[why] += 1
                if self.stats is not None:
                    self.stats.count(
                        "scheduler_wakeups_total", tags={"why": why}
                    )
                    if lead:
                        self._phase("handover", handover)

    def _release(self, waiter: _Waiter) -> bool:
        """End of the leader's one wave.  Nothing queued: nobody leads,
        the next enqueuer will.  Else the leadership goes to the call
        of the queue's head — the oldest item no wave has taken — by
        setting that one event; ``_leader_active`` stays set across the
        hand-over, so no enqueuer takes the lead meanwhile.  True when
        the head is the releasing call's own."""
        with self._lock:
            if not self._queue:
                self._leader_active = False
                return False
            heir = self._queue[0].waiter
            if heir is waiter:
                return True
            self._heir = heir
            heir.handed = self._clock()
            heir.event.set()
            return False

    def _phase(self, phase: str, seconds: float) -> None:
        """What one wave's time went to: ``handover`` (a timer of its
        own, across two threads), then ``window``, ``dispatch`` (the sum
        of the wave's ``scheduler.query``), ``readback`` and ``settle``,
        each observed once a wave from the duration its span already
        holds.  A phase a wave did not have is not observed."""
        self.stats.timing(
            "scheduler_wave_phase_seconds", seconds, tags={"phase": phase}
        )

    def _run_one_wave(self) -> None:
        # resolve the executor AT WAVE TIME, not from whatever instance
        # the leading submitter captured at its enqueue: the late mesh
        # attach swaps API.executor, and a wave led across the swap must
        # dispatch on the NEW engine (the whole point of executor_fn)
        executor = self._executor_fn()
        with self._lock:
            # never empty: a leader is made by its own enqueue, or by a
            # hand-over to the head of a queue only leaders drain
            batch = [self._queue.popleft()]
            while self._queue and len(batch) < self.max_queries:
                batch.append(self._queue.popleft())
        if len(batch) >= self.max_queries:
            reason = "full"
        else:
            with GLOBAL_TRACER.span("scheduler.window") as sp:
                reason = self._wait_window(executor, batch)
                sp.set_tag("reason", reason)
            if self.stats is not None:
                self._phase("window", sp.duration)
        try:
            self._execute_wave(executor, batch, reason)
        except Exception as e:  # noqa: BLE001 — harness backstop: a
            # failure OUTSIDE the per-query isolation paths must
            # still wake every waiter, or their HTTP threads hang
            # (_finish passes over what is already completed)
            error = ExecutionError(f"wave failed: {e!r}")
            with self._lock:
                for it in batch:
                    self._finish(it, error=error)

    def _wait_window(self, executor, batch: list[_WorkItem]) -> str:
        """First-arrival opened the window when the leader drained it;
        hold the wave open for stragglers up to the effective window,
        refilling from the queue as they land.  Returns the flush
        reason (``solo``/``drain`` when no window applied, ``timeout``
        when it expired, ``full`` when the wave filled first)."""
        eff = self._window_seconds(executor, len(batch))
        if eff <= 0:
            return "drain" if len(batch) > 1 else "solo"
        deadline = self._clock() + eff
        while len(batch) < self.max_queries:
            with self._lock:
                if not self._queue:
                    remaining = deadline - self._clock()
                    if remaining <= 0:
                        return "timeout"
                    self._wait_arrival(remaining)
                while self._queue and len(batch) < self.max_queries:
                    batch.append(self._queue.popleft())
            if len(batch) < self.max_queries and self._clock() >= deadline:
                return "timeout"
        return "full"

    def _wait_arrival(self, timeout: float) -> None:
        """Injectable for tests (fake clocks drive the window loop
        deterministically by pairing a scripted clock with a no-op
        wait).  Called holding ``_lock``, by the leader in its window
        alone; woken by the next enqueue."""
        self._arrival.wait(timeout)

    def _window_seconds(self, executor, have: int) -> float:
        from pilosa_tpu.parallel.resilience import current_deadline

        # the straggler window is bounded by the leader's own query
        # deadline: a wave must never hold its leader past the budget
        # the client was promised (retries upstream already consumed
        # their share — see docs/fault-tolerance.md)
        deadline = current_deadline()
        budget = deadline.remaining() if deadline is not None else None
        if budget is not None and budget <= 0:
            return 0.0
        if self.mode == "always":
            return (
                self.window_s if budget is None else min(self.window_s, budget)
            )
        # adaptive: solo traffic never pays the window (the c1 latency
        # guard); once waves coalesce — occupancy EWMA above the solo
        # threshold, or multiple queries already drained — wait for
        # stragglers, scaled to half the readback-RTT EWMA (a wait is
        # worth at most the round trip it shares, so on a local device
        # it shrinks with the RTT) and capped at the configured
        # batch-window-us.
        router = executor.router
        occ = getattr(router, "wave_occupancy", None)
        occ_v = occ.value if occ is not None and occ.value else 1.0
        if occ_v <= _SOLO_OCCUPANCY and have <= 1:
            return 0.0
        eff = min(self.window_s, 0.5 * router.readback_s.value)
        return eff if budget is None else min(eff, budget)

    def _execute_wave(
        self, executor, batch: list[_WorkItem], reason: str
    ) -> None:
        # occupancy at dispatch time (span/profile tags); dedup
        # followers keep joining primes until each seals, so the FINAL
        # occupancy for the stats/EWMA is recounted after the wave
        n = len(batch) + sum(len(it.followers) for it in batch)
        # The wave span nests in the LEADER's trace (the leader is a
        # request thread); each batched query's own span joins ITS
        # submitter's trace via detached()+activate and carries the wave
        # span id, so a cross-query wave is navigable from either side.
        with GLOBAL_TRACER.span(
            "scheduler.wave", queries=n, reason=reason
        ) as wave_span:
            settled: list[_WorkItem] = []
            # the wave's phases are read off these spans once it is over
            phase_spans: dict[str, list] = {
                "dispatch": [], "readback": [], "settle": []
            }
            for it in batch:
                ctx = it.trace_ctx or (None, None)
                try:
                    with GLOBAL_TRACER.detached(ctx[0], ctx[1]):
                        with tracing.use_profile(it.profile):
                            with GLOBAL_TRACER.span(
                                "scheduler.query",
                                wave=wave_span.span_id,
                                queries=n,
                            ) as sp:
                                phase_spans["dispatch"].append(sp)
                                it.raw = executor.dispatch(
                                    it.index,
                                    it.calls,
                                    it.shards,
                                    routes=it.routes,
                                )
                    it.pendings = [
                        r for r in it.raw if isinstance(r, _Pending)
                    ]
                    settled.append(it)
                except Exception as e:  # noqa: BLE001 — error isolation:
                    # one bad query errors alone; wave-mates proceed
                    with self._lock:
                        self._finish(it, error=e)
            all_pending = [p for it in settled for p in it.pendings]
            joint_ok = True
            fetch_seconds = 0.0
            if all_pending:
                try:
                    fetch_seconds = self._readback(
                        executor, all_pending, wave_span, phase_spans
                    )
                except Exception:  # noqa: BLE001 — a poisoned joint
                    # readback falls back to per-query fetches below so
                    # only the poisoned query errors
                    joint_ok = False
            # The leader's SETTLE, one span a wave: every query's results
            # are finished outside the lock (its own work, its own
            # errors), then all are completed in ONE pass under one
            # acquisition: the wave's waiters are woken together, by one
            # holder of the lock instead of one a query in turn
            with GLOBAL_TRACER.span(
                "scheduler.settle", wave=wave_span.span_id, queries=n
            ) as sp:
                phase_spans["settle"].append(sp)
                outcomes: list[tuple] = []
                for it in settled:
                    try:
                        if not joint_ok and it.pendings:
                            fetch_seconds = self._readback(
                                executor, it.pendings, wave_span, phase_spans
                            )
                        for p in it.pendings:
                            p.resolve_fetched()
                        outcomes.append(
                            (
                                it,
                                finalize(it.raw),
                                None,
                                fetch_seconds if it.pendings else None,
                            )
                        )
                    except Exception as e:  # noqa: BLE001 — per-query
                        # isolation at settle: a finish() failure (bad
                        # attr, overflow) errors its own query only
                        outcomes.append((it, None, e, None))
                with self._lock:
                    for it, results, error, readback in outcomes:
                        self._finish(
                            it,
                            results=results,
                            error=error,
                            readback=readback,
                            # under the lock that seals it: "shared"
                            # counts every follower this execution answers
                            wave={
                                "queries": n,
                                "shared": 1 + len(it.followers),
                                "flushReason": reason,
                            },
                        )
        # final occupancy: every prime plus every follower it fanned
        # out to (followers can no longer join — all items sealed)
        n = len(batch) + sum(len(it.followers) for it in batch)
        self.waves += 1
        self.batched_queries += n
        executor.router.observe_wave(n)
        if self.stats is not None:
            self.stats.observe("queries_per_wave", float(n))
            self.stats.count("wave_flush_reason", tags={"reason": reason})
            self.stats.timing("scheduler_wave_seconds", wave_span.duration)
            for phase, spans in phase_spans.items():
                if spans:
                    self._phase(phase, sum(sp.duration for sp in spans))

    @staticmethod
    def _readback(
        executor, pendings: "list[_Pending]", wave_span, phase_spans: dict
    ) -> float:
        """executor.fetch under the ``scheduler.readback`` span (the
        joint call and the per-query fall-back alike)."""
        with GLOBAL_TRACER.span(
            "scheduler.readback",
            wave=wave_span.span_id,
            arrays=sum(len(p.arrays) for p in pendings),
        ) as sp:
            phase_spans["readback"].append(sp)
            return executor.fetch(pendings)

    def _finish(
        self,
        item: _WorkItem,
        results=None,
        error=None,
        readback: float | None = None,
        wave: dict | None = None,
    ) -> None:
        """Called holding ``_lock``: seal ``item`` (no follower joins
        from here on) and complete it and its dedup followers, each
        once — an item already completed is passed over, so a second
        call (the backstop, a failure half-way) reaches only what the
        first left.  Wakes the call whose last item this was, and
        nobody else."""
        item.sealed = True
        if self._inflight.get(item.key) is item:
            del self._inflight[item.key]
        for it in (item, *item.followers):
            if it.done:
                continue
            if it.profile is not None:
                # every sharing query's ?profile=true response documents
                # the wave (docs/query-batching.md), dedup followers
                # too: the shared transfer's cost on a _readback line,
                # and the wave dict that tells the reader it was
                # amortized — stamped BEFORE the waiter is released to
                # serialize it
                if readback is not None:
                    it.profile.add_call("_readback", readback, None)
                if wave is not None:
                    it.profile.wave = dict(wave)
            it.results = results
            it.error = error
            it.done = True
            waiter = it.waiter
            waiter.pending -= 1
            if not waiter.pending:
                waiter.event.set()

    # ------------------------------------------------------ observability
    def snapshot(self) -> dict:
        """Live view for /debug/vars (queryBatching) and tests."""
        with self._lock:
            waves, batched = self.waves, self.batched_queries
            deduped, direct = self.deduped_queries, self.direct_queries
            wakeups = dict(self.wakeups)
        return {
            "mode": self.mode,
            "windowUs": self.window_s * 1e6,
            "maxQueries": self.max_queries,
            "waves": waves,
            "batchedQueries": batched,
            "dedupedQueries": deduped,
            "directQueries": direct,
            "meanQueriesPerWave": (batched / waves) if waves else 0.0,
            "wakeups": wakeups,
        }
