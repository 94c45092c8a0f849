"""Tracing: spans around executor calls, fragment ops, HTTP handlers —
with REAL trace identity and cross-node context propagation.

Reference: tracing/tracing.go (global Tracer, StartSpanFromContext) +
tracing/opentracing adapter (Jaeger span propagation across the per-shard
HTTP fan-out). OpenTracing/Jaeger isn't available here, so the Tracer
records spans in-process (ring buffer) and can dump them for inspection;
the API matches so an OTLP adapter can slot in later. What IS wire-real:

- every span carries a 128-bit ``trace_id`` and 64-bit ``span_id``
  (hex strings, Jaeger-sized);
- ``(trace_id, parent_span_id)`` travel node→node as HTTP headers
  (``X-Pilosa-Trace-Id`` / ``X-Pilosa-Parent-Span-Id``, injected by
  parallel/client.py and extracted by server/http.py), so one user query
  yields ONE coherent trace across coordinator and remote nodes;
- ``chrome_trace_stitched`` merges per-node span sets into one Chrome
  trace-event JSON (one pid per node) for Perfetto/chrome://tracing —
  the export story, with the coordinator fetching remote spans via
  ``GET /internal/trace``;
- a SECOND sink: while a ``jax.profiler`` session is recording, every
  span is also a ``TraceAnnotation`` in the trace's ``/host:CPU`` plane
  (its own thread's line; ``trace_id``/``span_id``/scalar tags as event
  stats), so the host's timeline sits on the clock the device planes
  use. jax is never imported from here — the sink exists only in a
  process that already imported it, and with no session open it costs
  one flag test per span;
- every span reads the thread's CPU clock beside the wall clock
  (``Span.cpu``, ``cpuSeconds``; ``cpu_ns`` on the annotation): wall
  less CPU is time the thread was not running, which inside a span that
  does not block on purpose is the wait for the interpreter lock. A
  closing span adds its time to the span open beneath it on its THREAD,
  so every span has a self time, and one table keyed by span name
  (count, wall, self wall, self off-CPU) is rendered when ``/metrics``
  is scraped (``Tracer.publish``), never pushed a span. Where that clock
  is a slow system call, one span tree in four of a thread reads it and
  stands for the rest (``_cpu_clock_every``).

A span makes no system call that releases the interpreter lock: ids come
from a process-seeded generator, the wall start from one anchor. (An
``os.urandom`` a span handed the lock to a waiting thread at every span
of a serving thread.)

The module also hosts the per-query profile collector (``profile_query``
/ ``current_profile``): a thread-local sink the executor and cluster
fan-out write per-call / per-shard-group timing+bytes records into, so
``?profile=true`` can return a breakdown without threading a collector
through every router signature.
"""

from __future__ import annotations

import os
import random
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from time import perf_counter, thread_time
from weakref import WeakKeyDictionary

MAX_SPANS = 4096

# cross-node propagation headers (reference: the opentracing adapter's
# Inject/Extract over Jaeger's uber-trace-id; spelled out here so curl
# can join a trace too)
TRACE_HEADER = "X-Pilosa-Trace-Id"
PARENT_HEADER = "X-Pilosa-Parent-Span-Id"

# ids come from one generator seeded from the OS when the process starts
# (and again in a forked child): a span draws its ids with no system
# call.  ``getrandbits`` is one C call, atomic under the interpreter
# lock.  Trace ids are labels, not secrets.
_IDS = random.Random(os.urandom(32))
_id_bits = _IDS.getrandbits
if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=lambda: _IDS.seed(os.urandom(32)))


def new_trace_id() -> str:
    """128-bit trace id, 32 hex chars (Jaeger-sized)."""
    return "%032x" % _id_bits(128)


def new_span_id() -> str:
    """64-bit span id, 16 hex chars."""
    return "%016x" % _id_bits(64)


# one wall↔monotonic anchor so exported timestamps share a single
# monotonic timeline (mixing time.time starts with perf_counter
# durations lets child slices cross parent boundaries in trace viewers)
_PERF_EPOCH = time.time() - perf_counter()  # pilosa: allow(wall-clock)

# Spans whose body blocks ON PURPOSE (a sleep on an event, a wait for the
# device): their time off the CPU is the wait they exist to show.  Every
# other span is ``work``: what its thread spent off the CPU inside it was
# a wait for the interpreter lock (plus what the OS took).  The ``kind``
# label of the span table's self-time families; the table in
# docs/observability.md prints the same set.
WAIT_SPANS = frozenset(
    {
        "scheduler.await",
        "scheduler.window",
        "readback.transfer",
        "executor.groupby.wait",
        "executor.groupby.readback",
        "executor.groupby.admit",
        "mesh.collective",
    }
)


def _cpu_clock_every() -> int:
    """On how many of a thread's span trees the CPU clock is read: all of
    them where ``time.thread_time()`` is cheap (a third of a microsecond
    on a plain Linux host), one in ``CPU_EVERY_WHEN_SLOW`` where it is a
    slow system call (5 us a call on the TPU v5e host of PR 36, where two
    a span would have cost more than the rest of the span).  Timed once,
    when the module is imported; nothing configures it."""
    best = float("inf")
    for _ in range(5):
        t = perf_counter()
        for _ in range(20):
            thread_time()
        best = min(best, (perf_counter() - t) / 20)
    return 1 if best < 1e-6 else CPU_EVERY_WHEN_SLOW


CPU_EVERY_WHEN_SLOW = 4
CPU_EVERY = _cpu_clock_every()


class _ThreadState:
    """What the tracer knows of one thread: the TRACE parent of its next
    span (``current``, cut by ``detached``), a propagated context
    (``remote``), the innermost span open on it whatever trace it
    belongs to (``top``: self time is reckoned by the thread), and
    whether the tree of spans now open on it reads the CPU clock
    (``timed``, decided when the tree's outermost span opens: tree
    number ``trees`` is timed when the tracer's ``cpu_every`` divides
    it)."""

    __slots__ = ("current", "remote", "top", "tid", "trees", "timed")

    def __init__(self):
        self.current: Span | None = None
        self.remote: tuple | None = None
        self.top: Span | None = None
        self.tid = threading.get_ident()
        self.trees = 0
        self.timed = False


class Span:
    """One span, and its own context manager.  ``duration`` is wall time,
    ``cpu`` the thread's CPU time (``time.thread_time``) between the same
    two points: their difference is time the thread was not running
    (None on a span whose tree does not read the CPU clock, see
    ``_cpu_clock_every``).  Ids are integers until something exports
    them (``trace_id``, ``span_id``, ``parent_id`` are hex strings,
    rendered once)."""

    __slots__ = (
        "name",
        "start_perf",
        "duration",
        "cpu",
        "tags",
        "parent",
        "tid",
        "_trace",
        "_span",
        "_parent",
        # while open
        "_tracer",
        "_state",
        "_prev",
        "_below",
        "_ann",
        "_cpu0",
        # wall and CPU seconds of the spans that closed directly above
        # this one on its thread: self time is the span's less these
        "_kids_wall",
        "_kids_cpu",
    )

    def __init__(self, tracer: "Tracer", state: _ThreadState, name: str, tags: dict):
        self.name = name
        self.tags = tags
        self._tracer = tracer
        self._state = state
        self.tid = state.tid
        parent = self._prev = state.current
        if parent is not None:
            self.parent = parent.name  # parent span NAME (human-readable)
            self._trace = parent._trace
            self._parent = parent._span  # parent span ID (joinable)
        else:
            # no local parent: join a propagated (remote) context if one
            # was activated for this request, else start a fresh trace
            self.parent = None
            if state.remote is not None:
                self._trace, self._parent = state.remote
            else:
                self._trace = _id_bits(128)
                self._parent = None
        self._span = _id_bits(64)
        self.duration = self._kids_wall = self._kids_cpu = 0.0
        self.cpu = None

    @property
    def trace_id(self) -> str:
        v = self._trace
        if v.__class__ is int:
            v = self._trace = "%032x" % v
        return v

    @property
    def span_id(self) -> str:
        v = self._span
        if v.__class__ is int:
            v = self._span = "%016x" % v
        return v

    @property
    def parent_id(self) -> str | None:
        v = self._parent
        if v.__class__ is int:
            v = self._parent = "%016x" % v
        return v

    @property
    def start(self) -> float:
        """Wall-clock start, from the one anchor: no ``time.time()`` a span."""
        return self.start_perf + _PERF_EPOCH

    def set_tag(self, k, v):
        self.tags[k] = v

    def __enter__(self) -> "Span":
        st = self._state
        below = self._below = st.top
        if below is None:
            # the outermost span open on this thread: its whole tree
            # reads the CPU clock, or none of it does
            st.trees += 1
            st.timed = st.trees % self._tracer.cpu_every == 0
        st.current = st.top = self
        # with no profiler session recording: one flag test
        cls = _ANNOTATION or _annotation_class()
        self._ann = (
            _annotation(cls, self)
            if cls is not None and cls.is_enabled()
            else None
        )
        # the wall pair outside the CPU pair: wall >= cpu on a clock
        # that is exact (one that moves in ticks can read more)
        self.start_perf = perf_counter()
        self._cpu0 = thread_time() if st.timed else None
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        timed = self._cpu0 is not None
        cpu = self.cpu = thread_time() - self._cpu0 if timed else None
        # same sample as the exported ts — ts and dur must share one
        # clock origin or child slices cross parent edges in viewers
        wall = self.duration = perf_counter() - self.start_perf
        if self._ann is not None:
            # tags set inside the body (a wave's flush reason) reach the
            # trace too; the entry tags are already on the event
            ann, entered = self._ann
            late = {
                k: v
                for k, v in self.tags.items()
                if k not in entered and isinstance(v, _SCALARS)
            }
            if timed:
                late["cpu_ns"] = int(cpu * 1e9)
            if late:
                ann.set_metadata(**late)
            ann.__exit__(None, None, None)
            self._ann = None
        st, below, tracer = self._state, self._below, self._tracer
        st.current, st.top = self._prev, below
        # the spans this one was opened over are not kept alive by it
        self._prev = self._below = None
        self_wall = wall - self._kids_wall
        if timed:
            # signed: where the CPU clock moves in ticks (10 ms on the
            # host of PR 36) a short span reads 0 or a whole tick, and
            # only the sums are right; scaled to stand for the trees
            # that were not timed
            off = (self_wall - (cpu - self._kids_cpu)) * tracer.cpu_every
        if below is not None:
            below._kids_wall += wall
            if timed:
                below._kids_cpu += cpu
        with tracer._lock:
            row = tracer._table.get(self.name)
            if row is None:
                row = tracer._table[self.name] = [0, 0.0, 0.0, 0.0]
            row[0] += 1
            row[1] += wall
            row[2] += self_wall
            if timed:
                row[3] += off
        tracer._spans.append(self)  # deque.append is atomic: no lock
        return False

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "parent": self.parent,
            "traceID": self.trace_id,
            "spanID": self.span_id,
            "parentSpanID": self.parent_id,
            "start": self.start,
            # wall-anchored monotonic start: chrome export needs ts and
            # dur on ONE clock, and remote spans arrive as these dicts
            "ts": self.start,
            "durationSeconds": self.duration,
            "cpuSeconds": self.cpu,
            "tags": self.tags,
            "tid": self.tid,
        }


_SCALARS = (str, int, float, bool)
_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax has it


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` in a process that has imported
    jax, else None (looked up, never imported: the jax-free parents of
    chip_smoke.py and the benchmark import this module). Looked up until
    it is found, then kept; the getattr chain tolerates a jax still
    half-imported on another thread."""
    global _ANNOTATION
    _ANNOTATION = getattr(
        getattr(sys.modules.get("jax"), "profiler", None), "TraceAnnotation", None
    )
    return _ANNOTATION


def _annotation(cls, s: Span) -> tuple:
    """The span as an ENTERED profiler annotation (a session records),
    with the names of the tags it went in with."""
    stats = {k: v for k, v in s.tags.items() if isinstance(v, _SCALARS)}
    stats.update(trace_id=s.trace_id, span_id=s.span_id)
    ann = cls(s.name, **stats)
    ann.__enter__()
    return ann, frozenset(s.tags)


class Tracer:
    def __init__(self, cpu_every: int | None = None):
        # one span tree in ``cpu_every`` of a thread reads the CPU clock
        # (every tree where the clock is cheap); tests pin it
        self.cpu_every = cpu_every or CPU_EVERY
        # taken by a closing span for its row of the table, and by the
        # table's readers; the ring needs none (deque.append is atomic)
        self._lock = threading.Lock()
        self._spans: deque[Span] = deque(maxlen=MAX_SPANS)
        self._local = threading.local()
        # span name -> [count, wall s, self wall s, self off-CPU s],
        # since the process started; rendered when /metrics is scraped
        self._table: dict[str, list] = {}
        self._publish_lock = threading.Lock()
        self._published: WeakKeyDictionary = WeakKeyDictionary()

    def _state(self) -> _ThreadState:
        try:
            return self._local.state
        except AttributeError:
            st = self._local.state = _ThreadState()
            return st

    def span(self, name: str, **tags) -> Span:
        """``with tracer.span(name, **tags) as s``: a child of the span
        open on this thread, else of the propagated context activated
        for this request, else the root of a fresh trace."""
        try:  # _state() inlined: this is every span's path
            st = self._local.state
        except AttributeError:
            st = self._state()
        return Span(self, st, name, tags)

    @contextmanager
    def activate(self, trace_id: str | None, parent_span_id: str | None):
        """Join a PROPAGATED trace context for the duration of a request:
        spans opened on this thread (with no local parent) adopt
        ``trace_id`` and parent onto ``parent_span_id`` — the server-side
        Extract half of cross-node propagation. A falsy trace_id is a
        no-op so call sites don't need to branch on header presence."""
        if not trace_id:
            yield
            return
        st = self._state()
        prev = st.remote
        st.remote = (trace_id, parent_span_id)
        try:
            yield
        finally:
            st.remote = prev

    @contextmanager
    def detached(self, trace_id: str | None, parent_span_id: str | None):
        """Run the body OUTSIDE this thread's current span stack,
        optionally joining a propagated context instead.  The wave
        scheduler (executor/scheduler.py) executes queued queries on
        the leader's thread: each query's spans must join the
        SUBMITTER's trace (captured at enqueue), not nest under the
        leader's own request span — otherwise every batched query's
        trace would collapse into whichever request happened to lead
        the wave.  Only the TRACE parent is cut: the thread's own stack
        (``top``) stays, so a span opened in the body still counts into
        the self time of the span open beneath it on this thread."""
        st = self._state()
        prev_cur, prev_rem = st.current, st.remote
        st.current = None
        st.remote = (trace_id, parent_span_id) if trace_id else None
        try:
            yield
        finally:
            st.current, st.remote = prev_cur, prev_rem

    def current_context(self) -> tuple[str, str] | None:
        """(trace_id, span_id) to INJECT into an outbound request — the
        active span's identity, or the activated remote context when no
        span is open on this thread. None outside any trace."""
        st = self._state()
        cur = st.current
        if cur is not None:
            return (cur.trace_id, cur.span_id)
        remote = st.remote
        if remote is not None and remote[0]:
            return (remote[0], remote[1] or "")
        return None

    def current_name(self) -> str | None:
        """Name of the innermost span open on this thread (the ``site``
        label of the compile counter, utils/xlaevents.py)."""
        cur = self._state().current
        return cur.name if cur is not None else None

    def current_trace_id(self) -> str | None:
        ctx = self.current_context()
        return ctx[0] if ctx else None

    def recent(self, n: int = 100) -> list[dict]:
        # list(deque) is one C call: a copy no append can tear, and no
        # closing span waits while the copy is serialized
        return [s.to_json() for s in list(self._spans)[-n:]]

    def depth(self) -> int:
        """Buffered span count (the /debug/resources tracer-ring row —
        counting must not pay for serializing 4k spans)."""
        return len(self._spans)

    def spans_for_trace(self, trace_id: str) -> list[dict]:
        """Every buffered span belonging to one trace (served to peers by
        GET /internal/trace for cross-node stitching)."""
        return [s.to_json() for s in list(self._spans) if s.trace_id == trace_id]

    def chrome_trace(self, n: int = 1000) -> dict:
        """Spans as Chrome trace-event JSON — loadable in
        chrome://tracing / Perfetto (the trace-EXPORT story; the
        reference exports spans to Jaeger, unavailable here)."""
        return {
            "traceEvents": _chrome_events(self.recent(n), pid=1),
            "displayTimeUnit": "ms",
        }

    # ------------------------------------------------------ the span table
    def span_table(self) -> dict[str, tuple]:
        """{span name: (count, wall s, self wall s, self off-CPU s)}
        since the process started.  Self time is by the THREAD's open
        spans: a span's own time is its time less that of the spans
        opened on its thread while it was open, whatever trace they
        joined.  The off-CPU seconds are those of the span trees that
        read the CPU clock, times ``cpu_every``: exact where every tree
        does, an estimate of the same total where one in four does."""
        with self._lock:
            return {name: tuple(row) for name, row in self._table.items()}

    def publish(self, stats) -> None:
        """The span table into ``stats``, when /metrics is scraped (never
        a span): what each row gained since this registry last saw it is
        counted into ``spans_total{span}``,
        ``span_wall_seconds_total{span}`` and, with ``kind`` = ``wait``
        for the names in WAIT_SPANS and ``work`` for the rest,
        ``span_self_wall_seconds_total`` and
        ``span_self_offcpu_seconds_total``.  Label values are the code's
        span names, which are literals."""
        with self._publish_lock:
            seen = self._published.setdefault(stats, {})
            for name, row in self.span_table().items():
                was = seen.get(name, (0, 0.0, 0.0, 0.0))
                n, wall, self_wall, off = (a - b for a, b in zip(row, was))
                if not n:
                    continue
                # a counter never falls: where a tick of the CPU clock
                # took the off-CPU sum back, it waits for the sum to pass
                # its published mark again
                off = max(off, 0.0)
                seen[name] = (*row[:3], was[3] + off)
                span = {"span": name}
                kinds = {
                    "kind": "wait" if name in WAIT_SPANS else "work",
                    "span": name,
                }
                stats.count("spans_total", n, tags=span)
                stats.count("span_wall_seconds_total", wall, tags=span)
                stats.count("span_self_wall_seconds_total", self_wall, tags=kinds)
                stats.count("span_self_offcpu_seconds_total", off, tags=kinds)


def _chrome_events(spans: list[dict], pid: int) -> list[dict]:
    """Span dicts (Span.to_json shape — local or fetched from a peer) →
    Chrome trace-event "X" slices on one pid."""
    events = []
    for s in spans:
        args = dict(s.get("tags") or {})
        if s.get("parent"):
            args["parent"] = s["parent"]
        for key in ("traceID", "spanID", "parentSpanID"):
            if s.get(key):
                args[key] = s[key]
        events.append(
            {
                "name": s["name"],
                "ph": "X",
                # one monotonic timeline anchored to wall time — ts and
                # dur must share a clock or nesting breaks
                "ts": s["ts"] * 1e6,
                "dur": s["durationSeconds"] * 1e6,
                "pid": pid,
                "tid": s.get("tid", 1),
                "args": args,
            }
        )
    return events


def chrome_trace_stitched(spans_by_node: dict[str, list[dict]]) -> dict:
    """One coherent Chrome trace from per-node span sets: each node gets
    its own pid (named via process_name metadata), every event keeps its
    traceID/spanID/parentSpanID args, so a distributed query renders as
    the coordinating HTTP span with each remote node's spans time-nested
    inside it on their own process track."""
    events: list[dict] = []
    for pid, node in enumerate(sorted(spans_by_node), start=1):
        events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": f"node {node}"},
            }
        )
        events.extend(_chrome_events(spans_by_node[node], pid=pid))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


GLOBAL_TRACER = Tracer()


# --------------------------------------------------------- query profiles
class QueryProfile:
    """Per-query timing/bytes breakdown (the reference's query-profile
    analogue). Filled by the executor (per-PQL-call dispatch + readback)
    and the cluster fan-out (per-node shard groups, RPC latency + wire
    bytes); surfaced by ``?profile=true`` and mined by the long-query
    log to name the slow shard group. Single-threaded by construction:
    the HTTP handler thread drives the whole query synchronously."""

    __slots__ = (
        "trace_id",
        "total_seconds",
        "calls",
        "fanout",
        "wave",
        "mesh",
        "residency",
        "admission_wait",
        "deadline",
        "retries",
        "failovers",
        "_last_rpc_bytes",
    )

    def __init__(self):
        self.trace_id: str | None = None
        self.total_seconds = 0.0
        self.calls: list[dict] = []  # local executor per-call entries
        self.fanout: list[dict] = []  # per-node shard-group entries
        # seconds this request waited in the event front end's admission
        # queue before a worker picked it up (None on the threaded
        # listener, which has no admission lane) — the flight recorder's
        # "was it the queue or the query" attribution
        self.admission_wait: float | None = None
        # per-query deadline accounting at settle: {"budgetS",
        # "remainingS"} — how much of the promised budget the query
        # spent (docs/fault-tolerance.md)
        self.deadline: dict | None = None
        # retry/failover attribution (docs/fault-tolerance.md): the
        # resilient RPC chain notes each retry sleep it takes on this
        # query's behalf, and the fan-out notes each leg it re-planned
        # onto a surviving replica — tail latency from a flaky peer is
        # visible in the evidence, not just in global counters
        self.retries: list[dict] = []
        self.failovers: list[dict] = []
        # set by the wave scheduler when this query rode a shared wave:
        # {"queries": occupancy, "flushReason": ...} — the ?profile=true
        # surface for cross-query coalescing
        self.wave: dict | None = None
        # set by the executor when a call routed to the explicit-SPMD
        # mesh path: device count + mesh geometry (the ?profile=true
        # surface for multi-chip execution; per-call entries carry the
        # route tag already)
        self.mesh: dict | None = None
        # set by the executor when the query touched tiered compressed
        # residency (docs/device-residency.md): container tiers,
        # promotion/demotion counters — the ?profile=true surface for
        # the hot/cold row tier
        self.residency: dict | None = None
        self._last_rpc_bytes = 0

    def add_call(
        self,
        call: str,
        seconds: float,
        shards: list[int] | None,
        route: str | None = None,
    ) -> None:
        # shards is stored by REFERENCE, not copied: the collector runs
        # on every query (the long-query log mines it), so a thousands-
        # of-shards index must not pay a per-call list copy; callers
        # pass lists they do not mutate afterwards
        entry: dict = {"call": call, "seconds": seconds}
        if route is not None:
            # which engine the cost router picked (host | device) — the
            # ?profile=true surface for the routing decision
            entry["route"] = route
        if shards is not None:
            entry["shards"] = shards
        self.calls.append(entry)

    def add_fanout(
        self,
        call: str,
        node: str,
        shards: list[int] | None,
        seconds: float,
        bytes_: int,
    ) -> None:
        self.fanout.append(
            {
                "call": call,
                "node": node,
                "shards": shards,  # by reference — see add_call
                "seconds": seconds,
                "bytes": bytes_,
            }
        )

    def note_retry(self, method: str, node: str, attempt: int) -> None:
        """The resilient client reports each retry attempt it makes for
        an RPC issued under this query (docs/fault-tolerance.md)."""
        self.retries.append({"method": method, "node": node, "attempt": attempt})

    def note_failover(self, node: str, to_node: str, shards: list[int] | None) -> None:
        """The cluster fan-out reports each leg it re-planned from a
        failed peer onto a surviving replica."""
        self.failovers.append(
            {"node": node, "toNode": to_node, "shards": shards}
        )

    def note_rpc_bytes(self, n: int) -> None:
        """The internal client reports each response's size here; the
        fan-out reads it back to attribute wire bytes to the shard-group
        entry it is about to record (same thread, no nesting between the
        RPC return and the read)."""
        self._last_rpc_bytes = n

    def take_rpc_bytes(self) -> int:
        n, self._last_rpc_bytes = self._last_rpc_bytes, 0
        return n

    def slowest(self) -> dict | None:
        """The slowest shard-group (preferred — it names a node) or
        per-call entry, for the long-query log."""
        pool = self.fanout or self.calls
        if not pool:
            return None
        return max(pool, key=lambda e: e["seconds"])

    def to_json(self) -> dict:
        out: dict = {
            "totalSeconds": self.total_seconds,
            "calls": self.calls,
            "fanout": self.fanout,
        }
        if self.wave is not None:
            out["wave"] = self.wave
        if self.mesh is not None:
            out["mesh"] = self.mesh
        if self.residency is not None:
            out["residency"] = self.residency
        if self.admission_wait is not None:
            out["admissionWaitSeconds"] = self.admission_wait
        if self.deadline is not None:
            out["deadline"] = self.deadline
        if self.retries:
            out["retries"] = self.retries
        if self.failovers:
            out["failovers"] = self.failovers
        if self.trace_id:
            out["traceID"] = self.trace_id
        return out


_PROFILE = threading.local()


@contextmanager
def profile_query():
    """Install a QueryProfile as this thread's active collector."""
    prof = QueryProfile()
    prev = getattr(_PROFILE, "current", None)
    _PROFILE.current = prof
    try:
        yield prof
    finally:
        _PROFILE.current = prev


def current_profile() -> QueryProfile | None:
    return getattr(_PROFILE, "current", None)


@contextmanager
def use_profile(prof: QueryProfile | None):
    """Install a SPECIFIC profile (possibly None) as this thread's
    collector — the wave scheduler dispatches queued queries on the
    leader's thread, and each query's executor calls must land in the
    profile its own submitter installed, not the leader's."""
    prev = getattr(_PROFILE, "current", None)
    _PROFILE.current = prof
    try:
        yield prof
    finally:
        _PROFILE.current = prev
