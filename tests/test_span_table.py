"""The tracer's CPU clock, self time and per-span table (ISSUE 36).

Every span reads the thread's CPU clock beside the wall clock; a closing
span adds its time to the span open beneath it on its thread, so each
span has a self time; one table keyed by span name is rendered when
``/metrics`` is scraped.  And a span makes no system call: ids come from
a process-seeded generator.  Where ``time.thread_time()`` is a slow
system call the tracer reads it on one span tree in four of a thread
(``tracing.CPU_EVERY``); the tests pin ``cpu_every``.
"""

import json
import os
import re
import threading
import time
import urllib.request

import pytest

from pilosa_tpu.executor.executor import GroupByLedger
from pilosa_tpu.server import Server
from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.config import Config
from pilosa_tpu.utils.stats import NopStats, StatsClient
from pilosa_tpu.utils.tracing import GLOBAL_TRACER, WAIT_SPANS, Tracer


def _spin(seconds: float) -> None:
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


# ------------------------------------------------------------ the CPU clock
def test_cpu_is_under_wall_when_the_body_sleeps():
    with Tracer(cpu_every=1).span("t.sleep") as sp:
        time.sleep(0.05)
    assert sp.duration >= 0.05
    assert sp.cpu < 0.02 < sp.duration - sp.cpu
    assert sp.to_json()["cpuSeconds"] == sp.cpu


def test_cpu_is_near_wall_when_the_body_spins():
    with Tracer(cpu_every=1).span("t.spin") as sp:
        _spin(0.05)
    assert sp.cpu >= 0.05
    # wall can only be longer (the OS may take the core); never shorter
    assert sp.cpu <= sp.duration


# ---------------------------------------------------------------- self time
def test_self_time_is_the_spans_less_the_spans_opened_over_it():
    t = Tracer(cpu_every=1)
    with t.span("t.outer"):
        _spin(0.02)
        with t.span("t.inner"):
            time.sleep(0.03)
        with t.span("t.inner"):
            _spin(0.01)
    table = t.span_table()
    n, wall, self_wall, off = table["t.outer"]
    assert n == 1 and wall >= 0.06
    # the outer's own time is its spin, not its children's sleep and spin
    assert 0.02 <= self_wall < wall - 0.035
    assert off < 0.01
    n, wall, self_wall, off = table["t.inner"]
    assert n == 2 and self_wall == pytest.approx(wall)
    assert 0.025 <= off <= wall  # the sleep is time off the CPU


def test_self_time_follows_the_thread_through_detached():
    """``detached`` cuts the TRACE parent (a wave's queries join their
    submitters' traces) and must not cut the accounting: the query's
    time still comes off the wave's self time."""
    t = Tracer(cpu_every=1)
    with t.span("t.wave") as wave:
        with t.detached("ab" * 16, "cd" * 8):
            with t.span("t.query") as query:
                time.sleep(0.03)
        assert t.current_name() == "t.wave"
    assert query.trace_id == "ab" * 16 and query.parent_id == "cd" * 8
    assert query.parent is None and wave.trace_id != query.trace_id
    _n, wall, self_wall, _off = t.span_table()["t.wave"]
    assert wall >= 0.03 and self_wall < 0.02
    assert t.span_table()["t.query"][2] >= 0.03


def test_each_thread_has_a_stack_of_its_own():
    t = Tracer(cpu_every=1)
    started, stop = threading.Event(), threading.Event()

    def other():
        with t.span("t.other"):
            started.set()
            stop.wait(10)

    th = threading.Thread(target=other)
    with t.span("t.main"):
        th.start()
        assert started.wait(10)
        time.sleep(0.02)
        stop.set()
        th.join()
    table = t.span_table()
    # neither took the other's time off its own
    assert table["t.main"][2] == pytest.approx(table["t.main"][1])
    assert table["t.other"][2] == pytest.approx(table["t.other"][1])


def test_one_tree_in_cpu_every_reads_the_cpu_clock_and_stands_for_the_rest():
    """Where the CPU clock is a slow system call: the fourth, eighth, ...
    tree of a thread is timed, root and children alike, and its off-CPU
    time is counted four times."""
    t = Tracer(cpu_every=4)
    timed = []
    for _ in range(8):
        with t.span("t.root") as root:
            with t.detached(None, None):
                with t.span("t.child") as child:
                    time.sleep(0.01)
        assert (root.cpu is None) == (child.cpu is None)
        timed.append(root.cpu is not None)
        assert root.to_json()["cpuSeconds"] == root.cpu
    assert timed == [False, False, False, True] * 2
    n, wall, self_wall, off = t.span_table()["t.child"]
    assert n == 8 and wall == pytest.approx(self_wall) and wall >= 0.08
    # two timed sleeps of 10 ms stand for eight
    assert 0.07 <= off <= wall * 1.3
    assert tracing.CPU_EVERY in (1, tracing.CPU_EVERY_WHEN_SLOW)
    assert GLOBAL_TRACER.cpu_every == tracing.CPU_EVERY


def test_a_published_counter_never_falls(monkeypatch):
    """A CPU clock that moves in ticks can charge a short span a whole
    tick: the signed sum then dips, the published counter waits."""
    t, stats = Tracer(cpu_every=1), StatsClient()
    clock = iter([0.0, 0.0, 0.0, 0.010, 0.010, 0.010])  # the second span eats a 10 ms tick
    monkeypatch.setattr(tracing, "thread_time", lambda: next(clock))
    key = 'kind="work",span="t.tick"'
    with t.span("t.tick"):
        time.sleep(0.002)
    t.publish(stats)
    first = _families(stats.prometheus())["span_self_offcpu_seconds_total"][key]
    assert first >= 0.002
    with t.span("t.tick"):
        pass
    assert t.span_table()["t.tick"][3] < 0  # the sum itself is signed
    t.publish(stats)
    assert _families(stats.prometheus())["span_self_offcpu_seconds_total"][key] == first
    with t.span("t.tick"):
        time.sleep(0.02)
    t.publish(stats)
    # ... and goes on from the sum once it has passed the mark: 2 + 0 - 10 + 20 ms
    assert _families(stats.prometheus())["span_self_offcpu_seconds_total"][key] == pytest.approx(
        t.span_table()["t.tick"][3]
    )


# ---------------------------------------------------------------- the table
def _families(text: str) -> dict:
    out: dict = {}
    for line in text.splitlines():
        m = re.match(r"pilosa_tpu_(span\w*)\{(.*)\} (\S+)$", line)
        if m:
            out.setdefault(m.group(1), {})[m.group(2)] = float(m.group(3))
    return out


def test_the_tables_families_render_and_a_wait_span_is_labelled_so():
    t, stats = Tracer(cpu_every=1), StatsClient()
    with t.span("executor.Count") as outer:
        _spin(0.005)
        with t.span("executor.groupby.wait") as inner:
            time.sleep(0.01)
    assert "executor.groupby.wait" in WAIT_SPANS
    t.publish(stats)
    fam = _families(stats.prometheus())
    assert set(fam) == {
        "spans_total",
        "span_wall_seconds_total",
        "span_self_wall_seconds_total",
        "span_self_offcpu_seconds_total",
    }
    assert fam["spans_total"] == {
        'span="executor.Count"': 1.0,
        'span="executor.groupby.wait"': 1.0,
    }
    # the label string is sorted: kind before span, so a substring such as
    # kind="work",span="executor. selects a family of working spans
    work, wait = 'kind="work",span="executor.Count"', 'kind="wait",span="executor.groupby.wait"'
    assert set(fam["span_self_wall_seconds_total"]) == {work, wait}
    assert fam["span_self_offcpu_seconds_total"][wait] >= 0.009
    assert fam["span_wall_seconds_total"]['span="executor.Count"'] >= 0.015
    # the work span's self time is its own wall and CPU readings less the
    # wait's, whatever else the machine runs: a loaded machine keeps the
    # thread off a core for longer, inside the spin or outside it, and
    # that is the work span's time off the CPU. The sleep is not: at
    # least the 5 ms spun of its self time was on the CPU. (A counter
    # does not go down: a sum below 0, a few microseconds of the two
    # clocks' skew, is published as 0.)
    self_wall = outer.duration - inner.duration
    assert fam["span_self_wall_seconds_total"][work] == pytest.approx(self_wall)
    assert fam["span_self_offcpu_seconds_total"][work] == pytest.approx(
        max(0.0, self_wall - (outer.cpu - inner.cpu))
    )
    assert 0.005 <= self_wall
    assert fam["span_self_offcpu_seconds_total"][work] <= max(0.0, self_wall - 0.005)


def test_a_scrape_counts_what_the_table_gained_since_the_last():
    t, stats, late = Tracer(cpu_every=1), StatsClient(), StatsClient()
    for _ in range(3):
        with t.span("t.a"):
            pass
    t.publish(stats)
    t.publish(stats)  # nothing gained: nothing counted twice
    with t.span("t.a"):
        pass
    t.publish(stats)
    assert _families(stats.prometheus())["spans_total"] == {'span="t.a"': 4.0}
    # a registry that joins late sees the process's whole table
    t.publish(late)
    assert _families(late.prometheus())["spans_total"] == {'span="t.a"': 4.0}
    t.publish(NopStats())  # a sink that keeps nothing is no error


def test_metrics_route_serves_the_table(tmp_path):
    srv = Server(Config(bind="127.0.0.1:0", data_dir=str(tmp_path / "d"),
                        anti_entropy_interval=0))
    srv.open()
    try:
        def get(path):
            with urllib.request.urlopen(f"http://127.0.0.1:{srv.port}{path}") as r:
                return r.read().decode()

        get("/status")
        fam = _families(get("/metrics"))
        assert fam["spans_total"]['span="http.status"'] >= 1
        key = 'kind="work",span="http.status"'
        assert fam["span_self_wall_seconds_total"][key] > 0
        assert key in fam["span_self_offcpu_seconds_total"]
        vars_ = json.loads(get("/debug/vars"))
        assert any(k.startswith("spans_total{") for k in vars_["counters"])
    finally:
        srv.close()


# ---------------------------------------------------------------------- ids
def test_ids_are_hex_of_the_wire_width_and_unique(monkeypatch):
    def no_urandom(n):
        raise AssertionError("a span made a system call for its id")

    monkeypatch.setattr(os, "urandom", no_urandom)
    t = Tracer(cpu_every=1)
    spans = []
    with t.span("t.parent") as parent:
        for _ in range(50_000):
            with t.span("t.child") as sp:
                spans.append(sp)
    for _ in range(50_000):
        with t.span("t.root") as sp:
            spans.append(sp)
    assert re.fullmatch(r"[0-9a-f]{32}", parent.trace_id)
    assert re.fullmatch(r"[0-9a-f]{16}", parent.span_id)
    assert len({s.span_id for s in spans}) == len(spans) == 100_000
    assert all(len(s.span_id) == 16 for s in spans)
    children, roots = spans[:50_000], spans[50_000:]
    # a child keeps its parent's trace id; every root starts a trace
    assert {s.trace_id for s in children} == {parent.trace_id}
    assert {s.parent_id for s in children} == {parent.span_id}
    assert len({s.trace_id for s in roots}) == 50_000
    assert all(len(s.trace_id) == 32 and s.parent_id is None for s in roots)
    assert re.fullmatch(r"[0-9a-f]{32}", tracing.new_trace_id())
    assert re.fullmatch(r"[0-9a-f]{16}", tracing.new_span_id())


def test_a_span_starts_on_the_anchored_clock():
    before = time.time()
    with Tracer(cpu_every=1).span("t.start") as sp:
        pass
    doc = sp.to_json()
    assert doc["start"] == doc["ts"] == sp.start_perf + tracing._PERF_EPOCH
    assert abs(doc["start"] - before) < 5.0


# ------------------------------------------------- the ledger's admission wait
def _admit_spans():
    return [s for s in GLOBAL_TRACER.recent(4096) if s["name"] == "executor.groupby.admit"]


def test_admit_opens_its_span_only_when_it_waits():
    with GLOBAL_TRACER._lock:
        GLOBAL_TRACER._spans.clear()
    ledger = GroupByLedger(NopStats())
    first = ledger.admit(60, budget=100)
    second = ledger.admit(30, budget=100)  # fits beside the first
    assert _admit_spans() == []
    threading.Timer(0.03, ledger.release, args=(first,)).start()
    third = ledger.admit(50, budget=100)  # 60 + 30 + 50: waits for a release
    spans = _admit_spans()
    assert spans and all(s["tags"] == {"bytes": 50} for s in spans)
    assert sum(s["durationSeconds"] for s in spans) >= 0.02
    assert sum(s["cpuSeconds"] or 0.0 for s in spans) < 0.02  # a wait, off the CPU
    assert ledger.held == 80
    ledger.release(second)
    ledger.release(third)
    assert ledger.held == 0
