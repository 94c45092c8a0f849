"""The host-spans reduction (harness/host_spans.py) and its two readers,
by hand like the other tests here: on synthetic planes whose answer can
be worked out on paper, and on the small recording from the chip
(tiny_spans.xplane.pb, made by record_tiny_spans.py)."""

import os

import pytest

from benchmark.harness import host_spans as hs
from benchmark.readers import host_spans as span_reader
from benchmark.readers import module_device_ms, prom_family

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # ns


def host(*threads):
    return {"name": hs.HOST_PLANE, "lines": [
        {"name": "python", "events": [(n, s * MS, (e - s) * MS) for n, s, e in t]} for t in threads]}


def device(ops, modules=()):
    ev = lambda rows: [(n, s * MS, (e - s) * MS) for n, s, e in rows]
    return {"name": "/device:TPU:0", "lines": [{"name": hs.OP_LINE, "events": ev(ops)},
                                               {"name": hs.MODULE_LINE, "events": ev(modules)}]}


def test_interval_arithmetic():
    a = hs.union([(5, 9), (0, 2), (1, 3), (9, 9)])
    assert a == [(0, 3), (5, 9)]
    assert hs.intersect(a, [(2, 6), (8, 20)]) == [(2, 3), (5, 6), (8, 9)]
    assert hs.subtract([(0, 10)], a) == [(3, 5), (9, 10)]
    assert hs.subtract(a, [(0, 10)]) == []
    assert hs.length(a) == 7


def test_a_thread_is_in_its_innermost_span():
    segs = hs.innermost([(0, 100, "pql.query"), (10, 60, "scheduler.await"),
                         (20, 30, "executor.TopN"), (30, 40, "executor.Sum"), (70, 101, "pql.reply")])
    assert segs == [(0, 10, "pql.query"), (10, 20, "scheduler.await"), (20, 30, "executor.TopN"),
                    (30, 40, "executor.Sum"), (40, 60, "scheduler.await"), (60, 70, "pql.query"),
                    (70, 100, "pql.reply")]  # the child's overrun is cut at its parent's end


def test_an_idle_gap_goes_to_the_first_category_any_thread_is_in():
    # device busy 0-10 and 90-100: one idle gap of 80 ms
    leader = [("http.query", 0, 100), ("pql.query", 1, 95), ("scheduler.await", 5, 80),
              ("scheduler.window", 6, 10), ("scheduler.wave", 10, 78), ("executor.TopN", 10, 14),
              ("scheduler.readback", 20, 70), ("readback.join", 20, 60), ("readback.transfer", 60, 70),
              ("pql.reply", 95, 99), ("foreign.event", 0, 100)]
    follower = [("http.query", 0, 100), ("pql.query", 0, 100), ("scheduler.await", 2, 85)]
    out = hs.reduce_planes([host(leader, follower), device(
        [("%fusion", 0, 10), ("%fusion", 90, 100)],
        [("jit_pilosa_topn(123)", 0, 10), ("jit_pilosa_topn_filtered(77)", 90, 96), ("jit_pilosa_sum(5)", 96, 100)])])
    by = {k: round(v * 1e3, 6) for k, v in out["idle_by"].items() if v}
    assert by == {
        "executor.*": 4,          # 10-14
        "scheduler.wave": 14,     # 14-20 between dispatches, 70-78 at settle
        "readback.join": 40,      # 20-60: the follower's await does not take it
        "readback.transfer": 10,  # 60-70
        "scheduler.await": 2,     # 78-80: both threads wait
        "pql.query": 10,          # 80-90: the leader's self time wins over the follower's await
    }
    assert sum(out["idle_by"].values()) == pytest.approx(out["idle_s"]) == pytest.approx(0.080)
    assert out["spans"]["readback.join"] == {"count": 1, "total_s": 0.040, "mean_ms": 40.0}
    assert out["spans"]["scheduler.await"]["count"] == 2
    assert out["spans"]["executor.*"]["count"] == 1 and "foreign.event" not in out["spans"]
    assert out["modules"]["jit_pilosa_topn"] == {"launches": 1, "total_s": 0.010, "mean_ms": 10.0}
    assert set(out["modules"]) == {"jit_pilosa_topn", "jit_pilosa_topn_filtered", "jit_pilosa_sum"}

    ctx = {"host_spans": out}
    share = lambda *spans: span_reader.read({"stat": "idle_share_pct", "spans": list(spans)}, ctx)
    assert share("readback.join", "readback.transfer") == pytest.approx(62.5)
    assert share(*hs.PRIORITY, hs.NO_SPAN) == pytest.approx(100.0)
    assert share("stack.pack") == 0.0  # spans there, none of this kind: a share of 0 is read
    assert span_reader.read({"stat": "mean_ms", "span": "scheduler.window"}, ctx) == pytest.approx(4.0)
    assert span_reader.read({"stat": "mean_ms", "span": "stack.pack"}, ctx) is None
    # a prefix is a call type: both TopN programs, 16 ms over 2 launches
    assert module_device_ms.read({"prefix": "jit_pilosa_topn"}, ctx) == pytest.approx(8.0)
    assert module_device_ms.read({"prefix": "jit_pilosa_count"}, ctx) is None


def test_waiting_takes_a_gap_only_when_no_thread_works():
    out = hs.reduce_planes([
        host([("pql.query", 0, 50), ("scheduler.await", 10, 40), ("scheduler.window", 12, 20)],
             [("scheduler.await", 15, 45)]),
        device([("%op", 0, 10), ("%op", 60, 70)])])
    by = {k: round(v * 1e3, 6) for k, v in out["idle_by"].items() if v}
    # 10-12 await, 12-20 window (ranked before await), 20-40 await, 40-50 pql.query's self
    # time (it wins over the other thread's await), 50-60 no thread in any span
    assert by == {"scheduler.window": 8, "scheduler.await": 22, "pql.query": 10, "no_span": 10}


def test_a_trace_without_the_programs_spans_gives_nothing_not_zero():
    out = hs.reduce_planes([host([("PjitFunction(<lambda>)", 0, 5)]),
                            device([("%op", 0, 10), ("%op", 60, 70)], [("jit__lambda(9)", 0, 10)])])
    assert out["idle_s"] == pytest.approx(0.050) and out["idle_by"] is None and out["spans"] == {}
    ctx = {"host_spans": out}
    assert span_reader.read({"stat": "idle_share_pct", "spans": ["no_span"]}, ctx) is None
    assert span_reader.read({"stat": "mean_ms", "span": "readback.join"}, ctx) is None
    assert module_device_ms.read({"prefix": "jit_pilosa_topn"}, ctx) is None
    assert module_device_ms.read({"prefix": "jit__lambda"}, ctx) == pytest.approx(10.0)
    # no device plane (a CPU rehearsal): spans are read, the device's idle time is not
    out = hs.reduce_planes([host([("readback.join", 0, 5)])])
    assert out["idle_s"] is None and out["idle_by"] is None
    assert span_reader.read({"stat": "mean_ms", "span": "readback.join"}, {"host_spans": out}) == 5.0
    # no trace at all (an untraced run has no trace directory)
    assert span_reader.reduction({"log_path": os.path.join(HERE, "no_such_dir", "server.log")}) is None


def test_prom_family_gives_nothing_for_a_family_the_program_lacks():
    scrapes = {"window_start": {"metrics": {"xla_compile_seconds_count": {'site="a"': 2.0}}},
               "window_end": {"metrics": {"xla_compile_seconds_count": {'site="a"': 5.0, 'site="b"': 4.0}}}}
    ctx = {"scrapes": scrapes}
    assert prom_family.read({"stat": "sum", "family": "xla_compile_seconds_count"}, ctx) == 7.0
    assert prom_family.read({"stat": "sum", "family": "stack_pack_seconds_sum"}, ctx) is None
    assert prom_family.read({"stat": "ratio", "family": "xla_compile_seconds_count", "labels": 'site="b"',
                             "of": [{"family": "xla_compile_seconds_count"}]}, ctx) == pytest.approx(4 / 7)


def test_the_recording_from_the_chip_reads_as_it_was_made():
    path = os.path.join(HERE, "tiny_spans.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace beside the tests")
    out = hs.reduce_planes(hs.read_xplane(path))
    assert out["devices"] == 1
    # five rounds (record_tiny_spans.py); the follower doubles http/pql/await
    for name, n in (("readback.join", 5), ("readback.transfer", 5), ("executor.*", 10),
                    ("scheduler.await", 10), ("pql.query", 10), ("pql.reply", 5)):
        assert out["spans"][name]["count"] == n, name
    assert {"jit_pilosa_topn", "jit_pilosa_sum", "jit_pilosa_wave_join"} <= set(out["modules"])
    assert all(out["modules"][m]["launches"] == 5 for m in ("jit_pilosa_topn", "jit_pilosa_sum"))
    assert sum(out["idle_by"].values()) == pytest.approx(out["idle_s"])
    ms = {k: v * 1e3 for k, v in out["idle_by"].items()}
    # per round 5 ms of pql.query's self time, 2 ms of pql.reply, 10 ms outside any span (less
    # at the ends: the device's span starts and ends with an op)
    assert 20 <= ms["pql.query"] <= 35 and 8 <= ms["pql.reply"] <= 14 and 38 <= ms["no_span"] <= 56
    # the follower's await covers every readback and never takes it
    assert ms["scheduler.await"] < 1.0
