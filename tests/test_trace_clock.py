"""The program's spans on the device trace's clock (ISSUE 26).

One Tracer, two sinks: the in-memory ring (``/debug/traces``, flight
recorder) and, while a ``jax.profiler`` session records, the trace's
``/host:CPU`` plane.  Plus what reads the new spans: the compile counter
with its site (utils/xlaevents.py), the stack pack/upload timers, the
readback split, and the program names (``named_jit``).

Every profiler session of the suite is in this file, opened inside a
test (never at import) under the options benchmark/harness/serve.py
uses, on the CPU backend.
"""

import ast
import glob
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import FIELD_INT, FieldOptions
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import compile as query_compile
from pilosa_tpu.executor.scheduler import WaveScheduler, fetch_wave
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import xlaevents
from pilosa_tpu.utils.stats import StatsClient
from pilosa_tpu.utils.tracing import GLOBAL_TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ helpers
def _record(tmp_path, body):
    """Run ``body()`` under a profiler session; the .xplane.pb's planes."""
    opts = jax.profiler.ProfileOptions()  # as benchmark/harness/serve.py
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    return jax.profiler.ProfileData.from_file(path)


def _host_events(data, names):
    """{name: [(line, start_ns, end_ns, stats)]} of /host:CPU; a line is
    one thread's (they are told apart by position: names repeat)."""
    out = {n: [] for n in names}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in out:
                    out[e.name].append(
                        (
                            i,
                            e.start_ns,
                            e.start_ns + e.duration_ns,
                            dict(e.stats),
                        )
                    )
    return out


def _rig(stats):
    rng = np.random.default_rng(26)
    h = Holder(None)
    idx = h.create_index("t")
    f = idx.create_field("f")
    v = idx.create_field(
        "v", FieldOptions(field_type=FIELD_INT, min=0, max=1000)
    )
    n = 3000
    cols = rng.integers(0, 2 * SHARD_WIDTH, n).astype(np.uint64)
    f.import_bulk(rng.integers(0, 5, n).astype(np.uint64), cols)
    vcols = np.unique(cols)
    v.import_values(vcols, rng.integers(0, 1000, vcols.size).astype(np.int64))
    idx.mark_columns_exist(cols)
    free = next(c for c in range(SHARD_WIDTH) if c not in set(cols.tolist()))
    return Executor(h, stats=stats, route_mode="device"), free


def _ring(name):
    return [s for s in GLOBAL_TRACER.recent(4096) if s["name"] == name]


@pytest.fixture(autouse=True)
def empty_ring():
    """The tests below count a span's occurrences in the ring before and
    after.  A ring that earlier tests of this worker filled (4096 spans)
    drops an old span for every new one, and the counts stand still: so
    every test starts with room."""
    with GLOBAL_TRACER._lock:
        GLOBAL_TRACER._spans.clear()


@pytest.fixture
def stats():
    """A fresh registry behind the process-wide compile listener."""
    client = StatsClient()
    xlaevents.set_stats(client)
    yield client
    xlaevents.set_stats(None)


def _count(client, family, **tags):
    hist = client.histogram(family, tags)
    return hist.count if hist is not None else 0


# ---------------------------------------------- (a) spans on the trace's clock
def test_spans_land_in_host_plane_on_two_threads(tmp_path):
    names = ("clock.parent", "clock.child", "clock.other")

    def other():
        with GLOBAL_TRACER.span("clock.other", n=2) as sp:
            time.sleep(0.005)
            sp.set_tag("reason", "late")

    def body():
        t = threading.Thread(target=other, name="clock-other")
        with GLOBAL_TRACER.span("clock.parent", index="i", wave=7):
            t.start()
            time.sleep(0.002)
            with GLOBAL_TRACER.span("clock.child", skipped={"not": "scalar"}):
                time.sleep(0.002)
            t.join()

    found = _host_events(_record(tmp_path, body), names)
    assert all(len(found[n]) == 1 for n in names), found
    parent, child, oth = (found[n][0] for n in names)
    # the child sits inside the parent's interval, on the same line; the
    # other thread's span is on a line of its own
    assert parent[1] <= child[1] and child[2] <= parent[2]
    assert parent[0] == child[0] != oth[0]
    # identity and scalar tags are event stats; a non-scalar tag is not
    ring = {n: _ring(n)[-1] for n in names}
    for n, ev in zip(names, (parent, child, oth)):
        assert ev[3]["trace_id"] == ring[n]["traceID"]
        assert ev[3]["span_id"] == ring[n]["spanID"]
    assert parent[3]["index"] == "i" and parent[3]["wave"] == 7
    assert "skipped" not in child[3]
    assert oth[3]["n"] == 2 and oth[3]["reason"] == "late"  # set inside the body
    # the same spans are in the ring, child parented onto parent
    assert ring["clock.child"]["parentSpanID"] == ring["clock.parent"]["spanID"]
    assert ring["clock.child"]["traceID"] == ring["clock.parent"]["traceID"]
    assert ring["clock.other"]["traceID"] != ring["clock.parent"]["traceID"]


def test_span_without_session_records_ring_only():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with GLOBAL_TRACER.span("clock.nosession", k=1) as sp:
        assert GLOBAL_TRACER.current_name() == "clock.nosession"
    assert GLOBAL_TRACER.current_name() is None
    assert _ring("clock.nosession")[-1]["spanID"] == sp.span_id


# ----------------------------------------------------- (b) jax stays optional
def test_tracing_alone_never_imports_jax():
    code = (
        "import sys\n"
        "import pilosa_tpu.utils.tracing as t\n"
        "assert 'jax' not in sys.modules\n"
        "with t.GLOBAL_TRACER.span('a.b', k=1) as s:\n"
        "    assert t.GLOBAL_TRACER.current_name() == 'a.b'\n"
        "    s.set_tag('late', 2)\n"
        "assert t.GLOBAL_TRACER.recent(1)[0]['tags'] == {'k': 1, 'late': 2}\n"
        "assert 'jax' not in sys.modules\n"
        "print('ok')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True
    )
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr


# ------------------------------------------- (c) the compile counter's site
def test_compile_counted_once_at_its_site(stats):
    fn = query_compile.named_jit("pilosa_clock_probe", lambda x: x * 3 + 1)
    x = jnp.arange(26, dtype=jnp.int32)
    tags = {"site": "readback.join", "program": "pilosa_clock_probe"}
    with GLOBAL_TRACER.span("readback.join"):
        fn(x).block_until_ready()
    assert _count(stats, "xla_compile_seconds", **tags) == 1
    lowered = _count(stats, "xla_lower_seconds", site="readback.join")
    assert lowered >= 2  # the jaxpr trace and the lowering to MLIR
    with GLOBAL_TRACER.span("readback.join"):
        fn(x).block_until_ready()  # same shape: no trace, no compile
    assert _count(stats, "xla_compile_seconds", **tags) == 1
    assert _count(stats, "xla_lower_seconds", site="readback.join") == lowered
    # outside any span the site is "none"; in /metrics both labels show
    fn(jnp.arange(27, dtype=jnp.int32)).block_until_ready()
    assert (
        _count(
            stats, "xla_compile_seconds", site="none", program="pilosa_clock_probe"
        )
        == 1
    )
    text = stats.prometheus()
    assert (
        'pilosa_tpu_xla_compile_seconds_count{program="pilosa_clock_probe",'
        'site="readback.join"} 1' in text
    )


def test_cache_lookup_events_are_counted(stats):
    xlaevents._on_event("/jax/compilation_cache/cache_hits")
    xlaevents._on_event("/jax/compilation_cache/cache_misses")
    xlaevents._on_event("/jax/compilation_cache/cache_misses")
    xlaevents._on_event("/jax/compilation_cache/tasks_using_cache")  # not ours
    counters = stats.expvar()["counters"]
    assert counters["xla_cache_lookups{result=hit}"] == 1
    assert counters["xla_cache_lookups{result=miss}"] == 2
    assert len(counters) == 2


# ------------------------------------------------- (d) stack pack and upload
def test_stack_build_is_timed_and_spanned(stats):
    e, free = _rig(stats)
    assert e.execute("t", "Count(Row(f=1))")[0] > 0
    assert _count(stats, "stack_pack_seconds") == 1
    assert _count(stats, "stack_upload_seconds") == 1
    pack, upload = _ring("stack.pack")[-1], _ring("stack.upload")[-1]
    for sp in (pack, upload):
        assert sp["tags"]["field"] == "f" and sp["tags"]["shards"] == 2
        assert sp["tags"]["bytes"] == sp["tags"]["rows"] * 2 * (SHARD_WIDTH // 8)
    assert pack["ts"] + pack["durationSeconds"] <= upload["ts"] + 1e-6
    # a cache hit packs nothing; a point write rides the delta span
    e.execute("t", "Count(Row(f=2))")
    assert _count(stats, "stack_pack_seconds") == 1
    before = len(_ring("stack.delta"))
    e.execute("t", f"Set({free}, f=1)")
    e.execute("t", "Count(Row(f=1))")
    delta = _ring("stack.delta")
    assert len(delta) == before + 1
    assert delta[-1]["tags"]["field"] == "f" and delta[-1]["tags"]["rows"] >= 1
    assert _count(stats, "stack_pack_seconds") == 1  # no restack


# ------------------------------------------------------- (e) readback split
def test_wave_readback_is_split_under_the_scheduler(stats):
    e, _ = _rig(stats)
    sched = WaveScheduler(lambda: e, stats=stats, mode="always", window_us=20000)
    queries = ["TopN(f, n=3)", "Sum(field=v)"]
    want = [e.execute("t", q) for q in queries]
    marks = {n: len(_ring(n)) for n in (
        "scheduler.readback", "readback.join", "readback.transfer",
        "scheduler.window", "scheduler.await", "scheduler.wave")}
    got = [None, None]

    def run(i):
        got[i] = sched.execute("t", queries[i])

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == want
    new = {n: _ring(n)[marks[n]:] for n in marks}
    assert len(new["scheduler.await"]) == 2
    if len(new["scheduler.wave"]) == 1:  # both rode one wave (the usual case)
        (rb,) = new["scheduler.readback"]
        (join,), (transfer,) = new["readback.join"], new["readback.transfer"]
        assert rb["tags"]["wave"] == new["scheduler.wave"][0]["spanID"]
        assert rb["tags"]["arrays"] == join["tags"]["arrays"] == 4  # 1 + 3
        assert join["parentSpanID"] == transfer["parentSpanID"] == rb["spanID"]
        assert transfer["tags"]["bytes"] > 0
        assert new["scheduler.window"][0]["tags"]["reason"] in ("timeout", "full")
    else:  # the window missed the second arrival: one readback per wave
        assert len(new["scheduler.readback"]) == len(new["readback.join"]) == 2


class _FakePending:
    def __init__(self, arrays):
        self.arrays, self.fetched = arrays, None


def test_fetch_wave_two_pendings_one_join_one_transfer():
    big = 2**31 + 5  # a uint32 count the host cast must not wrap
    a = _FakePending([jnp.arange(6, dtype=jnp.int32).reshape(2, 3)])
    b = _FakePending([
        jnp.ones(4, jnp.int64),
        jnp.asarray(7, jnp.int64),
        jnp.asarray([1, big], jnp.uint32),
        np.arange(3, dtype=np.uint16),  # already on the host: nothing crosses
    ])
    marks = len(_ring("readback.join")), len(_ring("readback.transfer"))
    with GLOBAL_TRACER.span("scheduler.readback") as rb:
        fetch_wave([a, b])
    joins = _ring("readback.join")[marks[0]:]
    transfers = _ring("readback.transfer")[marks[1]:]
    assert len(joins) == len(transfers) == 1
    assert joins[0]["parentSpanID"] == transfers[0]["parentSpanID"] == rb.span_id
    assert joins[0]["tags"]["arrays"] == 5
    # what crossed, in the arrays' own dtypes: int32[2,3], int64[4], int64[], uint32[2]
    assert transfers[0]["tags"]["bytes"] == 6 * 4 + 4 * 8 + 8 + 2 * 4
    for p in (a, b):
        assert [f.shape for f in p.fetched] == [np.shape(x) for x in p.arrays]
        assert all(type(f) is np.ndarray and f.dtype == np.int64 for f in p.fetched)
    assert a.fetched[0].tolist() == [[0, 1, 2], [3, 4, 5]]
    assert b.fetched[0].tolist() == [1] * 4 and int(b.fetched[1]) == 7
    assert b.fetched[2].tolist() == [1, big] and b.fetched[3].tolist() == [0, 1, 2]
    # one pending with one array: the same two spans
    c = _FakePending([jnp.arange(3)])
    fetch_wave([c])
    assert len(_ring("readback.join")) == marks[0] + 2
    assert c.fetched[0].tolist() == [0, 1, 2]


def test_fetch_wave_compiles_nothing_for_new_sequences_of_sizes(stats):
    """Settling a wave runs no XLA program: twenty sequences of result
    sizes never seen before leave the compile counter (all sites) where
    it was — the parent compiled one join per sequence."""
    rng = np.random.default_rng(27)
    waves = []
    for k in range(20):
        sizes = rng.integers(1, 40, size=2 + k % 5).tolist() + [100 + k]
        waves.append([
            _FakePending([jnp.arange(n, dtype=(jnp.int32, jnp.int64)[n % 2])])
            for n in sizes
        ])
    jax.block_until_ready([p.arrays for w in waves for p in w])
    before = _count(stats, "xla_compile_seconds"), _count(stats, "xla_lower_seconds")
    for w in waves:
        with GLOBAL_TRACER.span("scheduler.readback"):
            fetch_wave(w)
    assert (_count(stats, "xla_compile_seconds"), _count(stats, "xla_lower_seconds")) == before
    assert 'site="readback.join"' not in stats.prometheus()
    for w in waves:
        for p in w:
            assert p.fetched[0].dtype == np.int64
            assert p.fetched[0].tolist() == list(range(p.arrays[0].size))


def test_wave_of_two_queries_settles_without_a_compile(stats):
    """Through the scheduler: one wave, one ``scheduler.readback`` with one
    ``readback.join`` and one ``readback.transfer`` under it, and between
    the dispatches' end and the answers no compile at any site."""
    e, _ = _rig(stats)
    sched = WaveScheduler(lambda: e, stats=stats, mode="always", window_us=200000,
                          max_queries=2)
    queries = ["TopN(f, n=3)", "Sum(Row(f=1), field=v)"]
    want = [e.execute("t", q) for q in queries]  # compiles the dispatch programs
    names = ("scheduler.wave", "scheduler.readback", "readback.join", "readback.transfer")
    marks = {n: len(_ring(n)) for n in names}
    before = _count(stats, "xla_compile_seconds")
    got = [None, None]

    def run(i):
        got[i] = sched.execute("t", queries[i])

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == want
    assert _count(stats, "xla_compile_seconds") == before
    new = {n: _ring(n)[marks[n]:] for n in names}
    (wave,), (rb,) = new["scheduler.wave"], new["scheduler.readback"]
    (join,), (transfer,) = new["readback.join"], new["readback.transfer"]
    assert wave["tags"]["queries"] == 2 and wave["tags"]["reason"] == "full"
    assert rb["tags"]["wave"] == wave["spanID"]
    assert join["parentSpanID"] == transfer["parentSpanID"] == rb["spanID"]
    assert join["tags"]["arrays"] == rb["tags"]["arrays"] == 4  # 1 + 3


def test_fetch_wave_brings_mesh_results_back_whole():
    """The mesh route's arrays live on several devices: a result sharded
    over the mesh and one replicated on it come back whole, int64, and a
    replicated one crosses once."""
    from jax.sharding import NamedSharding, PartitionSpec

    from pilosa_tpu.parallel.mesh import AXIS_SHARDS, make_mesh

    devices = jax.devices()[:2]
    assert len(devices) == 2  # conftest forces eight host devices
    mesh = make_mesh(devices)
    rows = np.arange(8 * 3, dtype=np.int32).reshape(8, 3)
    sharded = jax.device_put(rows, NamedSharding(mesh, PartitionSpec(AXIS_SHARDS)))
    replicated = jax.device_put(
        np.asarray([5, 2**31 + 9], np.uint32), NamedSharding(mesh, PartitionSpec())
    )
    assert not sharded.is_fully_replicated and replicated.is_fully_replicated
    assert len(sharded.addressable_shards) == len(replicated.addressable_shards) == 2
    p = _FakePending([sharded, replicated, jnp.asarray(3, jnp.int64)])
    mark = len(_ring("readback.transfer"))
    fetch_wave([p])
    assert [f.dtype for f in p.fetched] == [np.int64] * 3
    assert p.fetched[0].tolist() == rows.tolist()
    assert p.fetched[1].tolist() == [5, 2**31 + 9] and int(p.fetched[2]) == 3
    assert _ring("readback.transfer")[mark]["tags"]["bytes"] == rows.nbytes + 2 * 4 + 8


# --------------------------------------------------- (f) the programs' names
def _module_name(prog, *args):
    head = prog.lower(*args).as_text().split("\n", 1)[0]
    return head.split("@", 1)[1].split(" ", 1)[0]


def test_every_program_of_a_query_mix_is_named():
    e, _ = _rig(None)
    for q in (
        "Row(f=1)", "Count(Intersect(Row(f=1), Row(f=2)))", "TopN(f, n=2)",
        "TopN(f, Row(f=1), n=2)", "TopN(f, ids=[0, 1])", "Sum(field=v)",
        "Sum(Row(f=1), field=v)", "Min(field=v)", "Max(Row(f=1), field=v)",
        "GroupBy(Rows(f))", "GroupBy(Rows(f), aggregate=Sum(field=v))",
        "Count(Row(v > 10))",
    ):
        e.execute("t", q)
    names = set()
    for prog in e.compiler._programs.values():
        wrapped = getattr(prog, "__wrapped__", None)
        names.add(getattr(wrapped, "__name__", "?"))
    assert names and all(n.startswith("pilosa_") for n in names), names
    assert {"pilosa_words", "pilosa_count", "pilosa_topn", "pilosa_topn_filtered",
            "pilosa_topn_ids", "pilosa_sum", "pilosa_sum_filtered",
            "pilosa_minmax", "pilosa_minmax_filtered"} <= names


@pytest.mark.parametrize(
    "prog, args, want",
    [
        (query_compile._apply_stack_delta,
         (np.zeros((2, 2, 8), np.uint32), np.zeros((1, 2), np.int32),
          np.zeros((1, 8), np.uint32)), "jit_pilosa_stack_delta"),
        (query_compile._scatter_rows,
         (np.zeros((2, 8), np.uint32), np.zeros((1,), np.int32),
          np.zeros((1, 8), np.uint32)), "jit_pilosa_scatter_rows"),
    ],
)
def test_module_level_programs_lower_under_their_names(prog, args, want):
    assert _module_name(prog, *args) == want


def test_wave_join_and_mesh_programs_lower_under_their_names():
    """The wave's join is no program any more (ISSUE 27): the scheduler
    has nothing to build one with; the mesh route's programs keep their names."""
    from pilosa_tpu.executor import scheduler

    assert not {"_wave_join", "named_jit", "jnp"} & set(vars(scheduler))
    from pilosa_tpu.parallel.mesh import MeshQueryEngine, make_mesh

    eng = MeshQueryEngine(make_mesh(jax.devices()[:2]))
    mode = eng.spec_mode(2, 1 << 11)
    prog = eng.topn_tree(mode, False, False)
    assert _module_name(prog, np.zeros((3, 2, 1 << 11), np.uint32)) == "jit_pilosa_mesh_topn"


def test_no_bare_jit_under_executor():
    """Every ``jax.jit`` under executor/ is the one inside named_jit (an
    AST walk in the style of tools/analysis: attribute or bare name, call
    or decorator alike)."""
    bare = []
    for path in sorted(glob.glob(os.path.join(ROOT, "pilosa_tpu", "executor", "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        allowed = set()
        for fn in ast.walk(tree):
            if isinstance(fn, ast.FunctionDef) and fn.name == "named_jit":
                allowed = {id(n) for n in ast.walk(fn)}
        for node in ast.walk(tree):
            is_jit = (
                isinstance(node, ast.Attribute)
                and node.attr in ("jit", "pjit")
                and isinstance(node.value, ast.Name)
                and node.value.id == "jax"
            ) or (isinstance(node, ast.Name) and node.id in ("jit", "pjit"))
            if is_jit and id(node) not in allowed:
                bare.append(f"{os.path.relpath(path, ROOT)}:{node.lineno}")
    assert not bare, bare
