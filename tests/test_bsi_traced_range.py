"""A BSI comparison constant is an operand of the program (PR 32).

``Row(f > v)`` and ``Row(lo < f < hi)`` used to close over the Python
integer: every new threshold was a new structure key, a new trace and a
new XLA program. Now the constant travels as ``ops.bsi.constant_words``
through the planner's traced scalars, as a row id does. Held here:

(a) ``ops.bsi.compare``/``between`` under ``jax.jit`` with the constant as
    an ARGUMENT equal a plain numpy comparison of the decoded values, for
    every operator, sign and depth, beyond-depth constants included, and
    one trace serves every constant;
(b) after one query a template, 200 distinct thresholds build no program
    on the device and the mesh route, and the host engine's plan memo
    stays within its bound;
(c) the host, device and mesh engines equal the benchmark's plain
    reference (``benchmark/datasets/taxi_range.py``) on the served path;
(d) the two counters move as docs/observability.md says.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [p for p in (HERE, os.path.dirname(HERE)) if p not in sys.path]

from benchmark.datasets import taxi_range  # noqa: E402
from benchmark.harness import pql as bench_pql, traffic  # noqa: E402
from benchmark.harness.server import Client  # noqa: E402
from benchmark.readers import prom_family  # noqa: E402
from benchmark.run import HERE as BENCH_DIR, load_json  # noqa: E402
from pilosa_tpu import ops  # noqa: E402
from pilosa_tpu.executor.compile import range_suffix  # noqa: E402
from test_taxi_mesh import SEED, ask, boot, change, metrics  # noqa: E402

SPEC = traffic.load("adhoc_range")
TEMPLATES = [t["name"] for t in SPEC["templates"]]
ROUTES = ["host", "device", "mesh"]
FIELD = "total_amount_dollars"
OPS = ["==", "!=", "<", "<=", ">", ">="]


# ------------------------------------------------- (a) the kernels, traced
def slices_of(values: np.ndarray, null: np.ndarray, depth: int) -> np.ndarray:
    """Sign-magnitude bit slices uint32[2 + depth, W] of int values (object
    dtype, so 63 bits and more are exact); ``null`` columns hold none."""
    def pack(bits):
        return np.packbits(np.asarray(bits, dtype=bool), bitorder="little").view(np.uint32)

    mags = [abs(int(v)) for v in values]
    rows = [pack(~null), pack([v < 0 and not n for v, n in zip(values, null)])]
    rows += [pack([(m >> k) & 1 and not n for m, n in zip(mags, null)]) for k in range(depth)]
    return np.stack(rows)


def decoded(words: np.ndarray, n: int) -> np.ndarray:
    return np.unpackbits(np.asarray(words).view(np.uint8), bitorder="little")[:n].astype(bool)


def constants(depth: int) -> list[int]:
    top = 1 << depth
    return [-(top - 1), -3, -1, 0, 1, 2, top // 2, top - 1, top, top + 5, 1 << 70,
            -top, -top - 7, -(1 << 70)]


def column_values(depth: int, n: int = 256):
    rng = np.random.default_rng(depth)
    top = 1 << depth
    drawn = [int(rng.integers(0, min(top, 1 << 62))) * (1 if rng.random() < 0.6 else -1)
             for _ in range(n)]
    fixed = [v for v in (0, 1, -1, 2, -3, top // 2) if abs(v) < top]
    drawn = drawn[: n - len(fixed) - 2]
    values = np.array(fixed + [top - 1, -(top - 1)] + drawn, dtype=object)
    return values, rng.random(n) < 0.2


@pytest.mark.parametrize("depth", [1, 11, 17, 40, 63])
@pytest.mark.parametrize("op", OPS + ["between"])
def test_traced_constants_equal_numpy_at_every_depth(op, depth):
    values, null = column_values(depth)
    slices = jax.numpy.asarray(slices_of(values, null, depth))
    cmp = {"==": lambda v, c: v == c, "!=": lambda v, c: v != c, "<": lambda v, c: v < c,
           "<=": lambda v, c: v <= c, ">": lambda v, c: v > c, ">=": lambda v, c: v >= c}
    if op == "between":
        fn = jax.jit(lambda s, lo, hi: ops.bsi.between(s, lo, hi))
        cases = [(lo, hi) for lo in constants(depth)[::2] for hi in constants(depth)[1::2]]
    else:
        fn = jax.jit(lambda s, c: ops.bsi.compare(s, op, c))
        cases = [(c,) for c in constants(depth)]
    for case in cases:
        got = decoded(fn(slices, *(ops.bsi.constant_words(c) for c in case)), len(values))
        if op == "between":
            want = [not n and case[0] <= v <= case[1] for v, n in zip(values, null)]
        else:
            want = [not n and cmp[op](v, case[0]) for v, n in zip(values, null)]
        assert got.tolist() == want, (op, depth, case)
    # the constant is data: one trace, one program, whatever it was
    assert fn._cache_size() == 1
    # a Python int is encoded in place and gives the same mask
    c = cases[3]
    direct = ops.bsi.between(slices, *c) if op == "between" else ops.bsi.compare(slices, op, *c)
    assert np.array_equal(np.asarray(direct),
                          np.asarray(fn(slices, *(ops.bsi.constant_words(x) for x in c))))


def test_constant_words_are_four_small_non_negative_words_for_any_int():
    for c in (0, 5, -5, (1 << 63) - 1, -(1 << 63) + 1, 1 << 63, -(1 << 64), 1 << 200):
        w = ops.bsi.constant_words(c)
        assert w.dtype == np.int32 and w.shape == (ops.bsi.CONSTANT_WORDS,)
        assert (w >= 0).all() and (w[1:] < 1 << 21).all()
        assert bool(w[0] & 1) == (c < 0) and bool(w[0] & 2) == (abs(c) >= 1 << 63)
        if not w[0] & 2:
            assert sum(int(x) << (21 * j) for j, x in enumerate(w[1:])) == abs(c)


def test_programs_with_a_condition_leaf_are_named_for_it():
    assert range_suffix("cmp[>](bsi(total_amount_dollars:17))") == "_range"
    assert range_suffix("Intersect(row(m:y/standard),between(bsi(f:17)))") == "_range"
    assert range_suffix("Intersect(row(m:y/standard),row(m:p/standard))") == ""
    assert range_suffix("notnull(bsi(f:17))") == ""


# ------------------------------------------------------- the served engines
def cell_config(shards: int) -> dict:
    with open(os.path.join(BENCH_DIR, "configs", "taxi-128r.json")) as f:
        cfg = json.load(f)
    cfg["scale"]["shards"] = shards
    return cfg


def load(srv, cfg: dict) -> dict:
    """Schema and seeded data over the bulk route; → the reference's state."""
    base, index = f"http://127.0.0.1:{srv.port}", cfg["index"]
    c = Client(base)
    c.json(f"/index/{index}", b"{}")
    for fname, opts in taxi_range.schema(cfg):
        c.json(f"/index/{index}/field/{fname}", opts)
    c.close()
    return taxi_range.load_part(base, index, SEED, cfg, list(range(cfg["scale"]["shards"])))


@pytest.fixture(scope="module")
def rig(tmp_path_factory):
    """The benchmark's ``taxi-128r`` deployment at 8 shards of the tests'
    width, one server a route over the same seeded data, and the plain
    reference."""
    assert jax.local_device_count() > 1, "conftest gives the suite its virtual devices"
    cfg = cell_config(8)
    servers = {r: boot(tmp_path_factory.mktemp(r), r) for r in ROUTES}
    states = [load(s, cfg) for s in servers.values()]
    assert all(np.array_equal(s["hist"], states[0]["hist"]) for s in states)
    yield {"cfg": cfg, "index": cfg["index"], "servers": servers,
           "ref": taxi_range.Reference(cfg, states[:1])}
    for s in servers.values():
        s.close()


def client(srv) -> Client:
    return Client(f"http://127.0.0.1:{srv.port}")


# ------------------------------------- (c) three engines, one plain answer
@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("template", TEMPLATES)
def test_every_template_equals_the_reference_on_every_engine(rig, route, template):
    t = next(t for t in SPEC["templates"] if t["name"] == template)
    gen = traffic.Generator(SPEC, [SEED, len(template)])
    srv = rig["servers"][route]
    before = metrics(srv)
    c = client(srv)
    texts = [gen.render(t, {}) for _ in range(8)]
    for text in texts:
        assert ask(c, rig["index"], text) == rig["ref"].answer(bench_pql.parse(text)), text
    c.close()
    delta = change(before, metrics(srv))
    took = lambda path: delta("queries_routed", f'path="{path}"')
    assert took(route) + delta("queries_deduped") == len(texts)
    assert sum(took(p) for p in ROUTES) == took(route)


VALUES = (17, 3, 1026, 0, -5, 1 << 17, -(1 << 17), 1 << 70)
EDGES = [(f"{FIELD} {op} {v}", bench_pql.Cond(FIELD, op, v)) for op in OPS for v in VALUES]
EDGES += [(f"{lo} {lo_op} {FIELD} {hi_op} {hi}", bench_pql.Cond(FIELD, "between", (lo_op, lo, hi_op, hi)))
          for lo, lo_op, hi_op, hi in [(5, "<", "<", 90), (5, "<=", "<", 90), (5, "<", "<=", 90),
                                       (5, "<=", "<=", 90), (-9, "<=", "<=", 4), (-9, "<", "<", 3),
                                       (90, "<=", "<=", 5), (3, "<=", "<=", 1026),
                                       (-200000, "<", "<", 200000)]]


def with_condition(text: str, cond) -> "bench_pql.Call":
    """The reference's call for ``text`` with ``{c}`` standing for a
    condition: the benchmark's reader takes no minus sign, so the
    condition is put into the parsed tree by hand."""
    call = bench_pql.parse(text.replace("{c}", f"{FIELD} > 0"))

    def put(node):
        if node.cond is not None:
            node.cond = cond
        for child in node.children:
            put(child)

    put(call)
    return call


@pytest.mark.parametrize("route", ROUTES)
def test_every_operator_sign_and_beyond_depth_constant_equals_the_reference(rig, route):
    c = client(rig["servers"][route])
    for cond_text, cond in EDGES:
        for text in ("Count(Row({c}))",
                     "Sum(Intersect(Row(cab_type=0), Row({c})), field=" + FIELD + ")",
                     "TopN(pickup_year, Union(Row(passenger_count=2), Row({c})))",
                     "Count(Not(Row({c})))"):
            asked = text.replace("{c}", cond_text)
            assert ask(c, rig["index"], asked) == rig["ref"].answer(with_condition(text, cond)), asked
    c.close()


# --------------------- (b) thresholds build no program, the memo is bounded
def programs(srv) -> tuple[int, int]:
    """(programs the compiler holds, executables behind them)."""
    progs = list(srv.api.executor.compiler._programs.values())
    return len(progs), sum(p._cache_size() for p in progs if hasattr(p, "_cache_size"))


@pytest.mark.parametrize("route", ["device", "mesh"])
def test_after_one_query_a_template_distinct_thresholds_build_no_program(rig, route):
    srv, index = rig["servers"][route], rig["index"]
    c = client(srv)
    gen = traffic.Generator(SPEC, [SEED, 32])
    for _, text in gen.warmup():  # set-up's part: one query a template
        ask(c, index, text)
    before, m0 = programs(srv), metrics(srv)
    seen = set()
    for k in range(200):
        for t in SPEC["templates"]:
            fixed = {"t": (7 * k + 3) % 1024, "lo": k % 61, "hi": (5 * k) % 963}
            text = gen.render(t, {ph: fixed[ph] for ph in t["params"] if ph in fixed})
            seen.add(text)
            assert ask(c, index, text) == rig["ref"].answer(bench_pql.parse(text)), text
    c.close()
    assert len(seen) >= 5 * 190
    assert programs(srv) == before, f"{route}: a threshold built a program"
    delta = change(m0, metrics(srv))
    assert delta("xla_compile_seconds_count") == 0
    # /debug/vars shows the program cache's size beside the stack cache's
    snap = srv.api.executor.compiler.cache_snapshot()
    assert snap["programs"] == before[0] and "entries" in snap
    # the benchmark's two counters, by the readers their files name
    ctx = {"scrapes": {"window_start": {"metrics": m0}, "window_end": {"metrics": metrics(srv)}}}
    leaves = load_json(BENCH_DIR, "layer_metrics", "range_leaves_per_query.json")
    uploads = load_json(BENCH_DIR, "layer_metrics", "scalar_uploads_per_query.json")
    assert leaves["reader"] == uploads["reader"] == "prom_family"
    assert prom_family.read(leaves["params"], ctx) == 1.0
    assert 0.5 < prom_family.read(uploads["params"], ctx) <= 1.0


def test_the_host_plan_memo_stays_within_its_bound(rig, monkeypatch):
    srv, index = rig["servers"]["host"], rig["index"]
    host = srv.api.executor.compiler.host
    monkeypatch.setattr(host, "MAX_PLANS", 64)
    c = client(srv)
    for k in range(200):
        text = f"Count(Row({FIELD} > {3 + k}))"
        assert ask(c, index, text) == rig["ref"].answer(bench_pql.parse(text))
    c.close()
    assert 0 < len(host._plans) <= 64


# ------------------------------------------------------- (d) the counters
@pytest.mark.parametrize("route", ROUTES)
def test_condition_leaves_are_counted_by_operator(rig, route):
    srv, index = rig["servers"][route], rig["index"]
    c = client(srv)
    before = metrics(srv)
    asked = {"<": 2, ">=": 1, "between": 3, "==": 1}
    for op, n in asked.items():
        for k in range(n):
            cond = f"{40 + k} < {FIELD} <= 977" if op == "between" else f"{FIELD} {op} {911 + k}"
            ask(c, index, f"Count(Row({cond}))")
    ask(c, index, f"Count(Intersect(Row({FIELD} > 30), Row({FIELD} < 35)))")  # two leaves
    ask(c, index, f"Count(Row({FIELD} != null))")  # the null forms are not scans
    c.close()
    delta = change(before, metrics(srv))
    asked["<"] += 1
    asked[">"] = 1
    for op, n in asked.items():
        assert delta("bsi_condition_leaves_total", f'op="{op}"') == n, op
    assert delta("bsi_condition_leaves_total") == sum(asked.values())


@pytest.mark.parametrize("route", ["device", "mesh"])
def test_a_scalar_upload_is_counted_at_the_miss_and_only_there(rig, route):
    srv, index = rig["servers"][route], rig["index"]
    c = client(srv)
    texts = [f"Count(Intersect(Row(pickup_year=1), Row({FIELD} >= {v})))" for v in (701, 702, 703)]
    before = metrics(srv)
    for text in texts:
        ask(c, index, text)
    first = change(before, metrics(srv))("device_scalar_uploads_total")
    again = metrics(srv)
    for text in texts * 3:
        ask(c, index, text)
    c.close()
    assert first == 3  # three operand vectors never seen
    assert change(again, metrics(srv))("device_scalar_uploads_total") == 0  # cached by value
