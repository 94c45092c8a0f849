"""The deployment ``taxi-512x4`` at a CPU size, on the served path.

The benchmark's four-chip cell (``taxi-512x4.four_queries``) pins
``route-mode = "mesh"`` and holds the program to the plain reference of
``benchmark/datasets/taxi.py``. Here the same configuration file, cut to
two shards a device at the tests' shard width, the same seeded generator,
the same traffic file and the same reference, against a real server over
HTTP on the suite's virtual CPU devices: every template on the device
and the mesh route, the mix under 16 concurrent clients, and what a
pinned mesh route does with work it cannot run as mesh programs (it is
served exactly, and counted).
"""

import json
import os
import sys
import threading

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.datasets import taxi  # noqa: E402
from benchmark.harness import pql as bench_pql, traffic  # noqa: E402
from benchmark.harness.server import Client, parse_metrics  # noqa: E402
from pilosa_tpu.executor import compile as query_compile  # noqa: E402
from pilosa_tpu.executor.router import QueryRouter  # noqa: E402
from pilosa_tpu.server.api import API  # noqa: E402
from pilosa_tpu.server import Server  # noqa: E402
from pilosa_tpu.utils.config import Config  # noqa: E402
from pilosa_tpu.utils.tracing import GLOBAL_TRACER  # noqa: E402

SEED = 2800000037
SPEC = traffic.load("four_queries")
TEMPLATES = [t["name"] for t in SPEC["templates"]]


def cell_config(shards: int) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs", "taxi-512x4.json")) as f:
        cfg = json.load(f)
    cfg["scale"]["shards"] = shards
    return cfg


def boot(tmp, route: str, **more) -> Server:
    """The server as the configuration's ``server`` block starts it."""
    s = Server(Config(bind="127.0.0.1:0", data_dir=str(tmp), anti_entropy_interval=0,
                      route_mode=route, result_cache_mode="off", **more))
    s.open()
    assert s.wait_mesh(60)
    return s


def load(srv: Server, cfg: dict, index: str) -> taxi.Reference:
    """Schema and seeded data over the bulk route; → the reference."""
    base = f"http://127.0.0.1:{srv.port}"
    c = Client(base)
    c.json(f"/index/{index}", b"{}")
    for fname, opts in taxi.schema(cfg):
        c.json(f"/index/{index}/field/{fname}", opts)
    c.close()
    state = taxi.load_part(base, index, SEED, cfg, list(range(cfg["scale"]["shards"])))
    return taxi.Reference(cfg, [state])


def ask(client: Client, index: str, text: str):
    return client.json(f"/index/{index}/query", text.encode())["results"][0]


def metrics(srv: Server) -> dict:
    c = Client(f"http://127.0.0.1:{srv.port}")
    try:
        return parse_metrics(c.request("GET", "/metrics")[1].decode())
    finally:
        c.close()


def family(m: dict, name: str, labels: str = "") -> float:
    return sum(v for k, v in m.get(name, {}).items() if labels in k)


def change(before: dict, after: dict):
    """(family, labels) → how far it moved between two scrapes."""
    return lambda name, labels="": family(after, name, labels) - family(before, name, labels)


@pytest.fixture(scope="module")
def rig(tmp_path_factory):
    """One server a route over the same data, and its reference."""
    n = jax.local_device_count()
    assert n > 1, "conftest gives the suite its virtual devices"
    cfg = cell_config(2 * n)
    servers = {r: boot(tmp_path_factory.mktemp(r), r) for r in ("device", "mesh")}
    refs = {r: load(s, cfg, cfg["index"]) for r, s in servers.items()}
    yield {"cfg": cfg, "servers": servers, "ref": refs["mesh"], "devices": n}
    for s in servers.values():
        s.close()


@pytest.mark.parametrize("route", ["device", "mesh"])
@pytest.mark.parametrize("template", TEMPLATES)
def test_every_template_equals_the_reference(rig, route, template):
    t = next(t for t in SPEC["templates"] if t["name"] == template)
    gen = traffic.Generator(SPEC, [SEED, len(template)])
    srv = rig["servers"][route]
    before = metrics(srv)
    c = Client(f"http://127.0.0.1:{srv.port}")
    texts = [gen.render(t, {}) for _ in range(6)]
    for text in texts:
        assert ask(c, rig["cfg"]["index"], text) == rig["ref"].answer(bench_pql.parse(text)), text
    c.close()
    delta = change(before, metrics(srv))
    took = lambda path: delta("queries_routed", f'path="{path}"')
    assert took(route) + delta("queries_deduped") == len(texts)
    assert sum(took(p) for p in ("host", "device", "mesh")) == took(route)


def test_sixteen_clients_on_the_mesh_route(rig):
    """The cell's own concurrency: every reply exact, every read a mesh
    program, none handed back to the device path."""
    srv, index, ref = rig["servers"]["mesh"], rig["cfg"]["index"], rig["ref"]
    before = metrics(srv)
    clients, each = int(SPEC["clients"]), 40
    wrong, errors = [], []

    def client(k: int) -> None:
        gen = traffic.Generator(SPEC, [SEED, 1, k])
        c = Client(f"http://127.0.0.1:{srv.port}")
        try:
            for _ in range(each):
                _ti, text = gen.draw()
                got = ask(c, index, text)
                if got != ref.answer(bench_pql.parse(text)):
                    wrong.append(text)
        except Exception as e:  # noqa: BLE001 — reported by the test's thread
            errors.append(repr(e))
        finally:
            c.close()

    threads = [threading.Thread(target=client, args=(k,)) for k in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(120)
    assert not errors and not wrong, (errors[:3], wrong[:3])
    after = metrics(srv)
    delta = change(before, after)
    sent = clients * each
    assert delta("queries_routed", 'path="mesh"') + delta("queries_deduped") == sent
    assert delta("queries_routed", 'path="device"') == delta("queries_routed", 'path="host"') == 0
    assert delta("mesh_fallbacks_total") == 0 and "mesh_fallbacks_total" in after
    assert family(after, "mesh_devices") == rig["devices"]
    ran = delta("queries_routed", 'path="mesh"')
    assert sum(delta("mesh_program_calls_total", f'program="{p}"') for p in ("topn", "sum", "count")) == ran


def test_mesh_dispatch_is_the_innermost_span(rig):
    """``mesh.dispatch`` spans each program's issue and nothing opens
    under it."""
    c = Client(f"http://127.0.0.1:{rig['servers']['mesh'].port}")
    ask(c, rig["cfg"]["index"], "TopN(dist_miles, Intersect(Row(pickup_year=1), Row(passenger_count=1)))")
    c.close()
    spans = GLOBAL_TRACER.recent(100000)
    issued = [s for s in spans if s["name"] == "mesh.dispatch"]
    assert {"topn", "sum", "count"} <= {s["tags"]["program"] for s in issued}
    assert all(s["tags"]["devices"] == rig["devices"] for s in issued)
    assert all(s["parent"].startswith("executor.") for s in issued)
    assert not [s for s in spans if s["parent"] == "mesh.dispatch"]


def test_shards_that_do_not_divide_by_the_devices_stay_on_the_mesh(rig):
    """Three shards cannot be dealt to the devices whole: the stacks are
    split along the word axis instead and the same mesh programs serve
    them, exactly; that is no fallback. The upload of a stack says over
    how many devices it went."""
    srv = rig["servers"]["mesh"]
    cfg = cell_config(3)
    ref = load(srv, cfg, "taxi_odd")
    assert srv.api.executor.compiler.mesh_mode(3) == "words"
    before = metrics(srv)
    c = Client(f"http://127.0.0.1:{srv.port}")
    texts = [text for _ti, text in traffic.Generator(SPEC, [SEED, 3]).warmup()]
    for text in texts:
        assert ask(c, "taxi_odd", text) == ref.answer(bench_pql.parse(text)), text
    c.close()
    delta = change(before, metrics(srv))
    assert delta("queries_routed", 'path="mesh"') == len(texts)
    assert delta("mesh_fallbacks_total") == 0
    uploads = [s for s in GLOBAL_TRACER.recent(100000) if s["name"] == "stack.upload"]
    assert uploads and all(s["tags"]["devices"] == rig["devices"] for s in uploads)


def test_a_tiered_field_on_a_pinned_mesh_route_is_a_counted_fallback(rig, tmp_path):
    """Fields over the stack budget live in the tiered stores, which a
    shard_map block cannot decode: the pinned mesh route hands such reads
    to the device path. The answers stay exact and every one is counted."""
    cfg = rig["cfg"]
    # room on each device for its share of the eight padded rows of
    # cab_type or pickup_year, not of passenger_count's 16, dist_miles's 32
    # or the amount's slices: the stack ledger counts a device's share
    plane = cfg["scale"]["shards"] // rig["devices"] * query_compile.WORDS_PER_SHARD * 4
    srv = boot(tmp_path, "mesh", device_stack_budget_bytes=8 * plane)
    try:
        ref = load(srv, cfg, cfg["index"])
        before = metrics(srv)
        c = Client(f"http://127.0.0.1:{srv.port}")
        texts = [text for _ti, text in traffic.Generator(SPEC, [SEED, 4]).warmup()]
        for text in texts:
            assert ask(c, cfg["index"], text) == ref.answer(bench_pql.parse(text)), text
        c.close()
        delta = change(before, metrics(srv))
        # Q1 reads cab_type alone and stays a mesh program; Q2, Q3 and Q4
        # touch a tiered field
        assert delta("mesh_fallbacks_total") == 3
        assert delta("queries_routed", 'path="mesh"') + delta("queries_routed", 'path="device"') == len(texts)
        assert delta("queries_routed", 'path="host"') == 0
    finally:
        srv.close()
        query_compile.set_stack_budget(None)


def test_a_stack_over_one_devices_budget_stays_dense_on_the_mesh_route(rig, tmp_path):
    """On a mesh the stack ledger counts a device's share of a stack.
    With the budget between dist_miles's share a device (32 padded rows
    of two shards) and its whole stack, every field stays a dense stack:
    the reads are mesh programs, none falls back, nothing is evicted, and
    the ledger's gauge holds a device's share of them all. An executor
    with no mesh, whose one device would hold the whole stack, serves the
    same field without a dense stack under the same budget."""
    cfg = rig["cfg"]
    share = cfg["scale"]["shards"] // rig["devices"] * query_compile.WORDS_PER_SHARD * 4
    # a device's share of every field's padded rows is under 128 planes;
    # dist_miles's whole stack is 32 x devices of them
    budget = 128 * share
    assert 32 * share <= budget < 32 * rig["devices"] * share
    texts = [text for _ti, text in traffic.Generator(SPEC, [SEED, 5]).warmup()]
    srv = boot(tmp_path, "mesh", device_stack_budget_bytes=budget)
    try:
        ref = load(srv, cfg, cfg["index"])
        before = metrics(srv)
        c = Client(f"http://127.0.0.1:{srv.port}")
        for text in texts:
            assert ask(c, cfg["index"], text) == ref.answer(bench_pql.parse(text)), text
        c.close()
        after = metrics(srv)
        delta = change(before, after)
        assert delta("queries_routed", 'path="mesh"') == len(texts)
        assert delta("mesh_fallbacks_total") == 0
        assert delta("stack_evictions_total") == 0 and "stack_evictions_total" in after
        holder = srv.api.holder
        idx = holder.index(cfg["index"])
        dist, scope = idx.field("dist_miles"), list(idx.shard_scope())
        stacks = srv.api.executor.compiler.stacks
        assert not stacks.is_over_budget(idx, dist, "standard", scope)
        assert stacks.residency_snapshot()["entries"] == 0 and "dist_miles" in {key[1] for key in stacks._cache}
        held = family(after, "stack_device_bytes")
        assert held == stacks.resident_bytes and 32 * share < held <= budget

        one = API(holder, router=QueryRouter(mode="device"))
        for text in texts:
            assert one.query(cfg["index"], text)["results"][0] == ref.answer(bench_pql.parse(text)), text
        alone = one.executor.compiler.stacks
        assert alone.is_over_budget(idx, dist, "standard", scope)
        assert "dist_miles" not in {key[1] for key in alone._cache} and alone.resident_bytes <= budget
    finally:
        srv.close()
        query_compile.set_stack_budget(None)
