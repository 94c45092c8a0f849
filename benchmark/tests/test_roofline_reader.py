"""The ``roofline`` reader on made-up numbers: one program's share (mean
least bytes of its replies over the mean device time of its launches),
the whole engine's share with scan_roofline's scaling, how an int field's
planes are counted, and nothing, never 0, where there is nothing to
read. Then its two metric files on the recorded trace's module names."""

import importlib
import os

import pytest

from benchmark.harness import pql
from benchmark.harness.min_bytes import min_bytes
from benchmark.readers import mesh_roofline, roofline, scan_roofline
from benchmark.run import HERE, load_json

CFG = {"schema": {"y": {"type": "set", "rows": 8}, "p": {"type": "set", "rows": 4},
                  "amount": {"type": "int", "bits": 17, "bits_filled": 11}},
       "scale": {"shards": 2}, "shard_width": 1 << 20}
PLANE = (2 << 20) // 8  # bytes of one plane


def scrape(device: float, mesh: float = 0.0, deduped: float = 0.0) -> dict:
    return {"metrics": {"queries_routed": {'path="device"': device, 'path="mesh"': mesh},
                        "result_cache_hits_total": {"": 0.0},
                        "result_cache_misses_total": {"": device + mesh},
                        "queries_deduped": {"": deduped}}}


def ctx_of(texts, modules=None, planes=1, device=10.0, mesh=0.0, deduped=0.0) -> dict:
    records = [(0, 1.0 + k / 100, 1.1 + k / 100, 200, t, b"") for k, t in enumerate(texts)]
    records += [(0, 1.2, 1.5, 500, "Count(Row(amount > 1))", None),      # failed
                (0, 1.9, 2.5, 200, "Count(Row(amount > 1))", b"")]       # completed after the slice
    return {"cfg": CFG, "records": records, "window": (0.0, 3.0), "peaks": {"hbm_bytes_per_s": 1e9},
            "trace": {"start": 1.0, "stop": 2.0, "busy_s": 0.05,
                      "devices": [{"plane": f"/device:TPU:{k}"} for k in range(planes)]},
            "host_spans": {"modules": modules or {}},
            "scrapes": {"window_start": scrape(0), "window_end": scrape(device, mesh, deduped)}}


def test_one_programs_share_is_mean_bytes_over_mean_launch_time():
    texts = ["Count(Row(amount > 5))", "Count(Intersect(Row(y=1), Row(amount >= 7)))",
             "TopN(p, Row(amount < 9))"]
    mods = {"jit_pilosa_count_range": {"launches": 4, "total_s": 0.02},
            "jit_pilosa_count": {"launches": 9, "total_s": 9.0}}
    p = {"module_prefix": "jit_pilosa_count_range", "text_prefix": "Count(", "int_planes": "filled"}
    # (12 + 13) / 2 planes over 1e9 B/s, of 5 ms a launch
    assert roofline.read(p, ctx_of(texts, mods)) == pytest.approx(12.5 * PLANE / 1e9 / 0.005 * 100)
    # the declared depth counts 18 + 19 planes for the same replies
    p["int_planes"] = "declared"
    assert roofline.read(p, ctx_of(texts, mods)) == pytest.approx(18.5 * PLANE / 1e9 / 0.005 * 100)


def test_the_whole_engines_share_scales_as_scan_roofline_does():
    texts = ["Count(Row(amount > 5))", "TopN(p, Row(3 <= amount <= 9))", "Sum(Row(y=2), field=amount)"]
    p = {"route": "device", "int_planes": "filled"}
    total = (12 + 16 + 13) * PLANE
    assert roofline.read(p, ctx_of(texts)) == pytest.approx(total / 1e9 / 0.05 * 100)
    assert roofline.read(p, ctx_of(texts, device=5.0, mesh=5.0)) == pytest.approx(total / 2 / 1e9 / 0.05 * 100)
    # four good replies in the window (one completed after the slice), one of them deduplicated
    assert roofline.read(p, ctx_of(texts, deduped=1.0)) == pytest.approx(total * 3 / 4 / 1e9 / 0.05 * 100)
    # on the mesh route the chips' peaks add
    assert roofline.read({"route": "mesh", "int_planes": "filled"},
                         ctx_of(texts, planes=4, device=0.0, mesh=10.0)) == pytest.approx(total / 4e9 / 0.05 * 100)


def test_under_the_declared_depth_it_is_the_two_readers_it_can_replace():
    texts = ["Count(Intersect(Row(y=1), Row(p=2)))", "Sum(Row(p=2), field=amount)", "TopN(p)"]
    for route, old, planes in (("device", scan_roofline, 1), ("mesh", mesh_roofline, 4)):
        ctx = ctx_of(texts, planes=planes, device=10.0 if route == "device" else 0.0,
                     mesh=10.0 if route == "mesh" else 0.0, deduped=1.0)
        schema = {f: ({"rows": s["rows"]} if "rows" in s else {"bits": s["bits"]}) for f, s in CFG["schema"].items()}
        ctx["min_bytes"] = lambda text: min_bytes(pql.parse(text), schema, 2 << 20)
        assert roofline.read({"route": route}, ctx) == pytest.approx(old.read({}, ctx))


def test_a_reply_counts_only_if_it_was_sent_and_completed_inside_the_slice():
    """A server that takes longer than the slice to answer (the parent of
    PR 32 compiled in every request: 6 s a reply, a 3 s slice) did the
    device work of the replies it completes in the slice before it."""
    ctx = ctx_of(["Count(Row(amount > 5))"])
    ctx["records"] = [(0, 0.2, 1.5, 200, "TopN(p, Row(amount > 5))", b""),   # sent before the slice
                      (0, 1.0, 1.4, 200, "Count(Row(amount > 5))", b"")]     # inside it
    assert roofline.read({"route": "device", "int_planes": "filled"}, ctx) == pytest.approx(
        12 * PLANE / 1e9 / 0.05 * 100)
    ctx["records"] = ctx["records"][:1]
    assert roofline.read({"route": "device", "int_planes": "filled"}, ctx) is None


def test_nothing_to_read_gives_nothing():
    p = {"module_prefix": "jit_pilosa_count_range", "text_prefix": "Count(", "int_planes": "filled"}
    texts = ["Count(Row(amount > 5))"]
    assert roofline.read(p, ctx_of(texts, {"jit_pilosa_count": {"launches": 9, "total_s": 1.0}})) is None
    assert roofline.read(p, ctx_of(["TopN(p)"], {"jit_pilosa_count_range": {"launches": 1, "total_s": 1.0}})) is None
    assert roofline.read(p, {"trace": None}) is None
    assert roofline.read({"route": "device"}, ctx_of(texts, device=0.0, mesh=10.0)) is None
    no_spans = ctx_of(texts)
    no_spans["host_spans"] = None  # a trace without the modules line
    assert roofline.read(p, no_spans) is None


@pytest.mark.parametrize("name", ["range_count_roofline_pct", "range_scan_roofline_pct",
                                  "range_count_device_ms", "range_leaves_per_query",
                                  "scalar_uploads_per_query"])
def test_the_metric_files_name_readers_that_are_there_and_cells_that_are(name):
    spec = load_json(HERE, "layer_metrics", f"{name}.json")
    importlib.import_module(f"benchmark.readers.{spec['reader']}")
    bench = load_json(os.path.dirname(HERE), "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    cells = [w["name"] for w in bench["workloads"]]
    assert set(entry["workloads"]) <= set(cells) and "taxi-128r.adhoc_range" in entry["workloads"]
    if name.endswith("roofline_pct"):
        assert spec["params"]["int_planes"] == "filled" and "taxi-128.four_queries" not in entry["workloads"]


def test_the_recorded_traces_programs_are_told_apart_by_prefix():
    """On the recording from the chip (tiny_spans.xplane.pb) no program is a
    range program: the per-program share gives nothing, while the
    popcount's own prefix finds its launches there."""
    from benchmark.harness import host_spans as hs

    red = hs.reduce_planes(hs.read_xplane(os.path.join(HERE, "tests", "tiny_spans.xplane.pb")))
    ctx = ctx_of(["Count(Row(amount > 5))"])
    ctx["host_spans"] = red
    assert red["modules"] and not any(n.startswith("jit_pilosa_count_range") for n in red["modules"])
    p = {"module_prefix": "jit_pilosa_count_range", "text_prefix": "Count(", "int_planes": "filled"}
    assert roofline.read(p, ctx) is None
    some = next(iter(red["modules"]))
    assert roofline.read(dict(p, module_prefix=some), ctx) > 0
