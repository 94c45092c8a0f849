"""``scope_rebuilds_per_query`` is a data file over the generic
``prom_family`` reader: it loads, reads 0 where the family stood still,
a ratio where it moved, and nothing on a program without the family."""

import importlib
import os

from benchmark.run import HERE, load_json

SPEC = load_json(HERE, "layer_metrics", "scope_rebuilds_per_query.json")


def read(start: dict, end: dict):
    reader = importlib.import_module(f"benchmark.readers.{SPEC['reader']}")
    ctx = {"scrapes": {"window_start": {"metrics": start}, "window_end": {"metrics": end}}}
    return reader.read(SPEC["params"], ctx)


def routed(device: float, mesh: float = 0.0) -> dict:
    return {'path="device"': device, 'path="mesh"': mesh}


def test_the_metric_is_declared_for_both_cells_and_its_file_is_there():
    bench = load_json(os.path.dirname(HERE), "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "scope_rebuilds_per_query"]
    assert entry["moves"] == "qps" and entry["source"] == "program_counter"
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]


def test_it_reads_zero_where_no_write_moved_the_stamp():
    start = {"shard_scope_rebuilds_total": {"": 7.0}, "queries_routed": routed(100.0)}
    end = {"shard_scope_rebuilds_total": {"": 7.0}, "queries_routed": routed(25100.0)}
    assert read(start, end) == 0.0


def test_it_reads_rebuilds_over_routed_reads_of_every_path():
    start = {"shard_scope_rebuilds_total": {"": 7.0}, "queries_routed": routed(100.0, 50.0)}
    end = {"shard_scope_rebuilds_total": {"": 12.0}, "queries_routed": routed(140.0, 60.0)}
    assert read(start, end) == 5.0 / 50.0


def test_a_program_without_the_family_gives_nothing_and_no_reads_give_nothing():
    assert read({"queries_routed": routed(1.0)}, {"queries_routed": routed(9.0)}) is None
    same = {"shard_scope_rebuilds_total": {"": 7.0}, "queries_routed": routed(3.0)}
    assert read(same, same) is None
