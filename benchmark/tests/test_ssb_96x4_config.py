"""The configuration ``ssb-96x4``: ssb-24's deployment at four nodes'
share on one four-chip host. Its schema and hierarchy are ssb-24's, its
96 shards are 24 a chip, and the ``/metrics`` family it requires is one
``pilosa_tpu/utils/stats.py`` registers, so a program that does not count
a chip's share of its stacks ends the run in ``ssb.schema()``, before a
byte is loaded."""

import json
import os

import pytest

from benchmark.datasets import ssb
from benchmark.harness.server import RunFailure
from pilosa_tpu.utils import stats as program_stats

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _config(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _config("ssb-96x4")


def test_the_configuration_loads_as_ninety_six_parts_of_ssb_24s_schema(cfg):
    twin = _config("ssb-24")
    assert ssb.schema(cfg) == ssb.schema(twin)
    assert ssb.parts(cfg) == 96 == cfg["scale"]["shards"]
    assert cfg["chips"] == 4 and cfg["layout"]["shards_per_chip"] * cfg["chips"] == 96
    assert cfg["scale"]["columns"] == 96 * cfg["shard_width"]
    assert cfg["hierarchy"] == twin["hierarchy"] and cfg["guarantees"]["answers"].startswith("exact")
    assert cfg["server"] == {"route-mode": "mesh", "result-cache-mode": "off"}
    assert cfg["reduced"] == ["scale"] and set(cfg["reduced_why"]) == {"scale"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == cfg["name"]]
    assert entry["file"] == "benchmark/configs/ssb-96x4.json" and entry["reduced"] == cfg["reduced"]


def test_the_required_family_is_in_the_programs_table(cfg):
    families = cfg["requires"]["metrics_families"]
    assert families == ["stack_device_bytes"]
    assert all(f in program_stats._METRIC_HELP for f in families)


def test_a_program_without_the_family_stops_in_schema(cfg, monkeypatch):
    table = dict(program_stats._METRIC_HELP)
    del table["stack_device_bytes"]
    monkeypatch.setattr(program_stats, "_METRIC_HELP", table)
    with pytest.raises(RunFailure, match="stack_device_bytes"):
        ssb.schema(cfg)
