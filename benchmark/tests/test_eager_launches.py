"""The reader of ``eager_launches_pct`` (readers/eager_launches.py), by
hand like the other tests here: on made-up ``modules`` tables, on planes
reduced by the harness, and on the two small recordings from the chip."""

import os

import pytest

from benchmark.harness import host_spans as hs
from benchmark.readers import eager_launches
from benchmark.tests.test_host_spans import device, host

HERE = os.path.dirname(os.path.abspath(__file__))
PARAMS = {"prefix": "jit_pilosa_"}


def table(**launches) -> dict:
    return {"host_spans": {"modules": {n: {"launches": k, "total_s": k * 1e-3, "mean_ms": 1.0}
                                       for n, k in launches.items()}}}


def test_a_share_with_eager_modules_present():
    # PERF.md §5, taxi-128.four_queries before PR 33: 71 pads of 1,446 launches
    ctx = table(jit_pilosa_topn_filtered=627, jit__pad=71, jit_pilosa_sum_filtered=71,
                jit_pilosa_count=668, jit_pilosa_topn=9)
    assert eager_launches.read(PARAMS, ctx) == pytest.approx(71 / 1446 * 100)
    # the name decides, not the time: a mesh program is the program's own
    ctx = table(jit_pilosa_mesh_sum=3, jit__pad=2, jit_dynamic_slice=1, jit_pilosa_mesh_count=4)
    assert eager_launches.read(PARAMS, ctx) == pytest.approx(30.0)


def test_launches_and_none_eager_is_zero_not_nothing():
    got = eager_launches.read(PARAMS, table(jit_pilosa_count=7, jit_pilosa_sum_filtered=1))
    assert got == 0.0 and got is not None


def test_no_launch_or_no_trace_gives_nothing():
    assert eager_launches.read(PARAMS, table()) is None
    assert eager_launches.read(PARAMS, {"host_spans": None}) is None


def test_from_planes_through_the_reduction():
    planes = [host([("executor.Sum", 0, 4)]),
              device([("fusion", 1, 2), ("fusion", 3, 4)],
                     modules=[("jit__pad(7)", 1, 2), ("jit_pilosa_sum_filtered(9)", 3, 4),
                              ("jit_pilosa_count(3)", 5, 6), ("jit_pilosa_count(3)", 7, 8)])]
    assert eager_launches.read(PARAMS, {"host_spans": hs.reduce_planes(planes)}) == pytest.approx(25.0)


@pytest.mark.parametrize("recording,share", [
    ("tiny.xplane.pb", 100.0),  # five launches of a bare jit's lambda
    ("tiny_spans.xplane.pb", 25.0),  # topn, sum, wave_join and an eager reshape, five each
])
def test_the_recordings_from_the_chip(recording, share):
    path = os.path.join(HERE, recording)
    if not os.path.exists(path):
        pytest.skip("no recorded trace beside the tests")
    ctx = {"host_spans": hs.reduce_planes(hs.read_xplane(path))}
    assert eager_launches.read(PARAMS, ctx) == pytest.approx(share)
