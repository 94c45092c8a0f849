"""The two readers of the four-chip cell on made-up numbers: the mesh
roofline's arithmetic and scaling, the collective share as unions per
device plane, the busy skew; and that each gives nothing, never 0, where
there is nothing to read (one device plane, no trace, a program whose
router never took the mesh route)."""

import pytest

from benchmark.readers import mesh_collectives, mesh_roofline


def scrape(mesh: float, device: float, deduped: float = 0.0) -> dict:
    return {"metrics": {"queries_routed": {'path="mesh"': mesh, 'path="device"': device},
                        "result_cache_hits_total": {"": 0.0}, "result_cache_misses_total": {"": mesh + device},
                        "queries_deduped": {"": deduped}}}


def ctx_of(planes: int, mesh: float = 10.0, device: float = 0.0, deduped: float = 0.0) -> dict:
    # ten good replies, all completed inside the slice [1, 2); 1e9 bytes each
    records = [(0, 0.5, 1.0 + k / 20, 200, "q", b"") for k in range(10)]
    return {
        "trace": {"start": 1.0, "stop": 2.0, "busy_s": 0.05,
                  "devices": [{"plane": f"/device:TPU:{k}", "busy_s": 0.05, "events": 9} for k in range(planes)]},
        "records": records, "window": (0.0, 3.0), "min_bytes": lambda text: 10**9,
        "peaks": {"hbm_bytes_per_s": 1e12},
        "scrapes": {"window_start": scrape(0, 0), "window_end": scrape(mesh, device, deduped)},
    }


def test_roofline_divides_the_bytes_by_all_planes_peaks():
    # 1e10 bytes over 4 planes x 1e12 bytes/s = 2.5 ms least, of 50 ms busy
    assert mesh_roofline.read({}, ctx_of(4)) == pytest.approx(5.0)
    assert mesh_roofline.read({}, ctx_of(1)) == pytest.approx(20.0)


def test_roofline_scales_by_the_mesh_routed_and_not_deduplicated_shares():
    assert mesh_roofline.read({}, ctx_of(4, mesh=5.0, device=5.0)) == pytest.approx(2.5)
    assert mesh_roofline.read({}, ctx_of(4, deduped=5.0)) == pytest.approx(2.5)


def test_roofline_gives_nothing_without_mesh_reads_or_device_planes():
    assert mesh_roofline.read({}, ctx_of(4, mesh=0.0, device=10.0)) is None
    assert mesh_roofline.read({}, ctx_of(0)) is None
    assert mesh_roofline.read({}, {"trace": None}) is None


def test_collectives_are_told_by_hlo_name_or_scope():
    yes = ["%all-reduce.3 = (s32[32]{0}, s32[32]{0}) all-reduce(...)", "%all-gather-start = u32[4]",
           "%reduce-scatter = ...", "all-reduce-done.1", "%fusion.7 = ... op_name=\"jit(f)/pilosa.mesh_psum/psum\""]
    no = ["%convert_reduce_fusion = s32[128]{0} fusion(...)", "%custom-call = s64[] custom-call(...)", "%reduce.1"]
    assert all(mesh_collectives.is_collective(n) for n in yes)
    assert not any(mesh_collectives.is_collective(n) for n in no)


def test_collective_share_and_busy_skew():
    # a scan, then two collectives that overlap: their union is 200 ns
    ops = lambda shift: [(0 + shift, 800, False), (800 + shift, 100, True), (850 + shift, 150, True)]
    planes = mesh_collectives.reduce_planes([{"name": f"/device:TPU:{k}", "ops": ops(k)} for k in range(4)])
    assert [p["busy_s"] for p in planes] == [pytest.approx(1e-6)] * 4
    assert [p["collective_s"] for p in planes] == [pytest.approx(2e-7)] * 4
    ctx = {"mesh_collectives": planes,
           "trace": {"devices": [{"busy_s": b} for b in (0.9, 1.0, 1.0, 1.1)]}}
    assert mesh_collectives.read({"stat": "collective_share_pct"}, ctx) == pytest.approx(20.0)
    assert mesh_collectives.read({"stat": "busy_skew_pct"}, ctx) == pytest.approx(20.0)


def test_one_plane_or_no_trace_gives_nothing():
    one = {"mesh_collectives": [{"busy_s": 1.0, "collective_s": 0.0}], "trace": {"devices": [{"busy_s": 1.0}]}}
    for stat in ("collective_share_pct", "busy_skew_pct"):
        assert mesh_collectives.read({"stat": stat}, one) is None
        assert mesh_collectives.read({"stat": stat}, {"mesh_collectives": None, "trace": None}) is None


def test_reductions_on_four_device_planes():
    """The recorded one-chip trace with its device plane copied to four
    (no four-chip recording is in the tree): the harness's reductions sum
    and average over the planes, the idle partition still adds up to the
    idle time, and the readers of the four-chip cell read from them."""
    import os

    from benchmark.harness import host_spans, reduce_trace

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_spans.xplane.pb")

    def four(planes):
        out = [p for p in planes if not p["name"].startswith(reduce_trace.DEVICE_PLANE_PREFIX)]
        (chip,) = [p for p in planes if p["name"].startswith(reduce_trace.DEVICE_PLANE_PREFIX)]
        return out + [dict(chip, name=f"{reduce_trace.DEVICE_PLANE_PREFIX}{k}") for k in range(4)]

    one = reduce_trace.reduce_planes(reduce_trace.read_xplane(path))
    red = reduce_trace.reduce_planes(four(reduce_trace.read_xplane(path)))
    assert len(red["devices"]) == 4 and red["busy_s"] == pytest.approx(one["busy_s"])

    spans_one = host_spans.reduce_planes(host_spans.read_xplane(path))
    spans = host_spans.reduce_planes(four(host_spans.read_xplane(path)))
    assert spans["devices"] == 4 and spans["idle_s"] == pytest.approx(4 * spans_one["idle_s"])
    assert sum(spans["idle_by"].values()) == pytest.approx(spans["idle_s"])
    assert {m: v["launches"] for m, v in spans["modules"].items()} == \
        {m: 4 * v["launches"] for m, v in spans_one["modules"].items()}

    planes = mesh_collectives.reduce_planes(four(
        [{"name": p["name"], "ops": [(s, d, mesh_collectives.is_collective(n)) for ln in p["lines"]
                                     if ln["name"] == reduce_trace.OP_LINE for n, s, d in ln["events"]]}
         for p in reduce_trace.read_xplane(path) if p["name"].startswith(reduce_trace.DEVICE_PLANE_PREFIX)]))
    assert [p["busy_s"] for p in planes] == [pytest.approx(one["busy_s"])] * 4
    ctx = {"trace": red, "mesh_collectives": planes}
    assert mesh_collectives.read({"stat": "busy_skew_pct"}, ctx) == pytest.approx(0.0)
    assert mesh_collectives.read({"stat": "collective_share_pct"}, ctx) == 0.0  # one chip: no collective
