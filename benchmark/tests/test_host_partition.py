"""The corrected partition (harness/host_partition.py) and its reader, by
hand like the other tests here: on the small recording from the chip as
it is (tiny_spans.xplane.pb), on planted planes whose answer can be
worked out on paper, and over the metric files this reduction and the
span table brought."""

import json
import os

import pytest

from benchmark.harness import host_partition as hp
from benchmark.harness import host_spans as hs
from benchmark.readers import idle_partition, prom_delta, prom_family

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_spans.xplane.pb")
US, MS = 1_000, 1_000_000  # ns


# ------------------------------------------------------- the chip's recording
@pytest.fixture(scope="module")
def tiny():
    if not os.path.exists(TINY):
        pytest.skip("no recorded trace beside the tests")
    return hp.reduce_planes(hp.read_xplane(TINY))


def test_the_recordings_lead_is_bounded_by_its_own_launches(tiny):
    (lead,) = tiny["leads"]
    assert lead["plane"] == "/device:TPU:0" and lead["pairs"] == 20  # 5 rounds of 4 programs
    assert 0.9 <= tiny["lead_ms"] <= 2.0
    assert lead["lo_ns"] <= lead["lead_ns"] <= lead["hi_ns"]
    assert lead["hi_ns"] - lead["lo_ns"] < 1 * MS  # the trace bounds it to under a millisecond
    assert lead["bounded_by_done"] >= 5  # the first launch of a round finds the queue empty


def test_after_the_shift_a_module_runs_between_its_launch_and_its_readback(tiny):
    """Every module starts at or after the ``PjitFunction`` event that
    launched it and ends before its round's ``readback.transfer`` does;
    as recorded, every module starts BEFORE its launch."""
    from jax.profiler import ProfileData

    shift = tiny["leads"][0]["lead_ns"]
    calls, transfers, modules = [], [], []
    for plane in ProfileData.from_file(TINY).planes:
        for ln in plane.lines:
            for e in ln.events:
                if plane.name == hs.HOST_PLANE and e.name.startswith("PjitFunction("):
                    calls.append((int(e.start_ns), e.name[len("PjitFunction("):-1]))
                elif plane.name == hs.HOST_PLANE and e.name == "readback.transfer":
                    transfers.append(int(e.start_ns + e.duration_ns))
                elif plane.name.startswith(hs.DEVICE_PLANE_PREFIX) and ln.name == hs.MODULE_LINE:
                    modules.append((int(e.start_ns), int(e.start_ns + e.duration_ns),
                                    e.name.split("(")[0].removeprefix("jit_")))
    calls.sort()  # the runtime writes each call twice, a microsecond apart: the later of the two
    assert [name for _, name in calls[0::2]] == [name for _, name in calls[1::2]]
    calls = calls[1::2]
    modules.sort()
    assert len(calls) == len(modules) == 20 and len(transfers) == 5
    assert [name for _, name in calls] == [name for _, _, name in modules]  # launch order is run order
    for k, ((called, _), (start, end, _)) in enumerate(zip(calls, modules)):
        assert start < called  # as recorded: the plane's clock leads
        assert start + shift >= called
        assert end + shift <= sorted(transfers)[k // 4]


def test_the_uncorrected_partition_is_host_spans_own(tiny):
    old = hs.reduce_planes(hs.read_xplane(TINY))
    assert tiny["idle_s_uncorrected"] == pytest.approx(old["idle_s"])
    for cat, seconds in old["idle_by"].items():
        assert tiny["idle_by_uncorrected"][cat] == pytest.approx(seconds), cat
    assert sum(tiny["idle_by"].values()) == pytest.approx(tiny["idle_s"])
    assert tiny["spans"]["readback.transfer"]["count"] == 5


# ------------------------------------------------------------ planted planes
def host(*threads):
    """Threads of (name, start_us, end_us[, stats]) events."""
    return {"name": hs.HOST_PLANE, "lines": [
        {"name": "python", "events": [(e[0], e[1] * US, (e[2] - e[1]) * US, e[3] if len(e) > 3 else {})
                                      for e in t]} for t in threads]}


def device(modules, ordinal=0, shift_us=0):
    """One module an op; (run id or None, true start_us, true end_us),
    written ``shift_us`` early as a leading plane writes them."""
    ops, mods = [], []
    for run_id, start, end in modules:
        ev = ((start - shift_us) * US, (end - start) * US)
        ops.append(("%fusion", *ev, {}))
        mods.append((f"jit_pilosa_count({run_id})", *ev, {} if run_id is None else {"run_id": run_id}))
    return {"name": f"/device:TPU:{ordinal}", "lines": [{"name": hs.OP_LINE, "events": ops},
                                                        {"name": hs.MODULE_LINE, "events": mods}]}


def launch(run_id, at, queued=1, ordinal=0):
    return [(hp.QUEUED, at - 40, at - 39, {"queued_executions_count": queued}),
            (hp.LAUNCH, at, at + 30, {"run_id": run_id, "device_ordinal": ordinal})]


# a leader's wave of two launches, then its readback and settle; a
# follower asleep in scheduler.await. Times in us.
LEADER = [("http.query", 0, 10_000), ("pql.query", 100, 9_000), ("scheduler.await", 200, 8_000),
          ("scheduler.wave", 300, 7_900),
          ("scheduler.query", 400, 1_400), ("executor.Count", 420, 1_380), *launch(7, 1_000),
          ("scheduler.query", 1_500, 2_500), ("executor.Count", 1_520, 2_480), *launch(8, 2_000, queued=2),
          ("scheduler.readback", 2_600, 5_000), ("readback.join", 2_610, 2_700),
          ("readback.transfer", 2_700, 4_990),
          ("scheduler.settle", 5_000, 7_800),
          ("pql.reply", 9_100, 9_900), ("stack.reshuffle", 9_910, 9_950), ("foreign.event", 0, 10_000)]
FOLLOWER = [("scheduler.await", 0, 8_100)]
DONES = [(hp.DONE, 1_812, 1_822), (hp.DONE, 4_700, 4_720)]
MODULES = [(7, 1_008, 1_810), (8, 2_100, 4_500)]  # true times: 8 us after the enqueue, done 12 us before Done ends


@pytest.mark.parametrize("shift_us", [0, 1_234, 1_900])
def test_a_planted_shift_is_found_to_ten_microseconds(shift_us):
    out = hp.reduce_planes([host(LEADER, FOLLOWER, DONES), device(MODULES, shift_us=shift_us)])
    (lead,) = out["leads"]
    assert lead["pairs"] == 2 and lead["bounded_by_done"] == 1  # run 8 found a queue of two: its Done is not told
    assert lead["lo_ns"] == (shift_us - 8) * US and lead["hi_ns"] == (shift_us + 12) * US
    assert out["lead_ms"] * 1e3 == pytest.approx(shift_us, abs=10)
    # whatever the plane's lead, the corrected partition is the true one (to the 2 us the middle is off):
    # idle 1810-2100 us between the modules, the leader inside executor.Count to 2480 of it
    by = {k: round(v * 1e6) for k, v in out["idle_by"].items() if v}
    assert by == {"executor.*": 290}
    assert out["idle_s"] == pytest.approx(290e-6)
    if shift_us == 1_900:  # as written the gap lies at -90..200 us, before the request began
        raw = {k: round(v * 1e6) for k, v in out["idle_by_uncorrected"].items() if v}
        assert raw == {"no_span": 90, "http.*": 100, "pql.query": 100}


def test_the_settle_is_a_category_of_its_own_ahead_of_the_wave():
    # one more module after the settle: the gap 4500-8500 us spans transfer, settle, wave, await, pql
    modules = [*MODULES, (9, 8_500, 8_600)]
    leader = [*LEADER, *launch(9, 8_400)]
    out = hp.reduce_planes([host(leader, FOLLOWER, [*DONES, (hp.DONE, 8_605, 8_610)]), device(modules, shift_us=1_000)])
    assert out["lead_ms"] == pytest.approx(1.0, abs=0.02)
    by = {k: round(v * 1e6, -1) for k, v in out["idle_by"].items() if round(v * 1e6, -1)}
    assert by == {
        "executor.*": 290,          # 1810-2100
        "readback.transfer": 490,   # 4500-4990
        "scheduler.wave": 110,      # 4990-5000 scheduler.readback's own, 7800-7900 the wave's
        "scheduler.settle": 2800,   # 5000-7800
        "scheduler.await": 100,     # 7900-8000: the leader's own and the follower's
        "pql.query": 500,           # 8000-8500: the leader's self time wins over the follower's await
    }
    assert hp.category("stack.reshuffle") == hp.OTHER and hp.category("foreign.event") is None
    assert hp.category("executor.groupby.admit") == "executor.*" and hp.category("http.metrics") == "http.*"
    assert out["spans"]["stack.reshuffle"]["count"] == 1 and "foreign.event" not in out["spans"]
    assert hp.PRIORITY.index("scheduler.settle") < hp.PRIORITY.index("scheduler.wave") < hp.PRIORITY.index(hp.OTHER)

    ctx = {"host_partition": out}
    share = lambda *spans: idle_partition.read({"stat": "idle_share_pct", "spans": list(spans)}, ctx)
    assert share("scheduler.settle") == pytest.approx(2800 / 4290 * 100, abs=0.3)
    assert share(*hp.PRIORITY, hp.NO_SPAN) == pytest.approx(100.0)
    assert share("mesh.*") is None  # no span of the kind in the slice: nothing, not 0
    assert idle_partition.read({"stat": "lead_ms"}, ctx) == out["lead_ms"]


@pytest.mark.parametrize("case", ["no run id on the modules", "no launch in the slice", "no bound from above",
                                  "one plane of two unpairable"])
def test_an_unpairable_plane_gives_nothing(case):
    planes = [host(LEADER, FOLLOWER, DONES), device(MODULES, shift_us=1_000)]
    if case == "no run id on the modules":
        planes[1] = device([(None, s, e) for _, s, e in MODULES], shift_us=1_000)
    elif case == "no launch in the slice":
        planes[0] = host([e for e in LEADER if e[0] != hp.LAUNCH], FOLLOWER, DONES)
    elif case == "no bound from above":  # neither a readback nor a Done after the launches
        planes[0] = host([e for e in LEADER if e[0] != hp.TRANSFER], FOLLOWER)
    else:  # a mesh: the second plane's launches are not in the trace
        planes.append(device([(3, 1_008, 1_810)], ordinal=1, shift_us=1_000))
    out = hp.reduce_planes(planes)
    assert any("reason" in lead for lead in out["leads"])
    assert out["lead_ms"] is None and out["idle_by"] is None and out["idle_s"] is None
    assert out["idle_by_uncorrected"] is not None  # the comparison's side is still there
    ctx = {"host_partition": out}
    assert idle_partition.read({"stat": "lead_ms"}, ctx) is None
    assert idle_partition.read({"stat": "idle_share_pct", "spans": ["http.*"]}, ctx) is None


# a slice of twelve waves of LEADER's kind, 10 ms apart: 24 launches, 12 of them with a Done of their own
def _waves(n=12):
    shift = lambda events, k: [(e[0], e[1] + k * 10_000, e[2] + k * 10_000, *e[3:]) for e in events]
    renumber = lambda events, k: [(*e[:3], {**e[3], "run_id": e[3]["run_id"] + 2 * k}) if e[0] == hp.LAUNCH else e
                                  for e in events]
    leader = [e for k in range(n) for e in renumber(shift(LEADER, k), k)]
    dones = [e for k in range(n) for e in shift(DONES, k)]
    modules = [(r + 2 * k, s + k * 10_000, e + k * 10_000) for k in range(n) for r, s, e in MODULES]
    return leader, dones, modules


@pytest.mark.parametrize("case", ["a Done that ends before its module could have", "two launches enqueued late",
                                  "the mapping steps by 300 us in the last quarter"])
def test_bounds_that_contradict_the_rest_are_outvoted(case):
    """The driver's first check of PR 36 met a recording whose bounds left
    no intersection, and the metric fell out of the line. The lead is
    where the most bounds hold; what it breaks is counted, not hidden."""
    leader, dones, modules = _waves()
    sound = hp.reduce_planes([host(leader, FOLLOWER, dones), device(modules, shift_us=1_000)])
    (lead,) = sound["leads"]
    assert lead["pairs"] == 24 and lead["bounds"] == 24 + 24 + 12 and lead["contradicting"] == 0
    assert (lead["lo_ns"], lead["hi_ns"]) == (992 * US, 1_012 * US)  # the intersection, as one wave gave it
    planes = [host(leader, FOLLOWER, dones), device(modules, shift_us=1_000)]
    if case == "a Done that ends before its module could have":
        dones[3] = (hp.DONE, 31_012, 31_020)  # 790 us before wave 3's first module ends
        planes[0] = host(leader, FOLLOWER, dones)
        broken = 1
    elif case == "two launches enqueued late":  # as a lower bound 400 us too high reads
        late = {7 + 2 * 5: 400, 7 + 2 * 9: 400}
        leader = [(e[0], e[1] + late.get(e[3]["run_id"], 0), e[2] + late.get(e[3]["run_id"], 0), e[3])
                  if e[0] == hp.LAUNCH else e for e in leader]
        planes[0] = host(leader, FOLLOWER, dones)
        broken = 2
    else:  # waves 9-11 are written 300 us earlier still: 6 lower bounds above every earlier upper one
        planes[1] = device([(r, s - (300 if r >= 7 + 2 * 9 else 0), e - (300 if r >= 7 + 2 * 9 else 0))
                            for r, s, e in modules], shift_us=1_000)
        broken = 6
    out = hp.reduce_planes(planes)
    (lead,) = out["leads"]
    assert "reason" not in lead and lead["pairs"] == 24
    assert lead["contradicting"] == broken
    assert (lead["lo_ns"], lead["hi_ns"]) == (992 * US, 1_012 * US), "the stretch the other launches agree on"
    assert out["lead_ms"] == sound["lead_ms"] and out["idle_by"] is not None
    ctx = {"host_partition": out}
    assert idle_partition.read({"stat": "lead_ms"}, ctx) == pytest.approx(1.002)
    assert idle_partition.read({"stat": "idle_share_pct", "spans": ["http.*"]}, ctx) is not None


def test_two_planes_are_paired_by_ordinal_and_bounded_by_the_readback_alone():
    """Done events name no device: on a mesh the upper bound is the
    wave's readback, and each plane has a lead of its own."""
    leader = [*LEADER, *launch(7, 1_001, ordinal=1), *launch(8, 2_001, queued=2, ordinal=1)]
    out = hp.reduce_planes([host(leader, FOLLOWER, DONES), device(MODULES, shift_us=1_000),
                            device(MODULES, ordinal=1, shift_us=1_300)])
    first, second = out["leads"]
    assert first["bounded_by_done"] == second["bounded_by_done"] == 0
    assert first["lo_ns"] == 992 * US and second["lo_ns"] == 1_293 * US
    assert first["hi_ns"] == (1_000 + 490) * US and second["hi_ns"] == (1_300 + 490) * US  # transfer end 4990 - 4500
    assert out["lead_ms"] == pytest.approx((first["lead_ns"] + second["lead_ns"]) / 2 / 1e6)
    assert out["devices"] == 2 and sum(out["idle_by"].values()) == pytest.approx(out["idle_s"])


def test_without_a_trace_or_a_device_the_reader_gives_nothing():
    assert idle_partition.reduction({"log_path": os.path.join(HERE, "no_such_dir", "server.log")}) is None
    out = hp.reduce_planes([host(LEADER)])  # a CPU rehearsal: spans, no device plane
    assert out["devices"] == 0 and out["idle_by"] is None and out["lead_ms"] is None
    assert out["spans"]["scheduler.settle"]["mean_ms"] == pytest.approx(2.8)


# ------------------------------------------------------------ the metric files
NEW = ("wave_ms", "wave_dispatch_ms", "wave_settle_ms", "wave_handover_ms", "gil_wait_ms",
       "offcpu_in_dispatch_pct", "offcpu_in_settle_pct", "offcpu_in_reply_pct",
       "groupby_host_between_ms", "groupby_admit_wait_ms", "device_clock_lead_ms",
       "idle_in_settle_pct", "idle_in_leader_pct", "idle_in_pql_pct", "idle_in_http_pct")


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_has_its_entry_its_file_and_a_reader_that_exists(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    cells = {w["name"] for w in bench["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert os.path.exists(os.path.join(ROOT, "benchmark", "readers", f"{spec['reader']}.py"))
    if spec["reader"] == "idle_partition":
        assert set(spec["params"].get("spans", [])) <= {*hp.PRIORITY, hp.NO_SPAN}


def _scrape(**families):
    return {"metrics": families}


def test_the_counter_metrics_read_the_span_table_and_the_wave_timers():
    def spec(name):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", f"{name}.json")) as f:
            s = json.load(f)
        return {"prom_delta": prom_delta, "prom_family": prom_family}[s["reader"]], s["params"]

    work, wait = 'kind="work",span="executor.Count"', 'kind="wait",span="executor.groupby.wait"'
    gb, admit = 'kind="work",span="executor.GroupBy"', 'span="executor.groupby.admit"'
    start = _scrape(
        scheduler_wave_seconds_sum={"": 1.0}, scheduler_wave_seconds_count={"": 100.0},
        scheduler_wave_phase_seconds_sum={'phase="dispatch"': 0.5, 'phase="settle"': 0.1},
        scheduler_wave_phase_seconds_count={'phase="dispatch"': 100.0, 'phase="settle"': 100.0},
        span_self_wall_seconds_total={work: 1.0, wait: 5.0, gb: 1.0},
        span_self_offcpu_seconds_total={work: 0.5, wait: 5.0, gb: 0.0},
        spans_total={'span="executor.GroupBy"': 10.0}, span_wall_seconds_total={'span="pql.query"': 1.0},
        queries_routed={'path="device"': 100.0})
    end = _scrape(
        scheduler_wave_seconds_sum={"": 3.0}, scheduler_wave_seconds_count={"": 200.0},
        scheduler_wave_phase_seconds_sum={'phase="dispatch"': 1.7, 'phase="settle"': 0.3, 'phase="handover"': 0.9},
        scheduler_wave_phase_seconds_count={'phase="dispatch"': 200.0, 'phase="settle"': 200.0, 'phase="handover"': 90.0},
        span_self_wall_seconds_total={work: 3.0, wait: 50.0, gb: 1.4},
        span_self_offcpu_seconds_total={work: 1.7, wait: 50.0, gb: 0.1},
        spans_total={'span="executor.GroupBy"': 210.0}, span_wall_seconds_total={'span="pql.query"': 2.0},
        queries_routed={'path="device"': 900.0})
    ctx = {"scrapes": {"window_start": start, "window_end": end}}
    read = lambda name: (lambda reader, params: reader.read(params, ctx))(*spec(name))
    assert read("wave_ms") == pytest.approx(20.0)
    assert read("wave_dispatch_ms") == pytest.approx(12.0)
    assert read("wave_settle_ms") == pytest.approx(2.0)
    assert read("wave_handover_ms") == pytest.approx(10.0)  # a phase new in the window
    # working spans only: the wait span's 45 s off the CPU are not a wait for the interpreter lock
    assert read("offcpu_in_dispatch_pct") == pytest.approx((1.2 + 0.1) / (2.0 + 0.4) * 100)
    assert read("offcpu_in_settle_pct") is None and read("gil_wait_ms") is None  # nothing closed, no probe
    assert read("groupby_host_between_ms") == pytest.approx(0.4 / 200 * 1e3)
    assert read("groupby_admit_wait_ms") == 0.0  # the family is there and nothing waited
    # a program without the families (the parent): nothing, never 0
    bare = {"scrapes": {"window_start": _scrape(queries_routed={"": 1.0}),
                        "window_end": _scrape(queries_routed={"": 9.0})}}
    for name in NEW[:10]:
        reader, params = spec(name)
        assert reader.read(params, bare) is None, name
    end["metrics"]["span_wall_seconds_total"][f'{admit}'] = 0.8
    assert read("groupby_admit_wait_ms") == pytest.approx(1.0)
