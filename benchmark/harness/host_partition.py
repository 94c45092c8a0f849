"""The device's idle time given to the program's spans, with the device
planes' clock LEAD measured and taken out first.

    python -m benchmark.harness.host_partition <trace dir> <out.json>

A second reduction of the slice's ``.xplane.pb`` beside host_spans.py
(whose interval arithmetic and ``innermost`` it uses, unchanged), as a
process of its own with ``JAX_PLATFORMS=cpu``.

**The lead.** A device plane carries the device's clock mapped onto the
host's, and the mapping is off by nearly a constant of the recording (0.5
to 2.2 ms over eleven recordings of PR 36): every ``XLA Modules`` event
starts BEFORE the host call that launched it. The trace bounds that
constant from both sides, launch by launch. The runtime's
``DoEnqueueProgram`` event in ``/host:CPU`` carries the ``run_id`` and
``device_ordinal`` of the module it enqueues, so a module is paired with
its launch exactly, whatever else ran, and

    enqueue start - module start  <=  lead

because a program cannot start before it was enqueued. From above,
``lead <= seen-done end - module end``, where the host has seen a module
done (a) at the end of the first ``readback.transfer`` span that starts
after its launch: the device runs launches in order and the wave's
readback reads the last one's result; and, on a single device, (b) at
the end of the first ``tpu::System::Execute=>Done`` that starts after
the launch, for a launch that found the queue empty (``Acquire
semaphore`` with ``queued_executions_count`` 1: that Done is its own).
Done events carry no device, so with several device planes only (a)
holds.

Every launch gives one bound from below and one or two from above, a few
thousand a slice. Where all of them agree the lead lies between the
largest lower and the smallest upper bound: their intersection, 0.2 ms
wide on one chip. The mapping is only nearly constant (in each of three
recordings kept whole the lower bounds step down by 35 us one second in,
and drift by 10-40 us after), and one traced run of the driver's gave no
intersection at all. A recording is not thrown away for the launches that
disagree: the lead is taken on the lowest stretch where the MOST bounds
hold, as Marzullo's rule takes a clock's offset from sources some of
which lie; with an intersection that stretch IS the intersection.
``contradicting`` counts the bounds the stretch breaks (0 then). The
plane is shifted by the MIDDLE of the stretch. Where nothing can be
paired, or nothing bounds the lead from above, the plane has no lead,
and then there is no partition either: never a share on an uncorrected
timeline.

**The partition.** Every ``/host:CPU`` event whose name starts with one
of the program's prefixes is a span of the program, so the next span
needs no edit here. A thread is in the innermost of its open spans; an
idle instant of a device goes to the first category of ``PRIORITY`` that
ANY thread is in, else ``no_span``. ``scheduler.settle`` is a category of
its own ahead of ``scheduler.wave``. ``idle_by_uncorrected`` is the same
rule with no shift, for the comparison with host_spans.py's partition.
"""

from __future__ import annotations

import bisect
import json
import sys

from .host_spans import HOST_PLANE, MODULE_LINE, innermost, intersect, length, subtract, union
from .reduce_trace import DEVICE_PLANE_PREFIX, OP_LINE, find_xplane

PREFIXES = ("http.", "pql.", "scheduler.", "executor.", "readback.", "stack.", "mesh.")
LAUNCH, QUEUED, DONE = "DoEnqueueProgram", "Acquire semaphore", "tpu::System::Execute=>Done"
TRANSFER = "readback.transfer"

OTHER, NO_SPAN = "other", "no_span"
PRIORITY = (
    "readback.join", "readback.transfer",
    "stack.pack", "stack.upload", "stack.delta",
    "executor.*", "mesh.*",
    "scheduler.settle",  # the leader finishing the wave's results and waking its waiters
    "scheduler.wave",    # the leader between dispatches (with scheduler.query's and .readback's own time)
    "pql.reply", "pql.query", "http.*",
    OTHER,               # a span of the program this list does not name yet
    "scheduler.window", "scheduler.await",
)
_FAMILIES = ("executor.", "mesh.", "http.")
_FOLD = {"scheduler.query": "scheduler.wave", "scheduler.readback": "scheduler.wave"}


def category(name: str) -> str | None:
    """The partition category of a host event; None for a foreign one."""
    if not name.startswith(PREFIXES):
        return None
    for family in _FAMILIES:
        if name.startswith(family):
            return family + "*"
    name = _FOLD.get(name, name)
    return name if name in PRIORITY else OTHER


def _ordinal(plane_name: str) -> int | None:
    tail = plane_name[len(DEVICE_PLANE_PREFIX):]
    return int(tail) if tail.isdigit() else None


def _launches(host: dict) -> dict:
    """{(device ordinal, run id): (enqueue start, enqueue end, queue
    length at its acquire or None)} from the runtime's events, line by
    line: an ``Acquire semaphore`` belongs to the next enqueue of its
    thread."""
    out = {}
    for line in host["lines"]:
        queued = None
        for name, start, dur, stats in sorted(line["events"], key=lambda e: e[1]):
            if name == QUEUED:
                queued = stats.get("queued_executions_count")
            elif name == LAUNCH and "run_id" in stats:
                out[(stats.get("device_ordinal", 0), stats["run_id"])] = (start, start + dur, queued)
                queued = None
    return out


def clock_lead(modules, launches, transfers, dones) -> dict:
    """One device plane's lead. ``modules``: [(start, end, run id)];
    ``launches``: {run id: (start, end, queued)} of this device;
    ``transfers``, ``dones``: [(start, end)] sorted by start, ``dones``
    None where Done events cannot be given to a device. All in ns.
    {"lead_ns", "lo_ns", "hi_ns", "pairs", "bounds", "contradicting",
    "bounded_by_done"} or {"reason"}."""
    lower, upper, by_done = [], [], 0
    t_starts = [t[0] for t in transfers]
    d_starts = [d[0] for d in dones or ()]
    for start, end, run_id in modules:
        launch = launches.get(run_id)
        if launch is None:
            continue  # launched before the slice began
        lower.append(launch[0] - start)
        k = bisect.bisect_left(t_starts, launch[1])
        if k < len(transfers):
            upper.append(transfers[k][1] - end)
        if dones is not None and launch[2] == 1:
            k = bisect.bisect_left(d_starts, launch[0])
            if k < len(dones):
                upper.append(dones[k][1] - end)
                by_done += 1
    if not lower:
        return {"reason": "no module of the plane has its launch in the slice", "pairs": 0}
    if not upper:
        return {"reason": "nothing bounds the lead from above", "pairs": len(lower)}
    # Walking up the line, a lower bound holds FROM its value on and an
    # upper one UP TO its own (at one value the lower sorts first): the
    # lowest stretch on which the most bounds hold. Below every edge all
    # upper bounds hold and no lower one, which is no measurement.
    edges = sorted([(x, False) for x in lower] + [(x, True) for x in upper])
    held = best = len(upper)
    lo = hi = None
    for i, (x, is_upper) in enumerate(edges):
        held += -1 if is_upper else 1
        if held > best:
            best, lo = held, x
            hi = edges[i + 1][0] if i + 1 < len(edges) else None
    if lo is None or hi is None:
        return {"reason": "the bounds agree on no stretch that has two ends", "pairs": len(lower)}
    return {"lead_ns": (lo + hi) // 2, "lo_ns": lo, "hi_ns": hi, "pairs": len(lower), "bounds": len(edges),
            "contradicting": len(edges) - best, "bounded_by_done": by_done}


def _partition(rest, by_category) -> dict:
    out = {}
    for cat in PRIORITY:
        took = intersect(rest, by_category.get(cat, []))
        out[cat] = length(took)
        rest = subtract(rest, took)
    out[NO_SPAN] = length(rest)
    return out


def reduce_planes(planes: list[dict]) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns, stats)]}]}] as read_xplane gives them."""
    host = next((p for p in planes if p["name"] == HOST_PLANE), {"lines": []})
    spans: dict[str, dict] = {}
    in_category: dict[str, list] = {}
    transfers, dones = [], []
    for line in host["lines"]:
        mine = []
        for name, start, dur, _stats in line["events"]:
            if name == DONE:
                dones.append((start, start + dur))
            if category(name) is None:
                continue
            mine.append((start, start + dur, name))
            if name == TRANSFER:
                transfers.append((start, start + dur))
            s = spans.setdefault(name, {"count": 0, "total_s": 0.0})
            s["count"] += 1
            s["total_s"] += dur / 1e9
        for start, end, name in innermost(mine):
            in_category.setdefault(category(name), []).append((start, end))
    for s in spans.values():
        s["mean_ms"] = s["total_s"] / s["count"] * 1e3
    by_category = {c: union(v) for c, v in in_category.items()}
    transfers.sort()
    dones.sort()
    launches = _launches(host)

    devices = [p for p in planes if p["name"].startswith(DEVICE_PLANE_PREFIX)
               and any(ln["name"] == OP_LINE and ln["events"] for ln in p["lines"])]
    leads, idle_ns, raw_ns = [], 0, 0
    idle_by = dict.fromkeys((*PRIORITY, NO_SPAN), 0)
    raw_by = dict(idle_by)
    for plane in devices:
        ordinal = _ordinal(plane["name"])
        mine = {run: v for (dev, run), v in launches.items() if dev == ordinal}
        modules = [(e[1], e[1] + e[2], e[3]["run_id"]) for ln in plane["lines"]
                   if ln["name"] == MODULE_LINE for e in ln["events"] if "run_id" in e[3]]
        lead = clock_lead(modules, mine, transfers, dones if len(devices) == 1 else None)
        leads.append({"plane": plane["name"], **lead})
        busy = union((e[1], e[1] + e[2]) for ln in plane["lines"] if ln["name"] == OP_LINE
                     for e in ln["events"])
        rest = subtract([(busy[0][0], busy[-1][1])], busy)
        raw_ns += length(rest)
        for cat, ns in _partition(rest, by_category).items():
            raw_by[cat] += ns
        if "lead_ns" in lead:
            shifted = [(a + lead["lead_ns"], b + lead["lead_ns"]) for a, b in rest]
            idle_ns += length(shifted)
            for cat, ns in _partition(shifted, by_category).items():
                idle_by[cat] += ns
    sound = bool(devices) and bool(spans) and all("lead_ns" in ld for ld in leads)
    def seconds(by):
        return {c: ns / 1e9 for c, ns in by.items()}

    return {
        "spans": spans,
        "devices": len(devices),
        "leads": leads,
        "lead_ms": sum(ld["lead_ns"] for ld in leads) / len(leads) / 1e6 if sound else None,
        "idle_s": idle_ns / 1e9 if sound else None,
        "idle_by": seconds(idle_by) if sound else None,
        "idle_s_uncorrected": raw_ns / 1e9 if devices else None,
        "idle_by_uncorrected": seconds(raw_by) if devices and spans else None,
    }


def read_xplane(path: str) -> list[dict]:
    """The host plane's program spans and the three runtime events the
    lead is measured from, and the device planes' two lines, with the
    stats that pair a module with its launch."""
    from jax.profiler import ProfileData

    runtime = {LAUNCH: ("run_id", "device_ordinal"), QUEUED: ("queued_executions_count",), DONE: ()}
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for ln in plane.lines:
            if device and ln.name not in (OP_LINE, MODULE_LINE):
                continue
            events = []
            for e in ln.events:
                if device:
                    keep = ("run_id",) if ln.name == MODULE_LINE else ()
                elif e.name in runtime:
                    keep = runtime[e.name]
                elif e.name.startswith(PREFIXES):
                    keep = ()
                else:
                    continue
                stats = {k: v for k, v in e.stats if k in keep} if keep else {}
                events.append((e.name, int(e.start_ns), int(e.duration_ns), stats))
            lines.append({"name": ln.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def main() -> int:
    trace_dir, out_path = sys.argv[1:3]
    path = find_xplane(trace_dir)
    if path is None:
        print(f"no .xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    with open(out_path, "w") as f:
        json.dump(reduce_planes(read_xplane(path)), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
