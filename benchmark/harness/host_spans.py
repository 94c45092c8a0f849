"""From a profiler trace (``.xplane.pb``) to what the HOST was doing while
the device sat idle, and to the device time of each named program.

    python -m benchmark.harness.host_spans <trace dir> <out.json>

Runs as a process of its own with ``JAX_PLATFORMS=cpu``, from the
checkout's root, beside reduce_trace.py (whose plane and line names it
shares): reading a trace needs jaxlib's ``ProfileData`` and no backend.
The program writes every ``GLOBAL_TRACER.span`` into the trace's
``/host:CPU`` plane while a profiler session records
(pilosa_tpu/utils/tracing.py), one line per thread, so its spans share a
file and a timeline with the device planes' ``XLA Ops`` and ``XLA Modules``.

Written out:

- ``spans``: per span name its count, total seconds and mean ms
  (``executor.*`` and ``http.*`` also under their family name);
- ``idle_s``: the device's idle time, the complement of the union of its
  ``XLA Ops`` intervals inside the span of those ops, summed over the
  device planes;
- ``idle_by``: a PARTITION of that idle time by the rule in ``PRIORITY``
  below: seconds per category, summing to ``idle_s``. None when the
  trace holds no span of the program (a program from before the spans):
  nothing to read is not a share of 0;
- ``modules``: per ``XLA Modules`` name, with the ``(id)`` suffix cut, its
  launches, total device seconds and mean ms a launch.

The device planes carry the device's clock mapped onto the host's, and in
the two small recordings beside the tests every module starts 1.0-1.4 ms
BEFORE the host call that launched it (PERF.md, open questions). Nothing
here corrects that: a span of 60 ms is attributed soundly, one of 1 ms
is not.
"""

from __future__ import annotations

import json
import re
import sys

from .reduce_trace import DEVICE_PLANE_PREFIX, OP_LINE, find_xplane

HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"

# the program's span names (exact) and families (prefix): the contract
# with pilosa_tpu; an event of the host plane under any other name is the
# runtime's, not the program's
SPAN_NAMES = (
    "pql.query", "pql.reply",
    "scheduler.await", "scheduler.window", "scheduler.wave", "scheduler.query",
    "scheduler.readback", "readback.join", "readback.transfer",
    "stack.pack", "stack.upload", "stack.delta",
    "mesh.dispatch", "mesh.collective",
)
SPAN_FAMILIES = ("executor.", "http.")

# The partition rule, as data. A thread is IN the innermost of its open
# spans (so ``pql.query`` means its self time: parse, route, settle; a
# thread blocked in ``scheduler.await`` under it is in ``scheduler.await``).
# An idle instant goes to the first category below that ANY thread is in;
# with no thread in any span it is ``no_span``. Work first, in the order
# of the serving path from the device outward; waiting last.
PRIORITY = (
    "readback.join", "readback.transfer",
    "stack.pack", "stack.upload", "stack.delta",
    "executor.*", "mesh.*",
    "scheduler.wave",  # the leader between dispatches and at settle
    "pql.reply", "pql.query", "http.*",
    "scheduler.window", "scheduler.await",
)
NO_SPAN = "no_span"
_FOLD = {"scheduler.query": "scheduler.wave", "scheduler.readback": "scheduler.wave",
         "mesh.dispatch": "mesh.*", "mesh.collective": "mesh.*"}


def category(name: str) -> str | None:
    """The partition category of a span name; None for a foreign event."""
    for family in SPAN_FAMILIES:
        if name.startswith(family):
            return family + "*"
    if name in SPAN_NAMES:
        return _FOLD.get(name, name)
    return None


# ------------------------------------------------------- interval arithmetic
# a "set" is a sorted list of disjoint [start, end) pairs, in ns
def union(intervals) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def intersect(a, b) -> list[tuple[int, int]]:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list[tuple[int, int]]:
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, cur = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def length(a) -> int:
    return sum(end - start for start, end in a)


# ----------------------------------------------------------------- reduction
def innermost(events) -> list[tuple[int, int, str]]:
    """One thread's span events [(start, end, name)] -> disjoint segments
    [(start, end, name of the innermost span open there)]. Spans of one
    thread nest; a child that overruns its parent by a rounding is cut."""
    out: list[tuple[int, int, str]] = []
    stack: list[tuple[int, int, str]] = []
    cursor = 0

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            _, end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close_until(start)
        if stack:
            if start > cursor:
                out.append((cursor, start, stack[-1][2]))
            end = min(end, stack[-1][1])
        cursor = max(cursor, start) if stack else start
        if end > start:
            stack.append((start, end, name))
    close_until(float("inf"))
    return out


def reduce_planes(planes: list[dict]) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns)]}]}] as read_xplane gives them."""
    # the program's spans, thread by thread
    spans: dict[str, dict] = {}
    in_category: dict[str, list] = {}
    for plane in planes:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            mine = []
            for name, start, dur in line["events"]:
                cat = category(name)
                if cat is None:
                    continue
                mine.append((start, start + dur, name))
                for key in (name, cat) if cat.endswith("*") else (name,):
                    s = spans.setdefault(key, {"count": 0, "total_s": 0.0})
                    s["count"] += 1
                    s["total_s"] += dur / 1e9
            for start, end, name in innermost(mine):
                in_category.setdefault(category(name), []).append((start, end))
    for s in spans.values():
        s["mean_ms"] = s["total_s"] / s["count"] * 1e3
    by_category = {c: union(v) for c, v in in_category.items()}

    idle_ns, idle_by, modules, devices = 0, dict.fromkeys((*PRIORITY, NO_SPAN), 0), {}, 0
    for plane in planes:
        if not plane["name"].startswith(DEVICE_PLANE_PREFIX):
            continue
        ops = [(s, s + d) for ln in plane["lines"] if ln["name"] == OP_LINE
               for _, s, d in ln["events"]]
        mods = [e for ln in plane["lines"] if ln["name"] == MODULE_LINE for e in ln["events"]]
        for name, _, dur in mods:
            m = modules.setdefault(re.sub(r"\(\d+\)$", "", name), {"launches": 0, "total_s": 0.0})
            m["launches"] += 1
            m["total_s"] += dur / 1e9
        if not ops:
            continue
        devices += 1
        busy = union(ops)
        rest = subtract([(busy[0][0], busy[-1][1])], busy)
        idle_ns += length(rest)
        for cat in PRIORITY:
            took = intersect(rest, by_category.get(cat, []))
            idle_by[cat] += length(took)
            rest = subtract(rest, took)
        idle_by[NO_SPAN] += length(rest)
    for m in modules.values():
        m["mean_ms"] = m["total_s"] / m["launches"] * 1e3
    return {
        "spans": spans,
        "devices": devices,
        "idle_s": idle_ns / 1e9 if devices else None,
        "idle_by": {c: ns / 1e9 for c, ns in idle_by.items()} if devices and spans else None,
        "modules": modules,
    }


def read_xplane(path: str) -> list[dict]:
    """Host and device planes with the lines this reduction reads."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for ln in plane.lines:
            if device and ln.name not in (OP_LINE, MODULE_LINE):
                continue
            events = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in ln.events
                      if device or category(e.name) is not None]
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
