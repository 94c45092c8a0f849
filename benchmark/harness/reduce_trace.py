"""From a profiler trace (``.xplane.pb``) to device busy time and the
device operations that took most of it.

    python benchmark/harness/reduce_trace.py <trace dir> <out.json>

Runs as a process of its own, after the server has gone, with
``JAX_PLATFORMS=cpu``: reading a trace needs jaxlib's ``ProfileData``
and nothing of a backend, and the parent of a run stays jax-free.

Busy time of a device is the UNION of the intervals in which an
operation ran on it (the events of its ``XLA Ops`` line: ops of
overlapping programs are not counted twice); it is averaged over the
device planes found. A device plane without that line is an error, not a
reason to read another. Gaps cannot be attributed to host work yet: no host
span of the program shares the trace's clock (PERF.md, tracing list).
"""

from __future__ import annotations

import glob
import json
import os
import sys

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"  # the device's own timeline of executed ops


def union_seconds(intervals: list[tuple[int, int]]) -> float:
    """Total length covered by [start, end) intervals given in ns."""
    total, cur_start, cur_end = 0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total / 1e9


def reduce_planes(planes: list[dict]) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns)]}]}] -> busy seconds per device, top operations, the span of
    the device events."""
    per_device, op_seconds, spans = [], {}, []
    for plane in planes:
        if not plane["name"].startswith(DEVICE_PLANE_PREFIX):
            continue
        lines = [ln for ln in plane["lines"] if ln["name"] == OP_LINE]
        if not lines:
            raise ValueError(f"plane {plane['name']} has no {OP_LINE!r} line: "
                             f"{[ln['name'] for ln in plane['lines']]}")
        intervals = []
        for ln in lines:
            for name, start, dur in ln["events"]:
                intervals.append((start, start + dur))
                op_seconds[name] = op_seconds.get(name, 0.0) + dur / 1e9
        if intervals:
            spans.append((min(i[0] for i in intervals), max(i[1] for i in intervals)))
        per_device.append({"plane": plane["name"], "busy_s": union_seconds(intervals),
                           "events": len(intervals),
                           "lines": [ln["name"] for ln in plane["lines"]]})
    top = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": per_device,
        "busy_s": (sum(d["busy_s"] for d in per_device) / len(per_device)) if per_device else None,
        "device_span_s": (max(s[1] for s in spans) - min(s[0] for s in spans)) / 1e9 if spans else None,
        # the trace names an op by its whole HLO text; its head is enough
        "device_ops": [[name[:160], seconds] for name, seconds in top],
    }


def read_xplane(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for ln in plane.lines:
            keep = plane.name.startswith(DEVICE_PLANE_PREFIX)
            events = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in ln.events] if keep else []
            lines.append({"name": ln.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def main() -> int:
    trace_dir, out_path = sys.argv[1:3]
    path = find_xplane(trace_dir)
    if path is None:
        print(f"no .xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    planes = read_xplane(path)
    out = reduce_planes(planes)
    out["trace_bytes"] = os.path.getsize(path)
    out["planes"] = [{"name": p["name"], "lines": [ln["name"] for ln in p["lines"]]} for p in planes]
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
