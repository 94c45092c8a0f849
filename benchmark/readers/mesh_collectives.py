"""What exists only across chips, from the device planes of the run's
profiler trace.

params:
  stat  "collective_share_pct"  share of the devices' busy time spent in
                                collective operations, %: per device plane
                                the union of the ``XLA Ops`` intervals of
                                the collectives over the union of all its
                                ops' intervals, both summed over the planes
        "busy_skew_pct"         (max - min) / mean of the planes' busy
                                time, %: 0 when the chips work alike

A collective is an op of the ``XLA Ops`` line whose HLO name starts with
one of ``COLLECTIVES`` (``%all-reduce.3 = ...``, ``%all-gather-start``),
or whose name carries the program's scope ``pilosa.mesh_psum``
(parallel/mesh.py puts its psum trees under it; this profiler keeps scope
names in the ops' metadata, which ``ProfileData`` does not show, so the
HLO name is what matches today).

The reduction reads the ``.xplane.pb`` in a process of its own on the CPU
(the run's parent stays jax-free), once a run; it is kept in ``ctx``. A
trace with one device plane or none gives nothing, never 0.

    python -m benchmark.readers.mesh_collectives <trace dir> <out.json>
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmark.harness.reduce_trace import DEVICE_PLANE_PREFIX, OP_LINE, find_xplane, union_seconds

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
SCOPE = "pilosa.mesh_psum"


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVES) or SCOPE in name


def reduce_planes(planes: list[dict]) -> list[dict]:
    """``planes``: [{"name", "ops": [(start_ns, dur_ns, collective)]}]
    -> per device plane its busy and collective seconds (unions)."""
    out = []
    for plane in planes:
        every = [(s, s + d) for s, d, _ in plane["ops"]]
        coll = [(s, s + d) for s, d, c in plane["ops"] if c]
        out.append({"plane": plane["name"], "busy_s": union_seconds(every),
                    "collective_s": union_seconds(coll), "collectives": len(coll)})
    return out


def read_xplane(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        ops = [(int(e.start_ns), int(e.duration_ns), is_collective(e.name))
               for ln in plane.lines if ln.name == OP_LINE for e in ln.events]
        planes.append({"name": plane.name, "ops": ops})
    return planes


def reduction(ctx: dict):
    """The run's per-plane reduction, or None without a trace."""
    if "mesh_collectives" not in ctx:
        workdir = os.path.dirname(ctx["log_path"])
        trace_dir, out = os.path.join(workdir, "trace"), os.path.join(workdir, "mesh_collectives.json")
        ctx["mesh_collectives"] = None
        if os.path.isdir(trace_dir):
            proc = subprocess.run(
                [sys.executable, "-m", "benchmark.readers.mesh_collectives", trace_dir, out],
                cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                capture_output=True, text=True, timeout=300,
            )
            if proc.returncode != 0:
                raise RuntimeError(f"mesh_collectives reduction failed: {proc.stderr[-2000:]}")
            with open(out) as f:
                ctx["mesh_collectives"] = json.load(f)
    return ctx["mesh_collectives"]


def read(params: dict, ctx: dict):
    stat = params["stat"]
    if stat == "busy_skew_pct":
        busy = [d["busy_s"] for d in (ctx.get("trace") or {}).get("devices") or []]
        if len(busy) < 2 or not sum(busy):
            return None
        return (max(busy) - min(busy)) / (sum(busy) / len(busy)) * 100.0
    if stat == "collective_share_pct":
        planes = reduction(ctx) or []
        busy = sum(p["busy_s"] for p in planes)
        if len(planes) < 2 or not busy:
            return None
        return sum(p["collective_s"] for p in planes) / busy * 100.0
    raise ValueError(f"mesh_collectives: unknown stat {stat!r}")


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
