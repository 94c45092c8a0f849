"""The device's idle time by what the host was in, on a timeline with the
device planes' clock lead taken out (harness/host_partition.py makes the
reduction, once a run, in a process of its own; it is kept in ``ctx``).

params:
  stat   "lead_ms"         the planes' mean lead over the host's clock, ms
         "idle_share_pct"  share of the device's idle time that the
                           corrected partition gives to ``spans``, %
  spans  categories of the partition (harness/host_partition.py PRIORITY,
         and "no_span")

Nothing, never 0, and the reason on stderr: without a trace; where a
device plane's launches cannot be paired or nothing bounds its lead from
above (no share is read off an uncorrected timeline; bounds that
contradict the rest are outvoted and counted, they do not silence the
metric); and for a share none of whose spans the program opened in the
slice (a program from before the span). The reduction's planes, bounds and both partitions go to stderr
once a run, one JSON line: the run's log keeps what the metrics leave out."""

import json
import os
import subprocess
import sys

from benchmark.harness.host_partition import category

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def reduction(ctx: dict):
    """The run's host_partition reduction, or None without a trace."""
    if "host_partition" not in ctx:
        workdir = os.path.dirname(ctx["log_path"])
        trace_dir, out = os.path.join(workdir, "trace"), os.path.join(workdir, "host_partition.json")
        ctx["host_partition"] = None
        if os.path.isdir(trace_dir):
            proc = subprocess.run(
                [sys.executable, "-m", "benchmark.harness.host_partition", trace_dir, out],
                cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                capture_output=True, text=True, timeout=300,
            )
            if proc.returncode != 0:
                raise RuntimeError(f"host_partition reduction failed: {proc.stderr[-2000:]}")
            with open(out) as f:
                ctx["host_partition"] = red = json.load(f)
            print("host_partition " + json.dumps(red), file=sys.stderr, flush=True)
            for lead in red["leads"]:
                if "reason" in lead:
                    print(f"host_partition: {lead['plane']} has no clock lead ({lead['reason']}): "
                          "no corrected partition", file=sys.stderr, flush=True)
    return ctx["host_partition"]


def read(params: dict, ctx: dict):
    red = reduction(ctx)
    if not red:
        return None
    stat = params["stat"]
    if stat == "lead_ms":
        return red["lead_ms"]
    if stat == "idle_share_pct":
        if not red["idle_by"] or not red["idle_s"]:
            return None
        if not any(category(name) in params["spans"] for name in red["spans"]):
            return None
        return sum(red["idle_by"][c] for c in params["spans"]) / red["idle_s"] * 100.0
    raise ValueError(f"idle_partition: unknown stat {stat!r}")
