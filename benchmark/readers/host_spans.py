"""What the host was doing, read from the program's own spans in the
profiler trace of the run's slice (harness/host_spans.py makes the
reduction, once a run, in a process of its own; it is kept in ``ctx``).

params:
  stat   "mean_ms"         mean duration of ``span`` in the slice, ms
         "idle_share_pct"  share of the device's idle time that the
                           partition gives to the categories ``spans``, %
  span   a span name, or a family as "executor.*"
  spans  categories of the partition (harness/host_spans.py PRIORITY, and
         "no_span")

A trace without the program's spans (a program from before them), or
without that span, gives nothing, never 0."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def reduction(ctx: dict):
    """The run's host_spans reduction, or None without a trace."""
    if "host_spans" not in ctx:
        workdir = os.path.dirname(ctx["log_path"])
        trace_dir, out = os.path.join(workdir, "trace"), os.path.join(workdir, "host_spans.json")
        ctx["host_spans"] = None
        if os.path.isdir(trace_dir):
            proc = subprocess.run(
                [sys.executable, "-m", "benchmark.harness.host_spans", trace_dir, out],
                cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                capture_output=True, text=True, timeout=300,
            )
            if proc.returncode != 0:
                raise RuntimeError(f"host_spans reduction failed: {proc.stderr[-2000:]}")
            with open(out) as f:
                ctx["host_spans"] = json.load(f)
    return ctx["host_spans"]


def read(params: dict, ctx: dict):
    red = reduction(ctx)
    if not red:
        return None
    stat = params["stat"]
    if stat == "mean_ms":
        span = red["spans"].get(params["span"])
        return span["mean_ms"] if span else None
    if stat == "idle_share_pct":
        if not red["idle_by"] or not red["idle_s"]:
            return None
        return sum(red["idle_by"][c] for c in params["spans"]) / red["idle_s"] * 100.0
    raise ValueError(f"host_spans: unknown stat {stat!r}")
