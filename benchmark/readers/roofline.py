"""A share of the HBM roofline in the traced slice, %: of one named
program, or of a whole engine.

Least time = the bytes an exact answer has to read (harness/min_bytes.py:
each distinct stored plane once) over the HBM peak; divided by the time
the device took. One reader for both kinds, so that the engines' two
readers (scan_roofline.py, mesh_roofline.py) can be pointed here too.

A reply counts when its request was SENT and completed inside the slice:
its device work then lies inside the slice for certain. The two older
readers count by completion alone, which is the same to a percent while
a request takes a hundredth of the slice, and wrong once a request takes
as long as the slice: a server that compiles in every request answers in
seconds, the replies completed inside a 3 s slice did their device work
before it, and the share read 112 % (my chip run, PR 32, the parent on
taxi-128r.adhoc_range). Such a run has no reply inside the slice and
reads nothing here.

params:
  module_prefix  given: ONE program. The mean least bytes of the good
                 replies inside the slice, over the peak, over
                 the mean device time of a launch of the programs whose
                 name starts with it (``XLA Modules`` line of the trace,
                 harness/host_spans.py). Give ``text_prefix`` with it, so
                 that the replies counted are those the program answers.
                 absent: the WHOLE engine of ``route``. The least bytes of
                 all those replies together over the device's busy time
                 in the slice, scaled as scan_roofline.py scales them: by
                 the window's share of calls routed to ``route``, by the
                 share the result cache missed and by the share that
                 single-flight dedup did not answer (such replies read
                 nothing from HBM).
  text_prefix    count only the replies whose query text starts with it
  route          "device" (default) or "mesh": the label of
                 ``queries_routed``; on "mesh" the chips' peaks add (each
                 reads its own shards of every plane) and the busy time
                 is the device planes' mean
  int_planes     how many planes a BSI int field counts:
                 "declared"  its declared depth + 1 (``bits``:
                             min_bytes.py's rule as run.py applies it)
                 "filled"    the planes that hold data, ``bits_filled``
                             + 1 from the configuration's schema. An
                             exact answer has to read those and no more;
                             where the data fills fewer bits than are
                             declared, "declared" counts planes that do
                             not exist and can read over 100.

No trace, no such launch or no such reply gives nothing, never 0."""

from ..harness import pql
from ..harness.min_bytes import min_bytes
from . import host_spans, prom_delta


def _least_bytes(params: dict, ctx: dict) -> list[int]:
    """The least bytes of every good reply sent and completed inside the
    slice (and matching ``text_prefix``)."""
    cfg, tr = ctx["cfg"], ctx["trace"]
    depth = "bits_filled" if params.get("int_planes", "declared") == "filled" else "bits"
    schema = {f: ({"rows": s["rows"]} if "rows" in s else {"bits": s.get(depth, s["bits"])})
              for f, s in cfg["schema"].items()}
    columns = cfg["scale"]["shards"] * cfg["shard_width"]
    prefix = params.get("text_prefix", "")
    return [min_bytes(pql.parse(text), schema, columns)
            for _, t0, t1, status, text, _ in ctx["records"]
            if status == 200 and tr["start"] <= t0 and t1 < tr["stop"] and text.startswith(prefix)]


def read(params: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    least = _least_bytes(params, ctx)
    if not least:
        return None
    peak = ctx["peaks"]["hbm_bytes_per_s"]
    if "module_prefix" in params:
        red = host_spans.reduction(ctx)
        mine = [m for name, m in (red or {}).get("modules", {}).items()
                if name.startswith(params["module_prefix"])]
        launches = sum(m["launches"] for m in mine)
        if not launches:
            return None
        launch_s = sum(m["total_s"] for m in mine) / launches
        return sum(least) / len(least) / peak / launch_s * 100.0
    route = params.get("route", "device")
    routed = prom_delta.read(
        {"stat": "ratio", "family": "queries_routed", "labels": f'path="{route}"',
         "of": [{"family": "queries_routed"}]}, ctx)
    if not routed:
        return None
    hits = prom_delta.read(
        {"stat": "ratio", "family": "result_cache_hits_total",
         "of": [{"family": "result_cache_hits_total"}, {"family": "result_cache_misses_total"}]}, ctx)
    good = sum(1 for r in ctx["records"] if r[3] == 200 and ctx["window"][0] <= r[2] < ctx["window"][1])
    deduped = prom_delta.read({"stat": "sum", "family": "queries_deduped"}, ctx) / max(good, 1)
    total = sum(least) * routed * (1.0 - (hits or 0.0)) * (1.0 - min(deduped, 1.0))
    chips = len(tr.get("devices") or [1]) if route == "mesh" else 1
    return total / (chips * peak) / tr["busy_s"] * 100.0
