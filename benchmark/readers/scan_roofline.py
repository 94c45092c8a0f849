"""The device engine's share of the HBM roofline in the traced slice, %.

Least time = the bytes an exact answer has to read (harness/min_bytes.py)
for every good reply completed inside the slice, over the chip's HBM
peak; divided by the device's busy time in the slice. A reply the result
cache or the host engine answered, or that shared an identical query's
execution in its wave, read nothing from HBM, so the bytes are scaled by
the window's share of calls the router sent to the device, by the share
the result cache missed and by the share that single-flight dedup did
not answer. One share for the whole engine: the trace has no named
scopes to tell kernels apart yet."""

from . import prom_delta


def read(params: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    lo, hi = tr["start"], tr["stop"]
    total = 0
    for _, _, t1, status, pql, _ in ctx["records"]:
        if status == 200 and lo <= t1 < hi:
            total += ctx["min_bytes"](pql)
    device = prom_delta.read(
        {"stat": "ratio", "family": "queries_routed", "labels": 'path="device"',
         "of": [{"family": "queries_routed"}]}, ctx)
    hits = prom_delta.read(
        {"stat": "ratio", "family": "result_cache_hits_total",
         "of": [{"family": "result_cache_hits_total"}, {"family": "result_cache_misses_total"}]}, ctx)
    if not total or not device:
        return None
    good = sum(1 for r in ctx["records"] if r[3] == 200 and ctx["window"][0] <= r[2] < ctx["window"][1])
    deduped = prom_delta.read({"stat": "sum", "family": "queries_deduped"}, ctx) / max(good, 1)
    total *= device * (1.0 - (hits or 0.0)) * (1.0 - min(deduped, 1.0))
    least_s = total / ctx["peaks"]["hbm_bytes_per_s"]
    return least_s / tr["busy_s"] * 100.0
