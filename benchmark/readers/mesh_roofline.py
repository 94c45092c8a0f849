"""The mesh engine's share of the HBM roofline of ALL its chips in the
traced slice, %.

Least time = the bytes an exact answer has to read (harness/min_bytes.py)
for every good reply completed inside the slice, over the HBM peak of the
device planes together (each chip reads its own shards of every plane, so
the chips' peaks add); divided by the planes' mean busy time. As
scan_roofline.py does for the device engine, the bytes are scaled by the
window's share of calls the router sent down the mesh route, by the share
the result cache missed and by the share that single-flight dedup did
not answer: those replies read nothing from HBM. One share for the whole
engine. It cannot pass 100: every mesh program reads at least the planes
counted here, and a chip is busy at least while it reads its part."""

from . import prom_delta


def read(params: dict, ctx: dict):
    tr = ctx.get("trace")
    planes = (tr or {}).get("devices") or []
    if not planes or not tr.get("busy_s"):
        return None
    lo, hi = tr["start"], tr["stop"]
    total = 0
    for _, _, t1, status, pql, _ in ctx["records"]:
        if status == 200 and lo <= t1 < hi:
            total += ctx["min_bytes"](pql)
    mesh = prom_delta.read(
        {"stat": "ratio", "family": "queries_routed", "labels": 'path="mesh"',
         "of": [{"family": "queries_routed"}]}, ctx)
    hits = prom_delta.read(
        {"stat": "ratio", "family": "result_cache_hits_total",
         "of": [{"family": "result_cache_hits_total"}, {"family": "result_cache_misses_total"}]}, ctx)
    if not total or not mesh:
        return None
    good = sum(1 for r in ctx["records"] if r[3] == 200 and ctx["window"][0] <= r[2] < ctx["window"][1])
    deduped = prom_delta.read({"stat": "sum", "family": "queries_deduped"}, ctx) / max(good, 1)
    total *= mesh * (1.0 - (hits or 0.0)) * (1.0 - min(deduped, 1.0))
    least_s = total / (len(planes) * ctx["peaks"]["hbm_bytes_per_s"])
    return least_s / tr["busy_s"] * 100.0
