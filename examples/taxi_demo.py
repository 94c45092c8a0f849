"""NYC-taxi-style demo: bulk import + TopN / GroupBy / BSI aggregates.

Parity target: the reference's canonical 1B-ride taxi tutorial
(reference: docs/ tutorial pages; see docs/examples.md). This script
generates a synthetic ride dataset, drives a live pilosa-tpu server over
plain HTTP — the exact surface an external client uses — and runs the
tutorial's representative queries, printing results and timings.

Run (CPU is fine; scale up on TPU):

    python examples/taxi_demo.py --rides 200000

Schema (mirrors the reference demo's field layout):
    cab_type          set   (0=yellow 1=green 2=fhv)
    passenger_count   set   (1..6)
    dist_miles        int   BSI, 0..500
    total_amount      int   BSI, dollars 0..100000
    pickup_time       time  quantum YMDH
"""

from __future__ import annotations

import os as _os
import sys as _sys

# runnable from anywhere: put the repo root on sys.path
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import argparse
import json
import os
import random
import time
import urllib.request

os.environ.setdefault("PILOSA_TPU_SHARD_WIDTH_EXP", "18")

BATCH = 50_000


def call(base: str, method: str, path: str, body=None):
    data = body if isinstance(body, (bytes, type(None))) else json.dumps(body).encode()
    req = urllib.request.Request(base + path, data=data, method=method)
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read() or b"{}")


def start_server(data_dir: str):
    from pilosa_tpu.server import Server
    from pilosa_tpu.utils.config import Config

    srv = Server(
        Config(
            bind="127.0.0.1:0",
            data_dir=data_dir,
            anti_entropy_interval=0,
            # bulk loads ship 50k-bit batches; the default 5k
            # max_writes_per_request cap (HTTP 413) is for serving, not
            # offline ingest — raise it the way an operator would
            max_writes_per_request=BATCH,
        )
    )
    srv.open()
    return srv


def generate(n: int, seed: int = 11):
    rng = random.Random(seed)
    rides = []
    for col in range(n):
        rides.append(
            {
                "col": col,
                "cab": rng.choices([0, 1, 2], weights=[70, 25, 5])[0],
                "pax": rng.choices([1, 2, 3, 4, 5, 6], weights=[70, 15, 6, 5, 3, 1])[0],
                "dist": max(0, int(rng.lognormvariate(1.0, 0.8))),
                "amount": 3 + int(rng.lognormvariate(2.4, 0.7)),
                "ts": int(
                    time.mktime((2024, 1 + rng.randrange(12), 1 + rng.randrange(28),
                                 rng.randrange(24), 0, 0, 0, 0, 0))
                ),
            }
        )
    return rides


def import_rides(base: str, rides) -> None:
    for lo in range(0, len(rides), BATCH):
        chunk = rides[lo : lo + BATCH]
        cols = [r["col"] for r in chunk]
        call(base, "POST", "/index/taxi/field/cab_type/import",
             {"rowIDs": [r["cab"] for r in chunk], "columnIDs": cols})
        call(base, "POST", "/index/taxi/field/passenger_count/import",
             {"rowIDs": [r["pax"] for r in chunk], "columnIDs": cols})
        call(base, "POST", "/index/taxi/field/pickup_time/import",
             {"rowIDs": [0] * len(chunk), "columnIDs": cols,
              "timestamps": [r["ts"] for r in chunk]})
        call(base, "POST", "/index/taxi/field/dist_miles/import-value",
             {"columnIDs": cols, "values": [r["dist"] for r in chunk]})
        call(base, "POST", "/index/taxi/field/total_amount/import-value",
             {"columnIDs": cols, "values": [r["amount"] for r in chunk]})


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rides", type=int, default=200_000)
    ap.add_argument("--data-dir", default=None)
    args = ap.parse_args()

    import tempfile

    data_dir = args.data_dir or tempfile.mkdtemp(prefix="taxi_demo_")
    srv = start_server(data_dir)
    base = f"http://127.0.0.1:{srv.port}"
    print(f"server up at {base}, data in {data_dir}")

    call(base, "POST", "/index/taxi", {})
    call(base, "POST", "/index/taxi/field/cab_type", {})
    call(base, "POST", "/index/taxi/field/passenger_count", {})
    call(base, "POST", "/index/taxi/field/pickup_time",
         {"options": {"type": "time", "timeQuantum": "YMDH"}})
    call(base, "POST", "/index/taxi/field/dist_miles",
         {"options": {"type": "int", "min": 0, "max": 500}})
    call(base, "POST", "/index/taxi/field/total_amount",
         {"options": {"type": "int", "min": 0, "max": 100000}})

    print(f"generating {args.rides:,} rides…")
    rides = generate(args.rides)
    t0 = time.perf_counter()
    import_rides(base, rides)
    dt = time.perf_counter() - t0
    print(f"imported {args.rides:,} rides in {dt:.1f}s "
          f"({args.rides / dt:,.0f} rides/s over HTTP)")

    queries = [
        "TopN(passenger_count, n=5)",
        "TopN(cab_type, n=3)",
        "Count(Intersect(Row(cab_type=0), Row(passenger_count=2)))",
        "GroupBy(Rows(cab_type), Rows(passenger_count), limit=8)",
        "Sum(Row(cab_type=0), field=total_amount)",
        "Min(field=dist_miles) Max(field=dist_miles)",
        "Count(Row(dist_miles > 10))",
        "GroupBy(Rows(cab_type), aggregate=Sum(field=total_amount))",
        'Count(Row(pickup_time=0, from="2024-06-01T00:00", to="2024-09-01T00:00"))',
    ]
    for q in queries:
        t0 = time.perf_counter()
        resp = call(base, "POST", "/index/taxi/query", q.encode())
        ms = (time.perf_counter() - t0) * 1e3
        print(f"\n  {q}\n    → {json.dumps(resp['results'])[:300]}   [{ms:.1f} ms]")

    srv.close()
    print("\ndone.")


if __name__ == "__main__":
    main()
