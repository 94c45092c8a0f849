"""Closed-loop load from persistent HTTP/1.1 connections.

One generator process holds a few client threads; each thread owns one
keep-alive connection and one seeded traffic generator, sends a request,
waits for the last byte of the reply, and sends the next. A single Python
process cannot out-run the server on cached or host-routed queries, so
the clients of a cell are spread over ``processes`` of these.

Every request is timed on this side with CLOCK_MONOTONIC, which all
processes of a machine share, so the parent can place a reply inside the
window or the traced slice.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from . import traffic
from .server import Client

REQUEST_TIMEOUT_S = 60.0  # an answer may come a minute past the close


def _client(base, path, gen, keep_rng, check_share, t_start, t_end, out):
    c = Client(base, timeout=REQUEST_TIMEOUT_S)
    time.sleep(max(0.0, t_start - time.monotonic()))
    while time.monotonic() < t_end:
        ti, pql = gen.draw()
        keep = keep_rng.random() < check_share
        t0 = time.monotonic()
        try:
            status, body = c.request("POST", path, pql.encode())
        except Exception:  # counted as a failed request, never hidden
            status, body = 0, b""
            c.close()
        t1 = time.monotonic()
        out.append((ti, t0, t1, status, pql, body if keep or status != 200 else None))
    c.close()


def process_main(pipe, base: str, index: str, spec: dict, seed: int, clients: list[int]):
    """Entry of one generator process (spawned): build the clients, say
    ready, take the window's start and end, run, send the records."""
    path = f"/index/{index}/query"
    records: list[list] = [[] for _ in clients]
    threads = []
    pipe.send("ready")
    t_start, t_end = pipe.recv()
    for k, cid in enumerate(clients):
        gen = traffic.Generator(spec, [seed, 0x7AF1C, cid])
        keep_rng = np.random.default_rng([seed, 0xC4EC, cid])
        threads.append(threading.Thread(
            target=_client,
            args=(base, path, gen, keep_rng, float(spec.get("check_share", 1.0)),
                  t_start, t_end, records[k]),
        ))
    time.sleep(max(0.0, t_start - 0.05 - time.monotonic()))
    cpu0, wall0 = time.process_time(), time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    cpu_s, wall_s = time.process_time() - cpu0, time.monotonic() - wall0
    pipe.send({"records": [r for rs in records for r in rs], "cpu_s": cpu_s, "wall_s": wall_s})
    pipe.close()
