"""Headline benchmark: PQL Intersect+Count QPS + TopN p50 at
multi-billion-column scale, through the REAL serving path.

BASELINE.md north star: PQL Intersect+Count QPS and TopN p50 latency on a
10B-column index. Unlike round 2 (which measured the raw fused kernel on
two flat arrays), every timed query here goes through the executor/
compiler: PQL AST → planner → StackCache-resident [R, S, W] device stack
→ compiled program → on-device reduction. The headline number is the
pipelined executor QPS (`QueryCompiler.count_async`, readback overlapped
— how a serving system issues queries); sync end-to-end latency
(parse → scalar on host) and TopN p50 are reported alongside.

The CPU baseline is the measured host execution of the same queries on
packed words (numpy bitwise ops + bitwise_count — generous to the
reference: upstream pilosa's Go roaring loops are at best comparable to
numpy's vectorized popcount at this density).

Data loading uses a bench-only shortcut: fragments' packed host matrices
are injected directly (shared blocks) instead of importing billions of
individual bits through roaring — the IMPORT path is not what this
bench measures, and the QUERY path (stacking, upload, planning,
compiled programs, readback) is identical to production.

Prints ONE final JSON line to stdout:
    {"metric", "value", "unit", "vs_baseline", "platform", "device_kind",
     "device_count", "topn_p50_ms", ...}

One process per chip: the parent never imports jax; it runs the
measurement in ONE child at a time (the only process that touches the
chip) and steps the operand scale down when a child dies (device OOM).
A child that finds no accelerator fails, and so does the parent: there
is no CPU fallback, and no number from a CPU run is ever printed.
Stage-by-stage progress goes to stderr.

Scale knobs via env:
    PILOSA_BENCH_SHARDS        (default 10240 → 10240·2^20 ≈ 10.7B columns,
                                the BASELINE.md north-star scale; an
                                [8, S, W] ≈ 10.7 GB stack resident in HBM)
    PILOSA_BENCH_CPU_ITERS / PILOSA_BENCH_TPU_ITERS
    PILOSA_BENCH_TOTAL_BUDGET  (parent wall-clock budget, s)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

TOTAL_BUDGET_S = float(os.environ.get("PILOSA_BENCH_TOTAL_BUDGET", "1500"))
# a child that finds no accelerator exits with this code: the parent
# stops instead of stepping the scale down (no smaller size has a chip)
NO_ACCELERATOR_RC = 4
NO_ACCELERATOR = "no accelerator (JAX found only the CPU backend)"
FULL_SHARDS = int(os.environ.get("PILOSA_BENCH_SHARDS", "10240"))
R_PAD = 8  # field rows per fragment; the parent sizes the device budget
# from this, the child builds the [R_PAD, S, W] stack with it


def _stage(msg: dict) -> None:
    print(json.dumps(msg), file=sys.stderr, flush=True)


def _metric_name(n_columns: int) -> str:
    scale = (
        f"{n_columns // 10**9}B" if n_columns >= 10**9 else f"{n_columns // 10**6}M"
    )
    return f"intersect_count_qps_{scale}_columns"


# --------------------------------------------------------------------- child
def _child_main(n_shards: int) -> None:
    """Measure at one scale; print one JSON result line on stdout."""
    import numpy as np

    t0 = time.perf_counter()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    device = {
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    _stage({"stage": "init_ok", **device,
            "seconds": round(time.perf_counter() - t0, 1)})
    if platform == "cpu":
        _stage({"stage": "no_accelerator",
                "error": "JAX found only the CPU backend"})
        sys.exit(NO_ACCELERATOR_RC)

    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.pql import parse
    from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD

    cpu_iters = int(os.environ.get("PILOSA_BENCH_CPU_ITERS", "5"))
    tpu_iters = int(os.environ.get("PILOSA_BENCH_TPU_ITERS", "50"))
    n_columns = n_shards * SHARD_WIDTH
    # per-call host/device routing (docs/query-routing.md):
    # PILOSA_TPU_ROUTE_MODE=host routes every query down the vectorized
    # numpy fast path — measured below as the headline instead of the
    # device-pipelined QPS
    route_mode = os.environ.get("PILOSA_TPU_ROUTE_MODE", "") or "auto"

    # ------------- build the index: G distinct packed blocks cycled over
    # the shards (generation stays O(G), the stacked upload and every
    # query remain the full O(S) work)
    rng = np.random.default_rng(7)
    G = min(n_shards, 64)
    blocks = []
    for _ in range(G):
        blk = rng.integers(0, 2**32, (R_PAD, WORDS_PER_SHARD), dtype=np.uint32)
        blk &= rng.integers(0, 2**32, (R_PAD, WORDS_PER_SHARD), dtype=np.uint32)
        blk &= rng.integers(0, 2**32, (R_PAD, WORDS_PER_SHARD), dtype=np.uint32)
        blocks.append(blk)

    h = Holder(None)
    idx = h.create_index("bench")
    f = idx.create_field("f")
    view = f.create_view_if_not_exists("standard")
    for s in range(n_shards):
        frag = view.create_fragment_if_not_exists(s)
        # bench-only shortcut: inject the packed matrix (see module doc)
        frag._np_matrix = blocks[s % G]
        frag._all_dirty = False
    shards = list(range(n_shards))
    e = Executor(h)
    _stage({"stage": "index_built", "shards": n_shards, "columns": n_columns})

    # ------------- CPU baseline (the reference's single-node hot loop):
    # contiguous row arrays, one vectorized pass per query
    row1 = np.stack([blocks[s % G][1] for s in range(n_shards)])
    row2 = np.stack([blocks[s % G][2] for s in range(n_shards)])

    def cpu_query():
        return int(np.bitwise_count(row1 & row2).sum())

    expect = cpu_query()  # warm page cache + correctness anchor
    t0 = time.perf_counter()
    for _ in range(cpu_iters):
        got = cpu_query()
    cpu_seconds = (time.perf_counter() - t0) / cpu_iters
    assert got == expect
    _stage({"stage": "cpu_baseline", "qps": round(1 / cpu_seconds, 3)})

    # ------------- executor path: build + upload the resident stack
    # (timed apart from the first execute so compile time is visible)
    pql = "Count(Intersect(Row(f=1), Row(f=2)))"
    if route_mode != "host":
        t0 = time.perf_counter()
        dev_stack, _rows = e.compiler.stacks.matrix(idx, f, "standard", shards)
        dev_stack.block_until_ready()
        _stage({"stage": "stack_built",
                "seconds": round(time.perf_counter() - t0, 1),
                "stack_gb": round(n_shards * R_PAD * WORDS_PER_SHARD * 4 / 2**30, 2)})
    t0 = time.perf_counter()
    first = e.execute("bench", pql, shards=shards)[0]
    _stage({"stage": "first_query_compiled",
            "seconds": round(time.perf_counter() - t0, 1)})
    assert first == expect, f"executor {first} != CPU {expect}"
    route = e.route_for("bench", pql, shards)
    _stage({"stage": "route", "route": route, "mode": route_mode})

    # pipelined QPS: issue the whole batch through the compiler, sync once.
    # On the host route there is nothing to pipeline (no readback to
    # overlap): the headline is the sync executor rate through the
    # vectorized host fast path — the engine the router actually picked.
    inner = parse(pql)[0].children[0]

    if route == "host":

        def pipelined(iters: int) -> float:
            t0 = time.perf_counter()
            for _ in range(iters):
                e.execute("bench", pql, shards=shards)
            return (time.perf_counter() - t0) / iters

    else:

        def pipelined(iters: int) -> float:
            t0 = time.perf_counter()
            out = None
            for _ in range(iters):
                out = e.compiler.count_async(idx, inner, shards)
            out.block_until_ready()
            return (time.perf_counter() - t0) / iters

    pipelined(3)  # warm
    tpu_seconds = pipelined(tpu_iters)
    _stage({"stage": "executor_qps", "qps": round(1 / tpu_seconds, 2)})

    # sync end-to-end latency: parse → execute → host scalar. Latencies
    # accumulate into the serving stack's own log-bucketed Histogram so
    # the artifact records the tail (p95/p99), not just the median —
    # under fan-out skew the tail IS the product metric.
    from pilosa_tpu.utils.stats import Histogram

    def hist_ms(h: Histogram) -> dict:
        return {
            "p50_ms": round(h.percentile(0.50) * 1e3, 2),
            "p95_ms": round(h.percentile(0.95) * 1e3, 2),
            "p99_ms": round(h.percentile(0.99) * 1e3, 2),
        }

    e2e_hist = Histogram()
    lats = []
    for _ in range(min(tpu_iters, 30)):
        t0 = time.perf_counter()
        e.execute("bench", pql, shards=shards)
        lats.append(time.perf_counter() - t0)
        e2e_hist.observe(lats[-1])
    e2e_p50_ms = sorted(lats)[len(lats) // 2] * 1e3

    # dispatch+readback floor: a trivial sync round trip, reported so
    # e2e/TopN latencies are interpretable (device work is the delta)
    import jax.numpy as jnp

    tiny = jax.jit(lambda v: v + 1)
    tz = jnp.zeros((8,), jnp.int32)
    np.asarray(tiny(tz))
    lats = []
    for _ in range(10):
        t0 = time.perf_counter()
        np.asarray(tiny(tz))
        lats.append(time.perf_counter() - t0)
    rtt_ms = sorted(lats)[len(lats) // 2] * 1e3
    _stage({"stage": "transport_rtt", "ms": round(rtt_ms, 1)})

    # ------------- TopN p50 (the other half of the north star): exact
    # one-pass over the full [8, S, W] stack, correctness-anchored
    # shard multiplicity of group g is closed-form over the s % G cycle
    row_counts = [
        sum(
            int(np.bitwise_count(blocks[g][r]).sum())
            * ((n_shards - 1 - g) // G + 1)
            for g in range(G)
        )
        for r in range(R_PAD)
    ]
    want_top = sorted(
        ((c, r) for r, c in enumerate(row_counts)), key=lambda cr: (-cr[0], cr[1])
    )[:5]
    topn_res = e.execute("bench", "TopN(f, n=5)", shards=shards)[0]
    got_top = [(p["count"], p["id"]) for p in topn_res]
    assert got_top == want_top, f"TopN {got_top} != {want_top}"
    topn_hist = Histogram()
    lats = []
    for _ in range(min(tpu_iters, 30)):
        t0 = time.perf_counter()
        e.execute("bench", "TopN(f, n=5)", shards=shards)
        lats.append(time.perf_counter() - t0)
        topn_hist.observe(lats[-1])
    topn_p50_ms = sorted(lats)[len(lats) // 2] * 1e3
    _stage({"stage": "topn", "p50_ms": round(topn_p50_ms, 2)})

    # ------------- cross-query wave coalescing (ISSUE 4): sync QPS with
    # REAL concurrent clients, c1 vs c8, through the wave scheduler —
    # the production shape (N users, each sync) the pipelined number
    # above cannot represent. Identical queries are the dashboard case:
    # single-flight dedup + shared readback waves are exactly what the
    # scheduler ships, so c8 is expected well above c1 on the device
    # route (on the host route the scheduler bypasses by design and the
    # sweep just measures host-path thread scaling).
    from pilosa_tpu.executor.scheduler import WaveScheduler
    from pilosa_tpu.utils.stats import StatsClient

    batch_stats = StatsClient()
    sched = WaveScheduler(lambda: e, stats=batch_stats, mode="adaptive")

    def sweep(run_fn, conc: int, per: int) -> float:
        barrier = threading.Barrier(conc + 1)
        errs: list = []

        def client():
            barrier.wait()
            try:
                for _ in range(per):
                    run_fn()
            except Exception as ex:  # noqa: BLE001 — re-raised below
                errs.append(ex)

        ts = [threading.Thread(target=client, daemon=True) for _ in range(conc)]
        for t in ts:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        if errs:
            raise errs[0]
        return conc * per / dt

    def count_q():
        return sched.execute("bench", pql, shards=shards)

    def topn_q():
        return sched.execute("bench", "TopN(f, n=5)", shards=shards)

    sweep(count_q, 1, 2)  # warm
    sweep(topn_q, 1, 2)
    iters = max(4, min(tpu_iters, 16))
    count_c1 = sweep(count_q, 1, iters)
    count_c8 = sweep(count_q, 8, max(2, iters // 4))
    topn_c1 = sweep(topn_q, 1, iters)
    topn_c8 = sweep(topn_q, 8, max(2, iters // 4))
    qpw = batch_stats.distribution("queries_per_wave")
    _stage({"stage": "concurrency_sweep",
            "count_c1": round(count_c1, 1), "count_c8": round(count_c8, 1),
            "topn_c1": round(topn_c1, 1), "topn_c8": round(topn_c8, 1)})

    def rtt_capped(p50_ms: float) -> bool:
        """Sync throughput within 10% of 1/RTT — the self-describing
        marker that the transport floor, not the server, is the
        bottleneck for this sync row."""
        if rtt_ms <= 0 or p50_ms <= 0:
            return False
        return abs(1 / p50_ms - 1 / rtt_ms) <= 0.1 * (1 / rtt_ms)

    # bytes a count query actually reads: 2 gathered rows across shards
    gbps = 2 * n_shards * WORDS_PER_SHARD * 4 / tpu_seconds / 1e9
    print(
        json.dumps(
            {
                "metric": _metric_name(n_columns),
                "value": round(1 / tpu_seconds, 2),
                "unit": "qps",
                "vs_baseline": round(cpu_seconds / tpu_seconds, 2),
                **device,
                "columns": n_columns,
                "path": (
                    "executor_host" if route == "host" else "executor_pipelined"
                ),
                "route": route,
                "rtt_capped": rtt_capped(e2e_p50_ms),
                "topn_rtt_capped": rtt_capped(topn_p50_ms),
                "e2e_p50_ms": round(e2e_p50_ms, 2),
                "topn_p50_ms": round(topn_p50_ms, 2),
                # log-bucketed histogram tails (pilosa_tpu.utils.stats
                # Histogram — the same distribution /metrics exposes)
                "e2e_hist": hist_ms(e2e_hist),
                "topn_hist": hist_ms(topn_hist),
                "transport_rtt_ms": round(rtt_ms, 1),
                # p50 minus the round-trip floor measured above
                "server_p50_ms": round(max(0.0, e2e_p50_ms - rtt_ms), 2),
                "topn_server_p50_ms": round(max(0.0, topn_p50_ms - rtt_ms), 2),
                "hbm_gbps": round(gbps, 1),
                # concurrency-swept sync rates through the wave
                # scheduler (ISSUE 4) + the wave-occupancy median
                "sync_count_qps_c1": round(count_c1, 2),
                "sync_count_qps_c8": round(count_c8, 2),
                "sync_topn_qps_c1": round(topn_c1, 2),
                "sync_topn_qps_c8": round(topn_c8, 2),
                "queries_per_wave_p50": (
                    round(qpw.percentile(0.5), 2) if qpw is not None else 1.0
                ),
            }
        ),
        flush=True,
    )


# -------------------------------------------------------------------- parent
def _run_child(n_shards: int, timeout_s: float):
    env = dict(os.environ)
    env["PILOSA_BENCH_CHILD_SHARDS"] = str(n_shards)
    # the resident stack is [R_PAD, S, W] — raise the device budget to
    # fit it (resolved lazily on first stack admit and cached per
    # process; the child's env is set before spawn, so this always wins)
    from pilosa_tpu.shardwidth import WORDS_PER_SHARD

    env.setdefault(
        "PILOSA_TPU_STACK_BUDGET",
        str(n_shards * R_PAD * WORDS_PER_SHARD * 4 + (1 << 30)),
    )
    try:
        # stdout carries the one result line; stderr is inherited so the
        # child's stage lines stream live (and survive a parent timeout)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env,
            stdout=subprocess.PIPE,
            text=True,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return None, "parent timeout"
    if proc.returncode == NO_ACCELERATOR_RC:
        return None, NO_ACCELERATOR
    if proc.returncode == 0:
        for line in reversed(proc.stdout.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line), None
                except json.JSONDecodeError:
                    continue
    tail = (proc.stdout or "").strip().splitlines()
    return None, f"rc={proc.returncode}: {tail[-1] if tail else 'no stdout'}"


def main() -> None:
    if os.environ.get("PILOSA_BENCH_CHILD_SHARDS"):
        _child_main(int(os.environ["PILOSA_BENCH_CHILD_SHARDS"]))
        return

    deadline = time.monotonic() + TOTAL_BUDGET_S
    # halving ladder: an HBM-limit failure at full scale should land on
    # the LARGEST feasible size, not fall straight to 1/8th
    scales = [FULL_SHARDS]
    while scales[-1] > 256:
        scales.append(max(256, scales[-1] // 2))

    # full scale first (the north-star number), stepping down only when
    # a child fails (device OOM lands on the LARGEST feasible size)
    best = None
    last_err = None
    for n_shards in scales:
        remaining = deadline - time.monotonic()
        if remaining < 60:
            break
        _stage({"stage": "attempt", "shards": n_shards,
                "timeout_s": round(remaining)})
        best, last_err = _run_child(n_shards, remaining)
        if best is not None:
            break
        _stage({"stage": "attempt_failed", "shards": n_shards,
                "error": last_err})
        if last_err == NO_ACCELERATOR:
            break
    if best is None:
        sys.exit(f"bench.py: all attempts failed: {last_err}")
    device = {k: best.get(k) for k in ("platform", "device_kind", "device_count")}
    print(json.dumps(best), flush=True)
    # HARD FLOOR (ISSUE 2 CI task): the host fast path exists so that no
    # query path ever runs below the 1-core numpy baseline — a host-
    # routed headline under 1.0x is a regression, not a datapoint.
    # Labeled error row + non-zero rc so the driver cannot miss it.
    # HARD FLOOR (ISSUE 4 satellite): cross-query batching must never
    # regress the solo path — on the device route (where the scheduler
    # actually coalesces) c8 aggregate sync QPS below c1 means the wave
    # machinery COSTS throughput instead of sharing it. Labeled error
    # row + non-zero rc, same contract as the host-path floor below.
    # (Host-routed runs bypass the scheduler by design, so their c8/c1
    # ratio measures host thread scaling, not batching.)
    if best.get("route") == "device":
        for m in ("count", "topn"):
            c1 = best.get(f"sync_{m}_qps_c1", 0)
            c8 = best.get(f"sync_{m}_qps_c8", 0)
            if c1 and c8 and c8 < c1:
                print(
                    json.dumps(
                        {
                            "metric": f"batching_regressed_{m}_c8_below_c1",
                            "value": round(c8 / c1, 3),
                            "unit": "error",
                            "vs_baseline": round(c8 / c1, 3),
                            **device,
                            "error": (
                                "c8 sync QPS fell below c1 with the wave "
                                "scheduler active — batching regressed "
                                "the solo path"
                            ),
                        }
                    ),
                    flush=True,
                )
                sys.exit(1)
    if best.get("route") == "host" and 0 < best.get("vs_baseline", 0) < 1.0:
        print(
            json.dumps(
                {
                    "metric": "host_path_below_baseline",
                    "value": best["vs_baseline"],
                    "unit": "error",
                    "vs_baseline": best["vs_baseline"],
                    **device,
                    "error": (
                        "host-routed bench row regressed below the CPU "
                        "baseline (vs_baseline < 1.0)"
                    ),
                }
            ),
            flush=True,
        )
        sys.exit(1)


if __name__ == "__main__":
    main()
