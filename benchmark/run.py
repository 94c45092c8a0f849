"""One run of one cell of BENCHMARK.json, in a new process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts the program's server as its ONE child (harness/serve.py; this
process never imports jax), loads the configuration's data from the
seed, warms every shape the cell's traffic can draw, drives
``--seconds`` of closed-loop traffic over HTTP, stops the server, and
compares the replies of the timed requests themselves with the
dataset's plain reference. The LAST line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``), then what was compared, each number
beside its limit. Without a TPU holding the cell's ``chips`` devices the
run exits non-zero and prints no such line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name in BENCHMARK.json (README.md).
"""

from __future__ import annotations

import argparse
import importlib
import json
import multiprocessing
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import loadgen, pql, stats, traffic  # noqa: E402
from benchmark.harness.min_bytes import min_bytes  # noqa: E402
from benchmark.harness.peaks import peaks  # noqa: E402
from benchmark.harness.server import Client, RunFailure, Server  # noqa: E402

TRACE_SLICE_S = 3.0  # traces are large and tracing slows the host
WARM_THREADS = 4
# JAX's persistent compile cache: one fixed directory inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str, **fields) -> None:
    print(json.dumps({"t": round(time.monotonic() - _T0, 2), "msg": msg, **fields}), flush=True)


_T0 = time.monotonic()


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(name: str) -> tuple[dict, dict, dict]:
    """(BENCHMARK.json, the cell's entry, its configuration's file)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise RunFailure(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return bench, cell, load_json(ROOT, conf["file"])


def server_config(cfg: dict) -> str:
    """The configuration's server settings as the program's TOML."""
    return "".join(f"{k} = {json.dumps(v)}\n" for k, v in cfg.get("server", {}).items())


def dataset(cfg: dict):
    """The module of a configuration's dataset: generator and reference."""
    return importlib.import_module(f"benchmark.datasets.{cfg['dataset']}")


# ------------------------------------------------------------------ set-up
def _load_part(args):
    base, index, seed, cfg, mine = args
    return dataset(cfg).load_part(base, index, seed, cfg, mine)


def load_data(srv: Server, cfg: dict, seed: int) -> list[dict]:
    """Create the schema and post the configuration's data, generated
    from the seed in a small pool of worker processes (numpy only): the
    generator, not the server, was the slow half of the load (PR 21)."""
    ds = dataset(cfg)
    c = Client(srv.base)
    index = cfg["index"]
    c.json(f"/index/{index}", b"{}")
    for fname, opts in ds.schema(cfg):
        c.json(f"/index/{index}/field/{fname}", opts)
    c.close()
    n_parts = ds.parts(cfg)
    workers = max(1, min(n_parts, (os.cpu_count() or 2) // 2, 8))
    shares = [list(range(w, n_parts, workers)) for w in range(workers)]
    log("load pool", workers=workers, parts=n_parts, cpus=os.cpu_count())
    jobs = [(srv.base, index, seed, cfg, mine) for mine in shares]
    if workers == 1:
        return [_load_part(jobs[0])]
    with multiprocessing.get_context("spawn").Pool(workers) as pool:
        return pool.map(_load_part, jobs)


def warm_up(srv: Server, index: str, spec: dict, seed: int) -> int:
    """Every compiled shape of the mix once: stacks packed and uploaded,
    every program compiled or read from the persistent cache. The first
    query of each template goes alone (it packs the stacks); the rest go
    a few at a time."""
    queries = traffic.Generator(spec, [seed, 0x3A97]).warmup()
    path = f"/index/{index}/query"
    first, rest, seen = [], [], set()
    for ti, q in queries:
        (rest if ti in seen else first).append(q)
        seen.add(ti)
    c = Client(srv.base)
    for q in first:
        c.json(path, q.encode())
    c.close()

    def some(qs):
        cl = Client(srv.base)
        for q in qs:
            cl.json(path, q.encode())
        cl.close()

    with ThreadPoolExecutor(WARM_THREADS) as ex:
        list(ex.map(some, [rest[k::WARM_THREADS] for k in range(WARM_THREADS)]))
    return len(queries)


def warm_waves(srv: Server, cfg: dict, spec: dict, seed: int) -> int:
    """A few seconds of the mix itself at the cell's own concurrency. The
    wave scheduler joins the results of the queries that share a wave in
    one jitted concatenate, a program per sequence of result sizes, so
    only concurrent traffic can warm the common ones."""
    seconds = float(spec.get("warm_seconds", 0))
    if seconds <= 0:
        return 0
    records = run_window(srv, cfg, spec, seed ^ 0x5EED, seconds, None)[0]
    bad = [r for r in records if r[3] != 200]
    if bad:
        raise RunFailure(f"warm-up: {len(bad)} requests failed, first {bad[0][4]!r} -> {bad[0][3]}")
    return len(records)


# ------------------------------------------------------------------ window
def run_window(srv: Server, cfg: dict, spec: dict, seed: int, seconds: float, trace_dir: str | None):
    """Closed-loop traffic for ``seconds``; returns (records, (t_start,
    t_end), per-process cpu/wall, trace stamps or None, scrapes)."""
    n_proc = max(1, min(int(spec.get("processes", 1)), int(spec["clients"])))
    ctx = multiprocessing.get_context("spawn")
    procs, pipes = [], []
    for p in range(n_proc):
        mine = list(range(p, int(spec["clients"]), n_proc))
        parent, child = ctx.Pipe()
        proc = ctx.Process(target=loadgen.process_main,
                           args=(child, srv.base, cfg["index"], spec, seed, mine))
        proc.start()
        child.close()
        procs.append(proc)
        pipes.append(parent)
    try:
        for pipe in pipes:
            if pipe.recv() != "ready":
                raise RunFailure("a generator process did not come up")
        start_scrape = srv.scrape()
        t_start = time.monotonic() + 0.5
        t_end = t_start + seconds
        for pipe in pipes:
            pipe.send((t_start, t_end))
        trace = None
        if trace_dir is not None:
            trace = {}
            tracer = threading.Thread(target=_trace_slice, args=(srv, trace_dir, t_start, seconds, trace))
            tracer.start()
        results = [pipe.recv() for pipe in pipes]
        if trace_dir is not None:
            tracer.join()
            if "error" in trace:
                raise RunFailure(f"trace: {trace['error']}")
        end_scrape = srv.scrape()
    finally:
        for proc in procs:
            proc.join(timeout=90)
            if proc.is_alive():
                proc.kill()
                proc.join()
    records = [r for res in results for r in res["records"]]
    gens = [{"cpu_s": res["cpu_s"], "wall_s": res["wall_s"]} for res in results]
    return records, (t_start, t_end), gens, trace, start_scrape, end_scrape


def _trace_slice(srv: Server, trace_dir: str, t_start: float, seconds: float, out: dict) -> None:
    """A short steady slice in the middle of the window."""
    length = min(TRACE_SLICE_S, seconds / 3.0)
    try:
        time.sleep(max(0.0, t_start + (seconds - length) / 2.0 - time.monotonic()))
        out["start"] = srv.control(cmd="trace_start", dir=trace_dir)["monotonic"]
        time.sleep(length)
        stop = srv.control(cmd="trace_stop")
        out["stop"], out["export_s"] = stop["monotonic"], stop["export_s"]
    except Exception as e:  # reported by the caller, in the main thread
        out["error"] = f"{type(e).__name__}: {e}"


def reduce_trace(trace_dir: str, workdir: str) -> dict:
    """The reduction runs in a process of its own, on the CPU, after the
    server has gone: this process stays jax-free."""
    out = os.path.join(workdir, "trace.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "harness", "reduce_trace.py"), trace_dir, out],
        env=env, capture_output=True, text=True, timeout=300,
    )
    if proc.returncode != 0:
        raise RunFailure(f"trace reduction failed: {proc.stderr[-2000:]}")
    return load_json(out)


# ----------------------------------------------------------------- compare
def compare(cfg: dict, states: list[dict], records) -> dict:
    """Every kept reply of the timed requests against the plain
    reference's answer to the same query text. Exact, so the limit is 0."""
    ref = dataset(cfg).Reference(cfg, states)
    compared = mismatched = unanswered = 0
    examples = []
    for _, _, _, status, text, body in records:
        if status != 200:
            unanswered += 1
            if len(examples) < 5:
                examples.append({"pql": text, "status": status, "body": (body or b"")[:200].decode("utf-8", "replace")})
            continue
        if body is None:
            continue
        compared += 1
        want = ref.answer(pql.parse(text))
        try:
            got = json.loads(body)["results"][0]
        except (ValueError, KeyError, IndexError, TypeError):
            got = {"unreadable": body[:200].decode("utf-8", "replace")}
        if got != want:
            mismatched += 1
            if len(examples) < 5:
                examples.append({"pql": text, "got": _short(got), "want": _short(want)})
    return {"compared": compared, "mismatched": mismatched, "unanswered": unanswered,
            "examples": examples}


def _short(v, n: int = 300):
    s = json.dumps(v)
    return s if len(s) <= n else s[:n] + "..."


# ----------------------------------------------------------------- metrics
def _in_cell(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def end_to_end_metrics(bench: dict, workload: str, records, window, setup_s: float) -> dict:
    lat = stats.latencies_ms(records, *window)
    values = {"qps": stats.completed_rate(records, *window),
              "p50_ms": stats.percentile(lat, 0.50),
              "p95_ms": stats.percentile(lat, 0.95),
              "setup_s": setup_s}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"] if _in_cell(m, workload)}


def per_layer_metrics(bench: dict, workload: str, cfg: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell through the reader its file
    names; a reader that finds nothing to read leaves its metric out."""
    schema = {f: ({"rows": s["rows"]} if "rows" in s else {"bits": s["bits"]})
              for f, s in cfg["schema"].items()}
    columns = cfg["scale"]["shards"] * cfg["shard_width"]
    ctx["min_bytes"] = lambda text: min_bytes(pql.parse(text), schema, columns)
    metrics = {}
    for m in bench["per_layer"]:
        if not _in_cell(m, workload):
            continue
        spec = load_json(HERE, "layer_metrics", f"{m['name']}.json")
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(spec.get("params", {}), ctx)
        if value is not None:  # never 0 for "nothing to read"
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


# --------------------------------------------------------------------- run
def check_device(facts: dict, cell: dict) -> dict:
    """The run fails unless the server holds a TPU with the cell's chips;
    returns that chip's peaks."""
    if facts["platform"] != "tpu":
        raise RunFailure(f"server runs on {facts['platform']!r}, the cell needs a tpu")
    if facts["count"] != cell["chips"]:
        raise RunFailure(f"server sees {facts['count']} devices, the cell needs {cell['chips']}")
    if facts["router_pinned_host"]:
        raise RunFailure("the router is pinned to the host")
    return peaks(facts["kind"])


def cache_entries() -> set:
    return set(os.listdir(CACHE_DIR)) if os.path.isdir(CACHE_DIR) else set()


def drop_cache_entries_since(before: set) -> int:
    """Remove what the compile cache gained since ``before``. The program
    compiles a small program per wave (PERF.md), a new set of them every
    run; kept, they would make a run's window depend on how many runs the
    checkout has seen. Set-up's programs, the same every run, stay."""
    gained = cache_entries() - before
    for name in gained:
        try:
            os.remove(os.path.join(CACHE_DIR, name))
        except OSError:
            pass
    return len(gained)


def run(workload: str, seed: int, seconds: float, trace: bool) -> dict:
    """The whole run; returns the last line's object. benchmark/tests
    rehearse it on the CPU and plant faults by wrapping the functions of
    this module it calls; it has no switch for them."""
    bench, cell, cfg = find_cell(workload)
    spec = traffic.load(cell["traffic"])
    workdir = tempfile.mkdtemp(prefix="pilosa_bench_")
    srv = Server(workdir, server_config(cfg), CACHE_DIR)
    warmed_cache = None
    try:
        srv.start()
        phases = {"boot_s": time.monotonic() - srv.spawned_at}
        facts = srv.device_facts()
        log("server up", **facts)
        table = check_device(facts, cell)
        scrapes = {"boot": srv.scrape()}

        t = time.monotonic()
        states = load_data(srv, cfg, seed)
        phases["load_s"] = time.monotonic() - t
        scrapes["loaded"] = srv.scrape()
        log("loaded", seconds=round(phases["load_s"], 2))

        t = time.monotonic()
        warmed = warm_up(srv, cfg["index"], spec, seed)
        phases["warm_shapes_s"] = time.monotonic() - t
        warmed_cache = cache_entries()
        warmed += warm_waves(srv, cfg, spec, seed)
        phases["warm_s"] = time.monotonic() - t
        setup_s = time.monotonic() - srv.spawned_at
        with open(srv.log_path, errors="replace") as f:
            text = f.read()
        log("warmed", queries=warmed, seconds=round(phases["warm_s"], 2),
            shapes_seconds=round(phases["warm_shapes_s"], 2), setup_s=round(setup_s, 2),
            compile_requests=text.count("Finished XLA compilation of"),
            persistent_cache_hits=text.count("Persistent compilation cache hit for"))

        trace_dir = os.path.join(workdir, "trace") if trace else None
        records, window, gens, tr, scrapes["window_start"], scrapes["window_end"] = run_window(
            srv, cfg, spec, seed, seconds, trace_dir
        )
        memory = [p for p in srv.control(cmd="memory")["peak_bytes_in_use"] if p]
        if not memory and facts["platform"] == "tpu":
            raise RunFailure("the devices report no peak_bytes_in_use")
        srv.stop()  # the program's state is freed before the reference runs
        log("window closed", requests=len(records))

        t = time.monotonic()
        verdict = compare(cfg, states, records)
        log("compared", seconds=round(time.monotonic() - t, 2))

        device = {"platform": facts["platform"], "kind": facts["kind"], "count": facts["count"],
                  "memory_peak_bytes": max(memory) if memory else None}
        sent = [r for r in records if window[0] <= r[1] < window[1]]
        failed = sum(1 for r in sent if r[3] != 200)
        if not trace:
            metrics, breakdown = end_to_end_metrics(bench, workload, records, window, setup_s), None
        else:
            reduced = reduce_trace(trace_dir, workdir)
            reduced.update(start=tr["start"], stop=tr["stop"], window_s=tr["stop"] - tr["start"])
            log("trace", busy_s=reduced["busy_s"], window_s=reduced["window_s"],
                export_s=tr["export_s"], trace_bytes=reduced["trace_bytes"],
                devices=reduced["devices"], planes=reduced["planes"])
            if not reduced["busy_s"]:
                raise RunFailure("the traced slice shows no operation on the device")
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            metrics = per_layer_metrics(bench, workload, cfg, {
                "scrapes": scrapes, "records": records, "window": window, "phases": phases,
                "log_path": srv.log_path, "trace": reduced, "loadgen": gens, "peaks": table, "cfg": cfg,
            })
            breakdown = {
                "device_ops": reduced["device_ops"],
                # no host span shares the trace's clock yet, so a gap
                # cannot be given to what the host was doing in it
                "idle_gaps": [["unattributed (no host spans on the trace's clock)",
                               reduced["window_s"] - reduced["busy_s"]]],
            }
        limits = {
            "answers_compared": {"value": verdict["compared"], "at_least": 1},
            "mismatched": {"value": verdict["mismatched"], "limit": 0},
            "unanswered": {"value": verdict["unanswered"], "limit": 0},
        }
        correct = verdict["compared"] >= 1 and verdict["mismatched"] == 0 and verdict["unanswered"] == 0
        for ex in verdict["examples"]:
            print("not equal: " + json.dumps(ex), file=sys.stderr)
        for name, item in limits.items():
            print(f"{name} {json.dumps(item)}", file=sys.stderr)
        line = {"correct": bool(correct), "attempted": len(sent), "failed": failed,
                "metrics": metrics, "device": device}
        if breakdown is not None:
            line["breakdown"] = breakdown
        line["compared"] = limits
        return line
    except BaseException:
        print(f"--- {srv.log_path} (tail)\n{srv.log_tail(40)}---", file=sys.stderr, flush=True)
        raise
    finally:
        srv.kill()
        if warmed_cache is not None:
            log("compile cache", dropped=drop_cache_entries_since(warmed_cache), kept=len(warmed_cache))
        shutil.rmtree(workdir, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        line = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except RunFailure as e:
        print(f"run failed: {e}", file=sys.stderr)
        return 1
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
