"""What benchmark/tests put around a run: the CPU rehearsal, the planted
faults and the control. Each wraps a function that ``run.run()`` calls,
for the length of a ``with``; ``run.py`` itself has no switch for them.
"""

import contextlib
import json

from benchmark import run as bench_run
from benchmark.harness.server import Client


@contextlib.contextmanager
def wrapped(name: str, make):
    """``bench_run.<name>`` replaced by ``make(original)``."""
    original = getattr(bench_run, name)
    setattr(bench_run, name, make(original))
    try:
        yield
    finally:
        setattr(bench_run, name, original)


@contextlib.contextmanager
def on_cpu(shards: int):
    """A rehearsal: the cell cut to ``shards``, a CPU server accepted, and
    the trace's reduction stubbed (a CPU trace has no device plane; the
    reduction itself is tested on tiny.xplane.pb). Its line is never a
    result: the times are the CPU backend's."""

    def find_cell(original):
        def cut(name):
            bench, cell, cfg = original(name)
            cfg["scale"]["shards"] = min(cfg["scale"]["shards"], shards)
            return bench, cell, cfg
        return cut

    def check_device(original):
        def accept(facts, cell):
            if facts["platform"] != "cpu":
                raise bench_run.RunFailure("a rehearsal runs on the CPU")
            return {"hbm_bytes_per_s": float("nan")}
        return accept

    def reduce_trace(original):
        return lambda trace_dir, workdir: {"busy_s": 1e-3, "device_ops": [], "devices": [],
                                           "planes": [], "trace_bytes": 0}

    with wrapped("find_cell", find_cell), wrapped("check_device", check_device), \
            wrapped("reduce_trace", reduce_trace):
        yield


def altered_replies(every: int):
    """Fault: one digit of every ``every``-th answer changed where the
    client receives it."""

    def alter(body: bytes) -> bytes:
        for i, ch in enumerate(body):
            if 48 <= ch <= 57:
                return body[:i] + bytes([48 + (ch - 47) % 10]) + body[i + 1:]
        return body + b" "

    def make(original):
        def run_window(*args):
            out = list(original(*args))
            out[0] = [r[:5] + (alter(r[5]),) if k % every == 0 and r[5] is not None else r
                      for k, r in enumerate(out[0])]
            return tuple(out)
        return run_window

    return wrapped("run_window", make)


def cleared_after_load(columns: int = 64):
    """Fault: after the load is acknowledged, the server loses the first
    ``columns`` columns' bits in rows 0-31 of every set field (a ``Clear``
    per row and column), so the answers are produced from other
    data than was loaded."""

    def make(original):
        def warm_up(srv, index, spec, seed):
            schema = Client(srv.base).json("/schema")
            fields = [f["name"] for ix in schema["indexes"] if ix["name"] == index
                      for f in ix["fields"] if f["options"].get("type", "set") == "set"]
            c = Client(srv.base)
            for f in fields:
                for col in range(columns):
                    body = "".join(f"Clear({col}, {f}={r})" for r in range(32))
                    c.json(f"/index/{index}/query", body.encode())
            c.close()
            return original(srv, index, spec, seed)
        return warm_up

    return wrapped("warm_up", make)


@contextlib.contextmanager
def control(out: dict):
    """The control beside the sound program, on the same replies: the
    reference with a guarantee broken (one part of the data lost) put in
    the program's place. Its verdict lands in ``out``."""

    def make(original):
        def compare(cfg, states, records):
            out.update(original(cfg, bench_run.dataset(cfg).drop_last_part(states), records))
            print("control " + json.dumps({k: out[k] for k in ("compared", "mismatched")}), flush=True)
            return original(cfg, states, records)
        return compare

    with wrapped("compare", make):
        yield
